"""Async DownPour-SGD parameter server (C1/C2/M1 parity — the reference's core).

Reference behavior being reproduced (``asgd/optim/Asynchronous.py:20-71``,
``example/main.py:135-138``, SURVEY.md §2.3):

- Workers train locally with plain SGD and keep a flat accumulator of
  lr-pre-scaled gradients: ``accum -= lr * grads`` every step (``:54-55``).
- Every ``n_pull`` steps a worker sends **ParameterRequest**; the server
  replies with **ParameterUpdate** carrying the current central params
  (``:48-49``).
- Every ``n_push`` steps the worker sends **GradientUpdate** with the
  accumulator, then zeroes it (``:58-60``); the server *adds* the payload to
  its central params (pre-scaled by ``-lr``, so addition is the update).
- At construction each worker sends one **ParameterUpdate** installing its
  initial params as the central params (``:34``).
- A listener thread receives server pushes concurrently with training
  (``:9-18``).

TPU-native re-design (SURVEY.md §7 hard part (a)): training steps stay fully
jitted on-device; the push/pull control plane runs host-side between steps
over the M2 messaging transports. The reference's deliberate data race — the
listener writing tensors into a model mid-backprop — becomes a race-free
**between-steps pytree swap**: the listener deposits the newest flat vector in
a mailbox, and the optimizer installs it at the next step boundary. Staleness
semantics (params may be replaced between any two steps, at pull cadence) are
preserved; torn reads are not.

The worker's per-step device work (local SGD + accumulator update) is one
fused jitted program; device↔host transfers happen only at push/pull
boundaries (the flat vector in/out), every ``n_push``/``n_pull`` steps.
"""

from __future__ import annotations

import logging
import queue
import sys
import threading
import time
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from distributed_ml_pytorch_tpu.utils.durability import atomic_write
from distributed_ml_pytorch_tpu.utils.health import (
    admission_from_args as _admission_from_args,
)
from distributed_ml_pytorch_tpu.utils import codecs
from distributed_ml_pytorch_tpu.utils.compress import (
    CODEC_DENSE,
    CODEC_TOPK,
    CompressionError,
    body_crc,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    SERVER_RANK,
    MessageCode,
    MessageListener,
    Transport,
    _join16,
    _next_incarnation,
    _split16,
    send_message,
)
from distributed_ml_pytorch_tpu.utils.serialization import (
    make_unraveler,
    ravel_model_params,
)

_LOGGER = logging.getLogger(__name__)

Pytree = Any


class ParameterServer:
    """Central parameter holder (M1 contract, ``example/main.py:137-138``).

    ``run()`` blocks serving messages until every worker has sent
    ``WorkerDone`` (an extension code — the reference server blocks forever,
    SURVEY.md §3.2 notes its post-``run()`` code is dead; a clean shutdown is
    the intent-preserving improvement).
    """

    def __init__(
        self,
        model: Pytree = None,
        *,
        params: Optional[np.ndarray] = None,
        transport: Optional[Transport] = None,
        n_workers: Optional[int] = None,
        worker_timeout: Optional[float] = None,
        ckpt_dir: Optional[str] = None,
        ckpt_every: int = 500,
        staleness_damping: float = 0.0,
        wal: bool = False,
        wal_group_n: int = 8,
        admission=None,
        recorder=None,
        combine: str = "add",
        optimizer=None,
    ):
        if params is not None:
            self.central = np.asarray(params, dtype=np.float32).copy()
        elif model is not None:
            self.central = np.asarray(ravel_model_params(model), dtype=np.float32).copy()
        else:
            raise ValueError("ParameterServer needs a model pytree or a flat params vector")
        self.transport = transport
        self.n_workers = n_workers
        self.worker_timeout = worker_timeout
        self.failed_workers: set = set()
        self.message_counts = {code: 0 for code in MessageCode}
        # preemption safety for the central params (the only training state
        # the topology cannot recover: a worker rejoins and re-pulls, but a
        # restarted server would otherwise reset to fresh init)
        self.ckpt_dir = ckpt_dir
        self.ckpt_every = int(ckpt_every or 0)
        self._push_count = 0
        self._restored = False
        self.rejected_installs = 0
        # --- numerical health plane (ISSUE 8) ---------------------------
        #: admission gate (``utils/health.GradientAdmission`` or None):
        #: every GradientUpdate passes finiteness + per-worker norm-outlier
        #: checks BEFORE any accounting or WAL append; rejects are
        #: quarantined with an explicit UpdateNack — never a silent drop,
        #: never a WAL record (a logged poisoned record would be replayed
        #: on every recovery, forever)
        self.admission = admission
        # --- observability plane (ISSUE 12) -----------------------------
        #: optional flight recorder (``utils/obs.SpanRecorder``): the PS
        #: side of the worker-push timeline — admission verdicts, WAL
        #: append/fsync spans, the apply span — all under the correlation
        #: id the delivering envelope restored into the serve thread.
        #: Purely observational (never consulted for a decision).
        self.recorder = recorder
        self.quarantined = 0
        self.quarantined_by_sender: dict = {}
        self.nacks_sent = 0
        #: most recent quarantine verdicts (sender, reason, norm, z)
        self.quarantine: "collections.deque" = None  # set below (needs import)
        #: applied updates discarded by coordinator-driven rollbacks
        self.rolled_back_updates = 0
        # --- durability plane (ISSUE 5) ---------------------------------
        #: this server LIFE's incarnation stamp (WAL records carry it so a
        #: dead life's late-flushed tail is detectable on replay)
        self.incarnation = _next_incarnation()
        #: server-side apply sequence: one increment per applied
        #: GradientUpdate, monotonic across lives (restored from the
        #: checkpoint meta) — the WAL/checkpoint handshake key
        self._apply_seq = 0
        #: per-sender applied-update counts — the server half of the
        #: drill's sequence accounting (survives restore via meta + WAL)
        self.applied_by_sender: dict = {}
        self.replayed_updates = 0
        self.dropped_bad_updates = 0
        self.wal_group_n = int(wal_group_n)
        #: envelope identities of recent applies, persisted in the ckpt
        #: meta: WAL truncation discards the per-record envelopes, but an
        #: ack can be lost in flight — this tail keeps the dedup seed for
        #: retries of updates the checkpoint already covers
        import collections

        self._recent_envelopes = collections.deque(maxlen=512)
        self.quarantine = collections.deque(maxlen=64)
        #: (incarnation, seq) of the reliability envelope that delivered
        #: the frame being handled (run() stashes transport.last_delivery
        #: here) — recorded per WAL record for restart-time dedup seeding
        self._envelope = None
        self._prev_ckpt_meta = None
        self.wal = None
        if wal:
            if not self.ckpt_dir:
                raise ValueError(
                    "wal=True needs a ckpt_dir — the write-ahead log lives "
                    "beside the checkpoint it protects")
            import os

            from distributed_ml_pytorch_tpu.utils.wal import WriteAheadLog

            self.wal = WriteAheadLog(
                os.path.join(self.ckpt_dir, "ps_wal.log"),
                incarnation=self.incarnation)
        #: staleness-weighted apply (arxiv 2006.02924 motivates weighting
        #: contributions by staleness): a push that raced `s` central
        #: versions since its worker last pulled applies scaled by
        #: 1/(1 + damping*s). 0 (default) is the exact reference behavior;
        #: under straggler-heavy fleets a small damping keeps one slow
        #: worker's very stale deltas from dragging the central params back.
        self.staleness_damping = float(staleness_damping)
        # --- scalable optimizer plane (ISSUE 14) ------------------------
        #: how concurrent pushes combine: "add" (the reference behavior)
        #: or "adasum" (arXiv:2006.02924) — an angle-aware merge against
        #: the OVERLAP (the sum of deltas applied since the pushing
        #: worker's last pull) that de-weights redundant directions
        #: instead of damping everything by staleness. The two knobs are
        #: alternatives by design, never stacked.
        if combine not in ("add", "adasum"):
            raise ValueError(f"combine must be 'add' or 'adasum', "
                             f"got {combine!r}")
        if combine == "adasum" and self.staleness_damping > 0.0:
            raise ValueError(
                "combine='adasum' replaces --staleness-damping — pick one "
                "(stacking them would damp the same staleness twice)")
        self.combine = combine
        #: per-sender overlap vectors (adasum only): reset on each pull,
        #: grown by every OTHER sender's applied delta
        self._overlap: dict = {}
        #: optional server-side sharded optimizer
        #: (``parallel/optplane.ShardedOptimizer``): transforms each
        #: admitted, combined update into the applied delta, owning the
        #: momentum/Adam state for exactly this server's range (the
        #: ZeRO-style 1/shards state scaling). The WAL logs the
        #: optimizer's INPUT, so replay re-runs ``step`` and rebuilds
        #: state bit-for-bit from the checkpointed generation.
        self.optimizer = optimizer
        if optimizer is not None and optimizer.size != self.central.shape[0]:
            raise ValueError(
                f"optimizer covers {optimizer.size} params but this "
                f"server holds {self.central.shape[0]}")
        from distributed_ml_pytorch_tpu.utils.failure import StalenessAuditor

        self.staleness = StalenessAuditor()
        #: version head for pull replies (ISSUE 6): when set (an np.float32
        #: array, the ``_split16`` halves of the owner's shard-map version)
        #: replies go out as ``ShardParams`` = ``[*head, *central]`` instead
        #: of a bare ``ParameterUpdate`` — the elastic plane's versioned
        #: wire. ``ElasticShardServer`` re-stamps it on every resize.
        self.pull_reply_head: Optional[np.ndarray] = None
        # --- codec plane (ISSUE 18): delta-encoded pull replies ---------
        #: pull epoch: bumped (and the base table cleared) on every
        #: restore / rollback / resize — the fence that forces the next
        #: reply to every worker back to a full dense install. The epoch
        #: rides the DeltaParams head, so a worker holding a pre-restore
        #: view can NEVER have a post-restore delta applied onto it.
        self._pull_epoch = 0
        #: sender -> (epoch, version, view): the worker's exact
        #: materialized vector, mirrored by replaying our own encode ->
        #: decode at send time. Error feedback is structural: the next
        #: delta is ``central - view``, which already contains everything
        #: the last lossy reply could not represent.
        self._pull_bases: dict = {}
        self.delta_replies = 0
        self.full_replies = 0
        #: wire floats actually sent on DeltaParams replies (head + body)
        self.delta_reply_wire_floats = 0
        #: distmodel mutation knobs (analysis/distmodel.py `dpull`): the
        #: clean server checks the worker's held stamp before shipping a
        #: delta, and re-fences the base table on restore. Flipping either
        #: reproduces the model's counterexample on this real stack.
        self._delta_check_held = True
        self._delta_reset_on_restore = True
        self._stop = threading.Event()

    def stop(self) -> None:
        self._stop.set()

    def _ckpt_path(self) -> str:
        import os

        return os.path.join(self.ckpt_dir, "ps_central.npy")

    def _meta_path(self) -> str:
        import os

        return os.path.join(self.ckpt_dir, "ps_meta.json")

    def _opt_path(self) -> str:
        import os

        return os.path.join(self.ckpt_dir, "ps_opt.npz")

    def save_checkpoint(self) -> None:
        """Persist the central params + resume clock, atomically AND
        power-loss durably (every write rides ``utils.atomic_write``:
        fsync'd temp file, rename, directory fsync).

        Vector (``ps_central.npy``) and meta (``ps_meta.json``) are BOUND by
        a CRC so the ISSUE 5 tear window — a crash between the two renames —
        can never pair a v+1 vector with a v clock silently: the meta is
        written FIRST, carries the new vector's checksum, and keeps the
        previous generation's fields under ``"prev"``; ``maybe_restore``
        cross-checks the CRC and resolves a tear to the consistent PREVIOUS
        generation (whose updates the WAL, when enabled, still holds — it is
        only truncated after both renames land)."""
        if not self.ckpt_dir:
            return
        import io
        import json
        import os
        import zlib

        os.makedirs(self.ckpt_dir, exist_ok=True)
        if self.wal is not None:
            self.wal.sync()  # never let the checkpoint get ahead of the log
        buf = io.BytesIO()
        np.save(buf, self.central)
        blob = buf.getvalue()
        meta = {
            "version": self.staleness.version,
            "push_count": self._push_count,
            "apply_seq": self._apply_seq,
            "applied_by_sender": {
                str(k): int(v) for k, v in self.applied_by_sender.items()},
            "central_crc": zlib.crc32(blob) & 0xFFFFFFFF,
            "recent_envelopes": [list(e) for e in self._recent_envelopes],
            "prev": self._prev_ckpt_meta,
        }
        if self.optimizer is not None:
            # optimizer state rides the checkpoint (ISSUE 14), written
            # FIRST and bound to this vector generation by the vector CRC:
            # the state file keeps two generations, so whichever meta/
            # vector generation a torn crash resolves to, a CRC-matching
            # optimizer generation exists (optplane.save_state). The
            # last COMPLETED generation's CRC tells save_state which
            # stored generation to keep as prev (a torn save's orphan
            # cur must not evict the still-live one).
            last_crc = (self._prev_ckpt_meta or {}).get("central_crc")
            self.optimizer.save_state(
                self._opt_path(), central_crc=int(meta["central_crc"]),
                apply_seq=self._apply_seq,
                prev_crc=None if last_crc is None else int(last_crc))
        atomic_write(self._meta_path(), json.dumps(meta).encode())
        atomic_write(self._ckpt_path(), blob)
        self._prev_ckpt_meta = {k: v for k, v in meta.items() if k != "prev"}
        if self.wal is not None:
            # the checkpoint just made every logged update durable: release
            # the delivery acks deferred behind them BEFORE truncating the
            # records (and their envelope identities) away — and since an
            # ack can still be lost in flight, the meta's recent_envelopes
            # tail (written above) keeps the dedup seed for retries of
            # updates the checkpoint already covers
            ack = getattr(self.transport, "ack_delivered", None)
            if ack is not None:
                ack()
            self.wal.truncate(self._apply_seq)

    def _read_checkpoint(self):
        """Load the on-disk (vector, meta) pair with the full tear-window
        resolution and CRC cross-check (shared by :meth:`maybe_restore` and
        :meth:`rollback_restore`). Raises on size mismatch or real
        corruption; the caller owns adopting the result."""
        import io
        import json
        import os
        import zlib

        path = self._ckpt_path()
        with open(path, "rb") as f:
            blob = f.read()
        arr = np.load(io.BytesIO(blob))
        if arr.shape != self.central.shape:
            raise ValueError(
                f"checkpoint at {path} holds {arr.shape[0]} params but "
                f"the model ravels to {self.central.shape[0]} — wrong "
                "--model?"
            )
        meta = None
        if os.path.exists(self._meta_path()):
            with open(self._meta_path()) as f:
                meta = json.load(f)
        if meta is not None and "central_crc" in meta:
            crc = zlib.crc32(blob) & 0xFFFFFFFF
            if crc != int(meta["central_crc"]):
                prev = meta.get("prev")
                if prev is not None and int(prev.get("central_crc", -1)) == crc:
                    # the tear window: the new meta landed, the vector
                    # rename did not — the on-disk vector IS the
                    # previous generation; adopt its matching clock
                    # (the WAL still holds the gap's updates)
                    _LOGGER.warning(
                        "checkpoint meta is one generation ahead of the "
                        "vector (crash between renames) — restoring the "
                        "previous consistent generation")
                    meta = prev
                else:
                    raise ValueError(
                        f"checkpoint at {path} matches neither its meta "
                        "CRC nor the previous generation's — refusing "
                        "to resume with an unverifiable staleness clock")
        return arr.astype(np.float32), meta

    def maybe_restore(self) -> bool:
        """Adopt the saved central vector + clock and replay the WAL past
        it; False if nothing restorable exists. Failure modes are LOUD: a
        size mismatch (wrong model), a vector matching neither its meta's
        CRC nor the previous generation's (real corruption), and mid-log
        WAL damage all raise — silently training a fresh init (or a wrong
        staleness clock) while claiming to resume is the one wrong answer."""
        if not self.ckpt_dir:
            return False
        import os

        path = self._ckpt_path()
        restored = False
        if os.path.exists(path):
            arr, meta = self._read_checkpoint()
            self.central = arr
            if meta is not None:
                self.staleness.version = int(meta.get("version", 0))
                self._push_count = int(meta.get("push_count", 0))
                self._apply_seq = int(meta.get("apply_seq", 0))
                self.applied_by_sender = {
                    int(k): int(v)
                    for k, v in meta.get("applied_by_sender", {}).items()}
                self._recent_envelopes.extend(
                    (int(s), int(i), int(q))
                    for s, i, q in meta.get("recent_envelopes", []))
                self._prev_ckpt_meta = {
                    k: v for k, v in meta.items() if k != "prev"}
            self._restore_optimizer_state(meta)
            restored = True
        if self.wal is not None:
            restored = bool(self._replay_wal()) or restored
        if restored:
            self._restored = True
            if self._delta_reset_on_restore:
                # a restored life must re-fence the delta plane: any base
                # tracked by the dead life describes a worker view this
                # life cannot prove, and the epoch bump forces full
                # replies even if version NUMBERS happen to line up again
                self.reset_pull_bases()
        return restored

    def reset_pull_bases(self) -> None:
        """Fence the delta-reply plane (restore / rollback / resize): drop
        every tracked worker base and bump the pull epoch so the next
        reply to each worker is a full dense install."""
        self._pull_epoch += 1
        self._pull_bases.clear()

    def _restore_optimizer_state(self, meta) -> None:
        """Adopt the checkpoint's optimizer generation (the one whose CRC
        binds it to the adopted central vector); a missing state file is
        a pre-optimizer checkpoint — fresh zero moments, loudly noted
        (WAL replay then rebuilds from there exactly as the live path
        would have)."""
        if self.optimizer is None:
            return
        crc = int(meta["central_crc"]) if (
            meta is not None and "central_crc" in meta) else None
        if not self.optimizer.load_state(self._opt_path(),
                                         central_crc=crc):
            self.optimizer.reset()  # never pair live moments with a
            # restored vector from another timeline
            _LOGGER.warning(
                "no optimizer state beside the checkpoint (%s) — "
                "resuming with fresh zero moments", self._opt_path())

    def _replay_wal(self) -> int:
        """Re-apply logged updates the checkpoint does not cover; returns
        how many replayed. Records the checkpoint already covers (``seq <=
        apply_seq`` — a checkpoint that raced the truncation) are skipped,
        so replay is idempotent; every surviving record's delivery envelope
        re-seeds the transport's dedup (``ReliableTransport.seed_dedup``)
        so a sender's retry of an applied-but-unacked frame is re-acked,
        never re-applied."""
        records, stats = self.wal.replay()
        # seed sources: the ckpt meta's recent-envelope tail (covers
        # records a truncation discarded whose acks may have been lost in
        # flight) plus every surviving record's own envelope
        envelopes = [tuple(e) for e in self._recent_envelopes]
        n = 0
        for rec in records:
            if rec.env_inc or rec.env_seq:
                envelopes.append((rec.sender, rec.env_inc, rec.env_seq))
                self._recent_envelopes.append(
                    (rec.sender, rec.env_inc, rec.env_seq))
            if rec.seq <= self._apply_seq:
                continue
            if rec.payload.shape != self.central.shape:
                raise ValueError(
                    f"WAL record seq {rec.seq} holds {rec.payload.shape[0]} "
                    f"params but the restored vector holds "
                    f"{self.central.shape[0]} — log/checkpoint mismatch")
            # the record holds the optimizer's INPUT: replay re-runs the
            # step, so the optimizer state catches up exactly (ISSUE 14)
            self._apply_delta(rec.payload)
            self._apply_seq = rec.seq
            self._push_count += 1
            self.staleness.version += 1
            self.applied_by_sender[rec.sender] = (
                self.applied_by_sender.get(rec.sender, 0) + 1)
            n += 1
        self.replayed_updates += n
        if stats["stale_skipped"] or stats["torn_tail"]:
            _LOGGER.warning(
                "WAL replay: %d stale-incarnation record(s) skipped, torn "
                "tail=%d", stats["stale_skipped"], stats["torn_tail"])
        seed = getattr(self.transport, "seed_dedup", None)
        if seed is not None and envelopes:
            seed(envelopes)
        return n

    def rollback_restore(self, target_seq: int) -> int:
        """In-place rollback (ISSUE 8): discard the live state and rebuild
        it as *checkpoint + WAL replay capped at* ``target_seq`` — the
        apply seq the coordinator's last good :class:`FleetManifest`
        promises. Returns how many applied updates were discarded.

        Unlike the drill's restore path this runs on a LIVE server (no
        process death): the transport and its dedup/ack state survive, so
        no reseeding happens. Deferred delivery acks are released first —
        delivery DID happen; the discard below is the explicit,
        coordinator-logged decision, not a loss. The WAL tail past the
        target is dropped (``WriteAheadLog.drop_after``) so the rolled-back
        updates cannot resurrect on a later crash-restore.

        Refuses LOUDLY when the on-disk checkpoint is already AHEAD of the
        target (a later generation overwrote the barrier's state — rolling
        "back" to it would silently keep the suspect updates)."""
        if not self.ckpt_dir:
            raise ValueError("rollback_restore needs a ckpt_dir")
        import os

        target_seq = int(target_seq)
        self.commit()  # release withheld acks before discarding their state
        if not os.path.exists(self._ckpt_path()):
            raise ValueError(
                f"rollback to apply seq {target_seq} impossible: no "
                f"checkpoint under {self.ckpt_dir!r}")
        before_seq = self._apply_seq
        arr, meta = self._read_checkpoint()
        ckpt_seq = int(meta.get("apply_seq", 0)) if meta is not None else 0
        if ckpt_seq > target_seq:
            raise ValueError(
                f"rollback target apply seq {target_seq} is BEHIND the "
                f"on-disk checkpoint ({ckpt_seq}) — a later checkpoint "
                "overwrote the snapshot generation; refusing to fake a "
                "rollback that keeps the suspect updates")
        self.central = arr
        if meta is not None:
            self.staleness.version = int(meta.get("version", 0))
            self._push_count = int(meta.get("push_count", 0))
            self._apply_seq = ckpt_seq
            self.applied_by_sender = {
                int(k): int(v)
                for k, v in meta.get("applied_by_sender", {}).items()}
        else:
            self._apply_seq = 0
        # a rollback discards the live optimizer state with the live
        # vector: re-adopt the checkpoint's generation, then the capped
        # replay below catches BOTH up to the target together
        self._restore_optimizer_state(meta)
        if self.combine == "adasum":
            self._overlap.clear()  # overlap windows described the
            # discarded regime; workers re-pull at the barrier anyway
        replayed = 0
        if self.wal is not None:
            records, _stats = self.wal.replay()
            for rec in records:
                if rec.seq <= self._apply_seq or rec.seq > target_seq:
                    continue
                if rec.payload.shape != self.central.shape:
                    raise ValueError(
                        f"WAL record seq {rec.seq} holds "
                        f"{rec.payload.shape[0]} params but the restored "
                        f"vector holds {self.central.shape[0]}")
                self._apply_delta(rec.payload)
                self._apply_seq = rec.seq
                self._push_count += 1
                self.staleness.version += 1
                self.applied_by_sender[rec.sender] = (
                    self.applied_by_sender.get(rec.sender, 0) + 1)
                replayed += 1
            self.wal.drop_after(target_seq)
        discarded = max(0, before_seq - self._apply_seq)
        self.rolled_back_updates += discarded
        self._restored = True
        if self._delta_reset_on_restore:
            # rollback rewinds apply seqs the delta plane may have already
            # stamped onto replies: same version number, different bytes.
            # The epoch bump is what keeps those from ever colliding.
            self.reset_pull_bases()
        _LOGGER.warning(
            "rollback: restored apply seq %d (ckpt %d + %d WAL records), "
            "DISCARDED %d applied update(s) past the good snapshot",
            self._apply_seq, ckpt_seq, replayed, discarded)
        return discarded

    def commit(self) -> None:
        """Group commit: fsync the WAL batch, then release the delivery
        acks deferred behind it (``ReliableTransport.ack_delivered``) —
        log-before-ack is what upgrades "acked" to "survives a crash"."""
        rec = self.recorder
        if self.wal is not None:
            had_pending = self.wal.pending > 0
            t0 = time.monotonic_ns() if rec is not None else 0
            self.wal.sync()
            if rec is not None and had_pending:
                # only real fsyncs land on the timeline — the idle-loop
                # commit() with an empty group is a no-op, not a span
                rec.record("wal-fsync", "wal", t0, time.monotonic_ns(),
                           corr=0)
        ack = getattr(self.transport, "ack_delivered", None)
        if ack is not None:
            ack()

    def handle(self, sender: int, code: MessageCode, payload: np.ndarray) -> None:
        _LOGGER.info("Processing message: %s", code.name)
        self.message_counts[code] = self.message_counts.get(code, 0) + 1
        if code == MessageCode.GradientUpdate:
            self._apply_update(sender, payload)
        # 13 == compress.HEAD_LEN + 1 = the schema's min_size — a literal
        # because the distcheck wire checker reads size guards statically
        elif code == MessageCode.CompressedUpdate and payload.size >= 13:
            # the compressed gradient wire (ISSUE 14): DECODE FIRST — the
            # admission gate, the WAL and the apply path must all see the
            # decoded delta (a gate judging wire bytes is exactly what the
            # distmodel `decode_before_admission` mutation breaks)
            from distributed_ml_pytorch_tpu.utils.compress import (
                CompressionError,
                decode_update,
            )

            try:
                _stamp, codec_id, delta = decode_update(payload)
            except CompressionError as e:
                # malformed/corrupt compressed frames are dropped BEFORE
                # any accounting — same contract as a wrong-size dense push
                self.dropped_bad_updates += 1
                _LOGGER.warning(
                    "dropping CompressedUpdate from %d: %s", sender, e)
                return
            self._apply_update(sender, delta, codec=codec_id)
        elif code == MessageCode.CompressedUpdate:
            # shorter than head+1: even the guarded branch above cannot
            # take it — still a malformed frame, still loudly counted
            self.dropped_bad_updates += 1
            _LOGGER.warning(
                "dropping truncated CompressedUpdate from %d "
                "(%d floats, head is 12)", sender, payload.size)
        elif code == MessageCode.ParameterRequest:
            # codec plane (ISSUE 18): a non-empty request tail is the
            # worker's held stamp ``[held_epoch, held_ver_lo, held_ver_hi]``
            # opting into delta replies; empty is the legacy full pull
            if payload.size >= 3 and np.isfinite(payload[:3]).all():
                held = (int(payload[0]), _join16(payload[1], payload[2]))
                self._reply_delta(sender, held)
            else:
                self._reply(sender, self.central)
            self.staleness.on_pull(sender)
            if self.combine == "adasum":
                # the worker now sees everything applied so far: its
                # overlap window restarts empty
                self._overlap[sender] = np.zeros_like(self.central)
        elif code == MessageCode.ParameterUpdate:
            if self._restored:
                # a restored server must not let a fresh worker's
                # construction-time install stomp the checkpoint; answer
                # with the authoritative params instead (the worker's
                # listener swaps them in between steps — the rejoin flow).
                # NOTE: _restored is PERMANENT — every later ParameterUpdate
                # from any worker is likewise answered, never applied. Only
                # construction-time installs use this message today; a future
                # protocol change that sends ParameterUpdate to the server
                # mid-run must account for this (counted + logged so the
                # rejection is observable, not silent).
                self.rejected_installs += 1
                _LOGGER.info(
                    "restored server: rejecting install #%d from worker %d, "
                    "answering with authoritative params",
                    self.rejected_installs, sender,
                )
                self._reply(sender, self.central)
            else:
                self.central = payload.astype(np.float32).copy()

    def _apply_update(self, sender: int, payload: np.ndarray,
                      codec: int = 0) -> None:
        """THE apply path, shared by dense and compressed pushes (ISSUE
        14): size gate -> admission on the DECODED delta -> staleness
        damping or Adasum combine -> WAL append (the optimizer's input +
        the codec id) -> optimizer step -> apply. Ordering is the
        protocol: validation and admission run before any accounting, the
        WAL record lands before the mutation (DC402), and the logged
        value is exactly what replay must feed the optimizer to reproduce
        both the vector and the optimizer state."""
        if payload.shape != self.central.shape:
            # validate BEFORE any accounting or WAL append: a wrong-size
            # update must not inflate the apply clock, poison the log
            # with a record replay can never fit (it would refuse every
            # future restore), or numpy-broadcast into the vector
            self.dropped_bad_updates += 1
            _LOGGER.warning(
                "dropping update from %d: %d params vs central "
                "%d (wrong model / stale partition?)", sender,
                payload.shape[0], self.central.shape[0])
            return
        if self.admission is not None:
            # the admission gate (ISSUE 8) runs BEFORE accounting and
            # BEFORE the WAL append: a quarantined update must not
            # inflate the apply clock nor enter the log (a logged
            # poisoned record would be replayed on every restore)
            verdict = self.admission.evaluate(sender, payload)
            if verdict is not None:
                self._quarantine_update(sender, verdict)
                return
        # workers pre-scale by -lr (Asynchronous.py:55) → server-side add
        rec = self.recorder
        staleness = self.staleness.on_push(sender)
        if self.staleness_damping > 0.0 and staleness > 0:
            delta = (payload / (1.0 + self.staleness_damping * staleness)
                     ).astype(np.float32)
        elif self.combine == "adasum":
            delta = self._adasum_combine(sender, payload)
        else:
            delta = payload
        self._apply_seq += 1
        self.applied_by_sender[sender] = (
            self.applied_by_sender.get(sender, 0) + 1)
        if self.wal is not None:
            # log-before-apply(-before-ack): the COMBINED delta (post
            # damping/adasum, pre optimizer) is what replay must feed the
            # optimizer to reproduce the applied bytes AND the optimizer
            # state; once the record is fsync'd (commit()) the delivery
            # ack is released and the update can never be lost. The codec
            # id records which wire encoding delivered it (drill-audited).
            env_inc, env_seq = self._envelope or (0, 0)
            t0 = time.monotonic_ns() if rec is not None else 0
            self.wal.append(self._apply_seq, delta, sender=sender,
                            env_inc=env_inc, env_seq=env_seq,
                            codec=codec)
            if rec is not None:
                rec.record("wal-append", "wal", t0, time.monotonic_ns(),
                           meta={"sender": sender,
                                 "seq": self._apply_seq})
            if env_inc or env_seq:
                self._recent_envelopes.append(
                    (sender, env_inc, env_seq))
        t0 = time.monotonic_ns() if rec is not None else 0
        applied = self._apply_delta(delta)
        if rec is not None:
            # the corr id the delivering envelope restored into this
            # thread stitches push -> admission -> WAL -> apply -> ack
            rec.record("apply", "apply", t0, time.monotonic_ns(),
                       meta={"sender": sender, "seq": self._apply_seq})
        if self.combine == "adasum":
            # what actually moved the params joins every OTHER worker's
            # overlap window (their next push raced this one)
            for other, o in self._overlap.items():
                if other != sender and o.shape == applied.shape:
                    o += applied
        self._push_count += 1
        if self.ckpt_dir and self.ckpt_every and (
            self._push_count % self.ckpt_every == 0
        ):
            self.save_checkpoint()

    def _apply_delta(self, delta: np.ndarray) -> np.ndarray:
        """Run the (optional) server-side optimizer and mutate the
        central vector; returns the delta that actually applied. Shared
        by the live path, WAL replay and rollback so the optimizer state
        can never drift between them."""
        if self.optimizer is not None:
            delta = self.optimizer.step(delta)
        self.central += delta
        return delta

    def _adasum_combine(self, sender: int, payload: np.ndarray,
                        ) -> np.ndarray:
        """Adasum against this worker's overlap window (the deltas applied
        since its last pull). No window yet — the worker has not pulled
        since the mode came up, or the vector was resized — means no
        overlap knowledge: plain add, and the stale window is discarded."""
        o = self._overlap.get(sender)
        if o is None or o.shape != payload.shape:
            self._overlap.pop(sender, None)
            return payload
        from distributed_ml_pytorch_tpu.parallel.optplane import (
            adasum_adjust,
        )

        return adasum_adjust(o, payload)

    def _quarantine_update(self, sender: int, verdict) -> None:
        """Record one rejected update and tell the worker EXPLICITLY.

        The ``UpdateNack`` frame (reason + clamped norm/z) is what keeps a
        reject from being a silent drop: the worker counts it, resyncs by
        pulling fresh params, and reports the count in its lease renewals
        (the coordinator's reputation input). The update itself never
        touches the central vector, the apply clock, or the WAL."""
        from distributed_ml_pytorch_tpu.utils.health import (
            NACK_REASONS,
            clamp_finite32,
        )

        reason, norm, z = verdict
        self.quarantined += 1
        self.quarantined_by_sender[sender] = (
            self.quarantined_by_sender.get(sender, 0) + 1)
        self.quarantine.append((sender, int(reason), float(norm), float(z)))
        if self.recorder is not None:
            self.recorder.event(
                "quarantine", sender=sender, reason=int(reason),
                norm=clamp_finite32(norm), z=clamp_finite32(z))
        _LOGGER.warning(
            "quarantined GradientUpdate #%d from worker %d: %s "
            "(norm %.3g, z %.2f) — nacking",
            self.quarantined_by_sender[sender], sender,
            NACK_REASONS.get(int(reason), reason), norm, z)
        # the wire carries float32: clamp inf norms (the very thing being
        # rejected) so the nack itself survives the receivers' finite guards
        frame = np.asarray(
            [float(reason), clamp_finite32(norm), clamp_finite32(z)],
            np.float32)
        try:
            send_message(MessageCode.UpdateNack, frame, dst=sender,
                         transport=self.transport)
            self.nacks_sent += 1
        except (OSError, ConnectionError, KeyError):
            _LOGGER.warning(
                "UpdateNack to worker %d failed (peer gone?) — the "
                "quarantine stands; its next pull resyncs it anyway", sender)

    def _reply_delta(self, sender: int, held: Tuple[int, int]) -> None:
        """Answer a delta-opted pull (ISSUE 18): ship ``central - view``
        on the top-k rung when this server tracks the worker's exact
        materialized view at the held stamp, a full dense install (codec
        0) otherwise — version miss, epoch fence, first pull, resize.

        The tracked base is updated by replaying our OWN encode -> decode,
        so server and worker views stay bitwise identical and the next
        delta automatically carries the error feedback (everything the
        top-k body could not represent is still in ``central - view``)."""
        central = self.central
        ver = self._apply_seq
        epoch = self._pull_epoch
        base = self._pull_bases.get(sender)
        held_epoch, held_ver = held
        use_delta = (
            base is not None
            and held_epoch >= 0
            and base[2].shape == central.shape)
        if use_delta and self._delta_check_held:
            # the held stamp must name EXACTLY the view we track — a
            # worker that missed a reply (or a server tracking a base the
            # worker never pulled) falls back to a full install. Skipping
            # this check is the `stale_delta_base` mutation.
            use_delta = (base[0] == held_epoch == epoch
                         and base[1] == held_ver)
        if use_delta:
            raw = central - base[2]
            cid, body = codecs.encode_body(
                MessageCode.DeltaParams, raw, CODEC_TOPK)
            base_ver = base[1]
        else:
            cid, body = codecs.encode_body(
                MessageCode.DeltaParams, central, CODEC_DENSE)
            base_ver = 0
        decoded = codecs.decode_body(
            MessageCode.DeltaParams, cid, body, central.size)
        view = (base[2] + decoded) if use_delta else decoded
        self._pull_bases[sender] = (epoch, ver, view.astype(np.float32))
        n = int(central.size)
        crc = body_crc(body)
        head = np.asarray(
            [float(cid), float(epoch), *_split16(base_ver), *_split16(ver),
             *_split16(0), *_split16(n), *_split16(n), *_split16(crc)],
            np.float32)
        if use_delta:
            self.delta_replies += 1
        else:
            self.full_replies += 1
        self.delta_reply_wire_floats += int(head.size) + int(body.size)
        try:
            send_message(
                MessageCode.DeltaParams, np.concatenate([head, body]),
                dst=sender, transport=self.transport)
        except (OSError, ConnectionError, KeyError):
            # the reply is lost but the BASE TABLE already moved on: the
            # held-stamp check above is what turns that into a full
            # install on the worker's next pull instead of divergence
            _LOGGER.warning(
                "delta reply to worker %d failed (peer gone?) — dropping "
                "it; its next pull full-syncs via the held-stamp miss",
                sender)

    def _reply(self, sender: int, payload: np.ndarray) -> None:
        """Answer one worker; a worker that died between its request and
        this reply must not take the whole server down (the send raises on
        a crashed peer — robustness, not protocol)."""
        code = MessageCode.ParameterUpdate
        if self.pull_reply_head is not None:
            # versioned elastic reply: the receiver checks the stamped map
            # version, so equal-size cross-version replies can never apply
            code = MessageCode.ShardParams
            payload = np.concatenate(
                [self.pull_reply_head,
                 np.asarray(payload, np.float32).ravel()])
        try:
            send_message(
                code, payload, dst=sender,
                transport=self.transport,
            )
        except (OSError, ConnectionError, KeyError):
            _LOGGER.warning(
                "reply to worker %d failed (peer gone?) — dropping it; the "
                "worker re-pulls on its next cadence if it returns", sender,
            )

    def run(self, timeout: Optional[float] = None) -> None:
        """Serve until all workers finish (or ``stop()``/``timeout``).

        With ``worker_timeout`` set, a worker silent past that many seconds
        (no frame of any kind — heartbeats count) is declared failed and
        stops being waited for, so one crashed worker can't hang the world
        (the reference server would wait forever, SURVEY.md §5.3).
        """
        done_workers = set()
        detector = None
        if self.worker_timeout and self.n_workers is not None:
            from distributed_ml_pytorch_tpu.utils.failure import FailureDetector

            # launcher convention: server is rank 0, workers are 1..n_workers
            detector = FailureDetector(
                self.worker_timeout, ranks=range(1, self.n_workers + 1)
            )
        deadline = None if timeout is None else time.monotonic() + timeout
        while not self._stop.is_set():
            if deadline is not None and time.monotonic() >= deadline:
                break
            if detector is not None:
                for rank in sorted(detector.expired()):
                    print(
                        "parameter server: worker {} silent for {:.1f}s — "
                        "declaring it failed".format(rank, self.worker_timeout)
                    )
                self.failed_workers = set(detector.failed)
                if (
                    len(done_workers) + len(self.failed_workers) >= self.n_workers
                ):
                    break
            msg = self.transport.recv(timeout=0.2)
            if msg is None:
                # idle: close out any open WAL group so deferred acks are
                # never withheld longer than one recv timeout
                self.commit()
                continue
            sender, code, payload = msg
            self._envelope = getattr(self.transport, "last_delivery", None)
            if detector is not None:
                detector.note(sender)  # a failed rank that speaks rejoins
                self.failed_workers = set(detector.failed)
            if code == MessageCode.Heartbeat:
                self.message_counts[code] = self.message_counts.get(code, 0) + 1
                continue
            if code == MessageCode.WorkerDone:
                done_workers.add(sender)
                self.commit()  # its (possibly deferred) ack must not wait
                if detector is not None:
                    detector.forget(sender)
                # failed_workers excludes done_workers by construction: note()
                # above rejoined this sender before it was marked done
                if self.n_workers is not None and (
                    len(done_workers) + len(self.failed_workers) >= self.n_workers
                ):
                    break
                continue
            self.handle(sender, code, payload)
            if (self.wal is None
                    or code not in (MessageCode.GradientUpdate,
                                    MessageCode.CompressedUpdate)
                    or self.wal.pending >= self.wal_group_n):
                # group-fsync batching applies to the gradient stream only;
                # everything else commits (and releases its ack) immediately
                self.commit()
        self.save_checkpoint()  # final state survives a clean shutdown too
        self.commit()
        line = self.staleness.report()
        if line:
            print("parameter server:", line)


def validate_downpour_args(lr: float, n_push: int, n_pull: int) -> None:
    """Cadence/lr validation shared by both DownPour clients."""
    if lr < 0.0:
        raise ValueError("Invalid learning rate: {}".format(lr))
    if int(n_push) < 1 or int(n_pull) < 1:
        raise ValueError(
            "Invalid cadence: n_push={}, n_pull={} (both must be >= 1)".format(
                n_push, n_pull
            )
        )


def init_downpour_accumulator(params: Pytree):
    """``(flat_init, flat_n, pad, accum)`` shared by both DownPour clients:
    accumulator allocation parity with the reference (zeros sized like the
    raveled model, Asynchronous.py:27) rounded up to a lane multiple so the
    device accumulate takes the Pallas flat-axpy path on TPU; the pad tail
    stays zero and is sliced off before anything leaves the device."""
    from distributed_ml_pytorch_tpu.ops.fused_update import LANES

    flat = np.asarray(ravel_model_params(params), np.float32)
    n = int(flat.shape[0])
    pad = (-n) % LANES
    return flat, n, pad, jnp.zeros(n + pad, jnp.float32)


def default_downpour_tx(lr: float):
    """The reference worker recipe as an optax transform: plain SGD, no
    momentum (``optim.SGD(lr, momentum=0.0)``, ``example/main.py:44``). Its
    updates are exactly ``−lr·grads``, which keeps :func:`_downpour_micro_update`
    bit-identical to the reference's lr-pre-scaled accumulation."""
    import optax

    return optax.sgd(lr)


def _downpour_micro_update(tx, params, opt_state, grads, accum, pad: int):
    """THE DownPour per-step device math (Asynchronous.py:55,63-68),
    shared verbatim by the per-step jitted step and the chunked scan body
    so the two dispatch disciplines cannot drift — generalized (VERDICT r3
    #1) from hardwired ``−lr·grads`` to any optax local optimizer:

    the local transform turns grads into UPDATES (param deltas; for the
    default :func:`default_downpour_tx` these are exactly ``−lr·grads``,
    since IEEE negation is exact — the reference math bit-for-bit), the
    flat update accumulates into the push buffer (Pallas flat-axpy on TPU),
    and the same deltas apply locally. The server contract is unchanged —
    it ADDS the pushed vector (M1 ``central += payload``); with momentum /
    adam / a schedule / clipping the payload is the sum of local param
    deltas rather than ``−lr·Σgrads``, the natural DownPour generalization
    (central moves by what the worker moved).
    """
    from distributed_ml_pytorch_tpu.ops import flat_axpy

    updates, opt_state = tx.update(grads, opt_state, params)
    flat_updates = ravel_model_params(params, grads=updates)
    if pad:
        # folds into the concatenate ravel already performs — the
        # padded flat vector costs no extra HBM pass
        flat_updates = jnp.concatenate(
            [flat_updates, jnp.zeros(pad, flat_updates.dtype)]
        )
    accum = flat_axpy(accum, flat_updates, 1.0)
    new_params = jax.tree.map(
        lambda p, u: p + u.astype(p.dtype), params, updates
    )
    return new_params, opt_state, accum


def make_downpour_device_step(tx, pad: int):
    """The jitted DownPour device step shared by the single-server and
    sharded-PS clients (``_downpour_micro_update`` under jit). ``accum`` is
    donated: the axpy's output aliases its buffer, so the accumulation
    really is in place in HBM; ``opt_state`` is donated for the same
    reason (momentum/adam buffers update in place)."""
    from functools import partial

    @partial(jax.jit, donate_argnums=(1, 3))
    def _device_step(params, opt_state, grads, accum):
        return _downpour_micro_update(tx, params, opt_state, grads, accum, pad)

    return _device_step


def downpour_chunk_schedule(
    n_push: int, n_pull: int, start: int, stop: int, max_chunk: int = 64
):
    """Static dispatch schedule for steps ``[start, stop)``: the runs of
    purely-local SGD between host-communication gaps.

    A comm gap sits between steps ``t−1`` and ``t`` when a pull opens step
    ``t`` (``t % n_pull == 0``) or a push closed step ``t−1``
    (``(t−1) % n_push == 0`` — note the +1 offset: a push fires AFTER its
    step, so gcd(n_push, n_pull)-sized uniform chunks would misplace push
    payloads). Every step inside a run is pure local SGD, so the whole run
    compiles into one ``lax.scan`` dispatch with identical semantics.

    Returns ``[(gap, length), …]`` with global gap indices and lengths
    summing to ``stop − start``; lengths are capped at ``max_chunk`` (bounds
    host-side batch stacking; an extra cut is a no-op boundary). Distinct
    lengths are few (≤ 4 for any cadence pair), so each scan compiles once.
    """
    gaps = {start, stop}
    gaps |= {t for t in range(start, stop) if t % n_pull == 0}
    gaps |= {t + 1 for t in range(start, stop) if t % n_push == 0}
    cuts = sorted(g for g in gaps if start <= g <= stop)
    out = []
    for a, b in zip(cuts, cuts[1:]):
        while b - a > max_chunk:
            out.append((a, max_chunk))
            a += max_chunk
        if b > a:
            out.append((a, b - a))
    return out


def make_downpour_chunk_step(model, tx, pad: int):
    """Fused multi-step DownPour dispatch (VERDICT r2 #2): one compiled
    ``lax.scan`` runs a whole between-comm run of local SGD — per micro-step
    the loss/grad, the flat update accumulation (Pallas flat-axpy on
    TPU) and the local update (``Asynchronous.py:55,63-68`` semantics,
    identical to :func:`make_downpour_device_step` iterated) — so a TPU
    worker pays one host dispatch per comm boundary instead of per batch
    (the per-step dispatch was ~1600× off the chip's scanned throughput).
    Emits per-step losses so the reference's per-iteration CSV telemetry
    survives chunking. ``params``, ``opt_state`` and ``accum`` buffers are
    donated.
    """
    from functools import partial

    from distributed_ml_pytorch_tpu.training.trainer import cross_entropy_loss

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def chunk_step(params, opt_state, accum, bxs, bys, rng, idx0):
        def body(carry, xs):
            params, opt_state, accum, idx = carry
            bx, by = xs

            def loss_fn(q):
                logits = model.apply(
                    {"params": q}, bx, train=True,
                    rngs={"dropout": jax.random.fold_in(rng, idx)},
                )
                return cross_entropy_loss(logits, by)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            params, opt_state, accum = _downpour_micro_update(
                tx, params, opt_state, grads, accum, pad
            )
            return (params, opt_state, accum, idx + 1), loss

        (params, opt_state, accum, _), losses = jax.lax.scan(
            body, (params, opt_state, accum, idx0), (bxs, bys)
        )
        return params, opt_state, accum, losses

    return chunk_step


class Listener(MessageListener):
    """C2 parity (``Asynchronous.py:9-18``): receives ParameterUpdate pushes.

    Instead of writing into live parameters mid-step (the reference's
    lock-free race), deposits the newest flat vector into a mailbox for the
    optimizer to swap in between steps.

    Elastic servers reply with ``ShardParams`` — the same vector prefixed
    with the server's shard-map version and the absolute range it serves
    (``[ver_lo, ver_hi, lo_lo, lo_hi, hi_lo, hi_hi, *params]``). The stamp
    rides the mailbox so the elastic client can drop a reply cut for other
    offsets even when the sizes coincide (the equal-size stale-map blind
    spot, closed in ISSUE 6).
    """

    def __init__(self, transport: Optional[Transport] = None):
        super().__init__(transport=transport)
        self._lock = threading.Lock()
        self._latest: Optional[np.ndarray] = None
        #: (version, lo, hi) of the newest reply; None for a legacy
        #: unversioned ParameterUpdate
        self._latest_stamp: Optional[Tuple[int, int, int]] = None
        self._got_update = threading.Event()
        #: admission nacks (ISSUE 8): total received, and the batch not yet
        #: consumed by the optimizer (``take_nacks`` — each consumed batch
        #: triggers ONE resync pull, not one per frame)
        self.nacks = 0
        self._nacks_pending = 0
        # --- codec plane (ISSUE 18): delta-reply state -------------------
        #: the worker's materialized view of the central vector and the
        #: (epoch, version) stamp it sits at — what the next pull's held
        #: stamp names, and the base the next delta applies onto
        self._view: Optional[np.ndarray] = None
        self._held: Optional[Tuple[int, int]] = None
        #: deltas dropped because the stamped base was not the held view
        self.delta_base_miss = 0
        self.delta_installs = 0
        self.full_installs = 0
        #: mutation knob (analysis/distmodel.py `stale_delta_base`): the
        #: clean listener refuses a delta whose base stamp is not exactly
        #: its held view; True applies it blindly onto whatever it has
        self.delta_trust = False
        #: gray plane (ISSUE 20): pull replies of ANY kind delivered on
        #: this link — even a malformed one proves the wire carried a
        #: frame. The worker's requests-vs-replies window delta is the
        #: third-party link evidence that catches a ONE-WAY partition the
        #: server's own renew tail can never see.
        self.replies = 0

    def held_stamp(self) -> np.ndarray:
        """This worker's pull-request tail: ``[held_epoch, held_ver_lo,
        held_ver_hi]`` (epoch −1 = no materialized view, force a full
        dense reply)."""
        with self._lock:
            if self._held is None or self._view is None:
                return np.asarray([-1.0, 0.0, 0.0], np.float32)
            epoch, ver = self._held
            return np.asarray([float(epoch), *_split16(ver)], np.float32)

    def _on_delta_params(self, parameter: np.ndarray) -> None:
        # head: codec epoch base(2) ver(2) lo(2) hi(2) n(2) crc(2) = 14
        if parameter.size < 15 or not np.isfinite(parameter[:14]).all():
            return  # malformed: drop, never die
        cid = int(parameter[0])
        epoch = int(parameter[1])
        base_ver = _join16(parameter[2], parameter[3])
        ver = _join16(parameter[4], parameter[5])
        lo = _join16(parameter[6], parameter[7])
        hi = _join16(parameter[8], parameter[9])
        n = _join16(parameter[10], parameter[11])
        crc = _join16(parameter[12], parameter[13])
        body = parameter[14:]
        # range-gate + integrity on the STAMP before paying for a decode
        if hi - lo != n or body_crc(body) != crc:
            return
        try:
            decoded = codecs.decode_body(
                MessageCode.DeltaParams, cid, body, n)
        except CompressionError:
            return
        with self._lock:
            if cid == CODEC_DENSE:
                # full install: adopt unconditionally (the fallback rung)
                self._view = decoded
                self._held = (epoch, ver)
                self.full_installs += 1
            else:
                ok = (self._view is not None and self._view.size == n
                      and (self.delta_trust
                           or self._held == (epoch, base_ver)))
                if not ok:
                    # a delta against a base this worker never
                    # materialized: drop it and let the next pull's held
                    # stamp (or epoch mismatch) force a full reply
                    self.delta_base_miss += 1
                    return
                self._view = (self._view + decoded).astype(np.float32)
                self._held = (epoch, ver)
                self.delta_installs += 1
            self._latest = self._view
            self._latest_stamp = None
        self._got_update.set()

    def receive(self, sender: int, message_code: MessageCode, parameter: np.ndarray) -> None:
        _LOGGER.info("Processing message: %s", message_code.name)
        if message_code in (MessageCode.DeltaParams,
                            MessageCode.ParameterUpdate,
                            MessageCode.ShardParams):
            with self._lock:
                self.replies += 1
        if message_code == MessageCode.DeltaParams:
            self._on_delta_params(parameter)
        elif message_code == MessageCode.ParameterUpdate:
            with self._lock:
                self._latest = parameter
                self._latest_stamp = None  # legacy unversioned reply
            self._got_update.set()
        elif message_code == MessageCode.ShardParams:
            if parameter.size < 7 or not np.isfinite(parameter[:6]).all():
                return  # malformed stamped reply: drop, never die
            from distributed_ml_pytorch_tpu.utils.messaging import _join16

            with self._lock:
                self._latest = parameter[6:]
                self._latest_stamp = (
                    _join16(parameter[0], parameter[1]),
                    _join16(parameter[2], parameter[3]),
                    _join16(parameter[4], parameter[5]))
            self._got_update.set()
        elif message_code == MessageCode.UpdateNack:
            # the server QUARANTINED one of this worker's pushes (admission
            # gate, ISSUE 8): count it — the optimizer resyncs by pulling
            # fresh params instead of silently diverging
            if parameter.size >= 3 and np.isfinite(parameter[:1]).all():
                with self._lock:
                    self.nacks += 1
                    self._nacks_pending += 1

    def take_latest(self) -> Optional[np.ndarray]:
        with self._lock:
            latest, self._latest = self._latest, None
            self._latest_stamp = None
        return latest

    def take_latest_versioned(
            self) -> Tuple[Optional[Tuple[int, int, int]],
                           Optional[np.ndarray]]:
        """Newest reply with its ``(version, lo, hi)`` stamp (``None``
        stamp for a legacy unversioned ``ParameterUpdate``)."""
        with self._lock:
            latest, self._latest = self._latest, None
            stamp, self._latest_stamp = self._latest_stamp, None
        return stamp, latest

    def take_nacks(self) -> int:
        """Unconsumed admission nacks since the last take (the optimizer's
        resync trigger)."""
        with self._lock:
            n, self._nacks_pending = self._nacks_pending, 0
            return n

    def wait_for_update(self, timeout: float) -> bool:
        """Block until at least one ParameterUpdate has ever arrived (it may
        already be consumed); False on timeout. Lets a worker synchronize on
        the server's authoritative install before its first step."""
        return self._got_update.wait(timeout)


class PushFlusher:
    """Background push pipeline (VERDICT r4 #5): overlap the DownPour push
    with compute.

    The worker's push previously blocked its loop twice at every cadence
    boundary — a device→host fetch of the flat accumulator (9.9 MB for
    AlexNet) and the socket write — before the next chunk could even be
    dispatched.
    Now the boundary just SNAPSHOTS the device-resident accumulator
    (``self.accum`` is rebound to zeros; the immutable snapshot rides the
    queue) and returns; this thread fetches and sends it while the device
    runs the next chunk — wire+fetch time hides under device time, and
    the reference's own listener-thread concurrency intent
    (``asgd/optim/Asynchronous.py:9-18``) is extended to the send side.

    FIFO by construction (one thread, one queue) so pushes arrive in
    cadence order; :meth:`drain` joins all pending sends — ``finish()``
    calls it before the final flush so the last push cannot overtake an
    earlier one. Transport sends are thread-safe (per-destination locks in
    ``utils/messaging.TCPTransport``; the in-process transport is
    queue-based), so a pull request from the training thread may interleave
    BETWEEN pushes on the wire — which is exactly the async-DownPour
    contract."""

    #: in-flight bound: one push being fetched/sent + one queued behind it.
    #: enqueue() BLOCKS beyond that — natural backpressure, so a wire slower
    #: than compute cannot pin unboundedly many device-resident snapshots
    #: (each is ~the model size) nor grow push staleness without limit; the
    #: training thread then waits at the cadence boundary exactly as the
    #: pre-overlap code always did, just two pushes later. With the adaptive
    #: wire (ISSUE 7) the chain extends one level down: a send blocked at
    #: the reliability layer's credit window holds THIS thread, this queue
    #: fills, and the cadence boundary stalls — receiver pressure reaches
    #: the training loop with no unbounded buffer anywhere in between.
    #: :attr:`wire_blocked_s` totals the time sends spent wire-blocked (the
    #: observable for "how much is the network the bottleneck").
    MAX_IN_FLIGHT = 2

    #: sends slower than this are attributed to wire backpressure in
    #: :attr:`wire_blocked_s` (fetch+serialize is well under it on any rig)
    _BLOCK_ATTRIB_S = 0.05

    def __init__(self, send_fn):
        self._send_fn = send_fn  # called with the fetched np.ndarray
        self._q: "queue.Queue" = queue.Queue(maxsize=self.MAX_IN_FLIGHT)
        self.wire_blocked_s = 0.0
        self._thread = threading.Thread(
            target=self._run, name="downpour-push-flusher", daemon=True)
        self._thread.start()

    def _run(self):
        while True:
            item = self._q.get()
            try:
                if item is None:
                    return
                # np.asarray blocks THIS thread for device completion + the
                # device→host transfer; the training thread keeps going
                arr = np.asarray(item)
                t0 = time.monotonic()
                self._send_fn(arr)
                dt = time.monotonic() - t0
                if dt > self._BLOCK_ATTRIB_S:
                    self.wire_blocked_s += dt
            except Exception as e:  # noqa: BLE001 — the thread must survive
                # degrade-never-crash, matching _send: a failed fetch/send
                # loses THIS push (accepted async staleness) instead of
                # killing the thread — a dead thread would strand queued
                # items and deadlock drain()/finish()
                print(f"push flusher: dropping one push after {type(e).__name__}: {e}",
                      file=sys.stderr)
            finally:
                self._q.task_done()

    def enqueue(self, device_vec) -> None:
        self._q.put(device_vec)

    def drain(self) -> None:
        """Block until every enqueued push has been fetched AND sent."""
        self._q.join()

    def stop(self) -> None:
        """End the thread behind every pending push (FIFO: the sentinel
        queues last), waiting at most ten seconds for the queue to take it
        and ten for the thread to reach it. A send stalled on a silently
        dead peer would otherwise hang here, or let ``finish()`` pass for
        clean with a push stuck: it is reported on stderr."""
        try:
            self._q.put(None, timeout=10)
        except queue.Full:
            pass
        self._thread.join(timeout=10)
        if self._thread.is_alive():
            print("push flusher: still sending at stop() — a push is stuck "
                  "on the wire and was not delivered", file=sys.stderr)


class Asynchronous:
    """DownPour-SGD client optimizer (C1 parity, ``Asynchronous.py:20-71``).

    Functional step API: ``params = opt.step(params, grads)``. Keeps the
    reference's cadence semantics exactly — including firing both the pull
    request and the push on step index 0, as the reference's ``idx % n == 0``
    tests do (``:48,58``).
    """

    def __init__(
        self,
        params: Pytree,
        lr: float,
        n_push: int,
        n_pull: int,
        *,
        tx=None,
        transport: Optional[Transport] = None,
        heartbeat: Optional["HeartbeatSender"] = None,
        rejoin: bool = False,
        install_timeout: float = 5.0,
        compress: Optional[str] = None,
        compress_opts: Optional[dict] = None,
        error_feedback: bool = True,
        delta_pull: bool = False,
    ):
        validate_downpour_args(lr, n_push, n_pull)
        self.lr = float(lr)
        self.n_push = int(n_push)
        self.n_pull = int(n_pull)
        #: codec plane (ISSUE 18): opt into delta-encoded pull replies —
        #: every ParameterRequest carries the listener's held stamp and
        #: the server answers on the DeltaParams wire (top-k delta in
        #: steady state, full dense install on any miss/restore/resize)
        self.delta_pull = bool(delta_pull)
        self.transport = transport
        self.idx = 0
        self.unravel = make_unraveler(params)
        # ``tx`` generalizes the local optimizer (momentum / adam / schedule /
        # clipping — VERDICT r3 #1); the default is the reference recipe and
        # reproduces its math exactly (see _downpour_micro_update). The opt
        # state is WORKER-LOCAL and survives server installs: a pulled central
        # vector replaces params, not the worker's momentum — matching
        # DownPour, where each replica owns its optimizer state.
        self.tx = tx if tx is not None else default_downpour_tx(self.lr)
        self.opt_state = self.tx.init(params)
        _flat, self._flat_n, self._pad, self.accum = init_downpour_accumulator(params)
        # the listener attaches BEFORE anything is sent, so a server reply
        # (e.g. a restored server answering the install below) can never
        # race the listener's start — it no longer relies on the transport
        # buffering messages until the thread attaches
        self.listener = Listener(transport=transport)
        self.listener.start()
        if rejoin:
            # elastic restart: ADOPT the server's current central params
            # instead of stomping them with this process's fresh init. The
            # reply is awaited (bounded) so the rejoined worker's first step
            # already runs on central params; on timeout it proceeds locally
            # and the normal failure path applies.
            send_message(
                MessageCode.ParameterRequest, self._pull_payload(),
                transport=transport
            )
            if not self.listener.wait_for_update(timeout=install_timeout):
                print(
                    "worker: rejoin pull unanswered after {:.1f}s — starting "
                    "from local init (server slow or down)".format(install_timeout),
                    file=sys.stderr,
                )
        else:
            # install this worker's initial params as the central params (:34).
            # If the server was RESUMED from a checkpoint it rejects this and
            # answers with its authoritative vector, which the listener
            # installs at the first step boundary. Any push issued before
            # that reply lands carries lr-scaled deltas computed at the fresh
            # init — a one-round-trip transient that is ACCEPTED async
            # staleness (DownPour tolerates stale deltas by design; keeping
            # construction to the reference's single install message,
            # Asynchronous.py:34, outweighs closing it with an extra
            # handshake).
            send_message(
                MessageCode.ParameterUpdate, ravel_model_params(params), transport=transport
            )
        # a dead server degrades the worker to purely-local SGD (see _send).
        # The heartbeat (if any) is owned by the process entry, started before
        # any jit compile — liveness must reflect process health, not compile
        # progress; the optimizer only consults its peer_down flag.
        self.server_down = False
        self.heartbeat = heartbeat
        #: admission nacks consumed so far (ISSUE 8) — each batch triggers
        #: a resync pull toward the server
        self.nacks = 0
        #: post-nack hold (same discipline as ShardedAsynchronous): device
        #: updates are skipped from the nack until one step after the
        #: fresh pull installs — grads derived from the diverged params
        #: must not stomp the resync install, or the loop never converges
        #: (install, stomp, explode, nack, repeat)
        self._hold_updates = False
        self.skipped_updates = 0

        self._device_step = make_downpour_device_step(self.tx, self._pad)
        # --- compressed push wire (ISSUE 14) ----------------------------
        #: with ``compress="int8"|"topk"``, pushes ride the
        #: ``CompressedUpdate`` frame through an error-feedback encoder
        #: (utils/compress.CompressingEncoder): what a push could not
        #: represent carries into the next one, so compressed DownPour
        #: stays in the fault-free corridor. Touched only by the flusher
        #: thread (finish() drains it before the final inline push).
        self.encoder = None
        if compress:
            from distributed_ml_pytorch_tpu.utils.compress import (
                CompressingEncoder,
                make_codec,
            )

            self.encoder = CompressingEncoder(
                self._flat_n, make_codec(compress, **(compress_opts or {})),
                error_feedback=error_feedback)
        self._flusher = PushFlusher(self._send_push)

    def _send_push(self, arr: np.ndarray) -> None:
        """One push toward the server: dense ``GradientUpdate``, or a
        compressed ``CompressedUpdate`` (head, body) pair riding the
        transport's scatter/gather ``sendv``."""
        if self.encoder is None:
            self._send(MessageCode.GradientUpdate, arr)
            return
        head, body = self.encoder.encode_range(arr, 0, self._flat_n)
        self._sendv(MessageCode.CompressedUpdate, (head, body))

    def _guarded_send(self, do_send) -> None:
        """THE degrade discipline, shared by every wire shape: a dead
        server flips :attr:`server_down` once (with one warning) and the
        worker trains purely locally from then on (the reference would
        raise out of ``optimizer.step`` mid-epoch — SURVEY.md §5.3 notes
        it has no failure handling anywhere)."""
        if self.server_down:
            return
        if self.heartbeat is not None and self.heartbeat.peer_down:
            self.server_down = True
        else:
            try:
                do_send()
                return
            except (OSError, ConnectionError):
                self.server_down = True
        print(
            "worker: parameter server unreachable — continuing with "
            "purely-local SGD (no further push/pull)",
            file=sys.stderr,
        )

    def _sendv(self, code: MessageCode, parts) -> None:
        """Degrade-guarded multi-part (scatter/gather) send."""
        self._guarded_send(lambda: self.transport.sendv(code, parts))

    def _send(self, code: MessageCode, payload) -> None:
        """Degrade-guarded single-payload send toward the server."""
        self._guarded_send(
            lambda: send_message(code, payload, transport=self.transport))

    def _pull_payload(self) -> np.ndarray:
        """The ParameterRequest body: empty for a legacy full pull, the
        listener's held stamp when this worker opted into delta replies."""
        if self.delta_pull:
            return self.listener.held_stamp()
        return np.zeros(0, np.float32)

    def _resync_on_nacks(self) -> None:
        """The nack response (ISSUE 8): a quarantined push means this
        worker's view may be diverging from the central params it can no
        longer influence — pull fresh ones NOW instead of waiting out the
        cadence. One resync per consumed batch, not per frame."""
        n = self.listener.take_nacks()
        if n:
            self.nacks += n
            self._hold_updates = True
            print(
                f"worker: {n} push(es) quarantined by the server's "
                "admission gate — resyncing with a fresh pull",
                file=sys.stderr,
            )
            self._send(MessageCode.ParameterRequest, self._pull_payload())

    def boundary(self, gap: int) -> Optional[np.ndarray]:
        """Host-side communication for inter-step gap ``gap`` (the point
        between step ``gap − 1`` and step ``gap``) — the chunked dispatch
        path's counterpart of :meth:`step`'s per-step bookkeeping, in the
        same order: the push owed by step ``gap − 1`` (it ended that
        iteration), then the freshest server install + the pull owed by
        step ``gap`` (they open this one). Returns the installed flat
        vector (caller unravels at the chunk boundary) or None.
        """
        if gap >= 1 and (gap - 1) % self.n_push == 0:
            # snapshot-and-go: the device accumulator rides the flusher
            # queue (immutable jax array); fetch + wire happen on the
            # flusher thread while the caller dispatches the next chunk
            self._flusher.enqueue(self.accum[: self._flat_n])
            self.accum = jnp.zeros_like(self.accum)
        self._resync_on_nacks()
        latest = self.listener.take_latest()
        if latest is not None:
            # chunked dispatch folds updates ON DEVICE inside the chunk, so
            # the post-nack hold cannot skip them from here — the install
            # at the next chunk boundary is the resync (the stomp window is
            # bounded by one chunk); clear the flag so it cannot go stale
            self._hold_updates = False
        if gap % self.n_pull == 0:
            self._send(MessageCode.ParameterRequest, self._pull_payload())
        self.idx = gap
        return latest

    def step(self, params: Pytree, grads: Pytree) -> Pytree:
        self._resync_on_nacks()
        # decide the skip BEFORE this step's install lands: even on the
        # step that completes the resync, the grads in hand were computed
        # on the pre-install params and must not apply over it
        held = self._hold_updates
        # install the freshest server push at the step boundary (race-free
        # version of the reference's mid-step unravel, Asynchronous.py:17-18)
        latest = self.listener.take_latest()
        if latest is not None:
            params = self.unravel(jnp.asarray(latest))
            if held:
                self._hold_updates = False  # updates resume NEXT step

        # request fresh params every n_pull steps (:48-49); the reference
        # ships the accumulator as a dummy payload — an empty payload is the
        # intent (the request carries no information)
        if self.idx % self.n_pull == 0:
            self._send(MessageCode.ParameterRequest, self._pull_payload())

        if held:
            self.skipped_updates += 1
        else:
            params, self.opt_state, self.accum = self._device_step(
                params, self.opt_state, grads, self.accum
            )

        # push the accumulated updates every n_push steps (:58-60), via the
        # flusher so the fetch+wire overlap the next step's dispatch
        if self.idx % self.n_push == 0:
            self._flusher.enqueue(self.accum[: self._flat_n])
            self.accum = jnp.zeros_like(self.accum)

        self.idx += 1
        return params

    def finish(self) -> None:
        """Flush a final push, notify the server, stop the listener."""
        # in-flight pushes must land BEFORE the final one (cadence order);
        # the drain also quiesces the encoder's residual, so the final
        # compressed push folds it in on this thread race-free
        self._flusher.drain()
        self._send_push(np.asarray(self.accum[: self._flat_n]))
        # over a reliable transport, WorkerDone must barrier behind every
        # prior push: the layer guarantees delivery, not ordering, so an
        # unflushed retry could land after the server counted this worker
        # done and exited (the listener is still pumping acks here)
        flush = getattr(self.transport, "flush", None)
        if flush is not None and not self.server_down:
            flush(timeout=10.0)
        self._send(MessageCode.WorkerDone, np.zeros(0, np.float32))
        self._flusher.stop()
        if self.heartbeat is not None:
            self.heartbeat.stop()
        self.listener.stop()


# M4 contract parity: the same optimizer under its original DownPour name
# (asgd/optim/__init__.py:1 re-exports `DownpourSGD`; the reference's rename
# left a dangling super(DownpourSGD, ...) at Asynchronous.py:40).
DownpourSGD = Asynchronous


def train_worker(
    args, transport: Transport, heartbeat=None, opt_factory=None
) -> Tuple[Pytree, "MetricsLogger"]:
    """Worker-side training loop (reference ``main(args)`` distributed branch,
    ``example/main.py:31-105``).

    ``opt_factory(params, tx) -> optimizer`` overrides the default
    ``Asynchronous`` construction (the sharded-PS entry passes a
    ``ShardedAsynchronous`` builder); ``transport`` then serves only for
    rank-derived seeds/filenames. ``tx`` is the local optax transform built
    from the full CLI knob surface (``tx_from_args``) — optimizer choice,
    momentum, weight decay, clipping, LR schedule and grad accumulation all
    work in PS mode (VERDICT r3 #1).
    """
    from distributed_ml_pytorch_tpu.data import get_dataset, iterate_batches
    from distributed_ml_pytorch_tpu.training.trainer import (
        cross_entropy_loss,
        evaluate,
        make_eval_fn,
        tx_from_args,
    )
    from distributed_ml_pytorch_tpu.models import get_model
    from distributed_ml_pytorch_tpu.utils.metrics import MetricsLogger, print_eval_line
    from distributed_ml_pytorch_tpu.utils.tracing import TraceWindow

    x_train, y_train, x_test, y_test = get_dataset(args)
    model = get_model(getattr(args, "model", "alexnet"))
    seed = getattr(args, "seed", 0)
    params = model.init(jax.random.key(seed), jnp.zeros((1, 32, 32, 3)))["params"]
    steps_per_epoch = len(x_train) // args.batch_size
    tx = tx_from_args(args, steps_per_epoch)
    if opt_factory is not None:
        opt = opt_factory(params, tx)
    else:
        from distributed_ml_pytorch_tpu.utils.compress import (
            compress_from_args,
        )

        opt = Asynchronous(
            params,
            lr=args.lr,
            n_push=args.num_push,
            n_pull=args.num_pull,
            tx=tx,
            transport=transport,
            heartbeat=heartbeat,
            rejoin=getattr(args, "rejoin", False),
            **compress_from_args(args),
        )
    dropout_rng = jax.random.key(seed + 1 + transport.rank)

    @jax.jit
    def grad_fn(p, images, labels, rng, step):
        def loss_fn(q):
            logits = model.apply(
                {"params": q}, images, train=True,
                rngs={"dropout": jax.random.fold_in(rng, step)},
            )
            return cross_entropy_loss(logits, labels)

        return jax.value_and_grad(loss_fn)(p)

    eval_step = make_eval_fn(model)
    # worker CSVs default to an untracked run directory (ISSUE 8 satellite:
    # the tracked log/node*.csv churn is gone; `runs/` is .gitignored)
    logger = MetricsLogger(getattr(args, "log_dir", "runs"))

    # chunked dispatch (VERDICT r2 #2): on TPU the per-batch host dispatch
    # — not the DownPour protocol — dominated the PS worker; between comm
    # gaps every step is purely
    # local SGD, so those runs compile into one scan with exact cadence
    # semantics (downpour_chunk_schedule). Opt-out/in via --chunked-dispatch.
    # --steps-per-dispatch K caps the fused runs at K steps (and turns
    # chunking on when K > 1); the default (1) means auto (cap 64).
    spd = int(getattr(args, "steps_per_dispatch", 1) or 1)
    chunked = getattr(args, "chunked_dispatch", "auto")
    chunked = (
        (jax.default_backend() == "tpu" or spd > 1)
        if chunked == "auto"
        else (chunked in ("on", True))
    )
    chunked = chunked and hasattr(opt, "boundary")
    max_chunk = spd if spd > 1 else 64

    # profile window (SURVEY.md §5.1), addressed in worker-global steps
    # (epoch * steps_per_epoch + i) — same numbering as the CSV telemetry
    tracer = TraceWindow(
        getattr(args, "profile_dir", None),
        start=getattr(args, "profile_start", 10),
        n_steps=getattr(args, "profile_steps", 10),
    )
    # each worker shuffles with its own seed — the reference's per-worker
    # DataLoader(shuffle=True) gives independent streams (example/main.py:27)
    for epoch in range(args.epochs):
        print("Training for epoch {}".format(epoch))
        batches = iterate_batches(
            x_train, y_train, args.batch_size, seed=seed + 1000 * transport.rank, epoch=epoch
        )
        if chunked:
            chunk_step = _chunk_step_cache(opt, model)
            start = epoch * steps_per_epoch
            # telemetry is flushed in batches: a per-chunk device→host loss
            # fetch would re-add one device→host round trip per dispatch —
            # the very cost chunking exists to amortize. Losses stay on
            # device until an eval, a flush quota, or epoch end forces them.
            pending = []  # (rel_start, device losses, eval step set, ev)

            def flush():
                for rel0, dev_losses, eval_is, ev in pending:
                    for off, loss in enumerate(np.asarray(dev_losses)):
                        if hasattr(opt, "observe_loss"):
                            opt.observe_loss(float(loss))
                        i = rel0 + off
                        rec_extra = (
                            {"test_loss": ev[0], "test_accuracy": ev[1]}
                            if ev is not None and i in eval_is else {}
                        )
                        rec = logger.log_step(i, float(loss), **rec_extra)
                        if rec_extra:
                            print_eval_line(rec)
                pending.clear()

            for gap, length in downpour_chunk_schedule(
                opt.n_push, opt.n_pull, start, start + steps_per_epoch,
                max_chunk=max_chunk,
            ):
                latest = opt.boundary(gap)
                if latest is not None:
                    params = opt.unravel(jnp.asarray(latest))
                pairs = [next(batches) for _ in range(length)]
                bxs = np.stack([p[0] for p in pairs])
                bys = np.stack([p[1] for p in pairs])
                tracer.on_step(gap, n_steps=length)
                params, opt.opt_state, opt.accum, losses = chunk_step(
                    params, opt.opt_state, opt.accum, bxs, bys, dropout_rng, gap
                )
                opt.idx = gap + length
                if tracer._active and gap + length >= tracer.stop:
                    # the capture must cover the window's device work; block
                    # before the stop_trace that after_step will trigger
                    # (only while a trace is open — a per-chunk sync would
                    # otherwise re-add the round trip batching amortizes)
                    jax.block_until_ready(losses)
                tracer.after_step(gap + length)
                # interval-crossing evals land at the chunk boundary
                # (params advance inside one dispatch, so mid-chunk params
                # don't exist); EVERY crossing step gets an eval record —
                # the same row count and step indices as the per-step path,
                # all carrying the chunk-end evaluation
                rel0 = gap - start
                eval_is = {
                    i for i in range(rel0, rel0 + length)
                    if i % args.log_interval == 0 and i > 0
                }
                ev = (
                    evaluate(eval_step, params, x_test, y_test,
                             args.test_batch_size)
                    if eval_is else None
                )
                pending.append((rel0, losses, eval_is, ev))
                if ev is not None or len(pending) >= 8:
                    flush()
            flush()
            # no trailing boundary here: the next epoch's first chunk (or
            # finish()'s flush after the last) owes any epoch-joint comm
        else:
            for i, (bx, by) in enumerate(batches):
                tracer.on_step(opt.idx)
                loss, grads = grad_fn(params, bx, by, dropout_rng, opt.idx)
                params = opt.step(params, grads)
                loss = float(loss)  # block: bounds the trace to this step
                if hasattr(opt, "observe_loss"):
                    # health telemetry (ISSUE 8): the loss EWMA + nonfinite
                    # count ride the coordinator lease renewals
                    opt.observe_loss(loss)
                tracer.after_step(opt.idx)
                rec_extra = {}
                if i % args.log_interval == 0 and i > 0:
                    test_loss, test_acc = evaluate(
                        eval_step, params, x_test, y_test, args.test_batch_size
                    )
                    rec_extra = {"test_loss": test_loss, "test_accuracy": test_acc}
                rec = logger.log_step(i, float(loss), **rec_extra)
                if rec_extra:
                    print_eval_line(rec)
        # a window straddling the epoch boundary is truncated here rather
        # than polluting the capture with the full-test-set eval below
        tracer.close()
        evaluate(eval_step, params, x_test, y_test, args.test_batch_size, verbose=True)
    tracer.close()
    tracer.warn_if_never_opened()
    opt.finish()
    return params, logger


def _chunk_step_cache(opt, model):
    """One compiled chunk step per optimizer instance (distinct scan lengths
    share it — lax.scan length comes from the stacked batch shape)."""
    if getattr(opt, "_chunk_step", None) is None:
        opt._chunk_step = make_downpour_chunk_step(model, opt.tx, opt._pad)
    return opt._chunk_step


def run_server(args, transport: Transport) -> ParameterServer:
    """Server-side entry (reference ``init_server``, ``example/main.py:135-138``)."""
    from distributed_ml_pytorch_tpu.models import get_model

    model = get_model(getattr(args, "model", "alexnet"))
    params = model.init(
        jax.random.key(getattr(args, "seed", 0)), jnp.zeros((1, 32, 32, 3))
    )["params"]
    from distributed_ml_pytorch_tpu.parallel.optplane import (
        optimizer_from_args,
    )
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params as _ravel,
    )

    n_params = int(np.asarray(_ravel(params)).shape[0])
    server = ParameterServer(
        params,
        transport=transport,
        n_workers=args.world_size - 1,
        worker_timeout=getattr(args, "worker_timeout", 0.0) or None,
        ckpt_dir=getattr(args, "ckpt_dir", "") or None,
        ckpt_every=getattr(args, "ckpt_every", 500),
        staleness_damping=getattr(args, "staleness_damping", 0.0),
        wal=getattr(args, "wal", False),
        admission=_admission_from_args(args),
        combine=getattr(args, "combine", "add") or "add",
        optimizer=optimizer_from_args(args, n_params),
    )
    if getattr(args, "resume", False) and server.maybe_restore():
        print("parameter server: resumed central params from", server._ckpt_path())
    server.run()
    if server.failed_workers:
        print(
            "parameter server: finished with failed workers: {}".format(
                sorted(server.failed_workers)
            )
        )
    return server


def run_ps_process(args) -> int:
    """CLI entry for one PS-topology process (rank 0 = server, 1+ = workers) —
    replaces the reference's gloo rendezvous + role dispatch
    (``example/main.py:163-168``)."""
    from distributed_ml_pytorch_tpu.utils.messaging import (
        TCPTransport,
        make_transport,
    )

    if args.rank is None:
        raise SystemExit("--rank is required for distributed --mode ps runs")
    is_server = args.server or args.rank == SERVER_RANK
    transport = make_transport(
        args.rank,
        args.world_size,
        args.master,
        int(args.port),
        kind=getattr(args, "transport", "auto"),
        reliable=getattr(args, "reliable", False),
        # --wal's log-before-ack guarantee: the SERVER defers delivery acks
        # until the WAL group commit (workers keep acking on delivery —
        # they never drive commit())
        durable_acks=is_server and getattr(args, "wal", False),
    )
    # name what "auto" resolved to: it must not hide that the C++ library did
    # not build (one write: the ranks share the launcher's pipe)
    python_tcp = isinstance(getattr(transport, "inner", transport), TCPTransport)
    why = ""
    if python_tcp and getattr(args, "transport", "auto") == "auto":
        from distributed_ml_pytorch_tpu import native

        why = f" (native unavailable: {native.native_load_error()})"
    print(f"transport: rank {args.rank} kind="
          f"{'python' if python_tcp else 'native'}{why}", flush=True)
    heartbeat = None
    try:
        if is_server:
            server = run_server(args, transport)
            if not server.failed_workers:
                print("parameter server: all workers done")
        else:
            hb_interval = getattr(args, "heartbeat_interval", 0.0)
            if hb_interval > 0:
                # started before any jit compile: the server's failure
                # detector must see liveness the moment the process is up,
                # not after the first (possibly minutes-long) compilation
                from distributed_ml_pytorch_tpu.utils.failure import HeartbeatSender

                heartbeat = HeartbeatSender(transport, interval=hb_interval)
                heartbeat.start()
            _params, logger = train_worker(args, transport, heartbeat=heartbeat)
            path = logger.to_csv("node{}.csv".format(args.rank))
            print("wrote", path)
            print("Finished Training")
    finally:
        if heartbeat is not None:
            heartbeat.stop()
        transport.close()
    return 0
