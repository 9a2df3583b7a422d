"""Sharded parameter server — the DistBelief topology the reference descends
from (VERDICT r1 #10; the reference's Makefile installs ``pytorch-distbelief``,
``Makefile:38``, whose namesake system sharded its server across machines).

Design: **sharding is pure composition over the existing pieces.** The
central vector splits into k contiguous ranges; shard ``s`` is an unmodified
:class:`~distributed_ml_pytorch_tpu.parallel.async_ps.ParameterServer`
holding ``flat[lo_s:hi_s]``, serving as the rank-0 hub of its OWN transport
star (TCP: ``port + s``; in-process: one world per shard). Workers hold one
transport per shard and run the exact DownPour cadence against all of them —
push sends each server its slice of the lr-pre-scaled accumulator, pull
requests every slice, and the per-shard listeners assemble whatever has
arrived at the next step boundary (a worker may install shard A's fresh
params alongside shard B's older ones — precisely DownPour's tolerated
staleness, now also per-shard). No new wire format, no new server code.

Scaling consequence (the design note): server-side bandwidth and apply cost
scale 1/k per shard host, which is what made DistBelief's central server
feasible at model sizes a single host couldn't absorb. Worker-side cost is
unchanged (same bytes, split across k sockets — and the k sends overlap).
"""

from __future__ import annotations

import sys
import threading
from typing import Any, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from distributed_ml_pytorch_tpu.parallel.async_ps import (
    Listener,
    ParameterServer,
    PushFlusher,
    init_downpour_accumulator,
    make_downpour_device_step,
    validate_downpour_args,
)
from distributed_ml_pytorch_tpu.utils.health import (
    admission_from_args as _admission_from_args,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    MessageCode,
    Transport,
    send_message,
)
from distributed_ml_pytorch_tpu.utils.serialization import (
    make_unraveler,
    ravel_model_params,
)

Pytree = Any


def _server_opt_args(args):
    """One extraction point for the server-optimizer CLI knobs (the
    canonical logic lives in ``optplane.server_opt_from_args``)."""
    from distributed_ml_pytorch_tpu.parallel.optplane import (
        server_opt_from_args,
    )

    return server_opt_from_args(args)


def shard_ranges(n: int, n_shards: int) -> List[Tuple[int, int]]:
    """Contiguous near-equal [lo, hi) ranges covering ``range(n)`` — the
    first ``n % n_shards`` shards are one element longer."""
    if n_shards < 1 or n_shards > n:
        raise ValueError(f"need 1 <= n_shards <= {n}, got {n_shards}")
    base, extra = divmod(n, n_shards)
    ranges, lo = [], 0
    for s in range(n_shards):
        hi = lo + base + (1 if s < extra else 0)
        ranges.append((lo, hi))
        lo = hi
    return ranges


def make_shard_server(
    model: Pytree = None,
    *,
    shard: int,
    n_shards: int,
    params: Optional[np.ndarray] = None,
    transport: Optional[Transport] = None,
    n_workers: Optional[int] = None,
    worker_timeout: Optional[float] = None,
    ckpt_dir: Optional[str] = None,
    ckpt_every: int = 500,
    staleness_damping: float = 0.0,
    wal: bool = False,
    admission=None,
    combine: str = "add",
    server_opt: Optional[str] = None,
    server_opt_kw: Optional[dict] = None,
) -> ParameterServer:
    """A shard server: a plain ParameterServer over its contiguous slice.

    ``ckpt_dir`` should be per-shard (each server checkpoints only its own
    slice) — callers typically pass ``f"{dir}/shard{shard}"``; with
    ``wal=True`` the shard's write-ahead log lives there too.
    ``server_opt`` (ISSUE 14) gives the shard a ZeRO-style sharded
    optimizer owning the momentum/Adam state for EXACTLY its ``[lo, hi)``
    range — state cost per shard scales 1/k by construction.
    """
    flat = (
        np.asarray(params, np.float32)
        if params is not None
        else np.asarray(ravel_model_params(model), np.float32)
    )
    lo, hi = shard_ranges(flat.shape[0], n_shards)[shard]
    optimizer = None
    if server_opt:
        from distributed_ml_pytorch_tpu.parallel.optplane import (
            ShardedOptimizer,
        )

        optimizer = ShardedOptimizer(server_opt, lo, hi,
                                     **(server_opt_kw or {}))
    return ParameterServer(
        params=flat[lo:hi],
        transport=transport,
        n_workers=n_workers,
        worker_timeout=worker_timeout,
        ckpt_dir=ckpt_dir,
        ckpt_every=ckpt_every,
        staleness_damping=staleness_damping,
        wal=wal,
        admission=admission,
        combine=combine,
        optimizer=optimizer,
    )


class ShardedAsynchronous:
    """DownPour client against k shard servers (same cadence semantics as
    :class:`async_ps.Asynchronous`, one transport per shard).

    Functional step API: ``params = opt.step(params, grads)``. Construction
    installs each server's slice of this worker's initial params — the same
    single-install wire pattern as the unsharded client, fanned out.

    Elastic mode (ISSUE 3): with a ``coord`` client and a
    ``transport_factory``, the shard set is no longer launch-time state.
    Whenever the coordinator broadcasts a newer
    :class:`~distributed_ml_pytorch_tpu.coord.shardmap.ShardMap`, the next
    step boundary cuts over: in-flight pushes DRAIN under the old map (the
    flusher queue empties, so no push is torn across maps), transports for
    surviving servers are reused, new servers get transports from the
    factory, and any range a server newly acquired is seeded with this
    worker's current values (``MessageCode.RangeInstall`` — first worker to
    cut over wins, the construction-install pattern scoped to the moved
    range). The accumulated gradient survives untouched: it is a flat
    vector over the WHOLE model, and the map only decides how it is sliced
    at push time.
    """

    def __init__(
        self,
        params: Pytree,
        lr: float,
        n_push: int,
        n_pull: int,
        *,
        tx=None,
        transports: Sequence[Transport],
        rejoin: bool = False,
        install_timeout: float = 5.0,
        heartbeats: Optional[Sequence] = None,
        coord=None,
        transport_factory=None,
        shard_map=None,
        compress: Optional[str] = None,
        compress_opts: Optional[dict] = None,
        error_feedback: bool = True,
    ):
        validate_downpour_args(lr, n_push, n_pull)
        if not transports:
            raise ValueError("need at least one shard transport")
        if coord is not None and heartbeats:
            raise ValueError(
                "elastic mode: shard liveness is the coordinator's lease "
                "job — per-shard heartbeat senders cannot follow a cutover")
        if coord is not None and transport_factory is None:
            raise ValueError("elastic mode needs a transport_factory")
        self.lr = float(lr)
        self.n_push = int(n_push)
        self.n_pull = int(n_pull)
        self.transports = list(transports)
        self.coord = coord
        self.transport_factory = transport_factory
        self.map_version = shard_map.version if shard_map is not None else -1
        #: stable per-shard server ids (coord-world ranks in elastic mode;
        #: positional 0..k-1 in static mode) — how map entries match slots
        self.server_ids = (
            [e.server_id for e in shard_map.entries]
            if shard_map is not None else list(range(len(self.transports))))
        self._owned: set = set()  # server ids whose transports WE created
        self.idx = 0
        self._last_step_t: Optional[float] = None
        from distributed_ml_pytorch_tpu.utils.metrics import Ewma

        #: inter-step latency EWMA fed to the coordinator — the shared
        #: implementation (``utils/metrics.Ewma``, ISSUE 12: decay
        #: constants live in one place; update rule bit-identical to the
        #: old hand-rolled 0.7/0.3 idiom, LeaseRenew floats unchanged)
        self._ewma = Ewma()
        # --- numerical health telemetry (ISSUE 8) -----------------------
        #: admission nacks received across all shards (rides LeaseRenew —
        #: the coordinator's reputation input)
        self.nacks = 0
        #: nonfinite losses observed (observe_loss) — the hard rollback
        #: signal; loss/grad-norm EWMAs ride the renewals too
        self._bad_loss = 0
        self._loss_ewma = Ewma()
        self._gnorm_ewma = Ewma()  # updated by the flusher thread (GIL-atomic)
        #: rollback-barrier mailbox: set by the coord listener on a phase-0
        #: RollbackRequest, consumed at the next step boundary (drop the
        #: in-flight accumulator, pull fresh params)
        self._rollback_pending = threading.Event()
        self.rollbacks_seen = 0
        #: post-rollback hold (ISSUE 8): device updates are SKIPPED from
        #: the barrier until one step after every shard's restored params
        #: have installed — grads computed on pre-rollback state must not
        #: be applied over the restored pull (NaN/explosions are absorbing
        #: through the SGD update, so one stale application can re-poison
        #: a perfectly good install forever). The push/pull CADENCE is
        #: untouched: a held step still sends its (zero) push, so chaos-
        #: plan channel indices stay a pure function of the step script.
        #: Known race, accepted: "fresh" is judged by arrival AFTER the
        #: barrier, so a pre-restore reply still in flight when the
        #: RollbackRequest lands can release the hold with diverged params
        #: (replies carry no rollback epoch to discriminate on). The
        #: admission gate is the backstop — pushes derived from that stale
        #: install are z-rejected, and each nack re-arms this same hold
        #: with a new pull until a post-restore install sticks.
        self._hold_updates = False
        self._fresh_installed: set = set()
        self.skipped_updates = 0
        self.unravel = make_unraveler(params)
        # worker-local optax transform (same contract as Asynchronous.tx:
        # default = the reference SGD recipe; state survives shard installs)
        from distributed_ml_pytorch_tpu.parallel.async_ps import default_downpour_tx

        self.tx = tx if tx is not None else default_downpour_tx(self.lr)
        self.opt_state = self.tx.init(params)
        flat, self._flat_n, self._pad, self.accum = init_downpour_accumulator(params)
        if shard_map is not None:
            if shard_map.n_params != self._flat_n:
                raise ValueError(
                    f"shard map covers {shard_map.n_params} params but the "
                    f"model ravels to {self._flat_n}")
            if len(shard_map.entries) != len(self.transports):
                raise ValueError(
                    f"shard map has {len(shard_map.entries)} entries but "
                    f"{len(self.transports)} transports were given")
            self.ranges = shard_map.ranges
        else:
            self.ranges = shard_ranges(self._flat_n, len(self.transports))
        self._device_step = make_downpour_device_step(self.tx, self._pad)
        # --- compressed push wire (ISSUE 14) ----------------------------
        #: ONE full-length error-feedback encoder: the residual is
        #: indexed absolutely, so an elastic cutover reslices it exactly
        #: like the accumulator — no residual is lost when a range moves.
        #: Touched only on the flusher thread (drained before cutovers
        #: and before finish()'s inline push).
        self.encoder = None
        if compress:
            from distributed_ml_pytorch_tpu.utils.compress import (
                CompressingEncoder,
                make_codec,
            )

            self.encoder = CompressingEncoder(
                self._flat_n, make_codec(compress, **(compress_opts or {})),
                error_feedback=error_feedback)
        # per-shard liveness: a dead shard degrades that SLICE to purely-
        # local SGD (same contract as Asynchronous._send, per shard — the
        # other shards keep their push/pull service). ``heartbeats[s]`` is
        # an optional per-shard HeartbeatSender whose peer_down flag catches
        # SILENT deaths (partition/power loss) that a blocking TCP send
        # would otherwise stall on instead of raising.
        self.shard_down = [False] * len(self.transports)
        #: scheduler park window (ISSUE 16): a HELD shard is parked by the
        #: fleet scheduler, not dead — its slice degrades to purely-local
        #: SGD exactly like shard_down, but deliberately and silently (no
        #: down/up transition logging, no revival probes: the resume's
        #: ``release_shard`` restores service). Unsent pushes are counted
        #: in ``held_pushes``; an unsent push is an unacked push, so the
        #: drill accounting (acked <= applied) holds through the window.
        self.shard_held = [False] * len(self.transports)
        self.held_pushes = 0
        #: gray plane (ISSUE 20): per-server pull requests actually sent
        #: (held shards excluded — a deliberate park is not link weather)
        #: and a short history of (reqs, replies, sent, retries, blocked)
        #: totals per server. The windowed requests-vs-replies delta is
        #: this worker's THIRD-PARTY evidence about each shard link — the
        #: only witness a one-way partition has, since the shard's own
        #: renew tail still flows. Rides the existing lease renewals via
        #: ``coord.report_gray_health(links=...)``.
        self._pull_reqs: dict = {}
        self._link_hist: list = []
        self.heartbeats = list(heartbeats) if heartbeats else None
        if self.heartbeats is not None and len(self.heartbeats) != len(self.transports):
            raise ValueError("need one heartbeat sender per shard transport")
        # listeners attach before any send (async_ps ordering invariant)
        self.listeners = [Listener(transport=t) for t in self.transports]
        for listener in self.listeners:
            listener.start()
        if rejoin:
            # elastic restart: ADOPT every shard's current slice instead of
            # stomping trained central params with this process's fresh init
            # (same contract as Asynchronous(rejoin=True), per shard)
            for s in range(len(self.transports)):
                self._send(s, MessageCode.ParameterRequest, np.zeros(0, np.float32))
            for s, listener in enumerate(self.listeners):
                if not listener.wait_for_update(timeout=install_timeout):
                    print(
                        f"worker: rejoin pull to shard {s} unanswered after "
                        f"{install_timeout:.1f}s — that slice starts from "
                        "local init",
                        file=sys.stderr,
                    )
        else:
            for s, (lo, hi) in enumerate(self.ranges):
                self._send(s, MessageCode.ParameterUpdate, flat[lo:hi])
        if coord is not None and getattr(coord, "on_rollback", None) is None:
            # wire the rollback mailbox (ISSUE 8): phase-0 barriers are
            # consumed at the next step boundary
            coord.on_rollback = self._note_rollback
        # overlap pushes with compute (VERDICT r4 #5): the fetched vector is
        # sliced per shard ON THE FLUSHER THREAD, so the training thread
        # never blocks on the device→host transfer or any shard's socket
        self._flusher = PushFlusher(self._push_all)

    def _push_all(self, arr: np.ndarray) -> None:
        """Send every shard its slice of one fetched push vector.

        Elastic mode stamps each slice with the map version AND the
        absolute ``[lo,hi)`` it was cut for (``ShardPush``): the server
        applies only slices cut for the range it currently serves, so
        cross-version traffic at moved offsets is dropped even when the
        sizes coincide (the old size-only check's blind spot), while a
        version bump that left the range in place stays compatible. The
        flusher drains before any cutover, so the stamp read here always
        matches the slicing."""
        # grad-norm EWMA (ISSUE 8): the flusher already fetched the vector,
        # so the norm is a free host-side pass — it rides LeaseRenew as the
        # coordinator's numerical-health telemetry
        norm = float(np.linalg.norm(arr.astype(np.float64, copy=False)))
        if np.isfinite(norm):
            self._gnorm_ewma.update(norm)
        if self.encoder is not None:
            # compressed wire (ISSUE 14): each shard's slice rides a
            # CompressedUpdate (head, body) pair through sendv; elastic
            # pushes carry the same (version, lo, hi) stamp ShardPush
            # does, so the server's range gate is codec-agnostic
            ver = max(0, self.map_version) if self.coord is not None else 0
            for s, (lo, hi) in enumerate(self.ranges):
                stamp = ((ver, lo, hi) if self.coord is not None else None)
                head, body = self.encoder.encode_range(arr, lo, hi,
                                                       stamp=stamp)
                self._sendv(s, MessageCode.CompressedUpdate, (head, body))
            return
        if self.coord is not None:
            from distributed_ml_pytorch_tpu.utils.messaging import _split16

            ver = _split16(max(0, self.map_version))
            for s, (lo, hi) in enumerate(self.ranges):
                head = np.asarray(
                    [*ver, *_split16(lo), *_split16(hi)], np.float32)
                self._send(s, MessageCode.ShardPush,
                           np.concatenate([head, arr[lo:hi]]))
            return
        for s, (lo, hi) in enumerate(self.ranges):
            self._send(s, MessageCode.GradientUpdate, arr[lo:hi])

    def _send(self, shard: int, code: MessageCode, payload: np.ndarray) -> None:
        """Send toward one shard server; its death degrades, never crashes.

        A down-marked shard still gets ParameterRequests: the pull cadence
        doubles as the revival probe (an empty frame, nothing to lose), and
        a restarted server's reply is exactly the contact that
        :meth:`_mark_up` revives on — without it the down flag would be a
        one-way door and the revive path dead code."""
        if self.shard_held[shard]:
            # parked by the scheduler (ISSUE 16): nothing is sent — not
            # even the pull probe; the park is deliberate and the resume
            # releases it explicitly. The skipped push was never acked.
            if code in (MessageCode.GradientUpdate, MessageCode.ShardPush):
                self.held_pushes += 1
            return
        if code == MessageCode.ParameterRequest:
            sid = self.server_ids[shard]
            self._pull_reqs[sid] = self._pull_reqs.get(sid, 0) + 1
        if self.shard_down[shard]:
            if code != MessageCode.ParameterRequest:
                return
            try:
                send_message(code, payload, transport=self.transports[shard])
            except (OSError, ConnectionError):
                pass  # still down; the next cadence probes again
            return
        if self.heartbeats is not None and self.heartbeats[shard].peer_down:
            self._mark_down(shard)
            return
        try:
            send_message(code, payload, transport=self.transports[shard])
        except (OSError, ConnectionError):
            self._mark_down(shard)

    def _sendv(self, shard: int, code: MessageCode, parts) -> None:
        """The ``_send`` degrade discipline for multi-part (scatter/
        gather) frames — compressed pushes ride here."""
        if self.shard_held[shard]:
            self.held_pushes += 1  # parked by the scheduler (see _send)
            return
        if self.shard_down[shard]:
            return  # pulls remain the revival probe (_send)
        if self.heartbeats is not None and self.heartbeats[shard].peer_down:
            self._mark_down(shard)
            return
        try:
            self.transports[shard].sendv(code, parts)
        except (OSError, ConnectionError):
            self._mark_down(shard)

    def hold_shard(self, server_id: int) -> None:
        """Scheduler park window (ISSUE 16): stop all traffic toward the
        named shard server — its slice degrades to purely-local SGD until
        :meth:`release_shard`. The flusher is drained first so no push cut
        before the hold lands after it."""
        self._flusher.drain()
        idx = self.server_ids.index(server_id)
        self.shard_held[idx] = True
        lo, hi = self.ranges[idx]
        print(
            f"worker: shard {server_id} HELD (parked by the scheduler) — "
            f"params [{lo},{hi}) continue with purely-local SGD",
            file=sys.stderr,
        )

    def release_shard(self, server_id: int) -> None:
        """End a park window: resume push/pull service to the shard (the
        resumed server answers under the same range)."""
        idx = self.server_ids.index(server_id)
        self.shard_held[idx] = False
        print(
            f"worker: shard {server_id} RELEASED — push/pull service "
            "resumes", file=sys.stderr,
        )

    def _gray_links(self) -> tuple:
        """Windowed per-shard link evidence for the renew tail (ISSUE 20).

        Snapshots per-server totals once per step and measures against the
        oldest snapshot in an 8-step window: pull requests sent vs replies
        delivered (ONE outstanding reply is tolerated — an answer still in
        flight is not weather), plus the reliable wire's retransmit and
        blocked-send deltas over the same window. A one-way partition that
        eats requests (or replies) on ONE direction shows here and nowhere
        else — the shard's own renew tail still flows, so this worker is
        the only witness."""
        snap = {}
        for s, sid in enumerate(self.server_ids):
            st = getattr(self.transports[s], "stats", None)
            blocked = 0.0
            if isinstance(st, dict):
                blocked = float(st.get("window_blocked_s", 0.0))
            snap[sid] = (self._pull_reqs.get(sid, 0),
                         int(getattr(self.listeners[s], "replies", 0)),
                         blocked)
        self._link_hist.append(snap)
        if len(self._link_hist) > 9:
            del self._link_hist[:-9]
        base = self._link_hist[0]
        links = []
        for sid, (reqs, reps, blocked) in snap.items():
            b = base.get(sid)
            if b is None:
                continue  # shard joined mid-window: no baseline yet
            req_w = reqs - b[0]
            rep_w = max(0, reps - b[1])  # listener rebuilt on resize: clamp
            # two outstanding replies tolerated: a busy-but-honest server
            # answering a window behind is latency, not weather. Raw
            # retransmit counts are deliberately NOT folded in: deferred
            # delivery acks make retransmits steady-state NORMAL on this
            # wire — the reliable channel's gray signature is blocked-send
            # seconds, which rides the second field.
            miss = (max(0, req_w - rep_w - 2) / req_w) if req_w > 0 else 0.0
            blk_w = max(0.0, blocked - b[2])
            if req_w > 0:
                links.append((sid, miss, blk_w))
        return tuple(links)

    def _mark_down(self, shard: int) -> None:
        # two threads get here: the training thread (pull requests, rejoin
        # sends) and the PushFlusher thread (``_push_all`` -> ``_send``).
        # ``shard_down`` carries no lock: each touch is one GIL-atomic read
        # or write of a list slot, and a lost race costs one repeated
        # transition line, never a wrong state.
        if self.shard_down[shard]:
            return  # already down: no repeat transition logging
        self.shard_down[shard] = True
        lo, hi = self.ranges[shard]
        print(
            f"worker: shard {self.server_ids[shard]} state up->down "
            f"(params [{lo},{hi})) — that slice continues with "
            "purely-local SGD until the server answers again",
            file=sys.stderr,
        )

    def _mark_up(self, shard: int) -> None:
        """Revive-on-contact: a reply from a down-marked shard is evidence
        of life (the reliable transport's any-frame-revives rule, lifted to
        the shard slot level) — resume its push/pull service."""
        self.shard_down[shard] = False
        if self.heartbeats is not None:
            # the sender keeps probing and clears this itself on the next
            # successful send; clearing here just closes the race where a
            # stale flag would re-mark the shard before that probe fires
            self.heartbeats[shard].peer_down = False
        lo, hi = self.ranges[shard]
        print(
            f"worker: shard {self.server_ids[shard]} state down->up "
            f"(params [{lo},{hi})) — push/pull service resumes",
            file=sys.stderr,
        )

    def _install_arrived(self, params: Pytree) -> Pytree:
        """Patch whichever shard slices have arrived into the current flat
        params — per-shard staleness is allowed by construction."""
        latest = [listener.take_latest_versioned()
                  for listener in self.listeners]
        if all(l is None for _v, l in latest):
            return params
        # np.array (not asarray): a jax array exports a read-only buffer
        flat = np.array(ravel_model_params(params), dtype=np.float32)
        for s, ((lo, hi), (stamp, sl)) in enumerate(zip(self.ranges, latest)):
            if sl is not None:
                if stamp is not None and stamp[1:] != (lo, hi):
                    # stamped elastic reply cut for OTHER offsets (the
                    # join+death same-count rebalance): dropped on the
                    # range stamp, so it can never install 50 params at
                    # the wrong place — a version bump whose range stayed
                    # put remains compatible
                    print(
                        f"worker: dropping shard {self.server_ids[s]} reply "
                        f"for [{stamp[1]},{stamp[2]}) v{stamp[0]} (this "
                        f"slot expects [{lo},{hi}) on v{self.map_version})",
                        file=sys.stderr,
                    )
                    continue
                if sl.shape[0] != hi - lo:
                    if self.coord is None:
                        # static fleet: ranges are launch-time constants, so
                        # a size mismatch is a BUG — fail loudly, never
                        # silently corrupt params
                        raise ValueError(
                            f"shard reply of {sl.shape[0]} params for a "
                            f"[{lo},{hi}) range — shard/worker ranges disagree"
                        )
                    # elastic fleet: a reply sized for another map version
                    # (the server resized mid-flight) is expected transient
                    # traffic — drop it; the next pull under the agreed map
                    # answers correctly
                    print(
                        f"worker: dropping shard {self.server_ids[s]} reply "
                        f"of {sl.shape[0]} params for a [{lo},{hi}) range "
                        "(stale shard-map traffic)",
                        file=sys.stderr,
                    )
                    continue
                if self.shard_down[s]:
                    self._mark_up(s)
                flat[lo:hi] = sl
                if self._hold_updates:
                    self._fresh_installed.add(self.server_ids[s])
        return self.unravel(jnp.asarray(flat))

    def observe_loss(self, loss: float) -> None:
        """Health telemetry (ISSUE 8): fold one observed training loss into
        the EWMA that rides this worker's lease renewals — a NONFINITE loss
        is counted instead of folded (the coordinator's hard rollback
        signal; folding NaN would poison the telemetry itself)."""
        if not np.isfinite(loss):
            self._bad_loss += 1
            return
        self._loss_ewma.update(loss)

    def _note_rollback(self, rollback_id: int, phase: int) -> None:
        """Coord-listener callback: park a phase-0 rollback barrier for the
        next step boundary."""
        if phase == 0:
            self._rollback_pending.set()

    def _resync_on_nacks(self) -> None:
        """Nack intake (ISSUE 8): a quarantined push means the server
        judged this worker's state garbage — resync by pulling EVERY shard
        AND holding further update application until the fresh installs
        land (``_hold_updates``, the mini-rollback discipline). Without
        the hold, each install would be stomped in the same step by
        updates derived from the still-diverged params: install, stomp,
        explode, nack, repeat — the resync could never converge."""
        got = 0
        for s, listener in enumerate(self.listeners):
            n = listener.take_nacks()
            if n:
                got += n
                print(
                    f"worker: {n} push(es) quarantined by shard "
                    f"{self.server_ids[s]}'s admission gate — resyncing "
                    "with a fresh pull",
                    file=sys.stderr,
                )
        if got:
            self.nacks += got
            self._hold_updates = True
            self._fresh_installed = set()
            for s in range(len(self.transports)):
                self._send(s, MessageCode.ParameterRequest,
                           np.zeros(0, np.float32))

    def _maybe_rollback(self) -> None:
        """Consume a parked rollback barrier (ISSUE 8): drain in-flight
        pushes (they carry pre-rollback deltas — they must not land AFTER
        the restore as zombie work), DROP the local accumulator, discard
        any stale mailbox reply, and pull every shard's restored params."""
        if not self._rollback_pending.is_set():
            return
        self._rollback_pending.clear()
        self.rollbacks_seen += 1
        self._flusher.drain()
        self.accum = jnp.zeros_like(self.accum)
        self._hold_updates = True
        self._fresh_installed = set()
        # the loss telemetry anchored the OLD (diverged) regime; reset so
        # post-restore renewals describe the restored one
        self._loss_ewma.reset()
        print(
            "worker: fleet ROLLBACK barrier — dropped the in-flight "
            "accumulator, pulling restored params from every shard",
            file=sys.stderr,
        )
        for s, listener in enumerate(self.listeners):
            listener.take_latest_versioned()  # discard pre-rollback replies
            self._send(s, MessageCode.ParameterRequest,
                       np.zeros(0, np.float32))

    def _maybe_cutover(self, params: Pytree) -> None:
        """Adopt a newer coordinator shard map at this step boundary."""
        if self.coord is None:
            return
        m = self.coord.take_shard_map()
        if m is None or m.version <= self.map_version:
            return
        self.apply_shard_map(m, params)

    def apply_shard_map(self, m, params: Pytree) -> None:
        """Cut this client over to shard map version ``m.version``.

        Ordering: (1) drain the flusher so every in-flight push lands under
        the OLD map (no push is split across maps — the accumulated
        gradient is never lost, it is the same flat vector under any map);
        (2) retire slots for servers the map dropped (stop their listeners;
        close their transports only if this client created them); (3) build
        slots for new servers via the factory, listener-before-any-send;
        (4) seed every freshly-acquired range with this worker's current
        values (``RangeInstall`` — first cutover wins server-side).
        """
        self._flusher.drain()
        old = {sid: (t, listener, down, held) for sid, t, listener, down, held
               in zip(self.server_ids, self.transports, self.listeners,
                      self.shard_down, self.shard_held)}
        new_transports, new_listeners, new_down, new_held = [], [], [], []
        for e in m.entries:
            if e.server_id in old:
                t, listener, down, held = old.pop(e.server_id)
            else:
                t = self.transport_factory(e)
                self._owned.add(e.server_id)
                listener = Listener(transport=t)
                listener.start()
                down = held = False
            new_transports.append(t)
            new_listeners.append(listener)
            new_down.append(down)
            new_held.append(held)
        for sid, (t, listener, _down, _held) in old.items():
            listener.stop()
            if sid in self._owned:
                self._owned.discard(sid)
                t.close()
        print(
            "worker: shard map v{} adopted — {} shard(s): {}".format(
                m.version, len(m.entries),
                ", ".join(f"s{e.server_id}=[{e.lo},{e.hi})"
                          for e in m.entries) or "none"),
            file=sys.stderr,
        )
        self.transports = new_transports
        self.listeners = new_listeners
        self.shard_down = new_down
        self.shard_held = new_held
        self.ranges = m.ranges
        self.server_ids = [e.server_id for e in m.entries]
        self.map_version = m.version
        # seed moved ranges from this worker's CURRENT values (stale by at
        # most one pull cadence — accepted DownPour staleness; losing the
        # range entirely is the alternative)
        flat = np.array(ravel_model_params(params), dtype=np.float32)
        from distributed_ml_pytorch_tpu.utils.messaging import _split16

        for s, e in enumerate(m.entries):
            if e.needs_install:
                frame = np.concatenate([
                    np.asarray([*_split16(e.fresh_lo), *_split16(e.fresh_hi)],
                               np.float32),
                    flat[e.fresh_lo:e.fresh_hi],
                ])
                self._send(s, MessageCode.RangeInstall, frame)

    def step(self, params: Pytree, grads: Pytree,
             loss: Optional[float] = None) -> Pytree:
        """One DownPour step. ``loss`` (optional, ISSUE 8) lets the worker
        gate its OWN update application: a nonfinite loss means the grads
        are garbage — applying them would poison even freshly pulled
        params (NaN is absorbing through the SGD update), so the device
        update is skipped while the push/pull cadence runs unchanged; the
        next install heals the worker. Passing ``loss`` also feeds
        :meth:`observe_loss`."""
        if loss is not None:
            self.observe_loss(float(loss))
        if self.coord is not None:
            # progress report: inter-call gap EWMA (captures the WHOLE loop
            # — data, grad compute, any stall — which is what a straggler
            # actually costs the fleet); the renew thread ships it
            import time as _time

            now = _time.monotonic()
            if self._last_step_t is not None:
                self._ewma.update((now - self._last_step_t) * 1e3)
            self._last_step_t = now
            # wire health rides the lease renewal (ISSUE 7): how many of
            # this worker's shard links have an open circuit breaker — the
            # coordinator then sees "alive but cut off" as its own state
            wire_open = 0
            for t in self.transports:
                counter = getattr(t, "open_breakers", None)
                if counter is not None:
                    wire_open += counter()
            self.coord.report(self.idx // self.n_push, self.idx,
                              self._ewma.value, wire_open=wire_open,
                              nacks=self.nacks, bad_loss=self._bad_loss,
                              loss_ewma=self._loss_ewma.value,
                              gnorm_ewma=self._gnorm_ewma.value)
            # per-link gray evidence rides the SAME renewals (ISSUE 20)
            grh = getattr(self.coord, "report_gray_health", None)
            if grh is not None:
                grh(links=self._gray_links())
        self._maybe_rollback()
        self._resync_on_nacks()
        self._maybe_cutover(params)
        # decide the skip BEFORE this step's installs land: even on the
        # step that completes the post-rollback install set, the grads in
        # hand were computed on pre-install params and must not apply
        held = self._hold_updates
        params = self._install_arrived(params)
        if self.idx % self.n_pull == 0:
            for s in range(len(self.transports)):
                self._send(s, MessageCode.ParameterRequest, np.zeros(0, np.float32))
        bad_loss = loss is not None and not np.isfinite(loss)
        if held or bad_loss:
            self.skipped_updates += 1
            if held and self._fresh_installed >= set(self.server_ids):
                # every shard's restored params are in: updates resume
                # NEXT step, when grads derive from the restored state
                self._hold_updates = False
                self._fresh_installed = set()
        else:
            params, self.opt_state, self.accum = self._device_step(
                params, self.opt_state, grads, self.accum
            )
        if self.idx % self.n_push == 0:
            self._flusher.enqueue(self.accum[: self._flat_n])
            self.accum = jnp.zeros_like(self.accum)
        self.idx += 1
        return params

    def push_speculative(self, task_id: int, flat_update: np.ndarray) -> None:
        """Push one Sandblaster backup-task result: the accumulated
        lr-scaled update of a straggler's remaining batches, tagged with
        the coordinator-assigned ``task_id``. BOTH the victim and its
        backup call this with the same id; each shard server applies the
        first arrival and drops the rest (``ElasticShardServer`` dedup) —
        first-result-wins without double-applying a whole tail of deltas.
        """
        from distributed_ml_pytorch_tpu.utils.messaging import _split16

        # stamped like every elastic push: a speculative tail sliced for
        # other offsets must never apply against the wrong range
        task_ver = (*_split16(int(task_id)),
                    *_split16(max(0, self.map_version)))
        flat_update = np.asarray(flat_update, np.float32).ravel()
        for s, (lo, hi) in enumerate(self.ranges):
            head = np.asarray(
                [*task_ver, *_split16(lo), *_split16(hi)], np.float32)
            self._send(s, MessageCode.SpeculativeUpdate,
                       np.concatenate([head, flat_update[lo:hi]]))

    def finish(self) -> None:
        """Flush the final push and close out every shard."""
        self._flusher.drain()  # in-flight pushes land before the final one
        self._push_all(np.asarray(self.accum[: self._flat_n]))
        for s, t in enumerate(self.transports):
            # reliable transports: WorkerDone barriers behind prior pushes
            # (delivery is guaranteed, ordering is not — async_ps.finish)
            flush = getattr(t, "flush", None)
            if flush is not None and not self.shard_down[s]:
                flush(timeout=10.0)
            self._send(s, MessageCode.WorkerDone, np.zeros(0, np.float32))
        self._flusher.stop()
        for listener in self.listeners:
            listener.stop()


def run_sharded_ps_process(args) -> int:
    """CLI entry for one sharded-PS process (``--n-servers K``): global
    ranks 0..K-1 are shard servers, K.. are workers.

    Shard ``s``'s star is its own transport world on ``port + s`` (server =
    star-rank 0, every worker = star-rank ``global_rank − K + 1``); the
    worker trains the exact reference loop with a :class:`ShardedAsynchronous`
    in place of the unsharded client. Checkpoints (``--ckpt-dir``) land in
    per-shard subdirectories.
    """
    import jax

    from distributed_ml_pytorch_tpu.models import get_model
    from distributed_ml_pytorch_tpu.parallel.async_ps import train_worker
    from distributed_ml_pytorch_tpu.utils.messaging import make_transport

    k = int(args.n_servers)
    n_workers = args.world_size - k
    if args.rank is None:
        raise SystemExit("--rank is required for distributed --mode ps runs")
    if n_workers < 1:
        raise SystemExit(
            f"--n-servers {k} leaves no workers in --world-size {args.world_size}"
        )
    kind = getattr(args, "transport", "auto")
    reliable = getattr(args, "reliable", False)
    coord_addr = getattr(args, "coord", "") or ""
    if coord_addr:
        return _run_elastic_ps_process(args, k, n_workers, kind, reliable,
                                       coord_addr)
    if args.rank < k:
        shard = args.rank
        transport = make_transport(
            0, n_workers + 1, args.master, int(args.port) + shard, kind=kind,
            reliable=reliable,
            # log-before-ack: a WAL'd shard defers delivery acks until its
            # group commit (ParameterServer.run drives ack_delivered)
            durable_acks=getattr(args, "wal", False),
        )
        try:
            model = get_model(getattr(args, "model", "alexnet"))
            params = model.init(
                jax.random.key(getattr(args, "seed", 0)),
                jnp.zeros((1, 32, 32, 3)),
            )["params"]
            ckpt_dir = getattr(args, "ckpt_dir", "") or None
            opt_kind, opt_kw = _server_opt_args(args)
            server = make_shard_server(
                model=params,
                shard=shard,
                n_shards=k,
                transport=transport,
                n_workers=n_workers,
                worker_timeout=getattr(args, "worker_timeout", 0.0) or None,
                ckpt_dir=f"{ckpt_dir}/shard{shard}" if ckpt_dir else None,
                ckpt_every=getattr(args, "ckpt_every", 500),
                staleness_damping=getattr(args, "staleness_damping", 0.0),
                # no ckpt_dir masking: --wal without --ckpt-dir must raise
                # loudly (ParameterServer does), not silently run undurable
                wal=getattr(args, "wal", False),
                admission=_admission_from_args(args),
                combine=getattr(args, "combine", "add") or "add",
                server_opt=opt_kind,
                server_opt_kw=opt_kw,
            )
            if getattr(args, "resume", False) and server.maybe_restore():
                print(f"shard server {shard}: resumed central params")
            server.run()
            print(f"shard server {shard}: done "
                  f"({server.central.shape[0]} params held)")
        finally:
            transport.close()
        return 0
    return _run_static_worker(args, k, n_workers, kind, reliable)


def _run_static_worker(args, k, n_workers, kind, reliable) -> int:
    from distributed_ml_pytorch_tpu.parallel.async_ps import train_worker
    from distributed_ml_pytorch_tpu.utils.messaging import make_transport

    star_rank = args.rank - k + 1
    transports = [
        make_transport(
            star_rank, n_workers + 1, args.master, int(args.port) + s,
            kind=kind, reliable=reliable,
        )
        for s in range(k)
    ]
    heartbeats = []
    try:
        hb_interval = getattr(args, "heartbeat_interval", 0.0)
        if hb_interval > 0:
            # one sender per shard star, started before any jit compile:
            # every shard server's failure detector must see liveness from
            # process start, not from first step (async_ps.run_ps_process
            # does the same for the single star)
            from distributed_ml_pytorch_tpu.utils.failure import HeartbeatSender

            for t in transports:
                hb = HeartbeatSender(t, interval=hb_interval)
                hb.start()
                heartbeats.append(hb)
        from distributed_ml_pytorch_tpu.utils.compress import (
            compress_from_args,
        )

        factory = lambda params, tx: ShardedAsynchronous(
            params, lr=args.lr, n_push=args.num_push, n_pull=args.num_pull,
            tx=tx, transports=transports, rejoin=getattr(args, "rejoin", False),
            heartbeats=heartbeats or None,
            **compress_from_args(args),
        )
        _params, logger = train_worker(
            args, transports[0], opt_factory=factory
        )
        # worker CSVs keep the unsharded node1..N convention (first worker
        # = node1.csv) regardless of how many server ranks precede them —
        # log-consuming tooling (log/, graph regeneration) assumes it
        path = logger.to_csv("node{}.csv".format(star_rank))
        print("wrote", path)
        print("Finished Training")
    finally:
        for hb in heartbeats:
            hb.stop()
        for t in transports:
            t.close()
    return 0


def _run_elastic_ps_process(args, k, n_workers, kind, reliable,
                            coord_addr) -> int:
    """``--coord host:port``: run this PS rank against an elastic control
    plane (``coord/``) instead of the static launch-time topology.

    Shard rank ``r`` (< k) serves as an :class:`~distributed_ml_pytorch_tpu.
    coord.elastic.ElasticShardServer` with server id ``r + 1`` on its own
    star (``port + r``, the static convention — which is also how the
    worker-side transport factory resolves a shard-map entry:
    ``port + server_id − 1``); worker ranks run the normal training loop
    with a coordinator-attached :class:`ShardedAsynchronous` that adopts
    pushed shard maps at step boundaries. Membership ranks in the
    coordination star are ``global rank + 1`` (the coordinator is 0).
    """
    import jax

    from distributed_ml_pytorch_tpu.coord.elastic import ElasticShardServer
    from distributed_ml_pytorch_tpu.coord.member import CoordClient
    from distributed_ml_pytorch_tpu.models import get_model
    from distributed_ml_pytorch_tpu.parallel.async_ps import train_worker
    from distributed_ml_pytorch_tpu.utils.messaging import (
        TCPTransport,
        make_transport,
    )

    host, _, cport = coord_addr.partition(":")
    # distcheck: ignore[DC105] the coordination star is deliberately
    # unreliable: joins retry until answered, LeaseRenew is periodic and
    # self-healing (ReliableTransport itself exempts it via
    # unreliable_codes), and a retry storm toward a dead coordinator would
    # be worse than the loss
    coord_transport = TCPTransport(
        rank=args.rank + 1, world_size=64, master=host or "localhost",
        port=int(cport or 29700))
    model = get_model(getattr(args, "model", "alexnet"))
    params = model.init(
        jax.random.key(getattr(args, "seed", 0)), jnp.zeros((1, 32, 32, 3))
    )["params"]
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params as _ravel,
    )

    flat = np.asarray(_ravel(params), np.float32)
    try:
        if args.rank < k:
            client = CoordClient(coord_transport, "shard")
            # wait_for=0: an ELASTIC server must join the coordinator and
            # serve immediately — workers dial in whenever the map reaches
            # them (the static path's blocking rendezvous would deadlock:
            # workers wait for the map, the map waits for this join).
            # Python transport only: the native lib has no elastic accept.
            from distributed_ml_pytorch_tpu.utils.messaging import (
                ReliableTransport as _Rel,
                TCPTransport as _Tcp,
            )

            star = _Tcp(0, n_workers + 1, args.master,
                        int(args.port) + args.rank, wait_for=0)
            if reliable:
                # log-before-ack when WAL'd (the elastic serve loop drives
                # ack_delivered via ps.commit)
                star = _Rel(star, ack_on_delivery=not getattr(
                    args, "wal", False))
            ckpt_dir = getattr(args, "ckpt_dir", "") or None
            elastic_opt = None
            opt_kind, opt_kw = _server_opt_args(args)
            if opt_kind is not None:
                from distributed_ml_pytorch_tpu.parallel.optplane import (
                    ShardedOptimizer,
                )

                # the coordinator assigns the range; start empty, resize
                # on the first shard map like the central slice does
                elastic_opt = ShardedOptimizer(opt_kind, 0, 0, **opt_kw)
            server = ElasticShardServer(
                server_id=args.rank + 1, n_params=flat.shape[0],
                transport=star, coord=client, init_params=flat,
                staleness_damping=getattr(args, "staleness_damping", 0.0),
                ckpt_dir=(f"{ckpt_dir}/shard{args.rank}" if ckpt_dir
                          else None),
                ckpt_every=getattr(args, "ckpt_every", 500),
                # unmasked: --wal without --ckpt-dir raises loudly in the
                # wrapped ParameterServer instead of silently dropping WAL
                wal=getattr(args, "wal", False),
                admission=_admission_from_args(args),
                manifest_path=getattr(args, "manifest_path", "") or None,
                combine=getattr(args, "combine", "add") or "add",
                optimizer=elastic_opt)
            try:
                server.run()
                print(f"elastic shard server {args.rank}: done "
                      f"(range [{server.lo},{server.hi}), "
                      f"stats {server.stats})")
            finally:
                star.close()
            return 0
        star_rank = args.rank - k + 1
        client = CoordClient(coord_transport, "worker")
        m = client.join(timeout=10)
        # an EMPTY map just means no shard server has joined yet — this is
        # an elastic fleet, wait for one (bounded) instead of failing
        import time as _time

        deadline = _time.monotonic() + 120
        while (m is None or not m.entries) and _time.monotonic() < deadline:
            _time.sleep(0.5)
            m = client.current_map()
        if m is None or not m.entries:
            raise SystemExit(
                "worker: no populated shard map from the coordinator at "
                f"{coord_addr} after 120s — is coord/cli.py running and "
                "did any shard rank join?")
        created = []

        def factory(entry):
            t = make_transport(
                star_rank, n_workers + 1, args.master,
                int(args.port) + entry.server_id - 1, kind=kind,
                reliable=reliable)
            created.append(t)
            return t

        from distributed_ml_pytorch_tpu.utils.compress import (
            compress_from_args,
        )

        try:
            initial = [factory(e) for e in m.entries]
            opt_factory = lambda p, tx: ShardedAsynchronous(
                p, lr=args.lr, n_push=args.num_push, n_pull=args.num_pull,
                tx=tx, transports=initial,
                coord=client, transport_factory=factory, shard_map=m,
                rejoin=getattr(args, "rejoin", False),
                **compress_from_args(args))
            _params, logger = train_worker(
                args, initial[0], opt_factory=opt_factory)
            path = logger.to_csv("node{}.csv".format(star_rank))
            print("wrote", path)
            print("Finished Training")
        finally:
            for t in created:
                t.close()
        return 0
    finally:
        client.close()
        coord_transport.close()
