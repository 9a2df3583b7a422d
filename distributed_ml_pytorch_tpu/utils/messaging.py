"""M2: tagged-tensor messaging layer (SURVEY.md §2.3, reference contract
recovered from ``asgd/optim/Asynchronous.py:5,9-18,34,37-38,49,59``).

The reference's missing ``asgd.utils.messaging`` module defines the wire API
of the DownPour parameter-server path:

- ``MessageCode`` enum ⊇ {ParameterUpdate, ParameterRequest, GradientUpdate},
- ``send_message(code, payload)`` — fire-and-forget tagged flat-tensor send
  toward the server (rank 0),
- ``MessageListener(model)`` — background thread looping on receive and
  dispatching to ``.receive(sender, message_code, parameter)``.

Here the same API sits on a pluggable :class:`Transport`:

- :class:`InProcessTransport` — queue-based, many "ranks" in one process; used
  by unit tests the way the reference smoke-tests on localhost (SURVEY.md §4).
- :class:`TCPTransport` — framed messages over sockets between controller
  processes in a star topology (workers ↔ server), replacing the reference's
  gloo send/recv. On a TPU pod these are *host-side* control-plane transfers
  between JAX controllers; the data-plane (sync DP) rides compiled ICI
  collectives instead (``parallel/sync.py``).

Wire format (TCP): little-endian header ``(sender:i32, code:i32, nbytes:i64)``
followed by a float32 payload — the flat raveled model vector, fixed size per
model, exactly the implied reference format (SURVEY.md §2.3 M2).

Reliability (codes 9-10, 26): :class:`ReliableTransport` wraps any transport
with per-peer sequence numbers, a frame checksum, ack + retransmission, and
receiver-side dedup — at-least-once delivery on the wire, exactly-once
application at the receiver. The envelope rides the existing float32 wire
(every header field < 2^16, exact in float32), so Python, TCP and native C++
endpoints all carry it; plain frames from a peer that did not negotiate
reliability pass through untouched.

Adaptive wire (ISSUE 7): the retransmission timer is per-peer RTT-estimated
(Jacobson/Karels SRTT/RTTVAR -> RTO with Karn's rule, jittered capped
backoff from ``utils/backoff.py``) instead of a fixed ``ack_timeout``;
senders run a sliding window bounded by receiver-advertised credit (a slow
peer exerts *backpressure* — sends block at the window instead of growing
pending without bound); receivers batch in-order deliveries into cumulative
``CumAck`` frames (piggybacking their credit) so the steady-state ack cost
is one small frame per batch, pipelined with the WAL group-fsync on durable
servers; and every peer carries a circuit breaker (closed -> open on
consecutive RTO blowups -> half-open probe) whose state feeds the
coordinator's lease health view and the HeartbeatSender.
"""

from __future__ import annotations

import collections
import dataclasses
import enum
import logging
import queue
import socket
import struct
import threading
import time
import zlib
from typing import Dict, Optional, Tuple

import numpy as np

from distributed_ml_pytorch_tpu.utils import obs as _obs

_LOGGER = logging.getLogger(__name__)

_HEADER = struct.Struct("<iiq")

#: Upper bound on a declared frame payload (satellite hardening): a malformed
#: or hostile header must not make the reader allocate unbounded memory. The
#: largest legitimate frame is a raveled model vector — 256M f32 params.
MAX_FRAME_BYTES = 1 << 30

SERVER_RANK = 0  # reference convention: rank 0 is the parameter server


class MessageCode(enum.IntEnum):
    """Message tags (reference ``Asynchronous.py:17,34,49,59``).

    ``WorkerDone`` and ``Heartbeat`` are extensions beyond the reference's
    three codes: ``WorkerDone`` lets the server terminate cleanly once every
    worker finishes instead of blocking forever (SURVEY.md §3.2 notes the
    reference server never returns), and ``Heartbeat`` carries worker
    liveness for failure detection (``utils/failure.py`` — the reference has
    none, SURVEY.md §5.3).

    Codes 5-8 are the serving control plane (``serving/frontend.py``): the
    same tagged-float32 wire carries inference requests and streamed tokens
    between clients and the continuous-batching engine — token ids and
    request metadata are exact in float32 (< 2^24).
    """

    ParameterUpdate = 0
    ParameterRequest = 1
    GradientUpdate = 2
    WorkerDone = 3
    Heartbeat = 4
    SubmitRequest = 5
    StreamTokens = 6
    ServeReject = 7
    CancelRequest = 8
    ReliableFrame = 9
    ReliableAck = 10
    StreamAck = 11
    ResumeStream = 12
    # --- coordination plane (coord/, ISSUE 3): the elastic control plane ---
    CoordJoin = 13
    CoordLeave = 14
    LeaseRenew = 15
    ShardMapUpdate = 16
    FleetState = 17
    SpeculateTask = 18
    SpeculativeUpdate = 19
    RangeInstall = 20
    # --- durability plane (ISSUE 5): coordinator-aligned fleet snapshots ---
    SnapshotRequest = 21
    SnapshotDone = 22
    # --- fleet serving + versioned shard traffic (ISSUE 6) ---
    SubmitRequestV2 = 23
    ShardPush = 24
    ShardParams = 25
    # --- adaptive wire (ISSUE 7): batched cumulative ack + credit ---
    CumAck = 26
    # --- numerical health plane (ISSUE 8): admission + auto-rollback ---
    UpdateNack = 27
    RollbackRequest = 28
    RollbackDone = 29
    # --- MPMD pipeline plane (ISSUE 10): stages as fleet members ---
    ActivationShip = 30
    ActivationGrad = 31
    StageReady = 32
    StageAssign = 33
    # --- scalable optimizer plane (ISSUE 14): compressed gradient wire ---
    CompressedUpdate = 34
    # --- multi-tenant scheduler plane (ISSUE 16): preempt / park / resume ---
    PreemptRequest = 35
    PreemptDone = 36
    SlotGrant = 37
    ResumeRequest = 38
    # --- codec plane (ISSUE 18): delta pull replies + KV migration ---
    DeltaParams = 39
    KvMigrate = 40


#: dedup-key vocabulary (ISSUE 13): WHICH receiver-side guard makes an
#: at-least-once redelivery of this code safe to apply.
#:
#: - ``env_seq``      — the reliability envelope's per-peer (incarnation,
#:   seq) dedup window, re-seeded across receiver restarts from the WAL /
#:   checkpoint meta (``ReliableTransport.seed_dedup``).
#: - ``step_mb``      — application-level ``(step, microbatch)`` dedup
#:   (the MPMD replay contract: chaos dups, redelivery and watermark
#:   replay can never double-apply a microbatch).
#: - ``request_id``   — an explicit id in the payload head (serving
#:   request ids, speculation task ids, snapshot / rollback ids):
#:   first-wins or offset-resumable per id.
#: - ``incarnation``  — lives of a rank are ordered by incarnation; stale
#:   lives' frames are ignored or merely re-acked (membership plane).
#: - ``version``      — versioned last-write-wins install (shard maps,
#:   stage placements, fleet views): an older version never rolls a
#:   consumer back, a duplicate of the current one is a no-op.
#: - ``idempotent``   — re-applying is harmless by construction (reads,
#:   whole-state installs, set-adds).
DEDUP_KEYS = ("env_seq", "step_mb", "request_id", "incarnation",
              "version", "idempotent")

#: durability vocabulary: ``wal_before_ack`` marks a code whose applied
#: state mutation must be WAL-logged before its delivery ack is released
#: (log-before-ack; the DC402/DC403 contract). Everything else is "none".
DURABILITY = ("none", "wal_before_ack")

#: delivery vocabulary: ``reliable`` rides the ReliableTransport envelope
#: (retry until acked), ``best_effort`` is deliberately un-enveloped
#: (periodic + self-healing: the ``unreliable_codes`` set), ``envelope``
#: is the reliability layer's own wire (the mechanism, not a user).
DELIVERY = ("reliable", "best_effort", "envelope")


@dataclasses.dataclass(frozen=True)
class PayloadSchema:
    """Declarative wire layout AND protocol contract of one
    :class:`MessageCode` (ISSUE 4; protocol-model annotations ISSUE 13).

    Every payload is ``[*fields, *rest]`` on the tagged-float32 wire:
    ``fields`` names the fixed head positions (``*_lo``/``*_hi`` pairs are
    uint16 halves of one 32-bit value — the :func:`_split16` idiom), and
    ``rest`` names the variable tail (``None`` for fixed-size frames;
    ``rest_min`` is the tail's minimum length when one is required).
    ``handled_by`` declares WHICH plane's modules must dispatch on the
    code — ``ps`` (parallel/, training/), ``serving``, ``coord``, or
    ``transport`` (utils/, native/).

    Protocol-model annotations (ISSUE 13) — the semantic half the
    ``analysis/protomodel.py`` extractor reads and cross-checks against
    the real handler/send sites (the DC4xx family):

    - ``dedup_key`` — one of :data:`DEDUP_KEYS`: the guard that makes
      at-least-once redelivery safe. A reliably-sent code with no dedup
      key is DC401.
    - ``durability`` — one of :data:`DURABILITY`: ``wal_before_ack``
      codes must log before they mutate (DC402) and fsync before they
      ack (DC403).
    - ``delivery`` — one of :data:`DELIVERY`; cross-checked against the
      ``ReliableTransport.unreliable_codes`` default (DC401).
    - ``rest_sections`` / ``rest_separator`` — a ``rest`` tail that
      EVOLVED into multiple sections must declare the sentinel separator
      old frames lack (the ``fleet_metrics`` ``-1`` pattern), and some
      handler on the declared plane must actually split on it (DC405).
    - ``fenced`` — a coordinator-issued COMMAND (ISSUE 17): the sender
      appends the epoch fence trailer (:func:`stamp_epoch`) and the
      member side strips it and rejects stale-epoch frames
      (:func:`strip_epoch` in ``coord/member.CoordClient``), so a zombie
      pre-crash coordinator cannot rebalance, preempt or roll back the
      fleet after its successor takes over. A frame WITHOUT the trailer
      still decodes (pre-ISSUE-17 coordinators are unfenced).

    This table is the single source of truth the ``distcheck`` wire
    checker (``analysis/wire.py``) validates send sites, handler guards
    and subscripts against — layouts are DATA here, not comments, so
    drifting either side of the wire fails ``make lint``. The receiver-
    side minimum frame size is :attr:`min_size`.
    """

    fields: Tuple[str, ...] = ()
    rest: Optional[str] = None
    rest_min: int = 0
    handled_by: Tuple[str, ...] = ()
    doc: str = ""
    dedup_key: Optional[str] = None
    durability: str = "none"
    delivery: str = "reliable"
    rest_sections: Tuple[str, ...] = ()
    rest_separator: Optional[float] = None
    fenced: bool = False

    def __post_init__(self):
        if self.dedup_key is not None and self.dedup_key not in DEDUP_KEYS:
            raise ValueError(
                f"unknown dedup_key {self.dedup_key!r} (vocabulary: "
                f"{DEDUP_KEYS})")
        if self.durability not in DURABILITY:
            raise ValueError(
                f"unknown durability {self.durability!r} (vocabulary: "
                f"{DURABILITY})")
        if self.delivery not in DELIVERY:
            raise ValueError(
                f"unknown delivery {self.delivery!r} (vocabulary: "
                f"{DELIVERY})")
        if len(self.rest_sections) >= 2 and self.rest_separator is None:
            raise ValueError(
                "a multi-section rest tail needs a declared rest_separator "
                "(old frames must still decode — the DC405 contract)")

    @property
    def min_size(self) -> int:
        return len(self.fields) + self.rest_min


WIRE_SCHEMAS: Dict[MessageCode, PayloadSchema] = {
    MessageCode.ParameterUpdate: PayloadSchema(
        rest="params", handled_by=("ps", "coord"),
        dedup_key="idempotent",
        doc="central flat params (server push / construction install)"),
    MessageCode.ParameterRequest: PayloadSchema(
        rest="held", handled_by=("ps", "coord"),
        dedup_key="idempotent",
        doc="pull request (also the TCP hello frame). Empty = legacy "
            "full pull. A delta-enabled worker appends its held stamp "
            "[held_epoch, held_ver_lo, held_ver_hi] (ISSUE 18): the "
            "server may then answer with a DeltaParams frame against "
            "exactly that (epoch, version) instead of the dense reply; "
            "held_epoch -1 forces a full reply (first pull / base miss)"),
    MessageCode.GradientUpdate: PayloadSchema(
        rest="params", handled_by=("ps", "coord"),
        dedup_key="env_seq", durability="wal_before_ack",
        doc="lr-pre-scaled accumulated update; server ADDS it"),
    MessageCode.WorkerDone: PayloadSchema(
        handled_by=("ps", "coord"), dedup_key="idempotent",
        doc="clean worker exit"),
    MessageCode.Heartbeat: PayloadSchema(
        handled_by=("ps", "coord"), dedup_key="idempotent",
        delivery="best_effort",
        doc="liveness only; never retried"),
    MessageCode.SubmitRequest: PayloadSchema(
        fields=("id", "max_new", "temperature", "top_k", "top_p", "seed",
                "eos"),
        rest="prompt", rest_min=1, handled_by=("serving",),
        dedup_key="request_id",
        doc="client -> engine; eos < 0 means none"),
    MessageCode.StreamTokens: PayloadSchema(
        fields=("id", "done_flag", "start_index"), rest="tokens",
        handled_by=("serving",),
        dedup_key="request_id",
        doc="engine -> client; start_index enables gap arithmetic"),
    MessageCode.ServeReject: PayloadSchema(
        fields=("id",), handled_by=("serving",),
        dedup_key="request_id",
        doc="queue full, or a resume the engine cannot serve"),
    MessageCode.CancelRequest: PayloadSchema(
        fields=("id",), handled_by=("serving",), dedup_key="request_id",
        doc="client -> engine"),
    MessageCode.ReliableFrame: PayloadSchema(
        fields=("inc_lo", "inc_hi", "seq_lo", "seq_hi", "crc_lo", "crc_hi",
                "code", "corr_lo", "corr_hi"),
        rest="payload", handled_by=("transport",),
        delivery="envelope",
        doc="reliability envelope; CRC covers header + body. corr (ISSUE "
            "12) is the flight-recorder CORRELATION id riding the "
            "envelope: the sender stamps its thread's active id "
            "(utils/obs.current_corr, 0 = none), the receiver restores it "
            "on delivery — one GradientUpdate / microbatch is followable "
            "across members without touching any inner payload layout"),
    MessageCode.ReliableAck: PayloadSchema(
        fields=("seq_lo", "seq_hi", "inc_lo", "inc_hi"),
        handled_by=("transport",),
        delivery="envelope",
        doc="ack echoes the frame's incarnation (stale-life acks ignored)"),
    MessageCode.StreamAck: PayloadSchema(
        fields=("id", "n_received"), handled_by=("serving",),
        dedup_key="request_id",
        doc="client progress + liveness"),
    MessageCode.ResumeStream: PayloadSchema(
        fields=("id", "n_received"), handled_by=("serving",),
        dedup_key="request_id",
        doc="re-send the stream from offset (gap recovery / reconnect)"),
    MessageCode.CoordJoin: PayloadSchema(
        fields=("kind", "inc_lo", "inc_hi"), handled_by=("coord",),
        dedup_key="incarnation",
        doc="member -> coordinator; idempotent, retried until answered"),
    MessageCode.CoordLeave: PayloadSchema(
        fields=("inc_lo", "inc_hi"), handled_by=("coord",),
        dedup_key="incarnation",
        doc="explicit leave; stale incarnations cannot evict newer lives"),
    MessageCode.LeaseRenew: PayloadSchema(
        fields=("inc_lo", "inc_hi", "push_count", "step", "ewma_ms",
                "wire_open", "nacks", "bad_loss", "loss_ewma", "gnorm_ewma",
                "retrans_rate", "nack_rate", "blocked_s", "fsync_p95_ms",
                "busy_ratio"),
        rest="gray_links", handled_by=("coord",),
        dedup_key="incarnation", delivery="best_effort",
        doc="lease refresh carrying the straggler-detector progress report, "
            "the member's open-circuit-breaker count (wire health), the "
            "numerical-health telemetry (ISSUE 8): cumulative admission "
            "nacks received, nonfinite-loss count, and loss / grad-norm "
            "EWMAs — the reputation + rollback-watchdog inputs — and the "
            "gray-health tail (ISSUE 20): retransmit rate, nack rate, "
            "blocked-send seconds, fsync p95 and busy-vs-wall ratio, plus "
            "per-directed-link (peer, retrans, blocked_s) evidence triples "
            "in the rest — the adaptive-suspicion inputs (receivers "
            "tolerate the 5/6/10-field pre-ISSUE-7/8/20 forms with "
            "neutral gray defaults)"),
    MessageCode.ShardMapUpdate: PayloadSchema(
        fields=("n_entries", "version_lo", "version_hi", "n_params_lo",
                "n_params_hi"),
        rest="entries", handled_by=("coord",),
        dedup_key="version", fenced=True,
        doc="encoded ShardMap; 9 floats per entry (coord/shardmap.py)"),
    MessageCode.FleetState: PayloadSchema(
        fields=("version_lo", "version_hi", "n_workers", "n_shards",
                "n_engines", "workers_done"),
        rest="engine_ranks", handled_by=("coord",),
        dedup_key="version", fenced=True,
        rest_sections=("engine_ranks", "fleet_metrics"), rest_separator=-1.0,
        doc="compact fleet broadcast the serving frontend consumes; the "
            "tail lists live engine coord-ranks (per-engine lease health) "
            "and, behind a -1 separator (ranks are non-negative, so the "
            "split is unambiguous; a tail without one decodes as "
            "pre-ISSUE-12), the fleet_metrics registry summary in "
            "coord/coordinator.FLEET_METRICS_FIELDS order (the decoder "
            "zips names to the floats that arrived, so the ISSUE-20 "
            "gray_suspects field is absent, not wrong, on short frames)"),
    MessageCode.SpeculateTask: PayloadSchema(
        fields=("task_id", "victim_rank", "from_step"),
        handled_by=("coord",),
        dedup_key="request_id", fenced=True,
        doc="coordinator -> backup AND victim; same id for dedup"),
    MessageCode.SpeculativeUpdate: PayloadSchema(
        fields=("task_lo", "task_hi", "ver_lo", "ver_hi", "lo_lo", "lo_hi",
                "hi_lo", "hi_hi"),
        rest="payload", handled_by=("coord",),
        dedup_key="request_id",
        doc="Sandblaster backup-task result stamped like ShardPush; first "
            "task id wins at the PS, wrong-offset traffic dropped"),
    MessageCode.RangeInstall: PayloadSchema(
        fields=("lo_lo", "lo_hi", "hi_lo", "hi_hi"), rest="values",
        handled_by=("coord",),
        dedup_key="idempotent",
        doc="worker seeds a freshly-acquired shard range; first install "
            "wins"),
    MessageCode.SnapshotRequest: PayloadSchema(
        fields=("snap_lo", "snap_hi", "map_lo", "map_hi"),
        handled_by=("coord",),
        dedup_key="request_id", fenced=True,
        doc="coordinator -> shard servers: checkpoint at your next version "
            "boundary under this snapshot id / shard-map version"),
    MessageCode.SnapshotDone: PayloadSchema(
        fields=("snap_lo", "snap_hi", "map_lo", "map_hi", "lo_lo", "lo_hi",
                "hi_lo", "hi_hi", "apply_lo", "apply_hi", "push_lo",
                "push_hi"),
        handled_by=("coord",),
        dedup_key="request_id",
        doc="shard -> coordinator: checkpoint taken (range + apply seq + "
            "push count); the coordinator assembles the FleetManifest"),
    MessageCode.SubmitRequestV2: PayloadSchema(
        fields=("id", "max_new", "temperature", "top_k", "top_p", "seed",
                "eos", "priority", "deadline_ms", "session"),
        rest="prompt", rest_min=1, handled_by=("serving",),
        dedup_key="request_id",
        doc="client -> engine with overload-plane metadata: priority "
            "(higher wins admission under shed), deadline_ms (0 = none; "
            "relative to submit) and session (affinity hint)"),
    MessageCode.ShardPush: PayloadSchema(
        fields=("ver_lo", "ver_hi", "lo_lo", "lo_hi", "hi_lo", "hi_hi"),
        rest="params", rest_min=1, handled_by=("coord",),
        dedup_key="env_seq", durability="wal_before_ack",
        doc="elastic worker -> shard server: GradientUpdate stamped with "
            "the sender's shard-map version AND the absolute [lo,hi) it "
            "sliced — the RANGE is the correctness gate (closes the "
            "equal-size stale-map blind spot, coord/shardmap.py; a benign "
            "version bump with unmoved ranges stays compatible)"),
    MessageCode.ShardParams: PayloadSchema(
        fields=("ver_lo", "ver_hi", "lo_lo", "lo_hi", "hi_lo", "hi_hi"),
        rest="params", rest_min=1, handled_by=("ps",),
        dedup_key="version",
        doc="elastic shard server -> worker: pull reply stamped like "
            "ShardPush (the versioned ParameterUpdate); the worker applies "
            "only a reply whose range matches its current expectation"),
    MessageCode.CumAck: PayloadSchema(
        fields=("inc_lo", "inc_hi", "cum_lo", "cum_hi", "credit"),
        handled_by=("transport",),
        delivery="envelope",
        doc="batched cumulative ack: every seq <= cum of the echoed "
            "incarnation is acknowledged at once, and the receiver "
            "piggybacks its advertised send-window credit (the "
            "backpressure signal) — one small frame per delivery batch "
            "instead of one ReliableAck per frame"),
    MessageCode.UpdateNack: PayloadSchema(
        fields=("reason", "norm", "z"), handled_by=("ps",),
        dedup_key="env_seq",
        doc="server -> worker: your GradientUpdate/ShardPush was QUARANTINED "
            "by the admission gate (utils/health.py) — reason is a NACK_* "
            "code, norm/z the offending magnitude (clamped finite for the "
            "wire). A reject is never silent: the worker counts it, resyncs "
            "by pulling fresh params, and reports the count in LeaseRenew"),
    MessageCode.RollbackRequest: PayloadSchema(
        fields=("roll_lo", "roll_hi", "snap_lo", "snap_hi", "map_lo",
                "map_hi", "phase"),
        handled_by=("coord",),
        dedup_key="request_id", fenced=True,
        doc="coordinator -> everyone: the auto-rollback barrier (ISSUE 8). "
            "phase 0 = start (shards restore the named FleetManifest "
            "snapshot in place, workers drop in-flight accumulators and "
            "pull, serving frontends hold submits), phase 1 = complete/"
            "abandoned (holds release; member-side holds also expire on a "
            "TTL so a lost completion frame fails open)"),
    MessageCode.RollbackDone: PayloadSchema(
        fields=("roll_lo", "roll_hi", "map_lo", "map_hi", "lo_lo", "lo_hi",
                "hi_lo", "hi_hi", "apply_lo", "apply_hi"),
        handled_by=("coord",),
        dedup_key="request_id",
        doc="shard -> coordinator: range [lo,hi) restored to the manifest "
            "snapshot at apply_seq under this map version; all-reported "
            "completes the rollback barrier (MTTR measured)"),
    MessageCode.ActivationShip: PayloadSchema(
        fields=("step_lo", "step_hi", "mb", "kind", "ver_lo", "ver_hi",
                "codec"),
        rest="payload", rest_min=1, handled_by=("ps",),
        dedup_key="step_mb",
        doc="MPMD pipeline data plane (ISSUE 10): stage s -> s+1 activation "
            "hand-off for (step, microbatch), stamped with the sender's "
            "StagePlacement version. kind 0 = activation, 1 = tokens "
            "(driver -> first stage), 2 = targets (driver -> last stage), "
            "3 = per-microbatch ce_sum report (last stage -> driver). "
            "codec (ISSUE 18, utils/codecs.py) names the body encoding — "
            "0 = dense f32 (mandatory for token/target/loss kinds: exact "
            "contract), 1 = int8 per-block absmax for activations "
            "(bounded contract, |x - x̂| <= scale/2); the receiver "
            "DECODES before its size/finite gates. Receivers dedup by "
            "(step, mb) so chaos dups, reliability redelivery and "
            "watermark replay can never double-apply a microbatch"),
    MessageCode.ActivationGrad: PayloadSchema(
        fields=("step_lo", "step_hi", "mb", "ver_lo", "ver_hi", "codec"),
        rest="payload", rest_min=1, handled_by=("ps",),
        dedup_key="step_mb",
        doc="MPMD backward hand-off: stage s+1 -> s activation cotangent "
            "for (step, microbatch); same (step, mb) dedup discipline and "
            "codec-plane discipline (ISSUE 18: 0 = dense, 1 = int8 "
            "bounded) as ActivationShip (no microbatch's gradient applied "
            "twice)"),
    MessageCode.StageReady: PayloadSchema(
        fields=("stage", "inc_lo", "inc_hi", "wm_lo", "wm_hi"),
        handled_by=("coord",),
        dedup_key="incarnation",
        doc="stage member -> coordinator: I serve pipeline stage `stage` "
            "at microbatch watermark wm (= step * n_microbatches, the "
            "global count my checkpoint has applied). A restarted member "
            "announces its recovery point here; the coordinator assigns "
            "it into the StagePlacement and broadcasts StageAssign"),
    MessageCode.StageAssign: PayloadSchema(
        fields=("ver_lo", "ver_hi", "n_stages", "n_params_lo",
                "n_params_hi"),
        rest="entries", handled_by=("coord",),
        dedup_key="version", fenced=True,
        doc="coordinator -> everyone: the versioned StagePlacement "
            "(coord/stages.py; 10 floats per entry: stage, rank, inc "
            "halves, lo/hi halves, watermark halves). Neighbors react to "
            "an entry whose member INCARNATION changed by re-shipping "
            "retained (step, mb) traffic at or past that entry's "
            "watermark — the bounded-replay restart contract"),
    MessageCode.CompressedUpdate: PayloadSchema(
        fields=("codec", "n_lo", "n_hi", "crc_lo", "crc_hi", "param",
                "ver_lo", "ver_hi", "lo_lo", "lo_hi", "hi_lo", "hi_hi"),
        rest="body", rest_min=1, handled_by=("ps", "coord"),
        dedup_key="env_seq", durability="wal_before_ack",
        doc="compressed GradientUpdate/ShardPush (ISSUE 14, "
            "utils/compress.py): codec names the encoding (1 = int8 "
            "per-block quant, 2 = top-k), n the decoded length, param the "
            "codec parameter (block size / k), crc a crc32 of the body "
            "bytes (the decoder's own integrity gate; chaos SDC must "
            "re-stamp it, compress.restamp_crc). The ver/lo/hi halves "
            "mirror ShardPush's elastic stamp — all-zero means unstamped "
            "(single-server wire); elastic servers gate on the RANGE "
            "before paying for a decode. The server DECODES before the "
            "admission gate (z-scores on the decoded norm — compression "
            "cannot slip the gate), WAL-logs the decoded delta plus this "
            "codec id, then applies — replay never re-decodes"),
    MessageCode.PreemptRequest: PayloadSchema(
        fields=("grant_lo", "grant_hi", "snap_lo", "snap_hi"),
        handled_by=("coord",),
        dedup_key="request_id", fenced=True,
        doc="scheduler (via coordinator) -> victim shard member: park "
            "yourself under grant_id; snap_id names the FleetManifest "
            "snapshot the scheduler barriered BEFORE issuing the preempt "
            "(the park-with-manifest gate the sched model checks). The "
            "member commits its WAL group, reports PreemptDone and stops "
            "serving WITHOUT a CoordLeave — a parked life, not a dead one"),
    MessageCode.PreemptDone: PayloadSchema(
        fields=("grant_lo", "grant_hi", "snap_lo", "snap_hi", "lo_lo",
                "lo_hi", "hi_lo", "hi_hi", "apply_lo", "apply_hi"),
        handled_by=("coord",),
        dedup_key="request_id",
        doc="parked shard -> coordinator: range [lo,hi) parked at "
            "apply_seq under snapshot snap_id; the scheduler frees the "
            "slot and only NOW may grant it to another tenant (the "
            "double-grant gate the sched model checks)"),
    MessageCode.SlotGrant: PayloadSchema(
        fields=("grant_lo", "grant_hi", "tenant", "action", "slot"),
        handled_by=("coord",),
        dedup_key="request_id", fenced=True,
        doc="scheduler -> node agent: actuate a placement decision — "
            "action 1 grants slot to tenant (the agent spawns that "
            "tenant's member kind, e.g. an EngineMember for a serving "
            "tenant), action 0 revokes it (the agent retires the member). "
            "grant_id makes redelivery first-wins idempotent"),
    MessageCode.ResumeRequest: PayloadSchema(
        fields=("grant_lo", "grant_hi", "rank", "snap_lo", "snap_hi"),
        handled_by=("coord",),
        dedup_key="request_id", fenced=True,
        doc="scheduler -> node agent: resume the member parked under "
            "grant_id as a fresh life of `rank`, restoring snapshot "
            "snap_id bit-for-bit from the FleetManifest and replaying "
            "WAL'd deltas exactly once before rejoining the fleet"),
    MessageCode.DeltaParams: PayloadSchema(
        fields=("codec", "epoch", "base_lo", "base_hi", "ver_lo", "ver_hi",
                "lo_lo", "lo_hi", "hi_lo", "hi_hi", "n_lo", "n_hi",
                "crc_lo", "crc_hi"),
        rest="body", rest_min=1, handled_by=("ps",),
        dedup_key="version",
        doc="server -> worker delta pull reply (ISSUE 18, utils/codecs.py "
            "DeltaParams plane, error-feedback contract): the body decodes "
            "to central[lo:hi) MINUS the worker's held base at (epoch, "
            "base version) — the server tracks each worker's exact "
            "materialized view, so base + decoded == central - residual "
            "holds exactly by construction. codec 0 = dense FULL install "
            "(the fallback rung: version miss, epoch change, restore, "
            "rebalance), 2 = top-k delta (the steady-state rung: the "
            "inter-pull delta is naturally sparse). A worker applies a "
            "delta only when (epoch, base) equals its held stamp, else it "
            "drops the reply and re-pulls full; crc guards the body like "
            "CompressedUpdate"),
    MessageCode.KvMigrate: PayloadSchema(
        fields=("codec", "id_lo", "id_hi", "n_tok_lo", "n_tok_hi",
                "n_kv_lo", "n_kv_hi", "crc_lo", "crc_hi"),
        rest="handoff", rest_min=1, handled_by=("serving",),
        dedup_key="request_id",
        doc="serving migration handoff (ISSUE 18, utils/codecs.py "
            "KvMigrate plane): the retiring engine's stream state for "
            "request id — n_tok token-history ids packed EXACT via tok16 "
            "(two ids per word; the resumed stream re-prefills from "
            "these, so token identity never depends on the lossy rung), "
            "then the slot's KV lane (n_kv elements) under `codec` (0 = "
            "dense f32, 1 = int8 per-block absmax, the serving cache's "
            "kv_quant recipe; bounded contract, verified at the "
            "receiver). crc covers the whole handoff body"),
}


Message = Tuple[int, MessageCode, np.ndarray]


class Transport:
    """Point-to-point tagged-tensor channel for one rank.

    This is THE wire abstraction every stack in the repo rides — the
    in-process queue world, the Python TCP star, and the native C++ fast
    path all implement it, and the reliability/chaos/durability layers wrap
    any of them interchangeably (``make_transport`` / ``make_world`` are
    the factories).
    """

    rank: int = 0

    def send(self, code: MessageCode, payload: np.ndarray, dst: int = SERVER_RANK) -> None:
        raise NotImplementedError

    def sendv(self, code: MessageCode, parts, dst: int = SERVER_RANK) -> None:
        """Scatter/gather send: one wire frame from several float32 parts.

        The base implementation concatenates (one copy); transports that
        can write parts sequentially (TCP ``sendall`` per part under the
        peer's send lock) override it to make envelope framing zero-copy —
        the reliability layer's 7-float header no longer costs a full
        payload-sized ``np.concatenate`` per send.
        """
        self.send(code, np.concatenate(
            [np.asarray(p, np.float32).ravel() for p in parts]), dst=dst)

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        """Blocking receive; returns ``None`` on timeout or closed transport."""
        raise NotImplementedError

    def close(self) -> None:
        pass


class InProcessTransport(Transport):
    """Queue-based transport: a whole world inside one process (for tests and
    single-host simulation of the PS topology)."""

    def __init__(self, rank: int, mailboxes: Dict[int, "queue.Queue[Message]"]):
        self.rank = rank
        self._boxes = mailboxes
        self._closed = False

    @classmethod
    def create_world(cls, world_size: int) -> Dict[int, "InProcessTransport"]:
        boxes: Dict[int, queue.Queue] = {r: queue.Queue() for r in range(world_size)}
        return {r: cls(r, boxes) for r in range(world_size)}

    def attach_rank(self, rank: int) -> "InProcessTransport":
        """Elastic join: a transport for ``rank`` sharing this world's
        mailboxes — a NEW rank gets a fresh mailbox, an existing rank id is
        a restarted life reusing its box (the coord/ membership layer tells
        those apart by incarnation, not by transport identity)."""
        self._boxes.setdefault(rank, queue.Queue())
        return InProcessTransport(rank, self._boxes)

    def send(self, code: MessageCode, payload: np.ndarray, dst: int = SERVER_RANK) -> None:
        # Copy: the receiver must never alias the sender's live buffer (e.g.
        # the server's central params, which it keeps updating in place) — the
        # TCP transport serializes and gets this isolation for free.
        arr = np.array(payload, dtype=np.float32, copy=True).ravel()
        self._boxes[dst].put((self.rank, MessageCode(code), arr))

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        if self._closed:
            return None
        try:
            return self._boxes[self.rank].get(timeout=timeout)
        except queue.Empty:
            return None

    def close(self) -> None:
        self._closed = True


def _send_frame(sock: socket.socket, sender: int, code: int, payload: np.ndarray) -> None:
    buf = payload.tobytes()
    sock.sendall(_HEADER.pack(sender, code, len(buf)) + buf)


def _recv_exact(sock: socket.socket, n: int) -> Optional[bytes]:
    chunks = []
    while n:
        try:
            b = sock.recv(min(n, 1 << 20))
        except (OSError, ValueError):
            return None
        if not b:
            return None
        chunks.append(b)
        n -= len(b)
    return b"".join(chunks)


#: Sentinel for "this frame was malformed but the stream is still framed" —
#: the reader logs, skips it, and keeps serving (``None`` still means the
#: connection is closed/unframeable and the reader should exit).
_MALFORMED = object()


def _recv_frame(sock: socket.socket):
    """One wire frame: a ``Message``, ``None`` (closed / unrecoverable), or
    :data:`_MALFORMED` (bad frame consumed; keep reading).

    Hardened (ISSUE 2 satellite): the declared payload length is bounded
    BEFORE any allocation, the MessageCode is validated before construction,
    and a malformed-but-framed frame is dropped with a log line instead of
    raising out of the reader thread. A length the framing cannot trust
    (negative, non-float32-aligned, or over :data:`MAX_FRAME_BYTES`) means
    the byte stream itself is garbage — there is no resync point — so the
    connection is dropped, loudly.
    """
    hdr = _recv_exact(sock, _HEADER.size)
    if hdr is None:
        return None
    sender, code, nbytes = _HEADER.unpack(hdr)
    if nbytes < 0 or nbytes > MAX_FRAME_BYTES:
        _LOGGER.warning(
            "dropping connection: unframeable payload length %d (sender=%d "
            "code=%d) — stream cannot be resynced", nbytes, sender, code,
        )
        return None
    body = _recv_exact(sock, nbytes)
    if body is None:
        return None
    try:
        mcode = MessageCode(code)
    except ValueError:
        _LOGGER.warning(
            "dropping malformed frame: unknown MessageCode %d from sender %d "
            "(%d bytes)", code, sender, nbytes,
        )
        return _MALFORMED
    if nbytes % 4:
        _LOGGER.warning(
            "dropping malformed frame: %d-byte payload is not float32-"
            "aligned (sender=%d code=%d)", nbytes, sender, code,
        )
        return _MALFORMED
    return sender, mcode, np.frombuffer(body, dtype=np.float32).copy()


class TCPTransport(Transport):
    """Star-topology socket transport (replaces the reference's gloo rendezvous
    at ``example/main.py:163-165`` for the async control plane).

    Rank 0 (the server) binds ``master:port`` and accepts ``world_size - 1``
    worker connections; workers dial in and identify themselves with a hello
    frame. Workers send to the server; the server replies to any worker.
    Incoming frames are pumped into a local queue by reader threads so
    :meth:`recv` has the same blocking-queue semantics as the in-process
    transport.
    """

    def __init__(
        self,
        rank: int,
        world_size: int,
        master: str = "localhost",
        port: int = 29500,
        connect_timeout: float = 60.0,
        wait_for: Optional[int] = None,
        handshake_timeout: float = 5.0,
    ):
        """``wait_for`` (server only) overrides how many worker connections
        the initial rendezvous blocks for — default ``world_size - 1``. An
        ELASTIC hub (the coordinator, ``coord/``) passes 0: it must serve
        the moment it is up, admitting members whenever they dial in;
        ``world_size`` then only bounds the valid rank space.

        ``handshake_timeout`` bounds how long one inbound connection may
        stall the hello handshake (ISSUE 7 satellite — previously a
        hard-coded 5 s): a half-open or malicious connection is dropped
        after this many seconds instead of wedging the accept loop."""
        self.rank = rank
        self.world_size = world_size
        self.handshake_timeout = float(handshake_timeout)
        self._inbox: "queue.Queue[Message]" = queue.Queue()
        self._peers: Dict[int, socket.socket] = {}
        self._threads = []
        self._closed = False
        # serializes concurrent senders (training loop + heartbeat thread) so
        # frames never interleave mid-write — sendall releases the GIL between
        # syscalls on large payloads. The native transport's send_mu
        # (native/transport.cpp) guards the same hazard.
        self._send_locks: Dict[int, threading.Lock] = {}
        # guards the peer-table structures (_peers/_send_locks/_retired):
        # the accept-loop thread rewires them on elastic rejoin while the
        # training/heartbeat threads look sockets up to send (distcheck
        # DC205 — the per-peer send lock orders I/O on one socket, but the
        # TABLE itself needs its own guard)
        self._peers_mu = threading.Lock()
        self._retired: list = []  # replaced-on-rejoin sockets, closed at close()
        if rank == SERVER_RANK:
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
            srv.bind((master if master != "localhost" else "", int(port)))
            srv.listen(world_size)
            self._server_sock = srv
            # block until world_size-1 DISTINCT workers are admitted (or
            # `wait_for`, for elastic hubs); garbage connections (malformed
            # hello) are dropped, not fatal, matching the native transport's
            # tolerant rendezvous
            need = world_size - 1 if wait_for is None else int(wait_for)
            while len(self._peers) < need:
                conn, _addr = srv.accept()
                try:
                    self._admit_worker(conn)
                except ConnectionError:
                    conn.close()
            # elastic rejoin: keep accepting after the initial rendezvous so
            # a restarted worker can reconnect mid-run (the reference has no
            # rejoin logic anywhere, SURVEY.md §5.3); a duplicate rank
            # replaces the dead socket
            t = threading.Thread(target=self._accept_loop, daemon=True)
            t.start()
            self._threads.append(t)
        else:
            # Retry refused dials until the server is listening — rendezvous
            # blocks until all ranks join, like the reference's
            # init_process_group (example/main.py:165), so worker processes
            # may start before the server. The poll rides the shared
            # jittered-backoff policy (seeded by rank+port, so N workers
            # launched together desynchronize their dials) instead of a
            # flat hard-coded sleep (ISSUE 7 satellite; distcheck DC108).
            from distributed_ml_pytorch_tpu.utils.backoff import Backoff

            deadline = time.monotonic() + connect_timeout
            policy = Backoff(0.05, 1.0, jitter=0.25,
                             seed=(rank << 16) ^ int(port))
            sock = None
            err: Optional[OSError] = None
            for _attempt in policy.attempts(deadline):
                try:
                    sock = socket.create_connection(
                        (master, int(port)),
                        timeout=min(self.handshake_timeout, connect_timeout))
                    break
                except OSError as e:
                    err = e
            if sock is None:
                raise err if err is not None else OSError(
                    f"connect to {master}:{port} timed out")
            sock.settimeout(None)  # connect timeout only; reads must block indefinitely
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            _send_frame(sock, rank, int(MessageCode.ParameterRequest), np.zeros(0, np.float32))
            self._peers[SERVER_RANK] = sock
            self._server_sock = None
            self._spawn_reader(sock)

    def _admit_worker(self, conn: socket.socket) -> None:
        """Handshake one inbound worker connection and start its reader.

        A rank that already has a peer socket is a *rejoin*: the stale socket
        (whose process died) is shut down — its reader exits — and replaced.
        """
        # bound the handshake: a half-open connection must not wedge the
        # single-threaded accept loop (or the rendezvous) forever; the
        # deadline is configurable (handshake_timeout), not hard-coded
        conn.settimeout(self.handshake_timeout)
        conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        hello = _recv_frame(conn)
        if hello is None or hello is _MALFORMED:
            raise ConnectionError("worker handshake failed")
        conn.settimeout(None)  # handshake done: reads must block indefinitely
        peer_rank = hello[0]
        if not (1 <= peer_rank < self.world_size):
            raise ConnectionError(f"invalid worker rank in hello: {peer_rank}")
        # swap under the peer's send lock so an in-flight send to the dead
        # socket finishes before the replacement (shutdown only — closing
        # here could recycle the fd under the old reader; closed at close())
        with self._send_lock_for(peer_rank):
            with self._peers_mu:
                old = self._peers.get(peer_rank)
                self._peers[peer_rank] = conn
                if old is not None:
                    self._retired.append(old)  # distcheck: ignore[DC503] one per peer REWIRE (finite incarnations); kept till close() so readers never see a recycled fd
            if old is not None:
                try:
                    old.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass
        self._spawn_reader(conn)

    def _send_lock_for(self, dst: int) -> threading.Lock:
        """The per-peer send serializer, created on first use. Lock ORDER
        is per-peer-lock → _peers_mu (send and _admit_worker both); this
        helper holds only _peers_mu, so the orders can never cross."""
        with self._peers_mu:
            lock = self._send_locks.get(dst)
            if lock is None:
                lock = self._send_locks[dst] = threading.Lock()
            return lock

    def _accept_loop(self) -> None:
        # poll with a timeout: a close() in another thread does not reliably
        # wake a blocked accept, so the loop must observe _closed itself
        self._server_sock.settimeout(0.25)
        while not self._closed:
            try:
                conn, _addr = self._server_sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return  # listener closed
            try:
                self._admit_worker(conn)
            except ConnectionError:
                conn.close()

    def _spawn_reader(self, sock: socket.socket) -> None:
        def pump():
            while not self._closed:
                msg = _recv_frame(sock)
                if msg is None:
                    break
                if msg is _MALFORMED:
                    continue  # logged in _recv_frame; the stream is intact
                self._inbox.put(msg)

        t = threading.Thread(target=pump, daemon=True)
        t.start()
        self._threads.append(t)  # distcheck: ignore[DC503] one reader per accepted conn, joined at close() — connection churn is bounded by peer rewires

    def send(self, code: MessageCode, payload: np.ndarray, dst: int = SERVER_RANK) -> None:
        self.sendv(code, (payload,), dst=dst)

    def sendv(self, code: MessageCode, parts, dst: int = SERVER_RANK) -> None:
        """Scatter/gather TCP send: header + each part written sequentially
        under the peer's send lock — one wire frame, zero payload-sized
        copies (the reliability envelope's header rides as its own tiny
        part instead of forcing a full-vector ``np.concatenate``)."""
        arrs = [np.ascontiguousarray(np.asarray(p, np.float32).ravel())
                for p in parts]
        nbytes = sum(a.nbytes for a in arrs)
        with self._send_lock_for(dst):
            # the socket lookup rides under BOTH locks: the per-peer lock
            # means no rejoin swap can land mid-send, _peers_mu means the
            # table read itself is never torn (KeyError for an unknown dst
            # is the documented contract, unchanged)
            with self._peers_mu:
                sock = self._peers[dst]
            if nbytes <= (1 << 16):
                # small frame: one syscall/packet beats zero-copy
                sock.sendall(b"".join(
                    [_HEADER.pack(self.rank, int(code), nbytes)]
                    + [a.tobytes() for a in arrs]))
                return
            sock.sendall(_HEADER.pack(self.rank, int(code), nbytes))
            for a in arrs:
                sock.sendall(memoryview(a).cast("B"))

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        # Poll in short slices so a blocking recv() still returns None once the
        # transport is closed (the documented Transport contract).
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._closed:
                return None
            slice_t = 0.1 if deadline is None else max(0.0, min(0.1, deadline - time.monotonic()))
            try:
                return self._inbox.get(timeout=slice_t)
            except queue.Empty:
                if deadline is not None and time.monotonic() >= deadline:
                    return None

    def close(self) -> None:
        self._closed = True
        with self._peers_mu:
            targets = list(self._peers.values()) + list(self._retired)
        for s in targets:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            s.close()
        if self._server_sock is not None:
            self._server_sock.close()


def _split16(value: int) -> Tuple[float, float]:
    """A uint32 as two float32-exact uint16 halves (the float32 wire carries
    integers exactly only below 2^24)."""
    return float(value & 0xFFFF), float((value >> 16) & 0xFFFF)


def _join16(lo: float, hi: float) -> int:
    return (int(lo) & 0xFFFF) | ((int(hi) & 0xFFFF) << 16)


#: the coordinator epoch fence trailer (ISSUE 17): every outbound frame a
#: coordinator life sends carries ``[FENCE_SEPARATOR, FENCE_MAGIC,
#: epoch_lo, epoch_hi]`` appended AFTER the schema's payload. A trailer
#: (not a head field) keeps every existing decoder layout untouched —
#: rest-bearing frames (ShardMapUpdate entries, FleetState tails) have no
#: spare head slot, and the member side strips the trailer BEFORE any
#: decode (``CoordClient._handle``). The separator alone is not enough
#: (FleetState tails already use -1 sections and payload floats are
#: arbitrary), so a magic constant no legitimate tail produces guards the
#: match; a frame without the trailer decodes as pre-ISSUE-17 (unfenced
#: coordinator — accepted, like the other optional-tail evolutions).
FENCE_SEPARATOR = -2.0
FENCE_MAGIC = 91217.0


def stamp_epoch(payload: np.ndarray, epoch: int) -> np.ndarray:
    """Append the coordinator epoch fence trailer to one outbound frame."""
    return np.concatenate([
        np.asarray(payload, np.float32),
        np.asarray([FENCE_SEPARATOR, FENCE_MAGIC, *_split16(int(epoch))],
                   np.float32)])


def strip_epoch(payload: np.ndarray):
    """Split a frame into ``(body, epoch)``; ``epoch`` is ``None`` for an
    unstamped (pre-fencing) frame. The inverse of :func:`stamp_epoch`."""
    if (payload.size >= 4
            and float(payload[-4]) == FENCE_SEPARATOR
            and float(payload[-3]) == FENCE_MAGIC):
        return payload[:-4], _join16(payload[-2], payload[-1])
    return payload, None


_INC_LOCK = threading.Lock()
_LAST_INC = 0


#: bodies at or above this many bytes switch from a full crc32 to the bulk
#: digest (64-bit word sum + length, crc-mixed with the header) — see
#: :func:`_frame_crc` for the integrity tradeoff. The choice is a pure
#: function of the body LENGTH, so both ends always agree.
_BULK_SUM_BYTES = 1 << 16


def _frame_crc(inc: int, seq: int, code: int, body, corr: int = 0) -> int:
    """Checksum over the WHOLE envelope (incarnation, seq, code,
    correlation id, body): a wire flip in any header field must fail the
    check, or e.g. a corrupted incarnation would be adopted as a 'newer
    life' and blackhole every subsequent legitimate frame as stale (and a
    flipped correlation id would stitch the flight-recorder timeline to
    the wrong unit of work).

    ``body`` is any buffer — bytes, memoryview, or a contiguous float32
    array — and is NEVER copied (ISSUE 7: the old ``tobytes()`` cost ~9 ms
    per end per direction on the 9.9 MB PS frames).

    Small frames (control plane, token streams) get a full crc32. Bulk
    frames use a 64-bit little-endian word sum + exact length, crc-mixed
    with the header — it runs at memory bandwidth (~6 GB/s vs ~1 GB/s for
    zlib's crc32, measured), which is what recovers the ack-tax the
    reliability layer used to charge on gradient-sized payloads. Integrity
    tradeoff, stated honestly: the sum catches EVERY corruption that
    changes any single 32-bit word (all single-burst flips, and exactly
    what the chaos layer injects) and all length changes, but unlike a CRC
    it can be fooled by multiple compensating word errors; beneath this
    layer TCP's own checksum already screens the wire, so the residual
    risk is compensating application-level corruption — accepted for a
    ~4x cheaper hot path."""
    head = struct.pack("<IIII", inc & 0xFFFFFFFF, seq & 0xFFFFFFFF,
                       code & 0xFFFFFFFF, corr & 0xFFFFFFFF)
    h = zlib.crc32(head)
    if isinstance(body, np.ndarray):
        mv = memoryview(np.ascontiguousarray(body)).cast("B")
    elif isinstance(body, memoryview):
        mv = body.cast("B")
    else:
        mv = memoryview(body)
    nbytes = mv.nbytes
    if nbytes >= _BULK_SUM_BYTES:
        # uint64 word sum at memory bandwidth (~0.5 ms / 9.9 MB measured,
        # vs ~10 ms for crc32); any sub-8-byte tail rides the crc
        n8 = nbytes // 8 * 8
        words = np.frombuffer(mv[:n8], np.uint64)
        digest = struct.pack(
            "<QI", int(words.sum(dtype=np.uint64)), nbytes)
        h = zlib.crc32(digest, h)
        if n8 != nbytes:
            h = zlib.crc32(mv[n8:], h)
        return h & 0xFFFFFFFF
    return zlib.crc32(mv, h) & 0xFFFFFFFF


def _next_incarnation() -> int:
    """Second-stamped (32 bits of epoch seconds wrap in 2106 — a
    millisecond stamp would wrap every ~50 days and make a post-wrap
    restart read as an OLDER life), strictly increasing within this
    process so transports created in the same second still read as
    distinct lives."""
    global _LAST_INC
    with _INC_LOCK:
        _LAST_INC = max(_LAST_INC + 1, int(time.time()) & 0xFFFFFFFF)
        return _LAST_INC


class _Pending:
    __slots__ = ("parts", "dst", "deadline", "attempt", "code",
                 "first_sent", "retransmitted", "corr")

    def __init__(self, parts, dst: int, deadline: float, code: int = -1,
                 corr: int = 0):
        self.parts = parts  # (header, body) — re-sent via sendv, zero-copy
        self.dst = dst
        self.deadline = deadline
        self.attempt = 1
        self.code = code  # inner MessageCode (per-code ack accounting)
        self.corr = corr  # flight-recorder correlation id (ISSUE 12)
        self.first_sent = 0.0
        #: Karn's rule: an RTT sample is only taken from a frame that was
        #: never retransmitted (an ack for a retransmitted frame is
        #: ambiguous about WHICH transmission it answers)
        self.retransmitted = False


class _PeerState:
    """Per-peer sender-side state: the RTT estimator, the sliding-window
    accounting, and the circuit breaker."""

    __slots__ = ("srtt", "rttvar", "rto", "inflight", "credit",
                 "consec_timeouts", "breaker", "dead", "probe_key",
                 "probe_at", "opens", "last_ack")

    def __init__(self, rto: float):
        self.srtt: Optional[float] = None
        self.rttvar = 0.0
        self.rto = rto
        self.last_ack = 0.0        # monotonic stamp of the last ack heard
        self.inflight = 0          # pending (unacked) frames toward the peer
        self.credit: Optional[int] = None  # receiver-advertised window
        self.consec_timeouts = 0   # RTO blowups since the last ack
        self.breaker = "closed"    # "closed" | "open" (probe_key => half-open)
        self.dead = False          # terminal give-up (revived only by contact)
        self.probe_key = None      # pending key currently serving as probe
        self.probe_at = 0.0        # when the open breaker may half-open
        self.opens = 0             # consecutive opens (cooldown exponent)


class _RxState:
    """Per-sender receiver-side state for cumulative acking."""

    __slots__ = ("inc", "cum_hw", "eligible", "dirty", "last_flush")

    def __init__(self, inc: int):
        self.inc = inc
        #: highest seq such that EVERY seq <= cum_hw has been delivered and
        #: is ack-eligible (durably applied, for deferred-ack receivers)
        self.cum_hw = -1
        #: ack-eligible seqs above a gap, waiting for it to fill
        self.eligible: set = set()
        self.dirty = 0             # eligible deliveries since the last flush
        self.last_flush = 0.0


class ReliableTransport(Transport):
    """Reliable delivery over any :class:`Transport` (the ISSUE 2 tentpole's
    reliability layer).

    Sender side: every frame is wrapped in a ``ReliableFrame`` envelope
    carrying a per-peer sequence number and a CRC-32 of the payload bytes; a
    background thread retries unacked frames with capped exponential backoff
    (``ack_timeout · 2^attempt``, capped at ``max_backoff``) until an
    ``ReliableAck`` arrives or ``max_retries`` is exhausted — at which point
    the peer is declared dead and subsequent sends to it raise
    ``ConnectionError``, feeding the existing degrade-to-local path
    (``parallel/async_ps.Asynchronous._send``).

    Receiver side: a corrupt frame (CRC mismatch) is dropped unacked — the
    sender retries; a duplicate (retry of an acked frame, or a wire-level
    dup) is re-acked but NOT redelivered, so e.g. the parameter server
    applies each ``GradientUpdate`` exactly once under duplicates/retries.

    Peer lifecycle: the envelope carries a per-instance *incarnation*
    (millisecond construction stamp), so a restarted peer's fresh sequence
    space is not mistaken for duplicates of its previous life — a NEWER
    incarnation resets that sender's dedup state, an older one (a straggler
    retry from the dead process) is acked-and-dropped. Symmetrically, any
    frame received from a rank previously declared dead revives it for
    sending (the rejoin path).

    Negotiation is per transport and symmetric-but-tolerant: both ends of a
    link should wrap (``--reliable``), yet plain frames from an unwrapped
    peer pass straight through, and :attr:`unreliable_codes` (heartbeats
    and coord lease renewals by default — periodic and self-healing) skip
    the envelope entirely so a dead peer cannot trigger a retry storm.

    Adaptive wire (ISSUE 7), per peer:

    - **RTO** — Jacobson/Karels ``SRTT/RTTVAR`` from ack round-trips
      (Karn's rule: never sample a retransmitted frame), clamped to
      ``[ack_timeout, max_backoff]``; retransmit backoff is exponential
      with seeded jitter. ``ack_timeout`` is thus the RTO *floor* and
      initial value, not a fixed timer.
    - **Sliding window** — at most ``min(send_window, advertised credit)``
      unacked frames in flight; :meth:`send` BLOCKS at the window (the
      backpressure surface: a slow receiver slows its senders instead of
      growing their pending without bound — the flapping-peer OOM is
      structurally impossible). A peer whose breaker opens while a sender
      waits raises ``ConnectionError`` out of the blocked send.
    - **Cumulative acks** — in-order deliveries are acked by one
      ``CumAck(inc, cum, credit)`` per batch (``ack_batch_n`` frames or
      one retry-tick, whichever first) instead of one ``ReliableAck`` per
      frame; out-of-order frames still get immediate individual acks
      (SACK-style), and deferred-ack receivers (``ack_on_delivery=False``)
      advance the cumulative frontier only at :meth:`ack_delivered` — the
      WAL group-fsync IS the ack batch boundary.
    - **Circuit breaker** — ``breaker_fails`` consecutive RTO blowups open
      the breaker: sends fail fast (``ConnectionError``), retransmits
      pause, and after a growing cooldown ONE pending frame probes
      (half-open). An ack closes the breaker; ``max_retries`` exhausted
      attempts still declare the peer dead (terminal until it speaks).
      Breaker state feeds the coordinator's lease view
      (``open_breakers()``) and the HeartbeatSender (``breaker_open()``).
    """

    def __init__(
        self,
        inner: Transport,
        *,
        ack_timeout: float = 0.1,
        max_backoff: float = 2.0,
        max_retries: int = 10,
        dedup_window: int = 4096,
        unreliable_codes: Tuple[MessageCode, ...] = (
            MessageCode.Heartbeat, MessageCode.LeaseRenew),
        ack_on_delivery: bool = True,
        send_window: int = 32,
        recv_window: int = 64,
        ack_batch_n: int = 8,
        batched_acks: bool = True,
        breaker_fails: int = 6,
        breaker_cooldown: float = 0.5,
        breaker_grace: Optional[float] = None,
        jitter: float = 0.25,
    ):
        import random

        self.inner = inner
        self.rank = inner.rank
        self.ack_timeout = float(ack_timeout)   # RTO floor + initial RTO
        self.max_backoff = float(max_backoff)   # RTO / backoff cap
        self.max_retries = int(max_retries)
        self.dedup_window = int(dedup_window)
        self.send_window = int(send_window)
        self.recv_window = int(recv_window)
        self.ack_batch_n = int(ack_batch_n)
        self.batched_acks = bool(batched_acks)
        self.breaker_fails = int(breaker_fails)
        self.breaker_cooldown = float(breaker_cooldown)
        #: the breaker opens only when the peer has been ACK-SILENT this
        #: long on top of breaker_fails timed-out ticks — a lossy-but-alive
        #: link (acks still trickling) keeps flowing; default = max_backoff
        self.breaker_grace = (
            float(breaker_grace) if breaker_grace is not None
            else self.max_backoff)
        self.jitter = float(jitter)
        self.unreliable_codes = frozenset(
            int(c) for c in unreliable_codes
        ) | {int(MessageCode.ReliableFrame), int(MessageCode.ReliableAck),
             int(MessageCode.CumAck)}
        self._lock = threading.Lock()
        #: seeded per-instance jitter stream (rank-derived): retransmit
        #: timing desynchronizes across peers, stays reproducible per rank
        self._jrng = random.Random((self.rank << 8) ^ 0x5EED)
        #: this sender instance's incarnation: restarted processes stamp a
        #: LATER value, which tells receivers to reset dedup state for the
        #: rank instead of blackholing the fresh seq space
        self.incarnation = _next_incarnation()
        self._next_seq: Dict[int, int] = {}
        self._pending: Dict[Tuple[int, int], _Pending] = {}
        self._peers: Dict[int, _PeerState] = {}
        #: frames surfaced while a blocked send()/flush() pumped the inner
        #: transport, parked for the next recv(). Each entry carries the
        #: correlation id its delivery installed, so popping RESTORES it —
        #: without this, a later delivery's corr would leak onto a parked
        #: frame's handler (the wrong-timeline stitch)
        self._requeue: "collections.deque" = collections.deque()
        self._seen: Dict[int, "collections.OrderedDict"] = {}
        self._peer_inc: Dict[int, int] = {}
        self._rx: Dict[int, _RxState] = {}
        self._credit_override: Optional[int] = None
        self._dead_peers: set = set()
        #: durability hook (ISSUE 5): with ``ack_on_delivery=False`` the ack
        #: for a DELIVERED data frame is withheld until the receiver calls
        #: :meth:`ack_delivered` — the parameter server does so only after
        #: the applied update is fsync'd into its WAL (log-before-ack), so
        #: "acked" really means "survives a crash". Duplicates of a frame
        #: whose ack is still deferred are NOT re-acked early (the retry is
        #: the sender doing its job until durability is committed).
        self.ack_on_delivery = bool(ack_on_delivery)
        self._deferred_acks: "collections.OrderedDict" = collections.OrderedDict()
        self._last_delivery: Optional[Tuple[int, int]] = None
        self._acked_codes: Dict[Tuple[int, int], int] = {}
        self._closed = False
        self.stats = {
            "sent": 0, "retries": 0, "acked": 0, "gave_up": 0,
            "crc_dropped": 0, "dup_dropped": 0, "delivered": 0,
            "passthrough": 0,
            # adaptive-wire telemetry (ISSUE 7)
            "cum_acked": 0, "acks_tx": 0, "cum_acks_tx": 0,
            "rto_expired": 0, "window_blocked": 0, "breaker_opens": 0,
            "probes": 0,
            # observability plane (ISSUE 12): cumulative seconds sends
            # spent BLOCKED at the credit window — serve loops carve this
            # out of their compute attribution (utils/obs.StateClock)
            "window_blocked_s": 0.0,
        }
        #: optional flight recorder (``utils/obs.SpanRecorder``), attached
        #: post-construction: wire-blocked spans, retransmit / breaker /
        #: give-up events, ack releases — the wire plane's side of the
        #: timeline. Never consulted for any protocol decision.
        self.recorder = None
        self._retry_wake = threading.Event()
        self._retry_thread = threading.Thread(
            target=self._retry_loop, name="reliable-retry", daemon=True)
        self._retry_thread.start()

    # ---------------------------------------------------------- peer state
    def _peer(self, dst: int) -> _PeerState:
        """Caller holds ``_lock``."""
        st = self._peers.get(dst)
        if st is None:
            st = self._peers[dst] = _PeerState(self.ack_timeout)
            # the grace anchor starts at peer birth: a link whose first
            # ack is merely SLOW (high-latency weather) must get the full
            # breaker_grace before it can read as gone
            st.last_ack = time.monotonic()
        return st

    def _rtt_sample(self, st: _PeerState, sample: float) -> None:
        """Jacobson/Karels; caller holds ``_lock``."""
        if sample <= 0:
            return
        if st.srtt is None:
            st.srtt = sample
            st.rttvar = sample / 2.0
        else:
            st.rttvar = 0.75 * st.rttvar + 0.25 * abs(st.srtt - sample)
            st.srtt = 0.875 * st.srtt + 0.125 * sample
        st.rto = min(max(st.srtt + max(4.0 * st.rttvar, 0.01),
                         self.ack_timeout), self.max_backoff)

    def _on_peer_ack(self, st: _PeerState) -> None:
        """An ack arrived: the send path to this peer works. Caller holds
        ``_lock``."""
        st.consec_timeouts = 0
        st.last_ack = time.monotonic()
        if st.breaker != "closed":
            st.breaker = "closed"
            st.probe_key = None
            st.opens = 0

    def _revive(self, sender: int) -> None:
        """ANY frame from a dead-declared rank is evidence of life (the
        rejoin path). A merely-OPEN breaker is NOT closed here: on a one-way
        degraded link the peer's data keeps arriving while our sends rot
        unacked — only an ack may close the breaker, or the revive would
        re-arm a retry storm every inbound frame. Caller holds ``_lock``."""
        if sender in self._dead_peers:
            self._dead_peers.discard(sender)
            st = self._peer(sender)
            st.dead = False
            st.breaker = "closed"
            st.probe_key = None
            st.consec_timeouts = 0

    def _backoff_delay(self, st: _PeerState, attempt: int) -> float:
        """Jittered capped exponential backoff off the ADAPTIVE RTO (the
        shared policy shape, ``utils/backoff.py``; inlined here because the
        base — st.rto — moves with the link weather)."""
        raw = st.rto * (2.0 ** max(0, attempt - 1))
        jit = 1.0 + self.jitter * (2.0 * self._jrng.random() - 1.0)
        return min(raw * jit, self.max_backoff)

    # ---------------------------------------------------------------- send
    def send(self, code: MessageCode, payload: np.ndarray, dst: int = SERVER_RANK) -> None:
        if int(code) in self.unreliable_codes:
            self.inner.send(code, payload, dst=dst)
            return
        arr = np.ascontiguousarray(np.asarray(payload, dtype=np.float32).ravel())
        # flight-recorder correlation (ISSUE 12): the sender's thread-local
        # id rides the envelope so the receiver's handler inherits it; 0
        # means "no active unit of work" and costs nothing downstream
        corr = _obs.current_corr()
        # sliding window: block while the peer's in-flight frames fill
        # min(send_window, advertised credit) — the backpressure that keeps
        # a slow/jittery link from growing pending without bound. The
        # blocked sender PUMPS the inner transport itself (like flush()):
        # acks must clear even on a rank with no recv thread, or a pure
        # sender would deadlock at its own window; data frames that arrive
        # meanwhile are requeued for the next recv().
        blocked = False
        block_t0 = 0
        while True:
            with self._lock:
                st = self._peer(dst)
                if st.dead or st.breaker == "open":
                    raise ConnectionError(
                        f"peer {dst} "
                        + ("declared dead after "
                           f"{self.max_retries} unacked retries" if st.dead
                           else "circuit breaker open (consecutive RTO "
                                "blowups)"))
                if self._closed or st.inflight < self._window(st):
                    seq = self._next_seq.get(dst, 0)
                    self._next_seq[dst] = seq + 1
                    # reserve the window slot INSIDE the admission check's
                    # critical section: two threads sending to one peer
                    # must not both pass the check and overshoot the
                    # window (check-then-act); _pop_pending releases it
                    st.inflight += 1
                    break
                if not blocked:
                    blocked = True
                    block_t0 = time.monotonic_ns()
                    self.stats["window_blocked"] += 1
            delivered = self._process(self.inner.recv(timeout=0.02))
            if delivered is not None:
                self._requeue.append((_obs.current_corr(), delivered))
        if blocked:
            # credit-blocked time is a first-class wait state: the serve
            # loop carves it out of whatever state it was in, and the span
            # itself lands on the wire plane's timeline
            now_ns = time.monotonic_ns()
            with self._lock:
                self.stats["window_blocked_s"] += (now_ns - block_t0) / 1e9
            rec = self.recorder
            if rec is not None:
                rec.record("wire-blocked", "wire-blocked", block_t0, now_ns,
                           corr=corr, meta={"dst": dst})
        try:
            crc = _frame_crc(self.incarnation, seq, int(code), arr, corr)
            header = np.asarray(
                [*_split16(self.incarnation), *_split16(seq), *_split16(crc),
                 float(int(code)), *_split16(corr)], np.float32)
            parts = (header, arr)
        except Exception:
            with self._lock:
                st = self._peer(dst)
                st.inflight = max(0, st.inflight - 1)
            raise
        now = time.monotonic()
        with self._lock:
            st = self._peer(dst)
            p = _Pending(parts, dst, now + st.rto, code=int(code), corr=corr)
            p.first_sent = now
            self._pending[(dst, seq)] = p
            self.stats["sent"] += 1
        try:
            self.inner.sendv(MessageCode.ReliableFrame, parts, dst=dst)
        except (OSError, ConnectionError, KeyError):
            # the retry loop owns recovery; a transient send failure is
            # exactly what the pending buffer exists for
            pass

    def _window(self, st: _PeerState) -> int:
        """Effective send window; never below 1 (one probe frame must stay
        allowed, or a zero-credit advertisement could deadlock the link —
        acks only flow when frames do)."""
        w = self.send_window
        if st.credit is not None:
            w = min(w, st.credit)
        return max(1, w)

    def _pop_pending(self, key) -> Optional[_Pending]:
        """Caller holds ``_lock``."""
        p = self._pending.pop(key, None)
        if p is not None:
            st = self._peer(p.dst)
            st.inflight = max(0, st.inflight - 1)
        return p

    def _give_up(self, key, p: _Pending, now: float) -> None:
        """Terminal give-up: the peer is dead until it speaks again.
        Caller holds ``_lock``."""
        st = self._peer(p.dst)
        self._pop_pending(key)
        # distcheck: ignore[DC201] caller holds _lock (documented contract)
        self.stats["gave_up"] += 1
        st.dead = True
        st.breaker = "open"
        st.probe_key = None
        self._dead_peers.add(p.dst)
        dropped = [k for k in self._pending if k[0] == p.dst]
        for k in dropped:
            self._pop_pending(k)
        _LOGGER.warning(
            "reliable: peer %d unacked after %d retries — declaring it "
            "dead (%d queued frames dropped)",
            p.dst, self.max_retries, len(dropped))

    def _retry_tick(self) -> None:
        """One pass of the adaptive retransmission machinery: RTO expiries,
        breaker transitions, half-open probes."""
        now = time.monotonic()
        resend: list = []
        timed_out: set = set()
        with self._lock:
            for key, p in list(self._pending.items()):
                st = self._peer(p.dst)
                if st.dead:
                    continue
                if st.breaker == "open":
                    if st.probe_key is None:
                        if now < st.probe_at:
                            continue
                        # half-open: exactly one pending frame probes the
                        # link (the oldest — dict order is send order)
                        if p.attempt > self.max_retries:
                            self._give_up(key, p, now)
                            continue
                        st.probe_key = key
                        p.attempt += 1
                        p.retransmitted = True
                        p.deadline = now + self._backoff_delay(st, p.attempt)
                        self.stats["probes"] += 1
                        resend.append(p)
                    elif st.probe_key == key and p.deadline <= now:
                        # probe unanswered: deepen the open state
                        st.probe_key = None
                        st.opens += 1
                        st.probe_at = now + min(
                            self.breaker_cooldown * (2.0 ** st.opens),
                            4.0 * self.max_backoff)
                        if p.attempt > self.max_retries:
                            self._give_up(key, p, now)
                    continue
                if p.deadline > now:
                    continue
                if p.attempt > self.max_retries:
                    self._give_up(key, p, now)
                    continue
                timed_out.add(p.dst)
                self.stats["rto_expired"] += 1
                p.attempt += 1
                p.retransmitted = True
                p.deadline = now + self._backoff_delay(st, p.attempt)
                self.stats["retries"] += 1
                resend.append(p)
            # a BURST of same-tick expiries (one loss event hitting a whole
            # window) is ONE piece of gone-ness evidence, not N: count the
            # breaker's "consecutive RTO blowups" per peer per pass, reset
            # by any ack — so a lossy-but-alive link keeps flowing while a
            # genuinely silent peer opens after breaker_fails quiet ticks
            for dst in timed_out:
                st = self._peer(dst)
                st.consec_timeouts += 1
                # Karn's rule, part 2: a timeout BACKS OFF the peer's base
                # RTO and the backed-off value persists for new frames —
                # without this, a floor below the true RTT retransmits
                # every frame, no frame ever yields a valid sample (part 1
                # excludes retransmitted frames), and the estimator can
                # never climb out of the spurious-retransmit storm. The
                # next clean sample recomputes from SRTT/RTTVAR.
                st.rto = min(st.rto * 2.0, self.max_backoff)
                ack_silent = now - st.last_ack >= self.breaker_grace
                if st.consec_timeouts >= self.breaker_fails and ack_silent \
                        and not st.dead and st.breaker == "closed":
                    st.breaker = "open"
                    st.opens += 1
                    st.probe_key = None
                    st.probe_at = now + min(
                        self.breaker_cooldown * (2.0 ** (st.opens - 1)),
                        4.0 * self.max_backoff)
                    self.stats["breaker_opens"] += 1
                    if self.recorder is not None:
                        self.recorder.event("breaker-open", corr=0, dst=dst)
                    _LOGGER.warning(
                        "reliable: circuit to peer %d OPEN after %d "
                        "consecutive RTO blowups (rto %.0f ms) — pausing "
                        "retransmits, probe in %.2f s", dst,
                        st.consec_timeouts, st.rto * 1e3,
                        st.probe_at - now)
        rec = self.recorder
        for p in resend:
            if rec is not None:
                rec.event("retransmit", corr=p.corr, dst=p.dst,
                          attempt=p.attempt, code=p.code)
            try:
                self.inner.sendv(MessageCode.ReliableFrame, p.parts,
                                 dst=p.dst)
            except (OSError, ConnectionError, KeyError):
                pass  # next pass retries or gives up

    def _retry_loop(self) -> None:
        tick = min(0.02, self.ack_timeout / 2)
        while not self._closed:
            self._retry_wake.wait(tick)
            self._retry_wake.clear()
            if self._closed:
                return
            self._flush_acks()  # timed cumulative-ack flush
            self._retry_tick()

    # ---------------------------------------------------------------- recv
    def _process(self, msg: Optional[Message]) -> Optional[Message]:
        """Handle one inner frame: acks and envelope bookkeeping are
        absorbed; returns a deliverable message or ``None``."""
        if msg is None:
            return None
        sender, code, payload = msg
        # ANY frame from a rank previously declared dead is evidence of
        # life: a restarted peer on the same rank must be sendable again
        # (the reconnect-and-resume / rejoin paths); discard is idempotent,
        # so the membership test rides inside the lock with it
        with self._lock:
            self._revive(sender)
        if code == MessageCode.ReliableAck:
            # the ack echoes the FRAME's incarnation: a straggler ack for a
            # previous life's frame (same seq, old inc) must not clear the
            # new life's pending entry — that frame still needs its retry
            if payload.size >= 4:
                try:
                    seq = _join16(payload[0], payload[1])
                    inc = _join16(payload[2], payload[3])
                except (ValueError, OverflowError):
                    return None
                if inc != self.incarnation:
                    return None
                now = time.monotonic()
                with self._lock:
                    p = self._pop_pending((sender, seq))
                    if p is not None:
                        st = self._peer(sender)
                        if not p.retransmitted:
                            self._rtt_sample(st, now - p.first_sent)
                        self._on_peer_ack(st)
                        self.stats["acked"] += 1
                        key = (sender, p.code)
                        self._acked_codes[key] = \
                            self._acked_codes.get(key, 0) + 1
            return None
        if code == MessageCode.CumAck:
            # batched cumulative ack: every seq <= cum of OUR incarnation
            # is acknowledged, and the peer's advertised credit rides along
            if payload.size >= 5 and np.isfinite(payload[:5]).all():
                try:
                    inc = _join16(payload[0], payload[1])
                    cum = _join16(payload[2], payload[3])
                    credit = int(payload[4])
                except (ValueError, OverflowError):
                    return None
                if inc != self.incarnation:
                    return None
                now = time.monotonic()
                with self._lock:
                    st = self._peer(sender)
                    st.credit = credit
                    keys = [k for k in self._pending
                            if k[0] == sender and k[1] <= cum]
                    freshest = None
                    for k in keys:
                        p = self._pop_pending(k)
                        self.stats["acked"] += 1
                        self.stats["cum_acked"] += 1
                        ck = (sender, p.code)
                        self._acked_codes[ck] = \
                            self._acked_codes.get(ck, 0) + 1
                        if not p.retransmitted and (
                                freshest is None
                                or p.first_sent > freshest):
                            freshest = p.first_sent
                    if freshest is not None:
                        self._rtt_sample(st, now - freshest)
                    self._on_peer_ack(st)
            return None
        if code != MessageCode.ReliableFrame:
            with self._lock:
                self.stats["passthrough"] += 1
                self._last_delivery = None  # no envelope to remember
            _obs.set_corr(0)  # no envelope: never inherit a stale id
            return msg  # plain frame from an unwrapped peer
        if payload.size < 9:
            return None  # truncated envelope: unacked → sender retries
        try:
            inc = _join16(payload[0], payload[1])
            seq = _join16(payload[2], payload[3])
            crc = _join16(payload[4], payload[5])
            inner_code = int(payload[6])
            corr = _join16(payload[7], payload[8])
        except (ValueError, OverflowError):
            # corruption turned a header float non-finite: unparseable,
            # unacked → the sender's retry delivers a clean copy
            with self._lock:
                self.stats["crc_dropped"] += 1
            return None
        body = payload[9:]
        if _frame_crc(inc, seq, inner_code, body, corr) != crc:
            with self._lock:
                self.stats["crc_dropped"] += 1
            return None  # corrupt: no ack, the retry delivers a clean copy
        with self._lock:
            known = self._peer_inc.get(sender)
            if known is None or inc > known:
                # a newer incarnation of this rank: fresh process, fresh
                # sequence space — the old dedup state would blackhole it
                self._peer_inc[sender] = inc
                self._seen.pop(sender, None)
                self._rx[sender] = _RxState(inc)
            # inc < known: straggler retry from the rank's previous life —
            # ack it below so the dead process stops retrying, never deliver
            stale = known is not None and inc < known
        deliver = not stale
        mcode: Optional[MessageCode] = None
        if deliver:
            try:
                mcode = MessageCode(inner_code)
            except ValueError:
                deliver = False  # ack (don't retry garbage), never deliver
        dup = False
        if deliver:
            with self._lock:
                rx = self._rx.setdefault(sender, _RxState(inc))
                seen = self._seen.setdefault(sender, collections.OrderedDict())
                if seq <= rx.cum_hw or seq in seen:
                    dup = True
                    self.stats["dup_dropped"] += 1
                else:
                    seen[seq] = True
                    while len(seen) > self.dedup_window:
                        seen.popitem(last=False)
                    self.stats["delivered"] += 1
        key = (sender, seq, inc)
        if deliver and not dup and not self.ack_on_delivery:
            # log-before-ack: the receiver releases this ack via
            # ack_delivered() once the applied update is durable
            with self._lock:
                self._deferred_acks[key] = True
                self._last_delivery = (inc, seq)
            # the envelope's correlation id becomes the recv thread's
            # active id: the handler about to run inherits the sender's
            # unit of work (ISSUE 12)
            _obs.set_corr(corr)
            return sender, mcode, body
        send_individual = False
        flush_now = False
        with self._lock:
            # a duplicate of a frame whose ack is still withheld must not
            # be re-acked early — the retry is the sender doing its job
            # until durability commits
            withheld = key in self._deferred_acks
            if not withheld:
                rx = self._rx.get(sender)
                if stale or not deliver or rx is None or rx.inc != inc \
                        or not self.batched_acks:
                    # stale-life straggler / undeliverable garbage /
                    # legacy-mode: the individual ack path
                    send_individual = True
                elif dup:
                    if seq <= rx.cum_hw:
                        # dup below the frontier: the next cumulative ack
                        # re-covers it — no per-frame re-ack storm
                        rx.dirty += 1
                        flush_now = rx.dirty >= self.ack_batch_n
                    else:
                        send_individual = True  # seeded/out-of-order dup
                else:
                    self._mark_eligible(rx, seq)
                    if seq <= rx.cum_hw:
                        rx.dirty += 1
                        flush_now = rx.dirty >= self.ack_batch_n
                    else:
                        # out-of-order (a gap below it): SACK-style
                        # immediate individual ack, cum catches up later
                        send_individual = True
        if send_individual:
            self._send_ack(sender, seq, inc)
        if flush_now:
            self._flush_acks()
        if deliver and not dup:
            with self._lock:
                self._last_delivery = (inc, seq)
            _obs.set_corr(corr)  # handler inherits the sender's unit of work
            return sender, mcode, body
        return None

    def _mark_eligible(self, rx: _RxState, seq: int) -> None:
        """Record one ack-eligible seq; advance the cumulative frontier
        through any now-contiguous run. Caller holds ``_lock``."""
        if seq == rx.cum_hw + 1:
            rx.cum_hw = seq
            while rx.cum_hw + 1 in rx.eligible:
                rx.cum_hw += 1
                rx.eligible.discard(rx.cum_hw)
        elif seq > rx.cum_hw:
            rx.eligible.add(seq)
            if len(rx.eligible) > self.dedup_window:
                # a permanent gap (frames lost to a peer death) must not
                # grow this set forever; dropped entries were individually
                # acked already, the frontier just can't cross the gap
                rx.eligible.discard(min(rx.eligible))

    def _send_ack(self, sender: int, seq: int, inc: int) -> None:
        with self._lock:
            self.stats["acks_tx"] += 1
        try:
            self.inner.send(
                MessageCode.ReliableAck,
                np.asarray([*_split16(seq), *_split16(inc)], np.float32),
                dst=sender)
        except (OSError, ConnectionError, KeyError):
            pass  # ack lost: the sender's retry re-triggers it

    def _credit_for(self, sender: int) -> int:
        """Advertised credit: how many more frames this receiver is willing
        to have in flight from ``sender``. Caller holds ``_lock``."""
        if self._credit_override is not None:
            return max(0, int(self._credit_override))
        # distcheck: ignore[DC204] caller holds _lock (documented contract)
        withheld = sum(1 for (s, _q, _i) in self._deferred_acks
                       if s == sender)
        return max(0, self.recv_window - withheld)

    def _flush_acks(self) -> None:
        """Send every dirty cumulative ack (called on batch-full, on the
        retry tick, and at durability commits). Sends ride OUTSIDE the
        lock."""
        out = []
        with self._lock:
            for sender, rx in self._rx.items():
                if rx.dirty <= 0 or rx.cum_hw < 0:
                    continue
                # a partial batch waits at most one retry tick (the timed
                # caller), well inside any sane RTO floor
                rx.dirty = 0
                out.append((sender, np.asarray(
                    [*_split16(rx.inc), *_split16(rx.cum_hw),
                     float(self._credit_for(sender))], np.float32)))
        for sender, frame in out:
            with self._lock:
                self.stats["cum_acks_tx"] += 1
            try:
                self.inner.send(MessageCode.CumAck, frame, dst=sender)
            except (OSError, ConnectionError, KeyError):
                pass  # lost ack: the sender's retransmit re-triggers it

    def ack_delivered(self) -> None:
        """Release every withheld delivery ack — call only once the applied
        updates behind them are durable (the WAL group commit). In-order
        runs collapse into ONE cumulative ack (the 36%-ack-tax recovery:
        ack batching pipelined with the group fsync); out-of-order stragglers
        keep their individual acks."""
        individual = []
        rec = self.recorder
        with self._lock:
            due = list(self._deferred_acks.keys())
            self._deferred_acks.clear()
        if rec is not None and due:
            # the durability commit just released these delivery acks —
            # the "ack release" instant of the worker-push timeline
            rec.event("ack-release", corr=0, n=len(due))
        with self._lock:
            for sender, seq, inc in due:
                rx = self._rx.get(sender)
                if rx is None or rx.inc != inc or not self.batched_acks:
                    individual.append((sender, seq, inc))
                    continue
                self._mark_eligible(rx, seq)
                if seq <= rx.cum_hw:
                    rx.dirty += 1
                else:
                    individual.append((sender, seq, inc))
        for sender, seq, inc in individual:
            self._send_ack(sender, seq, inc)
        self._flush_acks()

    def advertise_credit(self, credit: Optional[int]) -> None:
        """Pin the advertised send-window credit (``None`` restores the
        recv_window-derived default) and push it to every known sender —
        the receiver-side shed lever (an overloaded PS/engine narrows its
        senders' windows instead of letting queues grow)."""
        with self._lock:
            self._credit_override = credit
            for rx in self._rx.values():
                if rx.cum_hw >= 0:
                    rx.dirty = max(rx.dirty, 1)
        self._flush_acks()

    @property
    def last_delivery(self) -> Optional[Tuple[int, int]]:
        """``(incarnation, seq)`` of the most recently DELIVERED envelope
        (``None`` after a passthrough frame) — the identity a durable
        receiver records per WAL record so a restart can re-seed dedup."""
        with self._lock:
            return self._last_delivery

    def acked_count(self, dst: int, code: MessageCode) -> int:
        """How many frames of ``code`` sent to ``dst`` were acked — the
        sender half of the drill's sequence accounting."""
        with self._lock:
            return self._acked_codes.get((dst, int(code)), 0)

    def seed_dedup(self, entries) -> None:
        """Mark ``(sender, incarnation, seq)`` triples as already delivered
        — the receiver-restart path: a restored server replays its WAL,
        seeds the envelope identities it recorded, and a sender's retry of
        an applied-but-unacked frame is re-acked instead of re-applied
        (exactly-once application across receiver restarts)."""
        with self._lock:
            for sender, inc, seq in entries:
                known = self._peer_inc.get(sender)
                if known is None or inc > known:
                    self._peer_inc[sender] = inc
                    self._seen[sender] = collections.OrderedDict()
                if inc == self._peer_inc.get(sender):
                    seen = self._seen.setdefault(
                        sender, collections.OrderedDict())
                    seen[seq] = True
                    while len(seen) > self.dedup_window:
                        seen.popitem(last=False)
                    # the cumulative frontier stays below seeded entries
                    # (they may be sparse): dups of seeded seqs take the
                    # individual-ack path, which is exactly correct
                    self._rx.setdefault(sender, _RxState(inc))

    # -------------------------------------------------- wire-health surface
    def breaker_state(self, dst: int) -> str:
        """``closed`` / ``open`` / ``half-open`` / ``dead`` — the per-peer
        circuit state the coord lease view and HeartbeatSender consume."""
        with self._lock:
            st = self._peers.get(dst)
            if st is None:
                return "closed"
            if st.dead:
                return "dead"
            if st.breaker == "open":
                return "half-open" if st.probe_key is not None else "open"
            return "closed"

    def breaker_open(self, dst: int) -> bool:
        return self.breaker_state(dst) != "closed"

    def open_breakers(self) -> int:
        """How many peers currently have a non-closed circuit — rides the
        member's LeaseRenew so the coordinator sees wire health."""
        with self._lock:
            return sum(1 for st in self._peers.values()
                       if st.dead or st.breaker != "closed")

    def pending_depth(self, dst: Optional[int] = None) -> int:
        """Unacked frames in flight (toward ``dst``, or total) — the
        bounded-pending acceptance metric."""
        with self._lock:
            if dst is None:
                return len(self._pending)
            st = self._peers.get(dst)
            return 0 if st is None else st.inflight

    def pressure(self) -> float:
        """Worst-case window occupancy across peers, 0..1 — the wire
        backpressure signal the serving frontend folds into its overload
        pressure (a saturated window reads as a busy engine)."""
        with self._lock:
            worst = 0.0
            for st in self._peers.values():
                worst = max(worst, st.inflight / self._window(st))
            return min(1.0, worst)

    def rto(self, dst: int) -> float:
        """The peer's current adaptive retransmission timeout (seconds)."""
        with self._lock:
            st = self._peers.get(dst)
            return self.ack_timeout if st is None else st.rto

    def emit_wire_stats(self) -> None:
        """One summary event at teardown: the counters the timeline
        analyzer turns into wire attribution (retransmit share, ack frames
        per data frame, credit-block seconds) — cheap, once, instead of a
        per-send hot-path event (ISSUE 12)."""
        rec = self.recorder
        if rec is None:
            return
        with self._lock:
            stats = dict(self.stats)
        rec.event("wire-stats", corr=0,
                  **{k: (round(v, 6) if isinstance(v, float) else v)
                     for k, v in stats.items()})

    def detach(self) -> None:
        """Stop this wrapper (retry thread exits, ``recv`` returns None)
        WITHOUT closing the inner transport — for handing the endpoint to a
        replacement wrapper (the server-restart path in ``coord/drill.py``;
        a real restart replaces the process, here only the wrapper dies)."""
        if not self._closed:
            self.emit_wire_stats()
        self._closed = True
        self._retry_wake.set()

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            if self._closed:
                return None
            try:
                # frames surfaced by a blocked send()/flush(): re-install
                # the correlation id their delivery recorded
                corr, parked = self._requeue.popleft()
                _obs.set_corr(corr)
                return parked
            except IndexError:
                pass
            slice_t = 0.1
            if deadline is not None:
                slice_t = max(0.0, min(0.1, deadline - time.monotonic()))
            delivered = self._process(self.inner.recv(timeout=slice_t))
            if delivered is not None:
                return delivered
            if deadline is not None and time.monotonic() >= deadline:
                return None

    # --------------------------------------------------------------- admin
    def flush(self, timeout: float = 5.0) -> bool:
        """Block until every sent frame is acked (or a peer dies / timeout).

        Pumps the inner transport itself so acks clear even when no other
        thread is in :meth:`recv` (a pure sender); data frames that arrive
        meanwhile are requeued for the next ``recv``. Call before
        ``close()`` when the last frames matter (``WorkerDone``)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                live = [
                    k for k in self._pending if k[0] not in self._dead_peers
                ]
            if not live:
                return True
            delivered = self._process(self.inner.recv(timeout=0.02))
            if delivered is not None:
                self._requeue.append((_obs.current_corr(), delivered))
        return False

    def close(self) -> None:
        if not self._closed:
            self.flush(timeout=min(2.0, self.max_backoff))
            self.emit_wire_stats()
        self._closed = True
        self._retry_wake.set()
        self.inner.close()


def make_transport(
    rank: int,
    world_size: int,
    master: str = "localhost",
    port: int = 29500,
    kind: str = "auto",
    connect_timeout: float = 60.0,
    reliable: bool = False,
    durable_acks: bool = False,
    reliable_opts: Optional[dict] = None,
) -> Transport:
    """Transport factory for the PS control plane.

    ``kind``: ``"native"`` (C++ library, ``native/transport.cpp``),
    ``"python"`` (this module's :class:`TCPTransport`), or ``"auto"`` —
    native when the library builds/loads, Python otherwise. Both speak the
    same wire format, so mixed worlds (e.g. a native server with Python
    workers) interoperate.

    ``reliable=True`` wraps the result in a :class:`ReliableTransport`
    (seq + CRC + ack/retry + dedup). Negotiate it on every rank of a world
    (the CLI's ``--reliable``); an unwrapped peer's frames still pass
    through, it just gets no retransmit service.

    ``durable_acks=True`` (WAL'd servers only — the rank must drive
    ``ack_delivered`` via ``ParameterServer.commit``) defers delivery acks
    until the receiver declares the applied updates durable: log-before-ack,
    so "acked" survives a crash. Meaningless without ``reliable``.

    ``reliable_opts`` forwards tuning knobs (``ack_timeout``/``max_backoff``
    = RTO floor/cap, ``send_window``, ``ack_batch_n``, ``breaker_fails``,
    …) to :class:`ReliableTransport` without widening this signature for
    every one.
    """
    if kind not in ("auto", "native", "python"):
        raise ValueError(f"unknown transport kind: {kind!r}")
    t: Optional[Transport] = None
    if kind in ("auto", "native"):
        from distributed_ml_pytorch_tpu import native

        if native.native_available():
            t = native.NativeTCPTransport(
                rank, world_size, master, int(port), connect_timeout
            )
        elif kind == "native":
            raise RuntimeError(
                f"native transport requested but unavailable: {native.native_load_error()}"
            )
    if t is None:
        t = TCPTransport(rank, world_size, master, int(port), connect_timeout)
    if reliable:
        rt = ReliableTransport(t, ack_on_delivery=not durable_acks,
                               **(reliable_opts or {}))
        # CLI-process observability (ISSUE 12): the wrapper's counters are
        # visible in `--metrics-dump` snapshots without any caller wiring
        # (attach replaces any previous same-rank provider, so restarts
        # re-point it at the live instance)
        from distributed_ml_pytorch_tpu.utils.metrics import get_registry

        get_registry().attach(f"wire.rank{rank}",
                              lambda rt=rt: dict(rt.stats))
        return rt
    return t


def make_world(
    world_size: int,
    *,
    reliable: bool = False,
    plan=None,
    log=None,
    reliable_opts: Optional[dict] = None,
) -> Tuple[Dict[int, Transport], Optional[object]]:
    """One in-process world through the SAME layer stack the TCP/native
    paths use: raw mailboxes, optionally chaos-wrapped (``plan`` — a
    ``utils.chaos.ChaosPlan``), optionally reliability-wrapped on every
    rank. Returns ``(transports, chaos_log_or_None)``.

    This is the unified-transport entry the microbench ladder and the
    netweather tests build on: the wrapping ORDER (reliable over chaos over
    raw) is fixed here once, so every test and bench prices the same stack.
    """
    world: Dict[int, Transport] = InProcessTransport.create_world(world_size)
    chaos_log = None
    if plan is not None:
        from distributed_ml_pytorch_tpu.utils.chaos import FaultyTransport

        world, chaos_log = FaultyTransport.wrap_world(world, plan, log=log)
    if reliable:
        world = {r: ReliableTransport(t, **(reliable_opts or {}))
                 for r, t in world.items()}
    return world, chaos_log


# --- module-level default transport -----------------------------------------
# The reference's send_message has no transport argument — the gloo process
# group is ambient global state. We keep that call-site parity via a default
# transport installed at bootstrap.

_default_transport: Optional[Transport] = None


def set_default_transport(t: Optional[Transport]) -> None:
    global _default_transport
    _default_transport = t


def get_default_transport() -> Transport:
    if _default_transport is None:
        raise RuntimeError(
            "no default transport installed — call set_default_transport() "
            "(the analog of the reference's dist.init_process_group, "
            "example/main.py:165)"
        )
    return _default_transport


def send_message(
    message_code: MessageCode,
    payload,
    dst: int = SERVER_RANK,
    transport: Optional[Transport] = None,
) -> None:
    """Fire-and-forget tagged tensor send (reference ``Asynchronous.py:34,49,59``).

    ``payload`` may be a numpy array or a JAX array (device→host transfer
    happens here, outside any jitted computation).
    """
    t = transport or get_default_transport()
    t.send(MessageCode(message_code), np.asarray(payload, dtype=np.float32), dst=dst)


class MessageListener(threading.Thread):
    """Background receive loop (reference contract ``Asynchronous.py:9-18,37-38``).

    Subclasses override :meth:`receive`. Unlike the reference — whose listener
    mutates live model tensors mid-step (the deliberate DownPour data race,
    SURVEY.md §5.2) — subclasses here deposit results for the training loop to
    swap in *between* jitted steps (see ``parallel/async_ps.py``).
    """

    def __init__(self, model=None, transport: Optional[Transport] = None):
        super().__init__(daemon=True)
        self.model = model
        self.transport = transport or get_default_transport()
        self._running = threading.Event()
        self._running.set()

    def receive(self, sender: int, message_code: MessageCode, parameter: np.ndarray) -> None:
        raise NotImplementedError

    def run(self) -> None:
        while self._running.is_set():
            msg = self.transport.recv(timeout=0.1)
            if msg is None:
                continue
            sender, code, payload = msg
            self.receive(sender, code, payload)

    def stop(self) -> None:
        self._running.clear()
