"""Continuous-batching scheduler — the serving control plane.

DownPour's shape, transposed to inference (PAPER.md; DESIGN.md §3): many
asynchronous clients feed one compiled data plane, and all coordination is
host-side Python around jitted programs. The engine owns a
:class:`~distributed_ml_pytorch_tpu.serving.cache.SlotKVPool` and runs the
classic continuous-batching loop:

1. **evict** — free the slots of finished/cancelled requests;
2. **admit** — pop queued requests into free slots (one compiled prefill
   per request, bucketed prompt lengths), emitting each request's first
   token (TTFT ends here);
3. **decode** — one compiled block advances EVERY active slot by
   ``decode_block`` tokens, regardless of how heterogeneous the batch is.

Admission only happens between decode blocks, so a request arriving while
others are mid-decode joins the very next block — no draining, no
restarts. Backpressure is explicit: ``submit`` raises
:class:`QueueFullError` once ``max_queue`` requests are waiting, which the
transport frontend maps to a reject frame (``serving/frontend.py``).

SLO observability rides ``utils/metrics.py``/``utils/tracing.py``: TTFT,
TPOT and queue-wait samples summarized by ``latency_summary`` percentiles,
decode block latency through a ``StepTimer``, queue depth and slot
occupancy sampled every scheduling round, and the host time BETWEEN two
decode blocks with the phase that held the longest one (``slo_summary()``:
where a host stall shows), and how far into the allocation the decode
steps' reads of the K/V caches reached (``kv_read``), and whatever the model
counted on the device, returned with the tokens of an admission or a block
(``model_counters``: an expert layer's choices per expert). Tokens stream at block
granularity — per-token latency is the block time divided by the block's
tokens.

Tracing: every round writes ``serve.*`` spans through
``utils/tracing.span`` (``serve.step`` > ``serve.prefill`` (one a request,
with ``request_id``, ``queue_wait_us``, ``prompt_tokens`` and
``bucket_tokens``), ``serve.decode`` >
``serve.decode.dispatch|fetch``, ``serve.emit``; for a model that counts,
``serve.prefill.counters`` and ``serve.decode.counters`` with each counter's
sum and its entries that were not 0 as attributes) into whatever profile is
being taken, on the device trace's clock, and costs an object construction
each when none is. Each is read by a per-layer metric of the benchmark
(``benchmarks/program_trace.py``). The flight recorder
(``utils/obs.SpanRecorder``) is for the distributed planes (coordinator,
pipeline stages, the wire, the fleet router), not for the engine.

Determinism contract: with ``temperature=0`` (or any fixed sampling params
+ seed) a request's output is the same regardless of arrival order or what
shares the batch, and token-identical on CPU to ``generate(model, params,
prompt[None], max_new_tokens, rng=jax.random.key(seed))`` — slots are
independent vmap lanes over the same attention module (tested in
``tests/test_serving.py``).
"""

from __future__ import annotations

import collections
import dataclasses
import itertools
import threading
import time
from typing import Callable, Deque, List, Optional, Tuple

import numpy as np

from distributed_ml_pytorch_tpu.models.generate import (
    DECODE_BLOCK,
    sampled_and_filtered_rows,
)
from distributed_ml_pytorch_tpu.serving.cache import SlotKVPool
from distributed_ml_pytorch_tpu.utils.metrics import latency_summary
from distributed_ml_pytorch_tpu.utils.tracing import StepTimer, span


#: what the host can be doing between the end of one decode block's fetch
#: and the next block's dispatch
_GAP_PHASES = ("evict", "admit", "emit", "outside_step")


class QueueFullError(RuntimeError):
    """Raised by :meth:`ServingEngine.submit` when the wait queue is at
    ``max_queue`` — the engine's backpressure signal."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs, same semantics as ``generate()``:
    ``temperature <= 0`` is greedy (k/p/seed ignored); otherwise categorical
    at the given temperature with optional top-k / nucleus truncation, keys
    folded per token from ``jax.random.key(seed)``."""

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0


@dataclasses.dataclass
class Request:
    """One inference request and its whole lifecycle (the engine mutates it
    in place; ``wait()`` blocks until completion)."""

    request_id: int
    prompt: np.ndarray
    max_new_tokens: int
    sampling: SamplingParams
    eos_token: Optional[int] = None
    #: flight-recorder correlation id (ISSUE 12): minted at submit, so the
    #: queue -> prefill -> decode -> done journey is one timeline — and a
    #: MIGRATED request's resubmission keeps the original id across engines
    corr: int = 0
    #: sampling-key schedule offset: this request's token ``g`` is drawn
    #: with ``fold_in(key(seed), gen_offset + g)`` — nonzero only for a
    #: RESUMED request (fleet migration re-prefills prompt + generated-so-
    #: far on a surviving engine and continues the schedule mid-stream)
    gen_offset: int = 0
    tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    slot: Optional[int] = None
    #: number of OTHER requests mid-flight when this one was admitted —
    #: the continuous-batching witness (tests assert it's > 0 for a
    #: late-arriving request)
    active_at_admit: int = 0
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._event.wait(timeout)

    @property
    def ttft(self) -> Optional[float]:
        return (self.t_first_token - self.t_submit) if self.t_first_token else None

    @property
    def tpot(self) -> Optional[float]:
        """Mean seconds per token after the first (block-granular stream)."""
        if not self.t_done or len(self.tokens) < 2:
            return None
        return (self.t_done - self.t_first_token) / (len(self.tokens) - 1)


def _round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple if multiple > 1 else n


class ServingEngine:
    """Slot-based continuous-batching engine over one LM (``TransformerLM``,
    ``models/hybrid.HybridLM`` or ``models/latent_moe.LatentMoELM``). A slot
    holds one request's whole state for as long as it decodes: K/V rows (or one
    latent row a position) in the attention layers and, in a model that has
    them, each recurrent layer's fixed-size state; the schedule is the same
    for all.

    ``on_tokens(request, new_tokens, done)`` is invoked from the scheduling
    thread every time a request's stream advances (admission's first token,
    then each decode block's truncated share) — the transport frontend
    hangs its send path on it.
    """

    def __init__(self, model, params, *, slots: int = 4,
                 cache_size: int = 256, decode_block: int = DECODE_BLOCK,
                 kv_quant: bool = False, max_queue: int = 64,
                 prefill_bucket: int = 16,
                 on_tokens: Optional[Callable] = None):
        self.pool = SlotKVPool(
            model, params, slots=slots, cache_size=cache_size,
            decode_block=decode_block, kv_quant=kv_quant)
        self.max_queue = int(max_queue)
        self.prefill_bucket = max(1, int(prefill_bucket))
        self.on_tokens = on_tokens
        self._lock = threading.Lock()
        self._queue: Deque[Request] = collections.deque()
        self._ids = itertools.count()
        S = self.pool.slots
        self._slot_req: List[Optional[Request]] = [None] * S
        # per-slot compiled-state mirror (device sees these every dispatch)
        self._tok = np.zeros(S, np.int32)
        self._n_gen = np.zeros(S, np.int32)
        self._seeds = np.zeros(S, np.uint32)
        self._temps = np.zeros(S, np.float32)
        self._top_ks = np.zeros(S, np.int32)
        self._top_ps = np.ones(S, np.float32)
        # SLO samples (seconds; summaries convert to ms). Health samples
        # are bounded deques so a long-lived server cannot grow them
        # without limit; latency samples are per-request (bounded by
        # traffic actually served) and kept whole for exact percentiles.
        self._ttft: List[float] = []
        self._tpot: List[float] = []
        self._queue_depths: collections.deque = collections.deque(maxlen=65536)
        self._occupancy: collections.deque = collections.deque(maxlen=65536)
        self._block_timer = StepTimer(skip=1)
        self._queue_wait: List[float] = []
        # host seconds from a decode block's fetch to the next dispatch
        # while a slot is active, the open interval's phases, its last mark
        self._between: collections.deque = collections.deque(maxlen=65536)
        self._between_max: Tuple[float, Optional[str]] = (0.0, None)
        self._gap: Optional[dict] = None
        self._t_mark = 0.0
        self._completed = 0
        self._cancelled = 0
        self._rejected = 0
        # prompt tokens admitted, and bucket positions the padding added
        self._prefill_tokens = {"real": 0, "padded": 0}
        # decode blocks dispatched, and those whose active rows made the
        # sampler draw (a temperature) or sort (top-k / top-p besides)
        self._sampler_blocks = {
            "blocks": 0, "sampled_blocks": 0, "filtered_blocks": 0}
        # decode blocks by rows read, and the share each slot's own rows covered
        self._kv_read_blocks: collections.Counter = collections.Counter()
        self._slot_rows_share = 0.0
        # whatever the model counts on the device (its ``"counters"``
        # collection; ``pool.last_counters``), by phase and leaf
        self._model_counters: dict = {"prefill": {}, "decode": {}}

    # ------------------------------------------------------------- submit
    def submit(self, prompt, max_new_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               seed: int = 0, eos_token: Optional[int] = None,
               request_id: Optional[int] = None,
               gen_offset: int = 0) -> Request:
        """Queue one request; returns its live :class:`Request` handle.

        ``gen_offset`` resumes the sampling-key schedule at that generated-
        token index — the stream-migration path passes the number of tokens
        already emitted by a dead engine, with ``prompt`` extended by those
        tokens and ``max_new_tokens`` reduced by the same count, and the
        resumed stream continues token-identically.

        Raises :class:`QueueFullError` at ``max_queue`` waiting requests
        (admission control) and ``ValueError`` for requests the pool can
        never hold (those would wedge the queue forever).
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        bucket = self._bucket_len(int(prompt.size))
        need = self.pool.capacity_needed(int(prompt.size), bucket,
                                         int(max_new_tokens))
        if need > self.pool.cache_size:
            recurrent = self.pool.slot_bytes()["state_bytes_per_slot"] > 0
            raise ValueError(
                f"request needs {need} cache rows (prompt {prompt.size} "
                f"-> bucket {bucket}, {max_new_tokens} new tokens in "
                f"{self.pool.decode_block}-token blocks) but slots hold "
                f"{self.pool.cache_size}"
                + (" in each full-attention layer (the recurrent layers' "
                   "state sets no bound)" if recurrent else ""))
        from distributed_ml_pytorch_tpu.utils import obs

        req = Request(
            request_id=(request_id if request_id is not None
                        else next(self._ids)),
            prompt=prompt, max_new_tokens=int(max_new_tokens),
            sampling=SamplingParams(temperature, top_k, top_p, seed),
            eos_token=eos_token, gen_offset=max(0, int(gen_offset)),
            # adopt the submitting thread's active correlation id (a
            # frontend relaying an enveloped SubmitRequest, or a migration
            # resubmit) — mint a fresh one only at a true origin
            corr=obs.current_corr() or obs.next_corr(),
            t_submit=time.perf_counter())
        with self._lock:
            # cancelled entries (e.g. overload-shed work awaiting its
            # admission-pass drop) no longer hold queue room — a displacing
            # submit must be admittable the moment its victim is shed
            if sum(1 for r in self._queue
                   if not r.cancelled) >= self.max_queue:
                self._rejected += 1
                raise QueueFullError(
                    f"queue at max_queue={self.max_queue}; retry later")
            self._queue.append(req)
        return req

    def _bucket_len(self, prompt_len: int) -> int:
        """Padded prefill length for a prompt. Never 1: inside the blocked
        decode module ``s == 1`` is the branch discriminator for a DECODE
        step (the same hazard ``uses_block_decode`` guards in generate()),
        so a 1-token prompt pads to 2 even at prefill_bucket=1."""
        return max(2, _round_up(prompt_len, self.prefill_bucket))

    def cancel(self, request_id: int) -> bool:
        """Flag a request cancelled. Queued requests are dropped at the next
        admission pass; an active request's slot is evicted at the next
        block boundary. Returns whether the id was found live."""
        with self._lock:
            for req in self._queue:
                if req.request_id == request_id and not req.done:
                    req.cancelled = True
                    return True
        for req in self._slot_req:
            if req is not None and req.request_id == request_id:
                req.cancelled = True
                return True
        return False

    def kv_lane(self, request_id: int) -> Optional[np.ndarray]:
        """The flat KV-cache lane behind a live request's slot, or None
        when the request holds no slot (queued / finished). The migration
        handoff (``serving/fleet.py``, ISSUE 18) ships this on the
        ``KvMigrate`` wire under the pool's ``kv_quant`` recipe."""
        for slot, req in enumerate(self._slot_req):
            if req is not None and req.request_id == request_id:
                return self.pool.slot_kv(slot)
        return None

    # ------------------------------------------------------------ schedule
    def step(self) -> bool:
        """One scheduling round: evict → admit → decode one block. Returns
        False when there was nothing to do (caller may idle-sleep)."""
        with self._lock:
            queued = len(self._queue)
        if not queued and not any(r is not None for r in self._slot_req):
            return False  # an idle loop polls 500 times a second: no span
        self._mark("outside_step")
        with span("serve.step"):
            worked = self._evict()
            self._mark("evict")
            worked = self._admit() or worked
            active = [r is not None for r in self._slot_req]
            if worked or any(active):
                # sample scheduler health only on rounds that do work — a
                # serve_forever loop idles at ~500 rounds/s and would both
                # grow these lists without bound and dilute the occupancy
                # stats with idle zeros (the deques bound the busy case too)
                with self._lock:
                    self._queue_depths.append(len(self._queue))
                self._occupancy.append(sum(active) / len(active))
            if any(active):
                self._decode(np.asarray(active, bool))
                worked = True
            else:
                self._gap = None  # no slot waits for the host: nothing to time
        return worked

    def run_until_idle(self, max_rounds: int = 10_000) -> None:
        """Drive scheduling rounds until queue and slots are empty (the
        synchronous harness used by tests and the benchmark driver)."""
        for _ in range(max_rounds):
            if not self.step():
                with self._lock:
                    queued = len(self._queue)
                if queued == 0 and not any(
                        r is not None for r in self._slot_req):
                    return
        raise RuntimeError(f"not idle after {max_rounds} scheduling rounds")

    def _evict(self) -> bool:
        freed = []
        for slot, req in enumerate(self._slot_req):
            if req is None:
                continue
            if req.done or req.cancelled:
                self._finish(req)
                self._slot_req[slot] = None
                freed.append(slot)
        if freed:
            self.pool.reset_slots(freed)
        return bool(freed)

    def _admit(self) -> bool:
        admitted = False
        free = [s for s, r in enumerate(self._slot_req) if r is None]
        while free:
            with self._lock:
                while self._queue and self._queue[0].cancelled:
                    self._finish(self._queue.popleft())
                if not self._queue:
                    break
                req = self._queue.popleft()
            slot = free.pop(0)
            # the wait in the queue ends here and holds nothing of the
            # prefill: stamped BEFORE the dispatch
            req.t_admit = time.perf_counter()
            wait = req.t_admit - req.t_submit
            self._queue_wait.append(wait)
            p = int(req.prompt.size)
            bucket = self._bucket_len(p)
            with span("serve.prefill", request_id=req.request_id,
                      queue_wait_us=int(wait * 1e6), prompt_tokens=p,
                      bucket_tokens=bucket):
                padded = np.zeros(bucket, np.int32)
                padded[:p] = req.prompt
                sp = req.sampling
                # claim the slot BEFORE the admission dispatch: between the
                # queue pop above and this point the request is in neither
                # the queue count nor the slot count, and a fleet router
                # sampling pressure() cross-thread would see a falsely idle
                # engine and stack new work onto it (prefill dispatch is a
                # ~ms window)
                req.active_at_admit = sum(
                    r is not None for r in self._slot_req)
                req.slot = slot  # with it, "slot is None" == waiting, exactly
                self._slot_req[slot] = req
                tok0 = self.pool.admit(
                    slot, padded, p, seed=sp.seed,
                    temperature=sp.temperature, top_k=sp.top_k,
                    top_p=sp.top_p, gen_offset=req.gen_offset)
                self._tok[slot] = tok0
                self._count("prefill")
                self._prefill_tokens["real"] += p
                self._prefill_tokens["padded"] += bucket - p
                # the per-slot sampling clock continues the request's OWN
                # schedule: a resumed request's next draw is fold_in(key,
                # gen_offset + 1), exactly what its first life would have
                # drawn
                self._n_gen[slot] = req.gen_offset + 1
                self._seeds[slot] = np.uint32(sp.seed)
                self._temps[slot] = sp.temperature
                self._top_ks[slot] = sp.top_k
                self._top_ps[slot] = sp.top_p
                self._emit(req, [tok0])
                admitted = True
                if req.done:  # max_new_tokens == 1, or the first token was eos
                    self._finish(req)
                    self._slot_req[slot] = None
                    self.pool.reset_slots([slot])  # same sweep _evict gives others
                    free.insert(0, slot)
        return admitted

    def _decode(self, active: np.ndarray) -> None:
        self._mark("admit")
        if self._gap is not None:
            # the slots stood still from the last block's fetch to here
            gap = sum(self._gap.values())
            self._between.append(gap)
            if gap > self._between_max[0]:
                self._between_max = (gap, max(self._gap, key=self._gap.get))
        # the tier the compiled sampler takes for this block, by the
        # sampler's own predicate over the active rows' parameters
        sampled, filtered = sampled_and_filtered_rows(
            self._temps, self._top_ks, self._top_ps, active)
        self._sampler_blocks["blocks"] += 1
        self._sampler_blocks["sampled_blocks"] += bool(sampled.any())
        self._sampler_blocks["filtered_blocks"] += bool(filtered.any())
        with span("serve.decode"):
            self._block_timer.start()
            toks = self.pool.decode_block_step(
                self._tok, self._n_gen, self._seeds, self._temps,
                self._top_ks, self._top_ps, active)  # [S, T] host array (syncs)
            self._block_timer.tick()
            self._count("decode")
        self._kv_read_blocks[self.pool.last_read_rows] += 1
        self._slot_rows_share += self._slot_rows_read(active)
        # the device has nothing queued from here to the next dispatch
        self._t_mark = time.perf_counter()
        self._gap = dict.fromkeys(_GAP_PHASES, 0.0)
        T = toks.shape[1]
        with span("serve.emit"):  # on_tokens callbacks included
            for slot, req in enumerate(self._slot_req):
                if req is None:
                    continue
                self._tok[slot] = toks[slot, -1]
                self._n_gen[slot] += T  # sampling-step clock, even past finish
                remaining = req.max_new_tokens - len(req.tokens)
                self._emit(req, toks[slot, :remaining].tolist())
        self._mark("emit")

    def _slot_rows_read(self, active: np.ndarray) -> float:
        """Share of the pool's rows (slots x ``cache_size``) that a decode step
        of the block just dispatched reads when each slot reads its own rows in
        whole blocks of ``pool.slot_block_rows``: an active slot's ring base is
        its prompt and the tokens its earlier blocks fed. 0 for a pool whose
        steps do not read so (no such block)."""
        block, size = self.pool.slot_block_rows, self.pool.cache_size
        if not block:
            return 0.0
        rows = 0
        for slot, req in enumerate(self._slot_req):
            if req is not None and active[slot]:
                base = req.prompt.size + int(self._n_gen[slot]) - req.gen_offset - 1
                rows += min(-(-base // block) * block, size)
        return rows / (self.pool.slots * size)

    def _count(self, phase: str) -> None:
        """Add what the model counted in the pool's newest call to ``phase``:
        per leaf its sum entry by entry, the calls or decode steps it covers
        (``events``: a decode block's leaf has a row a step) and how many of
        its entries were not 0 in them. A model that counts nothing costs one
        look at an empty dictionary. The sums ride a child span too
        (``serve.prefill.counters``, ``serve.decode.counters``), as
        whole-number attributes, when a profile is being taken."""
        counted = self.pool.last_counters
        if not counted:
            return
        attrs = {}
        for name, leaf in counted.items():
            rows = np.asarray(leaf).reshape(-1, np.shape(leaf)[-1])
            acc = self._model_counters[phase].setdefault(
                name, {"sum": np.zeros(rows.shape[1], np.int64), "events": 0, "nonzero": 0})
            nonzero = int((rows > 0).sum())
            acc["sum"] += rows.sum(axis=0)
            acc["events"] += len(rows)
            acc["nonzero"] += nonzero
            key = name.replace("/", "_")
            attrs[key + "_sum"] = int(rows.sum())
            attrs[key + "_nonzero"] = nonzero
        with span(f"serve.{phase}.counters", **attrs):
            pass

    def _mark(self, phase: str) -> None:
        """Charge the host time since the last mark to ``phase`` of the open
        between-blocks interval (none open: only move the mark)."""
        now = time.perf_counter()
        if self._gap is not None:
            self._gap[phase] += now - self._t_mark
        self._t_mark = now

    def _emit(self, req: Request, new_tokens: List[int]) -> None:
        """Append ``new_tokens`` to a request's stream (truncating at eos),
        stamp TTFT/finish times, and fan out to ``on_tokens``."""
        if req.eos_token is not None and new_tokens:
            for i, t in enumerate(new_tokens):
                if t == req.eos_token:
                    new_tokens = new_tokens[: i + 1]
                    req.done = True
                    break
        req.tokens.extend(int(t) for t in new_tokens)
        now = time.perf_counter()
        if not req.t_first_token and req.tokens:
            req.t_first_token = now
            self._ttft.append(req.ttft)
        if len(req.tokens) >= req.max_new_tokens:
            req.done = True
        if req.done:
            req.t_done = now
            self._record_done(req)
        if self.on_tokens is not None and new_tokens:
            self.on_tokens(req, [int(t) for t in new_tokens], req.done)

    def _record_done(self, req: Request) -> None:
        """SLO accounting at the moment a stream closes (NOT at eviction —
        the last request's samples must exist before its slot is swept).
        Cancellations count separately: "completed" means served in full."""
        if req.cancelled:
            self._cancelled += 1
            return
        self._completed += 1
        if req.tpot is not None:
            self._tpot.append(req.tpot)

    def _finish(self, req: Request) -> None:
        if req.cancelled and not req.done:
            req.done = True
            req.t_done = time.perf_counter()
            self._record_done(req)
            if self.on_tokens is not None:
                self.on_tokens(req, [], True)
        req._event.set()

    # ------------------------------------------------------------- metrics
    def pressure(self) -> Tuple[int, int, int]:
        """Cheap load sample for routers and admission control:
        ``(busy_slots, total_slots, queued)``. Advisory — one scheduling
        round stale at worst, which is within the overload plane's
        contract (shed decisions are rate signals, not invariants)."""
        with self._lock:
            queued = len(self._queue)
        busy = sum(r is not None for r in self._slot_req)
        return busy, self.pool.slots, queued

    def recent_ttft_ms(self, k: int = 16) -> float:
        """Mean of the last ``k`` TTFT samples in milliseconds (0.0 when
        nothing completed yet) — the SLO-breach signal the overload plane
        and the coordinator's engine-scaling advisory consume."""
        tail = self._ttft[-k:]
        if not tail:
            return 0.0
        return float(np.mean(tail)) * 1e3

    def reset_metrics(self) -> None:
        """Drop accumulated SLO samples (e.g. after a compile warmup) while
        keeping the block timer's warmup state — mirrors
        ``StepTimer.reset_stats``."""
        self._ttft.clear()
        self._tpot.clear()
        self._queue_depths.clear()
        self._occupancy.clear()
        self._queue_wait.clear()
        self._between.clear()
        self._between_max = (0.0, None)
        self._block_timer.reset_stats()
        self._completed = 0
        self._cancelled = 0
        self._rejected = 0
        self._prefill_tokens = {"real": 0, "padded": 0}
        self._sampler_blocks = {
            "blocks": 0, "sampled_blocks": 0, "filtered_blocks": 0}
        self._kv_read_blocks.clear()
        self._slot_rows_share = 0.0
        self._model_counters = {"prefill": {}, "decode": {}}

    def slo_summary(self) -> dict:
        """Percentile SLO report (milliseconds) over everything completed so
        far, plus scheduler health (queue depth, occupancy, rejects)."""
        to_ms = lambda xs: [x * 1e3 for x in xs]
        depths = self._queue_depths or [0]
        between = latency_summary(to_ms(self._between))
        if between is not None:
            between["max_phase"] = self._between_max[1]
        blocks = sum(self._kv_read_blocks.values())
        rows_read = sum(rows * n for rows, n in self._kv_read_blocks.items())
        return {
            "completed": self._completed,
            "cancelled": self._cancelled,
            "rejected": self._rejected,
            "ttft_ms": latency_summary(to_ms(self._ttft)),
            "tpot_ms": latency_summary(to_ms(self._tpot)),
            # wait in the queue alone (submit to admission, no prefill in it)
            "queue_wait_ms": latency_summary(to_ms(self._queue_wait)),
            # host time between two decode blocks while a slot is active,
            # and the phase (evict, admit, emit, outside_step) that held
            # most of the longest one: where a host stall shows
            "between_blocks_ms": between,
            "decode_block": self._block_timer.summary(),
            "queue_depth": {"mean": float(np.mean(depths)),
                            "max": int(np.max(depths))},
            "slot_occupancy": float(np.mean(self._occupancy or [0.0])),
            # prompt tokens admitted, and bucket positions their padding added
            "prefill_tokens": dict(self._prefill_tokens),
            # decode blocks, those in which an active request was sampled
            # (the sampler drew random bits), and those of them in which one
            # asked for top-k or top-p (it sorted the vocabulary besides)
            "sampler": dict(self._sampler_blocks),
            # what the decode steps' reads of the big caches cost against the
            # allocation: blocks, the mean share of ``cache_size`` their steps
            # read (the longest active slot, in whole chunks: a member of the
            # pool's ladder), and the blocks by rows read. A mean near 1
            # with short requests says one long one holds every slot's read
            # up; at the first member, that a smaller ``cache_size`` or a
            # smaller chunk would serve
            "kv_read": {
                "blocks": blocks,
                "rows_share_mean": (
                    rows_read / (blocks * self.pool.cache_size)
                    if blocks else 0.0),
                "by_rows": {rows: self._kv_read_blocks[rows]
                            for rows in self.pool.read_ladder},
                # the mean share of ALL slots' rows their steps read where
                # each slot reads its own, in whole row blocks: what the pool's
                # per-slot read covers on a TPU, against the bound's share
                "slot_rows_share_mean": (
                    self._slot_rows_share / blocks if blocks else 0.0),
            },
            # what one slot holds: K/V or latent rows, and recurrent state
            # beside them
            "pool": self.pool.slot_bytes(),
            # what the model counted on the device, by phase (admissions,
            # decode steps) and counter: the sum entry by entry, the events
            # counted over, and the mean number of entries that were not 0 in
            # an event (for a router's choices per expert: its load, and the
            # distinct experts a decode step touched); empty for a model that
            # declares no counters
            "model_counters": {
                phase: {name: {"sum": acc["sum"].tolist(), "events": acc["events"],
                               "nonzero_mean": acc["nonzero"] / max(1, acc["events"])}
                        for name, acc in leaves.items()}
                for phase, leaves in self._model_counters.items()},
        }
