"""Driver ``hybrid_serve``: ``lm_serve``'s open loop, tails and comparison for
a model built by ``benchmarks/hybrid_model.py``.

A subclass of ``lm_serve``'s ``Session``: sampling of finished requests, the
reference's pass, the numbers compared and the calibration readings are
inherited; ``run_window`` is a copy of its loop with the traced run's two
changes below (``lm_serve.py`` may not be edited). What is its own:

- the model (``HybridLM`` from the configuration file) and the counts
  (``benchmarks/hybrid_counts.py``: layers of two kinds, a recurrent state
  beside the K/V rows);
- the reference pads a served sequence to the cell's ``cache_size`` (the
  configuration has no position table to take a length from);
- counters for the metrics that read the recurrence (``gdn_state_bytes``,
  ``gdn_chunk_flops``), ``traced_steps`` (decode steps traced, so that
  ``scope_time`` reads milliseconds a decode step), and the engine's own
  ``prefill_tokens`` and ``pool`` counters;
- a traced run takes the engine's ``slo_summary()`` when its traced sub-window
  closes, before ``tracer.stop()``, and stops offering there:
  ``jax.profiler.stop_trace`` blocks the loop for about half a minute
  (PERF.md section 7), and what queued up behind it would bend every counter
  read afterwards and could outlast the drain limit. Its ``attempted`` counts
  what was offered. An untraced run offers the whole window, as ``lm_serve``.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmarks import hybrid_counts, traffic
from benchmarks.drivers import lm_serve
from benchmarks.harness import seed_key
from benchmarks.hybrid_model import hybrid_lm

p95 = lm_serve.p95


class Session(lm_serve.Session):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

        lm = hybrid_lm(ctx.config)  # first: a program without this model stops here
        # an earlier session of this process (calibration runs a dozen) holds its
        # reference's 8 GB of weights in a cycle until the collector runs: two
        # sets of weights and a pool do not fit the chip
        gc.collect()
        self.ctx = ctx
        self.ref = ctx.cell.reference()
        self.eng = ctx.workload["engine"]
        # ``n_positions`` is the length the inherited ``reference_stats`` pads
        # a served sequence to; the reference reads its own keys and not this
        self.cfg = cfg = dict(ctx.config, n_positions=self.eng["cache_size"])
        self.key = seed_key(ctx.seed)
        params = jax.jit(lambda k: self.ref.make_params(k, cfg, jnp.bfloat16))(self.key)
        self.engine = ServingEngine(
            lm, params, slots=self.eng["slots"], cache_size=self.eng["cache_size"],
            decode_block=self.eng["decode_block"], prefill_bucket=self.eng["prefill_bucket"],
            max_queue=self.eng["max_queue"], on_tokens=self._on_tokens)
        del params
        self.requests: list = []
        self.n_submitted = self.n_done = 0
        self.counters = self._zero_counters()
        self.tokens_in_window = 0
        self.t_end = float("inf")
        self._ref_params, self._ref_stats = None, {}
        self._wrap_pool()
        self._warm_up()

    @staticmethod
    def _zero_counters() -> dict:
        return dict(lm_serve.Session._zero_counters(), gdn_state_bytes=0.0, gdn_chunk_flops=0.0)

    # ------------------------------------------------ spans around the pool
    def _wrap_pool(self) -> None:
        pool, tracer, cfg, c = self.engine.pool, self.ctx.tracer, self.cfg, hybrid_counts
        admit, decode = pool.admit, pool.decode_block_step
        block = int(self.eng["decode_block"])

        def traced_admit(slot, prompt, real_len, **kw):
            with tracer.span("bench:prefill"):
                tok = admit(slot, prompt, real_len, **kw)
            if tracer.active:
                self.counters["prefills"] += 1
                self.counters["prefill_flops"] += c.prefill_flops(cfg, int(real_len))
                self.counters["gdn_chunk_flops"] += c.rule_prefill_flops(cfg, int(real_len))
            return tok

        def traced_decode(tok, n_gen, seeds, temps, top_ks, top_ps, active):
            if tracer.active:
                live = [len(r.prompt) + len(r.tokens) for r in self.requests
                        if r is not None and r.slot is not None and not r.done]
                self.counters["decode_blocks"] += 1
                self.counters["decode_steps"] += block
                self.counters["decode_bytes"] += sum(  # step t reads one more row a slot
                    c.decode_step_bytes(cfg, sum(live) + len(live) * t, len(live))
                    for t in range(block))
                self.counters["gdn_state_bytes"] += block * c.state_step_bytes(cfg, len(live))
            with tracer.span("bench:decode"):
                return decode(tok, n_gen, seeds, temps, top_ks, top_ps, active)

        pool.admit, pool.decode_block_step = traced_admit, traced_decode

    def _on_tokens(self, req, new_tokens, done) -> None:
        if time.perf_counter() <= self.t_end:
            self.tokens_in_window += len(new_tokens)
        if self.ctx.tracer.active:
            start = len(req.tokens) - len(new_tokens)
            self.counters["tokens"] += len(new_tokens)
            self.counters["decode_flops"] += sum(
                hybrid_counts.decode_flops(self.cfg, len(req.prompt) + j)
                for j in range(max(start, 1), len(req.tokens)))
        if done:
            self.n_done += 1

    # --------------------------------------------------------------- window
    def run_window(self) -> dict:
        from distributed_ml_pytorch_tpu.serving.engine import QueueFullError

        ctx, engine, tracer = self.ctx, self.engine, self.ctx.tracer
        plan = traffic.request_plan(ctx.workload["traffic"], ctx.seed, ctx.seconds,
                                    self.cfg["vocab_size"])
        n = len(plan.due)
        requests = self.requests = [None] * n
        self.n_submitted = self.n_done = 0
        offered, stop_offering = threading.Event(), threading.Event()
        t0 = time.perf_counter() + 0.05
        self.t_end = t0 + ctx.seconds
        self.tokens_in_window = 0
        self.n_offered = 0

        def offer():
            for i in range(n):
                wait = t0 + plan.due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
                if stop_offering.is_set():
                    break
                self.n_offered += 1
                try:
                    self.n_submitted += 1
                    requests[i] = engine.submit(plan.prompts[i], int(plan.new_tokens[i]))
                except QueueFullError:
                    self.n_submitted -= 1
            offered.set()

        thread = threading.Thread(target=offer, name="bench-offer", daemon=True)
        trace_at = t0 + 0.25 * ctx.seconds
        trace_s = float(ctx.workload["trace"]["seconds"]) if tracer.enabled else 0.0
        traced = not tracer.enabled
        give_up = self.t_end + float(ctx.workload["drain_limit_s"])
        summary = None
        thread.start()
        while True:
            now = time.perf_counter()
            if not traced and not tracer.active and now >= trace_at:
                self.counters = self._zero_counters()
                tracer.start()
            elif tracer.active and now >= trace_at + trace_s:
                summary = engine.slo_summary()  # before stop_trace's half minute
                stop_offering.set()
                tracer.stop()
                traced = True
            with tracer.span("bench:engine.step"):
                worked = engine.step()
            if now > give_up or (offered.is_set() and self.n_done >= self.n_submitted):
                break
            if worked:
                continue
            with tracer.span("bench:idle"):
                time.sleep(lm_serve.IDLE_SLEEP_S)
        if tracer.active:
            summary = engine.slo_summary()
            tracer.stop()
        thread.join(timeout=5.0)
        t_gave_up = time.perf_counter()
        summary = summary or engine.slo_summary()

        ttft, tpot, late, self.finished = [], [], [], []
        failed = 0
        for i, req in enumerate(requests[:self.n_offered]):
            due = t0 + plan.due[i]
            if req is None or not req.done or req.cancelled:
                failed += 1  # refused or unfinished: the worst latency in both tails
                ttft.append(t_gave_up - due)
                tpot.append(t_gave_up - due)
                continue
            late.append(req.t_submit - due)
            ttft.append(req.t_first_token - due)
            tpot.append((req.t_done - req.t_first_token) / max(1, len(req.tokens) - 1))
            self.finished.append((np.asarray(req.prompt), list(req.tokens), int(plan.new_tokens[i])))
        tokens = summary["prefill_tokens"]
        counters = dict(self.counters, traced_steps=self.counters["decode_steps"],
                        slot_occupancy=summary["slot_occupancy"],
                        generator_late_p95_ms=1e3 * p95(late) if late else None,
                        ttft_p95_ms=1e3 * p95(ttft), tpot_p95_ms=1e3 * p95(tpot),
                        requests=self.n_offered, rejected=summary["rejected"],
                        prefill_tokens_real=tokens["real"], prefill_tokens_padded=tokens["padded"],
                        prefill_pad_share=(100.0 * tokens["padded"] / (tokens["real"] + tokens["padded"])
                                           if tokens["real"] else None),
                        **summary["pool"])
        return {"attempted": self.n_offered, "failed": failed, "counters": counters,
                "metrics": {"ttft_p95_ms": 1e3 * p95(ttft), "tpot_p95_ms": 1e3 * p95(tpot),
                            "serve_tokens_per_s": self.tokens_in_window / ctx.seconds}}


def setup(ctx) -> Session:
    return Session(ctx)
