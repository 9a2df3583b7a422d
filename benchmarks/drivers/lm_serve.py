"""Driver ``lm_serve``: one ``ServingEngine`` under an open loop.

A thread submits each request when it is due while the main thread loops
``engine.step()``: the division of work ``serving/frontend.py`` has (``_pump``
submits, ``serve_forever`` schedules, idling 2 ms when there is nothing to
do). Latencies count from the time a request was DUE, on the clock the engine
stamps ``Request`` with (``time.perf_counter``). When the window ends offering
has stopped (every request is due inside it) and what is in flight drains; the
drain is outside the rate and inside the tails.

The benchmark's own spans and counters sit around the two calls the engine
makes into its pool (``admit`` = one prefill, ``decode_block_step`` = one
block for every slot); nothing inside the program is touched.

``correct``: once the window has closed and the engine is freed, the plain
reference runs one full forward pass over prompt + served tokens of a sample
of the finished requests (drawn from the seed, the longest always in it) and
reads, for every served token, how far its logit lies under the reference's
best. Greedy decoding through admission, prefill, the slot cache and the
blocked decode path has to pick the reference's best, to within what bfloat16
rounding moves: the number held is the MEAN of that gap over the sampled
tokens (the widest gap swings too much to part bfloat16 from int8, PERF.md).
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmarks import counts, traffic
from benchmarks.harness import held, seed_key
from benchmarks.lm_model import transformer_lm

IDLE_SLEEP_S = 0.002  # serving/frontend.py::serve_forever's idle_sleep


def p95(values) -> float:
    """Nearest-rank 95th percentile of all the values."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    return float(v[max(0, int(np.ceil(0.95 * len(v))) - 1)])


def round_up(n: int, multiple: int) -> int:
    return -(-n // multiple) * multiple


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

        self.ctx = ctx
        self.ref = ctx.cell.reference()
        cfg, work = ctx.config, ctx.workload
        self.cfg, self.eng = cfg, work["engine"]
        self.key = seed_key(ctx.seed)
        lm = transformer_lm(cfg)
        params = jax.jit(lambda k: self.ref.make_params(k, cfg, jnp.bfloat16))(self.key)
        self.engine = ServingEngine(
            lm, params, slots=self.eng["slots"], cache_size=self.eng["cache_size"],
            decode_block=self.eng["decode_block"], prefill_bucket=self.eng["prefill_bucket"],
            max_queue=self.eng["max_queue"], on_tokens=self._on_tokens)
        del params  # the engine holds the (fused) tree it serves from
        self.requests: list = []      # the window's Request handles, None until submitted
        self.n_submitted = self.n_done = 0
        self.counters = self._zero_counters()
        self.tokens_in_window = 0
        self.t_end = float("inf")
        self._ref_params, self._ref_stats = None, {}
        self._wrap_pool()
        self._warm_up()

    @staticmethod
    def _zero_counters() -> dict:
        return {"prefills": 0, "prefill_flops": 0.0, "decode_blocks": 0, "decode_steps": 0,
                "decode_flops": 0.0, "decode_bytes": 0.0, "tokens": 0}

    # ------------------------------------------------ spans around the pool
    def _wrap_pool(self) -> None:
        pool, tracer, cfg = self.engine.pool, self.ctx.tracer, self.cfg
        admit, decode = pool.admit, pool.decode_block_step
        block = int(self.eng["decode_block"])

        def traced_admit(slot, prompt, real_len, **kw):
            with tracer.span("bench:prefill"):
                tok = admit(slot, prompt, real_len, **kw)
            if tracer.active:
                self.counters["prefills"] += 1
                self.counters["prefill_flops"] += counts.prefill_flops(cfg, int(real_len))
            return tok

        def traced_decode(tok, n_gen, seeds, temps, top_ks, top_ps, active):
            if tracer.active:
                live = [len(r.prompt) + len(r.tokens) for r in self.requests
                        if r is not None and r.slot is not None and not r.done]
                self.counters["decode_blocks"] += 1
                self.counters["decode_steps"] += block
                self.counters["decode_bytes"] += sum(  # step t reads one more row a slot
                    counts.decode_step_bytes(cfg, sum(live) + len(live) * t) for t in range(block))
            with tracer.span("bench:decode"):
                return decode(tok, n_gen, seeds, temps, top_ks, top_ps, active)

        pool.admit, pool.decode_block_step = traced_admit, traced_decode

    def _on_tokens(self, req, new_tokens, done) -> None:
        """Called by the engine whenever a stream advances."""
        if time.perf_counter() <= self.t_end:
            self.tokens_in_window += len(new_tokens)
        if self.ctx.tracer.active:
            # token j of a stream (j >= 1; token 0 is the prefill's) was decoded
            # over the prompt and the j tokens before it
            start = len(req.tokens) - len(new_tokens)
            self.counters["tokens"] += len(new_tokens)
            self.counters["decode_flops"] += sum(
                counts.decode_flops(self.cfg, len(req.prompt) + j)
                for j in range(max(start, 1), len(req.tokens)))
        if done:
            self.n_done += 1

    # --------------------------------------------------------------- set-up
    def _buckets(self) -> list:
        spec, b = self.ctx.workload["traffic"]["prompt_tokens"], self.eng["prefill_bucket"]
        return list(range(max(2, round_up(spec["lo"], b)), round_up(spec["hi"], b) + 1, b))

    def _warm_up(self) -> None:
        """Every shape the cell's traffic uses and no other: one prefill per
        bucket, the decode block, the slot reset."""
        rng = np.random.default_rng([self.ctx.seed, 0x3A93])
        for bucket in self._buckets():
            prompt = rng.integers(0, self.cfg["vocab_size"], size=bucket).astype(np.int32)
            self.engine.submit(prompt, self.eng["decode_block"] + 1)
        self.engine.run_until_idle()
        self.engine.reset_metrics()

    # --------------------------------------------------------------- window
    def run_window(self) -> dict:
        from distributed_ml_pytorch_tpu.serving.engine import QueueFullError

        ctx, engine, tracer = self.ctx, self.engine, self.ctx.tracer
        plan = traffic.request_plan(ctx.workload["traffic"], ctx.seed, ctx.seconds,
                                    self.cfg["vocab_size"])
        n = len(plan.due)
        requests = self.requests = [None] * n
        self.n_submitted = self.n_done = 0
        offered = threading.Event()
        t0 = time.perf_counter() + 0.05
        self.t_end = t0 + ctx.seconds
        self.tokens_in_window = 0

        def offer():
            for i in range(n):
                wait = t0 + plan.due[i] - time.perf_counter()
                if wait > 0:
                    time.sleep(wait)
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
        thread.start()
        while True:
            now = time.perf_counter()
            if not traced and not tracer.active and now >= trace_at:
                self.counters = self._zero_counters()
                tracer.start()
            elif tracer.active and now >= trace_at + trace_s:
                tracer.stop()
                traced = True
            with tracer.span("bench:engine.step"):
                worked = engine.step()
            if now > give_up or (offered.is_set() and self.n_done >= self.n_submitted):
                break
            if worked:
                continue
            with tracer.span("bench:idle"):
                time.sleep(IDLE_SLEEP_S)
        if tracer.active:
            tracer.stop()
        thread.join(timeout=5.0)
        t_gave_up = time.perf_counter()

        ttft, tpot, late, self.finished = [], [], [], []
        failed = 0
        for i, req in enumerate(requests):
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
        summary = engine.slo_summary()
        counters = dict(self.counters,
                        slot_occupancy=summary["slot_occupancy"],
                        generator_late_p95_ms=1e3 * p95(late) if late else None,
                        ttft_p95_ms=1e3 * p95(ttft), tpot_p95_ms=1e3 * p95(tpot),
                        requests=n, rejected=summary["rejected"])
        return {"attempted": n, "failed": failed, "counters": counters,
                "metrics": {"ttft_p95_ms": 1e3 * p95(ttft), "tpot_p95_ms": 1e3 * p95(tpot),
                            "serve_tokens_per_s": self.tokens_in_window / ctx.seconds}}

    def release(self) -> None:
        self.engine = None  # the pool's wrapped methods close a cycle: collect it
        self.requests = []
        gc.collect()

    # ----------------------------------------------------------- comparison
    def sample(self) -> list:
        """Finished requests the reference is run over: the longest, and more
        drawn from the seed, ``reference.requests`` in all."""
        k = int(self.ctx.workload["reference"]["requests"])
        order = sorted(range(len(self.finished)),
                       key=lambda i: -(len(self.finished[i][0]) + len(self.finished[i][1])))
        rng = np.random.default_rng([self.ctx.seed, 0x5A3])
        rest = [int(i) for i in rng.permutation(order[1:])[:max(0, k - 1)]]
        return [self.finished[i] for i in order[:1] + rest]

    def reference_stats(self, sample, control: bool = False) -> list:
        """The reference's account of each sampled request: per served token
        the best logit and the served token's (and, for the control, that of
        the token the int8 pass puts first)."""
        import jax
        import jax.numpy as jnp

        cfg, pad = self.cfg, self.cfg["n_positions"]
        if self._ref_params is None:  # made once the engine is freed, kept for later calls
            self._ref_params = jax.jit(
                lambda k: self.ref.make_params(k, cfg, jnp.bfloat16))(self.key)
        if control not in self._ref_stats:
            self._ref_stats[control] = jax.jit(
                lambda p, t: self.ref.served_token_stats(p, t, cfg, control))
        params, stats = self._ref_params, self._ref_stats[control]
        out = []
        for prompt, tokens, _want in sample:
            seq = np.zeros(pad, np.int32)
            seq[:len(prompt) + len(tokens)] = np.concatenate([prompt, np.asarray(tokens, np.int32)])
            rows = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
            out.append({k: np.asarray(v, np.float32)[rows]
                        for k, v in stats(params, jnp.asarray(seq)).items()})
        return out

    def numbers(self, stats, key: str = "served") -> dict:
        gaps = np.concatenate([s["best"] - s[key] for s in stats])
        return {
            "token_logit_gap_mean": float(gaps.mean()),
            "token_logit_gap_max": float(gaps.max()),
            "wrong_length_requests": float(sum(len(t) != want for _p, t, want in self.finished)),
        }

    def readings(self, control: bool) -> dict:
        """For calibration: the numbers compared as the program reads them
        and, with ``control``, as the int8 control reads them and as one
        altered token in each sampled request would (the least of them)."""
        sample = self.sample()
        stats = self.reference_stats(sample, control=control)
        gaps = lambda key: np.concatenate([s["best"] - s[key] for s in stats]).round(5).tolist()
        out = {"program": self.numbers(stats), "served_tokens": sum(len(t) for _p, t, _w in sample),
               "gaps": {"served": gaps("served")}}
        if control:
            out["control_int8"] = self.numbers(stats, "control")
            out["gaps"]["control_int8"] = gaps("control")
            rng = np.random.default_rng([self.ctx.seed, 0xA17])
            altered = []
            for (prompt, tokens, want), st in zip(sample, stats):
                i = int(rng.integers(len(tokens)))
                tokens = list(tokens)
                tokens[i] = (tokens[i] + 1 + int(rng.integers(self.cfg["vocab_size"] - 1))) % self.cfg["vocab_size"]
                one = self.reference_stats([(prompt, tokens, want)])[0]
                altered.append(float((one["best"] - one["served"])[i]))
            out["fault_altered_token"] = {"token_logit_gap_max": min(altered)}
        return out

    def compare(self) -> list:
        sample = self.sample()
        if not sample:
            return []
        return held(self.numbers(self.reference_stats(sample)), self.ctx.workload["limits"])


def setup(ctx) -> Session:
    return Session(ctx)
