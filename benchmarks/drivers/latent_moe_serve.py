"""Driver ``latent_moe_serve``: ``hybrid_serve``'s session (``lm_serve``'s open
loop, tails and comparison; a traced run takes the engine's ``slo_summary()``
when its traced sub-window closes) for a model built by
``benchmarks/latent_moe_model.py``.

A subclass of ``hybrid_serve``'s ``Session``, itself one of ``lm_serve``'s:
the window's loop, the sampling of finished requests and the calibration
readings are inherited. What is its own:

- the model (``LatentMoELM`` from the configuration file) and the counts
  (``benchmarks/latent_moe_counts.py``);
- counters from the PROGRAM's own counts of choices, which the pool returns
  with every admission and decode block (``pool.last_counters``): in the traced
  sub-window the routed experts' FLOPs of the real rows' choices
  (``moe_group_flops``), the bytes of the experts an active slot chose, once a
  step each (``moe_expert_bytes``), the live latent rows and absorbed
  projections a step reads (``mla_absorb_bytes``); over the whole window, from
  ``slo_summary()['model_counters']``, ``router_load_max_over_mean`` (per expert
  layer the most-chosen expert's choices over the mean, averaged over the
  layers) and ``experts_touched`` (distinct experts an expert layer's decode
  step touched, the mean); and ``ttft_p95_ms`` over the first tokens given
  up to that summary (submit to first token, the engine's own ``Request.ttft``),
  which a traced run's tail is not: ``stop_trace`` blocks the loop for half a
  minute and every request offered just before it waits that long;
- the comparison holds, beside ``lm_serve``'s numbers,
  ``expert_set_mismatch_share``: the share of (served token, expert layer)
  pairs at which the program's router, run over the served sequence by the
  program's own full pass in the served precision, chooses another set of
  experts than the reference's.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmarks import latent_moe_counts as counts
from benchmarks.drivers import hybrid_serve, lm_serve
from benchmarks.harness import seed_key
from benchmarks.latent_moe_model import latent_moe_lm

CHOICES = "expert_choices"
p95 = lm_serve.p95


class Session(hybrid_serve.Session):
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp

        from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

        self.lm = lm = latent_moe_lm(ctx.config)  # first: a program without this model stops here
        gc.collect()  # an earlier session's reference weights (calibration runs a dozen)
        self.ctx = ctx
        self.ref = ctx.cell.reference()
        self.eng = ctx.workload["engine"]
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
        self._ref_params, self._ref_stats, self._replay = None, {}, None
        self._wrap_pool()
        self._warm_up()

    @staticmethod
    def _zero_counters() -> dict:
        return dict(lm_serve.Session._zero_counters(), moe_group_flops=0.0,
                    moe_expert_bytes=0.0, mla_absorb_bytes=0.0)

    # ------------------------------------------------ spans around the pool
    def _wrap_pool(self) -> None:
        pool, tracer, cfg = self.engine.pool, self.ctx.tracer, self.cfg
        admit, decode = pool.admit, pool.decode_block_step
        block = int(self.eng["decode_block"])
        chosen = lambda: [np.asarray(v) for k, v in pool.last_counters.items()
                          if k.endswith(CHOICES)]

        def traced_admit(slot, prompt, real_len, **kw):
            with tracer.span("bench:prefill"):
                tok = admit(slot, prompt, real_len, **kw)
            if tracer.active:
                self.counters["prefills"] += 1
                self.counters["prefill_flops"] += counts.prefill_flops(cfg, int(real_len))
                self.counters["moe_group_flops"] += counts.expert_choice_flops(
                    cfg, sum(int(c.sum()) for c in chosen()))
            return tok

        def traced_decode(tok, n_gen, seeds, temps, top_ks, top_ps, active):
            live = [len(r.prompt) + len(r.tokens) for r in self.requests
                    if r is not None and r.slot is not None and not r.done]
            with tracer.span("bench:decode"):
                toks = decode(tok, n_gen, seeds, temps, top_ks, top_ps, active)
            if tracer.active:
                # experts an ACTIVE slot chose, a row a step, over the expert layers
                touched = sum((c > 0).sum(axis=-1) for c in chosen())
                rows = [sum(live) + len(live) * t for t in range(block)]  # a row more a slot a step
                self.counters["decode_blocks"] += 1
                self.counters["decode_steps"] += block
                self.counters["decode_bytes"] += sum(
                    counts.decode_step_bytes(cfg, rows[t], int(touched[t])) for t in range(block))
                self.counters["moe_expert_bytes"] += counts.expert_touch_bytes(
                    cfg, int(touched.sum()))
                self.counters["mla_absorb_bytes"] += sum(
                    counts.absorb_step_bytes(cfg, r) for r in rows)
            return toks

        pool.admit, pool.decode_block_step = traced_admit, traced_decode

    def _on_tokens(self, req, new_tokens, done) -> None:
        if time.perf_counter() <= self.t_end:
            self.tokens_in_window += len(new_tokens)
        if self.ctx.tracer.active:
            start = len(req.tokens) - len(new_tokens)
            self.counters["tokens"] += len(new_tokens)
            self.counters["decode_flops"] += sum(
                counts.decode_flops(self.cfg, len(req.prompt) + j)
                for j in range(max(start, 1), len(req.tokens)))
        if done:
            self.n_done += 1

    # --------------------------------------------------------------- window
    def run_window(self) -> dict:
        """``hybrid_serve``'s window; the summary it took (at the traced
        sub-window's close, or at the end) gives the routing counters too."""
        taken, summarise = [], self.engine.slo_summary

        def summary_and_first_tokens():
            # with the summary, the first-token times it covers: a traced run's
            # own ``ttft_p95_ms`` holds the half minute ``stop_trace`` blocks for
            ttft = [r.ttft for r in self.requests if r is not None and r.ttft is not None]
            taken.append((summarise(), ttft))
            return taken[-1][0]

        self.engine.slo_summary = summary_and_first_tokens
        out = super().run_window()
        summary, ttft = taken[0]
        out["counters"].update(routing_counters(summary["model_counters"]),
                               ttft_p95_ms=1e3 * p95(ttft) if ttft else None)
        return out

    # ----------------------------------------------------------- comparison
    def program_choices(self, tokens):
        """The experts the PROGRAM's router chooses for every row of
        ``tokens`` in every expert layer, each row's sorted (``(seq, layers,
        k)``): the program's full pass (expanded attention, grouped experts) in
        the served precision over the weights the engine served from."""
        import jax
        import jax.numpy as jnp

        if self._replay is None:
            lm, first = self.lm, self.cfg["first_k_dense_replace"]
            is_router = lambda module, _method: type(module).__name__ == "SigmoidTopKRouter"

            def chosen(params, seq):
                _, state = lm.apply({"params": params}, seq[None], mutable=["intermediates"],
                                    capture_intermediates=is_router)
                layers = [state["intermediates"][f"layer_{i}"]["moe"]["router"]["__call__"][0][0]
                          for i in range(first, self.cfg["num_hidden_layers"])]
                return jnp.sort(jnp.stack(layers, axis=1), axis=-1)

            self._replay = jax.jit(chosen)
        return self._replay(self._ref_params, tokens)

    def reference_stats(self, sample, control: bool = False) -> list:
        import jax.numpy as jnp

        stats = super().reference_stats(sample, control=control)
        for (prompt, tokens, _want), st in zip(sample, stats):
            seq = np.zeros(self.cfg["n_positions"], np.int32)
            seq[:len(prompt) + len(tokens)] = np.concatenate([prompt, np.asarray(tokens, np.int32)])
            rows = slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))
            st["program_chosen"] = np.asarray(self.program_choices(jnp.asarray(seq)))[rows]
        return stats

    def numbers(self, stats, key: str = "served") -> dict:
        theirs = "control_chosen" if key == "control" else "program_chosen"
        differs = np.concatenate([(s[theirs] != s["chosen"]).any(axis=-1).ravel() for s in stats])
        return dict(super().numbers(stats, key), expert_set_mismatch_share=float(differs.mean()))


def routing_counters(model_counters: dict) -> dict:
    """``router_load_max_over_mean`` and ``experts_touched`` from the engine's
    ``model_counters`` (nothing where the model counted nothing)."""
    out = {"router_load_max_over_mean": None, "experts_touched": None}
    load, touched = [], []
    for name, step in model_counters["decode"].items():
        if not name.endswith(CHOICES):
            continue
        total = np.asarray(step["sum"], np.float64)
        if name in model_counters["prefill"]:
            total = total + np.asarray(model_counters["prefill"][name]["sum"], np.float64)
        if total.sum() > 0:
            load.append(total.max() / total.mean())
        if step["events"]:
            touched.append(step["nonzero_mean"])
    if load:
        out["router_load_max_over_mean"] = float(np.mean(load))
    if touched:
        out["experts_touched"] = float(np.mean(touched))
    return out


def setup(ctx) -> Session:
    return Session(ctx)
