"""Driver ``lm_train``: the jitted step of ``parallel/fsdp.make_fsdp_lm_train_step``
on a one-device mesh, built as ``examples/train_lm.py --mode single`` builds it
(dense loss, flash attention), with ``optax.adam`` where the example has
``optax.sgd`` (see the workload file), fed a different batch each step.

Set-up builds ONE object, the compiled step with its state, drives it from the
seed through its first three steps by the window's own call and feed, and
hands that same object to the window. What those three steps did (each loss,
each leaf's gradient norm as the optimizer got it, worked out from Adam's first
moment after step 1, each leaf's change after step 3) is what the plain
reference is asked about once the window has closed and the state is freed.
"""

from __future__ import annotations

import collections
import statistics
import time

from benchmarks import traffic
from benchmarks.harness import held, seed_key
from benchmarks.lm_model import transformer_lm

CHECK_STEPS = 3
#: a leaf whose reference gradient is under this share of the median leaf's
#: moves by round-off alone and is left out of the change comparison
TINY_GRADIENT = 1e-3


def norm_gap(got: dict, want: dict, skip=()) -> float:
    """Worst leaf's gap between two norms, against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    median = statistics.median(want.values())
    return max(abs(got[k] - want[k]) / max(want[k], median) for k in want if k not in skip)


def compare_steps(got: dict, want: dict) -> dict:
    """The numbers compared for a training cell, from two accounts of the
    first steps (``losses``, ``grad_norms``, ``change_norms``)."""
    median = statistics.median(want["grad_norms"].values())
    tiny = [k for k, v in want["grad_norms"].items() if v < TINY_GRADIENT * median]
    return {
        "loss_gap": max(abs(a - b) / abs(b) for a, b in zip(got["losses"], want["losses"])),
        "grad_norm_gap": norm_gap(got["grad_norms"], want["grad_norms"]),
        "change_norm_gap": norm_gap(got["change_norms"], want["change_norms"], skip=tiny),
    }


class Session:
    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        import optax

        from distributed_ml_pytorch_tpu.parallel.fsdp import (
            create_fsdp_train_state,
            make_fsdp_lm_train_step,
            shard_fsdp_batch,
        )
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh
        from distributed_ml_pytorch_tpu.training.trainer import TrainState

        self.ctx = ctx
        self.ref = ctx.cell.reference()
        cfg, work = ctx.config, ctx.workload
        self.cfg = cfg
        self.batch, self.seq = work["traffic"]["batch"], work["traffic"]["seq"]
        self.lr = float(work["trainer"]["lr"])
        self.vocab = cfg["vocab_size"]
        lm = transformer_lm(cfg)
        tx = optax.adam(self.lr)
        self.mesh = make_mesh({"data": 1}, devices=list(ctx.devices[:1]))
        self.key = seed_key(ctx.seed)

        # the benchmark's weights have to be the tree the program would make
        theirs = jax.eval_shape(lambda: lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
        ours = jax.eval_shape(lambda: self.ref.make_params(self.key, cfg))
        if jax.tree.map(lambda a: a.shape, theirs["params"]) != jax.tree.map(lambda a: a.shape, ours):
            raise RuntimeError("the reference's parameter tree is not TransformerLM's")

        init_fn = lambda key: TrainState.create(self.ref.make_params(key, cfg), tx)
        self.state, shardings = create_fsdp_train_state(init_fn, self.key, self.mesh)
        self.step = make_fsdp_lm_train_step(lm, tx, self.mesh, shardings)
        self._shard = lambda t, g: shard_fsdp_batch(self.mesh, t, g)
        self._diff_norms = jax.jit(lambda params, key: self.ref.leaf_norms(
            jax.tree.map(jnp.subtract, params, self.ref.make_params(key, cfg))))
        self._leaf_norms = jax.jit(self.ref.leaf_norms)
        self.index = 0
        self.first = self._first_steps()

    # ------------------------------------------------- the window's own call
    def advance(self):
        """One step by the window's call and feed; returns the loss (on the
        device)."""
        span = self.ctx.tracer.span
        with span("bench:feed"):
            tokens, targets = traffic.token_batch(
                self.ctx.seed, self.index, self.batch, self.seq, self.vocab)
            tokens, targets = self._shard(tokens, targets)
        with span("bench:dispatch"):
            self.state, loss = self.step(self.state, tokens, targets)
        self.index += 1
        return loss

    def _first_steps(self) -> dict:
        import jax

        losses, grad_norms = [], None
        for i in range(CHECK_STEPS):
            losses.append(float(self.advance()))
            if i == 0:  # after one step Adam's first moment is (1 - b1) x the gradient
                (adam,) = [s for s in jax.tree.leaves(
                    self.state.opt_state, is_leaf=lambda x: hasattr(x, "mu")) if hasattr(s, "mu")]
                grad_norms = {k: float(v) / (1.0 - self.ref.ADAM_B1)
                              for k, v in self._leaf_norms(adam.mu).items()}
        change = {k: float(v) for k, v in self._diff_norms(self.state.params, self.key).items()}
        return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}

    def _run(self, until, pending) -> int:
        """Steps until ``until()`` says stop, at most two dispatched ahead of
        the device; returns how many."""
        n = 0
        while not until(n):
            pending.append(self.advance())
            n += 1
            if len(pending) > 2:
                with self.ctx.tracer.span("bench:wait"):
                    pending.popleft().block_until_ready()
        return n

    def _sync(self, pending):
        import jax

        pending.clear()
        jax.block_until_ready(self.state)

    def run_window(self) -> dict:
        seconds, tracer = self.ctx.seconds, self.ctx.tracer
        pending = collections.deque()
        counters = {"batch": self.batch, "seq": self.seq}
        t0 = time.perf_counter()
        elapsed = lambda: time.perf_counter() - t0
        if tracer.enabled:
            n_trace = int(self.ctx.workload["trace"]["steps"])
            steps = self._run(lambda n: elapsed() >= 0.25 * seconds, pending)
            self._sync(pending)
            tracer.start()
            steps += self._run(lambda n: n >= n_trace, pending)
            self._sync(pending)
            tracer.stop()
            counters["traced_steps"] = n_trace
        else:
            steps = 0
        steps += self._run(lambda n: elapsed() >= seconds, pending)
        self._sync(pending)
        window_s = elapsed()
        tokens = steps * self.batch * self.seq
        return {"attempted": steps, "failed": 0, "counters": counters,
                "metrics": {"train_tokens_per_s": tokens / window_s}}

    def release(self) -> None:
        self.state = self.step = self._diff_norms = self._leaf_norms = None

    # ------------------------------------------------------------ comparison
    def reference_steps(self, **kw) -> dict:
        batches = [traffic.token_batch(self.ctx.seed, i, self.batch, self.seq, self.vocab)
                   for i in range(CHECK_STEPS)]
        return self.ref.adam_steps(self.key, batches, self.cfg, self.lr,
                                  rows=int(self.ctx.workload["reference"]["rows"]), **kw)

    def readings(self, control: bool) -> dict:
        """For calibration: the numbers compared as the program reads them
        and, with ``control``, as the int8 control and the half-batch fault
        read them (each the reference put in the program's place)."""
        want = self.reference_steps()
        out = {"program": compare_steps(self.first, want)}
        if control:
            out["control_int8"] = compare_steps(self.reference_steps(quant=True), want)
            out["fault_half_batch"] = compare_steps(
                self.reference_steps(keep_rows=self.batch // 2), want)
        return out

    def compare(self) -> list:
        numbers = compare_steps(self.first, self.reference_steps())
        return held(numbers, self.ctx.workload["limits"])


def setup(ctx) -> Session:
    return Session(ctx)
