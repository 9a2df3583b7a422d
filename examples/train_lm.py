"""Train the Transformer LM under any parallelism mode — executable example.

The reference's ``example/main.py`` is the CNN application; this is its
long-context counterpart: one script that builds a ``TransformerLM``, picks a
parallelism strategy, and trains on synthetic token streams, printing loss
and steady-state tokens/sec. It is the documented entry into the LM API:

    python -m examples.train_lm --mode single --steps 20
    python -m examples.train_lm --mode sp      # ring attention over seq axis
    python -m examples.train_lm --mode ulysses # all-to-all head re-sharding
    python -m examples.train_lm --mode fsdp    # ZeRO-3 sharded state
    python -m examples.train_lm --mode tp      # Megatron GSPMD shardings
    python -m examples.train_lm --mode pp      # GPipe stages over layers
    python -m examples.train_lm --mode moe     # dp x ep Switch-MoE experts
    python -m examples.train_lm --mode composite  # 3-D dp x fsdp x tp

Every mode supports ``--steps-per-dispatch K`` (K steps fused into one
compiled program via ``lax.scan`` over the mode's own sharded step — the
same chunked-dispatch idea as the CNN trainer's flag) and checkpoint/resume
via ``--ckpt-dir``/``--ckpt-every``/``--resume`` (orbax, sharding-aware:
states restore directly into the mode's device layout).

On one host, meshes come up on whatever devices exist (use
``JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8`` for the virtual-mesh
simulation); on a pod, run under
``runtime.initialize_distributed`` and the same code scales.
"""

from __future__ import annotations

import argparse
import time

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--mode", default="single",
                   choices=["single", "sp", "ulysses", "fsdp", "tp", "pp",
                            "moe", "composite"])
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--n-experts", type=int, default=4,
                   help="(--mode moe) experts per MoE layer")
    p.add_argument("--microbatches", type=int, default=4,
                   help="(--mode pp) GPipe microbatches per step")
    p.add_argument("--pp-dp", type=int, default=1, metavar="D",
                   help="(--mode pp) data-parallel pipeline replicas on a "
                        "(data=D, stage) mesh — dp x pp composition")
    p.add_argument("--pp-tp", type=int, default=1, metavar="T",
                   help="(--mode pp) tensor-parallel width INSIDE each "
                        "pipeline stage (Megatron block sharding over a "
                        "model axis); composes with --pp-dp for the full "
                        "dp x pp x tp 3-D layout")
    p.add_argument("--loss-chunk", type=int, default=0, metavar="C",
                   help="(single/fsdp modes) compute the LM loss in C-token "
                        "sequence chunks without materializing the full "
                        "(batch, seq, vocab) logits — required at very long "
                        "context (e.g. --seq 32768); 0 = dense loss")
    p.add_argument("--steps-per-dispatch", type=int, default=1, metavar="K",
                   help="fuse K steps (distinct batches) into one compiled "
                        "program via lax.scan; --steps must divide by K")
    p.add_argument("--ckpt-dir", type=str, default="",
                   help="enable orbax checkpointing under this directory")
    p.add_argument("--ckpt-every", type=int, default=100,
                   help="save every N global steps")
    p.add_argument("--resume", action="store_true", default=False,
                   help="restore the latest checkpoint from --ckpt-dir")
    p.add_argument("--batch", type=int, default=8, help="global batch (sequences)")
    p.add_argument("--seq", type=int, default=256, help="global sequence length")
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--d-ff", type=int, default=256)
    p.add_argument("--lr", type=float, default=0.05)
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--pos-encoding", default="learned", choices=["learned", "rope"])
    p.add_argument("--remat", action="store_true",
                   help="per-block rematerialization (long sequences)")
    p.add_argument("--seed", type=int, default=0)
    return p


def _scalar_loss(metrics) -> float:
    """Last scalar loss out of any mode's metrics: moe returns (loss, aux),
    chunked dispatch returns per-step stacks — take the primary, then the
    final element."""
    if isinstance(metrics, tuple):
        metrics = metrics[0]
    return float(np.asarray(metrics).reshape(-1)[-1])


def _stack_sharded(samples):
    """Stack identically-sharded per-step arrays onto a leading scan axis,
    keeping each step's sharding (spec lifted to ``P(None, *spec)``).
    Host-only inputs (e.g. pp's microbatched numpy arrays) stay numpy —
    jit shards them on entry."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    host = np.stack([np.asarray(a) for a in samples])
    sh = getattr(samples[0], "sharding", None)
    if isinstance(sh, NamedSharding):
        host = jax.device_put(
            host, NamedSharding(sh.mesh, PartitionSpec(None, *sh.spec))
        )
    return host


def _make_chunked_step(step):
    """K steps in one compiled program: ``lax.scan`` over the mode's own
    step (jit-of-jit inlines it; inner donation is subsumed by the outer)."""
    from functools import partial

    import jax

    @partial(jax.jit, donate_argnums=(0,))
    def chunked(state, tokens_k, targets_k):
        return jax.lax.scan(lambda s, b: step(s, *b), state, (tokens_k, targets_k))

    return chunked


def main(argv=None) -> int:
    run(argv)
    return 0


def run(argv=None) -> dict:
    """Parse ``argv``, train, and return what a caller may want to inspect:
    ``losses`` (one per dispatch), the jitted ``step`` with the final
    ``state`` and the ``batch`` it ran on (so the compiled program can be
    looked at), and ``setup_s`` — the first dispatch, compilation included."""
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.steps < 1:
        parser.error("--steps must be >= 1")
    if args.d_model % args.n_heads:
        parser.error(
            f"--d-model {args.d_model} must be divisible by --n-heads "
            f"{args.n_heads} (attention splits d_model into heads)"
        )
    if args.loss_chunk and args.seq % args.loss_chunk:
        parser.error(
            f"--seq {args.seq} must divide by --loss-chunk {args.loss_chunk}"
        )

    import math

    import jax
    import jax.numpy as jnp
    import optax

    from distributed_ml_pytorch_tpu.models import TransformerLM
    from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
        create_lm_train_state,
        next_token_targets,
    )
    from distributed_ml_pytorch_tpu.runtime import startup

    startup.enable_compile_cache()
    startup.announce_devices("train_lm")

    lm = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff,
        max_len=max(args.seq, 256),
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
        pos_encoding=args.pos_encoding, remat=args.remat,
    )
    tx = optax.sgd(args.lr)
    rng = np.random.default_rng(args.seed)
    tokens = rng.integers(0, args.vocab, size=(args.batch, args.seq)).astype(np.int32)
    targets = next_token_targets(tokens)

    n_dev = len(jax.devices())
    if args.mode in ("sp", "ulysses"):
        from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
            make_sp_train_step,
            shard_lm_batch,
        )
        from distributed_ml_pytorch_tpu.parallel.ulysses import make_ulysses_train_step
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

        # each axis must divide what it shards (seq over the seq axis, batch
        # over data; Ulysses additionally shards heads over the seq axis)
        d_seq = math.gcd(n_dev, args.seq)
        if args.mode == "ulysses":
            d_seq = math.gcd(d_seq, args.n_heads)
        d_data = math.gcd(n_dev // d_seq, args.batch)
        mesh = make_mesh(
            {"data": d_data, "seq": d_seq}, devices=jax.devices()[: d_data * d_seq]
        )
        state = create_lm_train_state(lm, jax.random.key(args.seed), tx)
        make = make_sp_train_step if args.mode == "sp" else make_ulysses_train_step
        step = make(lm, tx, mesh)
        shard = lambda t, g: shard_lm_batch(mesh, t, g)
        desc = f"{d_data}x{d_seq} dp x seq ({'ring' if args.mode == 'sp' else 'all-to-all'})"
    elif args.mode in ("single", "fsdp"):
        from distributed_ml_pytorch_tpu.parallel.fsdp import (
            create_fsdp_train_state,
            make_fsdp_lm_train_step,
            param_shard_fraction,
            shard_fsdp_batch,
        )
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh
        from distributed_ml_pytorch_tpu.training.trainer import TrainState

        # the batch shards over the data axis, so the mesh cannot be wider;
        # "single" is literally fsdp on a 1-device mesh (same step factory,
        # provably identical update semantics — fsdp.make_sharded_step)
        n_fsdp = 1 if args.mode == "single" else math.gcd(n_dev, args.batch)
        mesh = make_mesh({"data": n_fsdp}, devices=jax.devices()[:n_fsdp])

        def init_fn(key):
            params = lm.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
            return TrainState.create(params, tx)

        state, shardings = create_fsdp_train_state(
            init_fn, jax.random.key(args.seed), mesh
        )
        step = make_fsdp_lm_train_step(lm, tx, mesh, shardings,
                                       loss_chunk=args.loss_chunk)
        shard = lambda t, g: shard_fsdp_batch(mesh, t, g)
        desc = "single-device" if args.mode == "single" else (
            f"{n_fsdp}-way fsdp "
            f"({param_shard_fraction(state, mesh):.3f} of params/device)"
        )
    elif args.mode == "tp":
        from distributed_ml_pytorch_tpu.parallel.tensor_parallel import (
            create_tp_train_state,
            make_tp_train_step,
            shard_tp_batch,
        )
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

        # the model axis must divide every dimension tp shards
        d_model_axis = math.gcd(math.gcd(n_dev, args.n_heads),
                                math.gcd(args.d_ff, args.vocab))
        d_data = math.gcd(n_dev // d_model_axis, args.batch)
        mesh = make_mesh(
            {"data": d_data, "model": d_model_axis},
            devices=jax.devices()[: d_data * d_model_axis],
        )
        state = create_tp_train_state(lm, jax.random.key(args.seed), tx, mesh)
        step = make_tp_train_step(lm, tx, mesh)
        shard = lambda t, g: shard_tp_batch(mesh, t, g)
        desc = f"{d_data}x{d_model_axis} dp x tp"
    elif args.mode == "pp":
        from jax.sharding import Mesh

        from distributed_ml_pytorch_tpu.parallel.pipeline import (
            PipelineLMConfig,
            create_pp_train_state,
            make_pp_train_step,
            microbatch,
        )

        # stages must divide the layer count; microbatches must divide batch
        d_pp = int(args.pp_dp)
        d_tp = int(args.pp_tp)
        if d_pp < 1:
            parser.error(f"--pp-dp must be >= 1, got {d_pp}")
        if d_tp < 1:
            parser.error(f"--pp-tp must be >= 1, got {d_tp}")
        if n_dev % (d_pp * d_tp):
            parser.error(f"--pp-dp {d_pp} x --pp-tp {d_tp} must divide the "
                         f"device count {n_dev}")
        if args.n_heads % d_tp or args.d_ff % d_tp:
            parser.error(f"--pp-tp {d_tp} must divide n_heads "
                         f"{args.n_heads} and d_ff {args.d_ff}")
        n_stages = math.gcd(n_dev // (d_pp * d_tp), args.n_layers)
        n_mb = math.gcd(args.microbatches, args.batch)
        cfg = PipelineLMConfig(
            vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=args.d_ff, max_len=max(args.seq, 256),
        )
        model_axis = "model" if d_tp > 1 else None
        tp_desc = f" x {d_tp} tp-in-stage" if d_tp > 1 else ""
        if d_pp > 1:
            if (args.batch // n_mb) % d_pp:
                parser.error(f"--pp-dp {d_pp} must divide the per-microbatch "
                             f"batch {args.batch // n_mb}")
            shape = ((d_pp, n_stages, d_tp) if d_tp > 1
                     else (d_pp, n_stages))
            axes = (("data", "stage", "model") if d_tp > 1
                    else ("data", "stage"))
            mesh = Mesh(
                np.array(jax.devices()[: d_pp * n_stages * d_tp]).reshape(
                    shape),
                axes,
            )
            step = make_pp_train_step(cfg, tx, mesh, n_microbatches=n_mb,
                                      data_axis="data", model_axis=model_axis)
            desc = (f"{d_pp}x{n_stages} dp x pp GPipe{tp_desc}, {n_mb} "
                    f"microbatches, grads averaged over {d_pp} pipeline "
                    "replicas")
        else:
            shape = (n_stages, d_tp) if d_tp > 1 else (n_stages,)
            axes = ("stage", "model") if d_tp > 1 else ("stage",)
            mesh = Mesh(
                np.array(jax.devices()[: n_stages * d_tp]).reshape(shape),
                axes)
            step = make_pp_train_step(cfg, tx, mesh, n_microbatches=n_mb,
                                      model_axis=model_axis)
            desc = f"{n_stages}-stage GPipe{tp_desc}, {n_mb} microbatches"
        state = create_pp_train_state(cfg, jax.random.key(args.seed), tx,
                                      mesh, model_axis=model_axis)
        shard = lambda t, g: microbatch(t, g, n_mb)
    elif args.mode == "moe":
        from distributed_ml_pytorch_tpu.models.moe import MoETransformerLM
        from distributed_ml_pytorch_tpu.parallel.expert_parallel import (
            create_ep_train_state,
            make_ep_train_step,
            shard_ep_batch,
        )
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

        # experts divide over the expert axis; batch over the data axis
        d_expert = math.gcd(n_dev, args.n_experts)
        d_data = math.gcd(n_dev // d_expert, args.batch)
        mesh = make_mesh(
            {"data": d_data, "expert": d_expert},
            devices=jax.devices()[: d_data * d_expert],
        )
        moe = MoETransformerLM(
            vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
            n_layers=args.n_layers, d_ff=args.d_ff, n_experts=args.n_experts,
            max_len=max(args.seq, 256),
            dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
            remat=args.remat,
        )
        state = create_ep_train_state(moe, jax.random.key(args.seed), tx, mesh)
        step = make_ep_train_step(moe, tx, mesh)
        shard = lambda t, g: shard_ep_batch(mesh, t, g)
        desc = f"{d_data}x{d_expert} dp x ep ({args.n_experts} experts)"
    else:  # composite
        from distributed_ml_pytorch_tpu.parallel.composite import (
            create_composite_train_state,
            make_composite_train_step,
            shard_composite_batch,
        )
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

        # the model axis must divide heads/d_ff/vocab (1 when they're odd);
        # whatever it doesn't use goes to the combined data x fsdp group,
        # which must divide the batch
        d_model_c = math.gcd(2, math.gcd(args.n_heads, math.gcd(args.d_ff, args.vocab)))
        if d_model_c > n_dev:
            d_model_c = 1  # fewer devices than the model axis wants
        combined = math.gcd(n_dev // d_model_c, args.batch)
        d_data = 2 if combined % 2 == 0 and combined > 1 else 1
        shape = {"data": d_data, "fsdp": combined // d_data, "model": d_model_c}
        n_used = 1
        for v in shape.values():
            n_used *= v
        mesh = make_mesh(shape, devices=jax.devices()[:n_used])
        state, shardings = create_composite_train_state(
            lm, jax.random.key(args.seed), tx, mesh
        )
        step = make_composite_train_step(lm, tx, mesh, shardings)
        shard = lambda t, g: shard_composite_batch(mesh, t, g)
        desc = "x".join(str(v) for v in shape.values()) + " dp x fsdp x tp"

    k = args.steps_per_dispatch
    if k < 1:
        parser.error("--steps-per-dispatch must be >= 1")
    if args.steps % k:
        parser.error(f"--steps {args.steps} must divide by "
                     f"--steps-per-dispatch {k}")
    if k > 1:
        # K distinct host batches stacked on a scan axis, each sharded the
        # way this mode shards a single batch (spec lifted to P(None, *spec))
        pairs = []
        for _ in range(k):
            t = rng.integers(0, args.vocab,
                             size=(args.batch, args.seq)).astype(np.int32)
            pairs.append(shard(t, next_token_targets(t)))
        batch = tuple(_stack_sharded(leaves) for leaves in zip(*pairs))
        step = _make_chunked_step(step)
    else:
        batch = shard(tokens, targets)

    ckpt, start_step = None, 0
    if args.ckpt_dir:
        from distributed_ml_pytorch_tpu.utils.checkpoint import (
            Checkpointer,
            maybe_restore,
        )

        ckpt = Checkpointer(args.ckpt_dir, save_interval_steps=args.ckpt_every)
        if args.resume:
            state, start_step = maybe_restore(ckpt, state)
            if start_step:
                print(f"resumed from checkpoint step {start_step}")

    print(
        f"training {args.n_layers}-layer LM "
        f"({desc}, {mesh.devices.size} of {n_dev} devices)"
    )
    n_disp = args.steps // k
    t0 = time.perf_counter()
    setup_s = 0.0
    losses = []
    for i in range(n_disp):
        state, loss = step(state, *batch)
        losses.append(loss)
        if i == 0:
            jax.block_until_ready(loss)
            setup_s = time.perf_counter() - t0
            print(f"  first dispatch (compilation included) {setup_s:.1f}s")
            t0 = time.perf_counter()  # exclude compile from the rate
        if ckpt is not None:
            ckpt.save(start_step + (i + 1) * k, state)
        if i % max(1, n_disp // 5) == 0:
            print(f"  step {i * k:4d}  loss {_scalar_loss(loss):.4f}")
    losses = [_scalar_loss(l) for l in losses]
    dt = time.perf_counter() - t0
    rate = (n_disp - 1) * k * args.batch * args.seq / dt if n_disp > 1 else 0.0
    print(f"final loss {losses[-1]:.4f}; ~{rate:.0f} tokens/s "
          f"(naive wall-clock; benchmarks/run.py measures)")
    if ckpt is not None:
        ckpt.save(start_step + args.steps, state, force=True)
        ckpt.close()
    startup.report_compile_cache("train_lm")
    return {"losses": losses, "setup_s": setup_s, "step": step,
            "state": state, "batch": batch}


if __name__ == "__main__":
    raise SystemExit(main())
