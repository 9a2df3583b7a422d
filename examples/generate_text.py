"""Sample from a Transformer LM — the inference-side executable example.

``examples/train_lm.py`` is the training entry into the LM API; this is its
decode counterpart: build (or restore) a ``TransformerLM`` and sample
continuations with the full knob surface of ``models/generate.py``:

    python -m examples.generate_text --new-tokens 64
    python -m examples.generate_text --temperature 0.8 --top-k 50 --top-p 0.9
    python -m examples.generate_text --kv-quant        # int8 KV cache
    python -m examples.generate_text --tp 4            # tensor-parallel decode
    python -m examples.generate_text --ckpt-dir /tmp/lm --d-model 128 ...

``--ckpt-dir`` restores params saved by ``examples/train_lm.py`` (orbax;
the model flags must match the training run — the restore validates
shapes). Without it, sampling runs from a fresh init: useless text, but the
full compiled path, which is what the example demonstrates.

Decode runs the ring-buffered block path for 17+ token runs (per-step ring
appends, static live-prefix cache reads, once-per-block merges — see
``models/generate.py``); ``--kv-quant`` stores completed blocks as int8 +
per-key scales for half the cache footprint.
"""

from __future__ import annotations

import argparse

import numpy as np


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--prompt-len", type=int, default=32,
                   help="length of the random prompt (token ids)")
    p.add_argument("--new-tokens", type=int, default=64)
    p.add_argument("--batch", type=int, default=2,
                   help="number of prompts sampled in parallel")
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy; >0 = categorical sampling")
    p.add_argument("--top-k", type=int, default=0,
                   help="keep only the k highest logits (0 = off)")
    p.add_argument("--top-p", type=float, default=1.0,
                   help="nucleus truncation mass (1.0 = off)")
    p.add_argument("--kv-quant", action="store_true",
                   help="int8 KV cache: half the cache footprint, exact "
                        "prefill logits (models/generate.py)")
    p.add_argument("--tp", type=int, default=0, metavar="D",
                   help="tensor-parallel decode over D model-axis devices "
                        "(generate_tp; requires D to divide --n-heads)")
    p.add_argument("--ckpt-dir", type=str, default="",
                   help="restore params from a train_lm.py orbax checkpoint")
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--d-ff", type=int, default=256)
    p.add_argument("--max-len", type=int, default=0,
                   help="learned-position table size (0 = derived from the "
                        "decode length). Restoring a train_lm.py checkpoint "
                        "with learned positions requires the TRAINING run's "
                        "table size: train_lm uses max(--seq, 256)")
    p.add_argument("--dtype", default="float32", choices=["float32", "bfloat16"])
    p.add_argument("--pos-encoding", default="learned",
                   choices=["learned", "rope"])
    p.add_argument("--seed", type=int, default=0)
    return p


def main(argv=None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.d_model % args.n_heads:
        parser.error(f"--d-model {args.d_model} must divide by --n-heads "
                     f"{args.n_heads}")
    if args.temperature <= 0.0 and (args.top_k or args.top_p < 1.0):
        parser.error("--top-k/--top-p need --temperature > 0 (greedy decode "
                     "ignores them)")
    if args.tp and args.kv_quant:
        parser.error("--kv-quant is not supported with --tp (generate_tp "
                     "runs the exact-cache path) — drop one of the flags")
    # the kv_quant guard against runs the blocked path cannot serve lives
    # below (it needs the constructed model)

    import time

    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import TransformerLM
    from distributed_ml_pytorch_tpu.models.generate import generate, generate_tp
    from distributed_ml_pytorch_tpu.runtime import startup

    startup.enable_compile_cache()
    startup.announce_devices("generate_text")

    total = args.prompt_len + args.new_tokens
    lm = TransformerLM(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff,
        # blocked decode pads the step loop to whole 16-token blocks; keep
        # the learned-position table large enough for the padded positions
        # (checkpoint restores must instead match the training run's table
        # via --max-len: the param shapes are part of the checkpoint)
        max_len=args.max_len or max(total + 16, 256),
        dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
        pos_encoding=args.pos_encoding,
    )
    if args.kv_quant:
        from distributed_ml_pytorch_tpu.models.generate import uses_block_decode

        blocked, _ = uses_block_decode(lm, args.prompt_len, args.new_tokens)
        if not blocked:
            parser.error(
                "--kv-quant only applies on the ring-buffered block path "
                "(>= 17 new tokens, prompt length > 1, <= 1025 new tokens, "
                "and the padded run must fit --max-len) — this shape would "
                "silently run the exact cache")

    if not args.ckpt_dir:
        params = lm.init(
            jax.random.key(args.seed), jnp.zeros((1, 8), jnp.int32)
        )["params"]
    else:
        from distributed_ml_pytorch_tpu.utils.checkpoint import Checkpointer

        with Checkpointer(args.ckpt_dir) as ckpt:
            step = ckpt.latest_step()
            if step is None:
                raise SystemExit(
                    f"no checkpoint under {args.ckpt_dir} — train one with "
                    "examples/train_lm.py --ckpt-dir first")
            # train_lm checkpoints a TrainState; restore against a template
            # of the same shape and keep its params
            import optax
            from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
                create_lm_train_state,
            )

            # abstract template: no wasted full init before orbax
            # overwrites everything (Checkpointer.restore accepts shapes)
            template = jax.eval_shape(lambda: create_lm_train_state(
                lm, jax.random.key(args.seed), optax.sgd(0.1)))
            state, step = ckpt.restore(template)
            params = state.params
            print(f"restored params from step {step} of {args.ckpt_dir}")

    rng = np.random.default_rng(args.seed)
    prompt = jnp.asarray(
        rng.integers(0, args.vocab, size=(args.batch, args.prompt_len)),
        jnp.int32,
    )
    sample_rng = jax.random.key(args.seed + 1)
    kwargs = dict(
        temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
        rng=sample_rng if args.temperature > 0 else None,
    )

    t0 = time.perf_counter()
    if args.tp:
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

        n_dev = len(jax.devices())
        if args.tp > n_dev:
            raise SystemExit(f"--tp {args.tp} exceeds {n_dev} devices")
        if args.n_heads % args.tp:
            raise SystemExit(f"--tp {args.tp} must divide --n-heads "
                             f"{args.n_heads}")
        mesh = make_mesh({"data": 1, "model": args.tp},
                         devices=jax.devices()[: args.tp])
        out = generate_tp(lm, params, prompt, args.new_tokens, mesh, **kwargs)
        mode = f"tensor-parallel over {args.tp} devices"
    else:
        out = generate(lm, params, prompt, args.new_tokens,
                       kv_quant=args.kv_quant, **kwargs)
        mode = "int8 KV cache" if args.kv_quant else "bf16/f32 KV cache"
    out = np.asarray(out)
    dt = time.perf_counter() - t0

    n_generated = args.batch * args.new_tokens
    print(f"decode ({mode}): {n_generated} tokens in {dt:.2f}s "
          f"(compile included)")
    for b in range(args.batch):
        print(f"[{b}] prompt : {' '.join(map(str, out[b, :args.prompt_len]))}")
        print(f"[{b}] sampled: {' '.join(map(str, out[b, args.prompt_len:]))}")
    startup.report_compile_cache("generate_text")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
