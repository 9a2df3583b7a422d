"""Measure every BASELINE.md config this environment can measure honestly.

``bench.py`` stays the driver's one-line headline (config #1); this harness
produces the full table — one JSON line per config on stdout, narration on
stderr — and its results are recorded in BASELINE.md.

Measurement boundaries, per config (honesty notes in each JSON record):

1. single-process example CNN (reference ``Makefile:23``): differenced
   steady-state img/s on the real chip (``bench.bench_jax``), with the torch
   CPU leg as the measured reference baseline.
2. 2-process gradient exchange (reference ``pytorch_p2p_ex.py:7-23``): a
   2-device psum allreduce of the raveled AlexNet gradient vector (the
   sync-DP collective that replaces gloo send/recv). Only one real chip is
   attached here, so this runs on 2 virtual CPU devices — a functional
   measurement of the compiled collective, not ICI bandwidth.
3. async-SGD, 4 workers (reference ``asgd/optim/Asynchronous.py:42-70``):
   the real thing — 5 localhost processes (1 server + 4 workers) over the
   TCP transport, aggregate img/s with process startup and compile INCLUDED
   (the reference's own launch pattern pays the same costs).
4. ResNet-18 8-way data-parallel: single-chip TPU throughput (the per-chip
   number that an 8-way ICI allreduce scales, per the sync-DP exactness
   tests), plus an 8-virtual-device functional run of the actual sharded
   step.
5. ResNet-50 ImageNet-shaped (north star): single-chip TPU throughput at
   224x224. Pod-scale (v4-32) ICI needs hardware this environment lacks;
   the sharded program itself is validated by ``__graft_entry__`` /
   ``dryrun_multichip``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

from bench import (
    BATCH,
    LEG_NOTES,
    LR,
    bench_jax,
    bench_torch_cpu,
    log,
    make_batch,
    run_headline_legs,
)

RESULTS: list = []


def emit(config: int, metric: str, value: float, unit: str, hardware: str,
         note: str, extra: dict = None) -> None:
    rec = {
        "config": config,
        "metric": metric,
        "value": round(float(value), 1),
        "unit": unit,
        "hardware": hardware,
        "note": note,
    }
    # VERDICT r1 #1: every leg carries its FLOPs story when the harness
    # measured one (bench.Rate) — model FLOPs/step, achieved TFLOP/s, MFU
    from bench import Rate

    if isinstance(value, Rate) and value.tflops is not None:
        rec.update(value.record_fields())
        rec["note"] = f"{note}; {value.mfu_note()}"
    if extra:
        # structured side-channel fields (e.g. mpmd_phase's
        # bubble_attribution, ISSUE 12) — schema-checked by the caller
        rec.update(extra)
    RESULTS.append(rec)
    print(json.dumps(rec), flush=True)


#: the exclusive serve-loop states a bubble_attribution record may name
#: (utils/obs.StateClock vocabulary for the mpmd plane)
BUBBLE_STATES = ("compute", "wait-act", "wait-grad", "wire-blocked", "ckpt",
                 "idle")


def check_bubble_attribution(attr: dict) -> dict:
    """Schema gate for ``mpmd_phase``'s ``bubble_attribution`` JSON field
    (ISSUE 12; the ``test_bench_gate.py``-style check): fractions over the
    known exclusive states, summing to ~1, with ``bubble_fraction``
    consistent with ``1 - compute``. Raises ``ValueError`` on any breach —
    a malformed attribution must not ship in the bench record."""
    if not isinstance(attr, dict):
        raise ValueError(f"bubble_attribution must be a dict, got "
                         f"{type(attr).__name__}")
    fractions = attr.get("fractions")
    if not isinstance(fractions, dict) or not fractions:
        raise ValueError("bubble_attribution.fractions missing/empty")
    unknown = sorted(k for k in fractions if k not in BUBBLE_STATES)
    if unknown:
        raise ValueError(f"bubble_attribution names unknown state(s) "
                         f"{unknown} (known: {list(BUBBLE_STATES)})")
    total = sum(float(v) for v in fractions.values())
    if not 0.95 <= total <= 1.05:
        raise ValueError(
            f"bubble_attribution fractions sum to {total:.4f}, not ~1 — "
            "the exclusive-state clock contract is broken")
    bubble = attr.get("bubble_fraction")
    if not isinstance(bubble, (int, float)) or not 0.0 <= bubble <= 1.0:
        raise ValueError(f"bubble_fraction {bubble!r} not in [0, 1]")
    if abs((1.0 - float(fractions.get("compute", 0.0))) - float(bubble)) \
            > 1e-3:
        raise ValueError("bubble_fraction != 1 - compute fraction")
    stages = attr.get("stages")
    if not isinstance(stages, int) or stages < 1:
        raise ValueError(f"bubble_attribution.stages {stages!r} invalid")
    return attr


def tpu_phase() -> None:
    import jax

    platform = jax.devices()[0].platform
    hw = f"1x {platform}"

    # config 1 — flagship AlexNet, all three headline legs (identical to
    # bench.py's record: parity recipe, large-batch ceiling, grad-accum)
    legs = run_headline_legs()

    emit(1, "alexnet_cifar10_train_throughput", legs["parity_b64"],
         "images/sec/chip", hw, LEG_NOTES["parity_b64"])
    emit(1, "alexnet_cifar10_train_throughput_large_batch",
         legs["large_batch_b1024"], "images/sec/chip", hw,
         LEG_NOTES["large_batch_b1024"])
    emit(1, "alexnet_cifar10_train_throughput_grad_accum",
         legs["grad_accum_b1024"], "images/sec/chip", hw,
         LEG_NOTES["grad_accum_b1024"])
    base = bench_torch_cpu()
    if base:
        emit(1, "alexnet_cifar10_train_throughput_torch_reference", base,
             "images/sec", "cpu",
             "reference `make single` recipe re-measured in torch")

    # config 1 (MXU-native leg) — the same flagship with bf16 activations
    # (f32 params; the framework's --dtype bfloat16 path)
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import AlexNet

    ips_bf16 = bench_jax(model=AlexNet(num_classes=10, dtype=jnp.bfloat16))
    emit(1, "alexnet_cifar10_train_throughput_bf16", ips_bf16,
         "images/sec/chip", hw,
         "same recipe with bfloat16 activations feeding the MXU natively")

    # config 1 (north-star metric #2) — steps to target accuracy, both
    # frameworks, identical batch stream
    jax_steps, torch_steps, torch_status, _jacc, _tacc, _curves = bench_steps_to_accuracy()
    if jax_steps is None:
        emit(1, "steps_to_99pct_test_accuracy", -1, "steps", hw,
             "did NOT reach the target within the 2000-step cap — "
             "investigate before trusting other rows (-1 = cap hit)")
    else:
        torch_part = {
            "measured": f"torch on the identical batch stream took "
                        f"{torch_steps} steps",
            "cap": "torch on the identical batch stream did NOT reach the "
                   "target within the 2000-step cap (its default kaiming "
                   "init plateaus at this lr; flax's lecun-normal escapes "
                   "early — init is part of each framework's recipe)",
            "unavailable": "torch leg unavailable in this environment "
                           "(not a measured outcome)",
        }[torch_status]
        emit(1, "steps_to_99pct_test_accuracy", jax_steps, "steps", hw,
             f"reference recipe on the deterministic synthetic set; {torch_part}")

    # config 1 (identical-init leg, VERDICT r3 #3) — the cross-framework
    # steps ratio needs a target BOTH frameworks reach; torch's default init
    # never learns at this lr (chance accuracy at the cap), so this leg
    # installs the identical flax init into the torch model and compares
    # steps-to-60% — isolating the training machinery from init luck
    mj, mt, mstat, mjacc, mtacc, _ = bench_steps_to_accuracy(
        target=0.60, torch_init="matched")
    if mj is not None and mt is not None:
        emit(1, "steps_to_60pct_matched_init_ratio", mt / mj, "torch/jax steps",
             hw, f"identical init + identical batch stream: jax {mj} vs "
             f"torch {mt} steps to 60%; final acc delta "
             f"{abs(mjacc - mtacc):.4f} (north-star parity bar is 0.001)")
    else:
        emit(1, "steps_to_60pct_matched_init_ratio", -1, "torch/jax steps",
             hw, f"matched-init leg incomplete: jax {mj}, torch {mt} "
             f"({mstat}); -1 = no finite ratio")

    from distributed_ml_pytorch_tpu.models import TransformerLM, get_resnet

    # config 4 (per-chip leg) — ResNet-18, CIFAR shapes, batch 64
    r18 = bench_jax(model=get_resnet("resnet18"), k=20, trials=3)
    emit(4, "resnet18_cifar10_train_throughput", r18, "images/sec/chip", hw,
         "single-chip leg of the 8-way DP config; the sync-DP step is "
         "numerically validated on an 8-device mesh (tests/test_resnet.py)")

    # config 4 (MXU-native leg) — ResNet-18 in bf16 at a batch that fills
    # the MXU (the f32/batch-64 leg above keeps the reference-recipe shape)
    r18bf = bench_jax(model=get_resnet("resnet18", dtype=jnp.bfloat16),
                      batch=256, k=10, trials=3)
    emit(4, "resnet18_cifar10_train_throughput_bf16", r18bf,
         "images/sec/chip", hw,
         "bf16 activations + f32 master params, batch 256, device-resident "
         "input")

    # config 5 (per-chip leg) — ResNet-50, ImageNet shapes (224x224, 1000-way)
    r50 = bench_jax(model=get_resnet("resnet50", num_classes=1000), batch=32,
                    input_shape=(224, 224, 3), n_classes=1000, k=4,
                    trials=3)
    emit(5, "resnet50_imagenet_shape_train_throughput", r50, "images/sec/chip",
         hw, "224x224 synthetic, batch 32, f32; pod-scale ICI requires a "
         "v4-32 this environment lacks — sharded program validated by "
         "dryrun_multichip")

    # config 5 (MXU-native leg, VERDICT r1 #1) — ResNet-50 in bf16 at a
    # batch that fills the MXU; this is the MFU-judged leg
    r50bf = bench_jax(
        model=get_resnet("resnet50", num_classes=1000, dtype=jnp.bfloat16),
        batch=256, input_shape=(224, 224, 3), n_classes=1000, k=2,
        trials=3,
    )
    emit(5, "resnet50_imagenet_shape_train_throughput_bf16", r50bf,
         "images/sec/chip", hw,
         "224x224 synthetic, batch 256, bf16 activations + f32 master params, "
         "device-resident input (compute ceiling)")

    # config 5 (host-fed leg) — same step with every batch starting in host
    # RAM, double-buffered device_put overlapping the previous step
    r50h = bench_hostfed_resnet50()
    if r50h is not None:
        emit(5, "resnet50_hostfed_overlapped_input_throughput", r50h,
             "images/sec/chip", hw,
             "batch 256 bf16, each step's input device_put from host while "
             "the prior step runs")

    # config 6 (capability extension, no reference counterpart) — long-context
    # Transformer-LM training throughput at seq 8192
    tok_s = bench_lm(tag="lm-512d-seq8192")
    emit(6, "transformer_lm_seq8192_train_throughput", tok_s, "tokens/sec/chip",
         hw, "default TransformerLM (512d/8h/6L), bf16 activations, per-block "
         "remat, RoPE, batch 1 x seq 8192; capability extension — the "
         "reference has no sequence models (SURVEY.md §5.7)")

    # config 6 (MFU-judged leg, VERDICT r1 #1) — GPT-2-small-scale LM
    # (162M params incl. untied embeddings; vocab padded to a multiple of
    # 128 for MXU-aligned logits). remat=False measured faster than
    # remat=True at both shapes (flash attention removed the S² temps that
    # made remat necessary: 88.1k vs 65.9k tok/s at b8/s2048). The flash
    # kernel's FLOPs are invisible to cost_analysis and are added
    # analytically inside bench_lm (utils/flops.flash_attention_train_flops).
    gpt2 = TransformerLM(
        vocab_size=50304, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
        dtype=jnp.bfloat16, remat=False, pos_encoding="rope",
    )
    tok_s2 = bench_lm(gpt2, batch=8, seq=2048, n_long=6, tag="gpt2-small-seq2048")
    emit(6, "gpt2_small_seq2048_train_throughput", tok_s2, "tokens/sec/chip",
         hw, "GPT-2-small config (768d/12h/12L, padded vocab 50304), bf16, "
         "RoPE, Pallas flash attention, batch 8 x seq 2048; kernel FLOPs "
         "counted analytically on top of the XLA count")
    tok_s3 = bench_lm(gpt2, batch=1, seq=8192, n_long=6, tag="gpt2-small-seq8192")
    emit(6, "gpt2_small_seq8192_train_throughput", tok_s3, "tokens/sec/chip",
         hw, "same GPT-2-small config at long context, batch 1 x seq 8192; "
         "attention dominates at this S (the analytic kernel count is most "
         "of the numerator)")

    # config 6 (extreme-length leg) — full model at 32k context via the
    # sequence-chunked loss
    bench_lm_32k()

    # config 6 (MoE family leg) — Switch-MoE at GPT-2-small dims
    moe_tok = bench_moe_lm()
    emit(6, "moe_lm_4expert_seq2048_train_throughput", moe_tok,
         "tokens/sec/chip", hw,
         "Switch-MoE (768d/12L, 4 experts top-1, 2.0 capacity), bf16, batch "
         "8 x seq 2048 — single-chip leg of the dp x ep sharding "
         "(dryrun_multichip runs the sharded step)")

    # config 8 (inference) — KV-cache autoregressive decode, with the HBM
    # roofline that judges it (decode reads all params + the live KV cache
    # per step; utilization column per VERDICT r2 #4)
    dec_rate, dec_frac, dec_bytes = bench_decode()
    emit(8, "gpt2_small_decode_throughput", dec_rate, "tokens/sec/chip", hw,
         f"batch 32, 128-token prompt prefill + 256 generated tokens per "
         f"call, ring-buffered block decode (models/generate.py: per-step "
         f"ring appends, static live-prefix cache reads, once-per-block "
         f"merges); greedy, device-true timing. "
         f"{dec_bytes / 1e6:.0f} MB/step of mandatory HBM traffic → "
         f"{100 * dec_frac:.0f}% of the measured streaming roofline")
    emit(8, "gpt2_small_decode_hbm_utilization", 100 * dec_frac,
         "percent of measured HBM roofline", hw,
         "mandatory traffic (bf16 params + average live K/V read) per step "
         "x steps/s, judged against the bandwidth a pure streaming read "
         "actually sustains on this chip (~715 GB/s, 87% of the 819 GB/s "
         "nameplate) — decode's MFU-equivalent, a lower bound on achieved "
         "bandwidth. Remaining gap: weight-DMA latency stalls between "
         "small per-layer matmuls (measured as async copy/slice waits)")

    # config 8 (capacity knob) — int8 KV cache: halves the cache's HBM
    # footprint (2x decode batch or context per chip); measured here so the
    # throughput-neutrality claim stays current
    q_rate, _, _ = bench_decode(kv_quant=True)
    emit(8, "gpt2_small_decode_throughput_int8_kv", q_rate, "tokens/sec/chip",
         hw, "same leg with kv_quant=True (int8 cache + per-key f32 scales, "
         "quantized at block merges; prefill attends with exact K/V). A "
         "CAPACITY knob, not a speed knob on this runtime: bytes halve but "
         "the fused convert+dequantize read runs at ~half the bf16 GB/s, "
         "so read time is ~flat")


def install_flax_alexnet_init(tmodel, flax_params) -> None:
    """Copy a flax AlexNet init into the torch AlexNet (the inverse of
    ``utils/interop``'s torch→flax direction, specialized to the one
    architecture the steps-to-target comparison uses): conv kernels
    (kH, kW, I, O) → (O, I, kH, kW), the classifier (in, out) → (out, in),
    biases as-is. Layer order is structural (conv1..conv5, classifier), so
    no shape-matching heuristics are needed."""
    import torch

    convs = [m for m in tmodel if isinstance(m, torch.nn.Conv2d)]
    linears = [m for m in tmodel if isinstance(m, torch.nn.Linear)]
    names = [f"conv{i}" for i in range(1, len(convs) + 1)]
    with torch.no_grad():
        # np.array(copy=True): jax exports read-only buffers and
        # torch.from_numpy warns on non-writable sources
        as_t = lambda a: torch.from_numpy(np.array(a, np.float32, copy=True))
        for name, m in zip(names, convs):
            m.weight.copy_(as_t(
                np.asarray(flax_params[name]["kernel"]).transpose(3, 2, 0, 1)))
            m.bias.copy_(as_t(flax_params[name]["bias"]))
        (lin,) = linears
        lin.weight.copy_(as_t(np.asarray(flax_params["classifier"]["kernel"]).T))
        lin.bias.copy_(as_t(flax_params["classifier"]["bias"]))


def bench_steps_to_accuracy(target: float = 0.99, max_steps: int = 2000,
                            eval_every: int = 25, n_eval: int = 2000,
                            synthetic: bool = True, root: str = "./data",
                            torch_init: str = "default"):
    """North-star metric #2: steps to reach ``target`` test accuracy with the
    reference recipe (AlexNet, batch 64, SGD lr 0.008) — measured for BOTH
    frameworks on the IDENTICAL batch stream (same sampled indices), so the
    comparison isolates the framework, not the data order. Inits differ
    (torch default vs flax lecun), which is part of each framework's
    recipe. ``synthetic=False`` runs on real CIFAR-10 under ``root``
    (``verify_real_data.py``'s path — raises if absent). Returns
    ``(jax_steps, torch_steps, torch_status, jax_acc, torch_acc, curves)``
    — steps are None on a cap-hit, accs are the FINAL evaluated accuracies
    either way (the parity bar's ingredients), and ``curves`` holds each
    framework's per-eval accuracy trajectory so a caller can derive any
    target's first crossing from ONE run; ``torch_status`` is one of
    ``"measured" | "cap" | "unavailable" | "skipped"`` — a cap-hit is a
    *measured outcome*, an exception is not, and the caller must not
    conflate them.
    """
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.data import load_cifar10
    from distributed_ml_pytorch_tpu.models import AlexNet
    from distributed_ml_pytorch_tpu.training.trainer import (
        create_train_state,
        make_eval_fn,
        make_scan_train_step,
    )

    x, y, xt, yt, _ = load_cifar10(root=root, synthetic=synthetic)
    xe, ye = xt[:n_eval], yt[:n_eval]
    idx = np.random.default_rng(0).integers(
        0, len(x), size=(max_steps // eval_every, eval_every, BATCH)
    )

    model = AlexNet()
    state, tx = create_train_state(model, jax.random.key(0), lr=LR)
    # snapshot the init to host BEFORE training: the scan donates the state,
    # so the initial device buffers will be reused
    init_np = jax.tree.map(np.asarray, state.params)
    scan = make_scan_train_step(model, tx)
    ev = make_eval_fn(model)
    rng = jax.random.key(1)
    jax_steps, jax_acc = None, 0.0
    jax_curve, torch_curve = [], []  # per-eval accs (steps = (i+1)*eval_every)
    xe_j = jnp.asarray(xe)
    for chunk, sel in enumerate(idx):
        state, _losses = scan(state, jnp.asarray(x[sel]), jnp.asarray(y[sel]), rng)
        _, preds = ev(state.params, xe_j, jnp.asarray(ye))
        jax_acc = float((np.asarray(preds) == ye).mean())
        jax_curve.append(jax_acc)
        if jax_steps is None and jax_acc >= target:
            jax_steps = (chunk + 1) * eval_every
            if synthetic:
                break  # real-data runs continue to the cap for the parity acc
    log(f"steps-to-{target:.0%}: jax {jax_steps} (final acc {jax_acc:.4f})")
    if jax_steps is None and synthetic:
        # the comparison leg is moot (and minutes of CPU) when the primary
        # leg missed the target — report the cap-hit instead of discarding
        return None, None, "skipped", jax_acc, None, {
            "jax": jax_curve, "torch": [], "eval_every": eval_every}

    torch_steps, torch_status, torch_acc = None, "cap", None
    try:
        import torch
        import torch.nn.functional as F

        from bench import make_torch_alexnet

        torch.manual_seed(0)
        tmodel = make_torch_alexnet()
        if torch_init == "matched":
            # identical-init leg (VERDICT r3 #3): torch's default kaiming
            # init never escapes its plateau at this lr on the synthetic
            # stream (measured: 9.1% after 2000 steps — chance), so no
            # target yields a finite cross-framework ratio. Installing the
            # IDENTICAL flax init isolates what the row is about — the
            # training machinery — instead of init luck.
            install_flax_alexnet_init(tmodel, init_np)
        elif torch_init != "default":
            raise ValueError(f"torch_init must be 'default' or 'matched', "
                             f"got {torch_init!r}")
        opt = torch.optim.SGD(tmodel.parameters(), lr=LR, momentum=0.0)
        xe_t = torch.from_numpy(xe.transpose(0, 3, 1, 2).copy())
        for chunk, sel in enumerate(idx):
            for step_idx in sel:
                bx = torch.from_numpy(x[step_idx].transpose(0, 3, 1, 2).copy())
                by = torch.from_numpy(y[step_idx].astype(np.int64))
                opt.zero_grad()
                loss = F.cross_entropy(tmodel(bx), by)
                loss.backward()
                opt.step()
            with torch.no_grad():
                torch_acc = float((tmodel(xe_t).argmax(1).numpy() == ye).mean())
            torch_curve.append(torch_acc)
            if torch_steps is None and torch_acc >= target:
                torch_steps = (chunk + 1) * eval_every
                torch_status = "measured"
                if synthetic:
                    break
    except Exception as e:
        torch_status = "unavailable"
        log(f"torch steps-to-accuracy unavailable: {e}")
    log(f"steps-to-{target:.0%}: torch {torch_steps} ({torch_status}, "
        f"final acc {torch_acc if torch_acc is not None else float('nan'):.4f})")
    return (jax_steps, torch_steps, torch_status, jax_acc, torch_acc,
            {"jax": jax_curve, "torch": torch_curve,
             "eval_every": eval_every})


def bench_lm(lm=None, batch: int = 1, seq: int = 8192, n_long: int = 11,
             cross_check: bool = True,
             trials: int = 3, tag: str = "lm"):
    """Differenced steady-state tokens/sec (+ FLOPs/MFU) of one LM train step
    on the default device (chained through the donated state: each dispatch's
    params feed the next, so the final scalar fetch forces the whole chain)."""
    from functools import partial

    import jax
    import jax.numpy as jnp
    import optax

    from bench import Rate
    from distributed_ml_pytorch_tpu.models import TransformerLM
    from distributed_ml_pytorch_tpu.parallel.fsdp import lm_loss_builder
    from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
        create_lm_train_state,
        next_token_targets,
    )
    from distributed_ml_pytorch_tpu.utils.flops import compiled_flops

    if lm is None:
        # remat=False: with flash attention the S² temporaries are gone, so
        # at this scale rematerialization only adds recompute — measured
        # 184.5k vs 154.9k tok/s at b1×S8192 (remat stays the right call
        # where activations genuinely exceed HBM, e.g. the 32k leg)
        lm = TransformerLM(dtype=jnp.bfloat16, remat=False, pos_encoding="rope")
    lr = 1e-3  # ONE recipe for both step builders below: plain SGD at lr
    tx = optax.sgd(lr)
    state = create_lm_train_state(lm, jax.random.key(0), tx)
    tokens = np.random.default_rng(0).integers(
        0, lm.vocab_size, size=(batch, seq)
    ).astype(np.int32)
    targets = jnp.asarray(next_token_targets(tokens))
    tokens = jnp.asarray(tokens)
    loss_builder = lm_loss_builder(lm)  # the shared masked-LM loss convention

    if getattr(lm, "head", None) is True:
        # detachable-head models take the restructured lm_head step
        # (ops/fused_head.py): same function as the AD step below (tested),
        # one lse for loss+backward+update — measured +2.1% tokens/s at
        # GPT-2-small b1×S8192 together with the S=8192 flash backward
        # blocking (121.57 → 119.11 ms/step, device-true). It implements
        # plain SGD at `lr` — exactly the tx above; change them together.
        from distributed_ml_pytorch_tpu.ops.fused_head import (
            make_fused_head_sgd_step,
        )

        step = make_fused_head_sgd_step(lm, lr)
    else:
        @partial(jax.jit, donate_argnums=(0,))
        def step(state, tokens, targets):
            loss, grads = jax.value_and_grad(
                loss_builder(state, tokens, targets))(state.params)
            updates, opt_state = tx.update(grads, state.opt_state, state.params)
            params = optax.apply_updates(state.params, updates)
            return state.replace(params=params, opt_state=opt_state,
                                 step=state.step + 1), loss

    step_flops = compiled_flops(step, state, tokens, targets)
    # the Pallas flash kernels' FLOPs are invisible to cost_analysis; when
    # this leg runs them (TPU + blockable shape + model-default attention),
    # add the analytic kernel count so MFU is real, not a floor
    from distributed_ml_pytorch_tpu.ops.attention import flash_block_choice

    uses_flash = (
        step_flops is not None
        and jax.default_backend() == "tpu"
        and getattr(lm, "attn_fn", None) is None
        and flash_block_choice(seq, seq) is not None
    )
    if uses_flash:
        from distributed_ml_pytorch_tpu.utils.flops import (
            flash_attention_train_flops,
        )

        step_flops += flash_attention_train_flops(
            batch, lm.n_heads, seq, lm.d_model // lm.n_heads, lm.n_layers,
            causal=True, remat=bool(getattr(lm, "remat", False)),
        )

    # audit cross-check (VERDICT r2 #8): the hybrid numerator must agree
    # with an independent scaling-book 6ND count within 15%
    from distributed_ml_pytorch_tpu.utils.flops import (
        check_flops_agreement,
        lm_train_flops_6nd,
    )

    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    embed_params = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
        if any("embed" in str(getattr(k, "key", k)).lower() for k in path)
    )
    if cross_check:
        analytic = lm_train_flops_6nd(
            n_params - embed_params, batch, seq, lm.n_heads,
            lm.d_model // lm.n_heads, lm.n_layers,
            causal=True, remat=bool(getattr(lm, "remat", False)))
        warn = check_flops_agreement(step_flops, analytic)
        if warn:
            log(f"{tag}: {warn}")
    else:
        warn = None

    from distributed_ml_pytorch_tpu.utils.devtime import device_time

    holder = {"s": state}

    def one_step():
        holder["s"], loss = step(holder["s"], tokens, targets)
        return loss

    t = device_time(one_step, calls=max(2, n_long), warmup=2)
    per_step = t.per_call_s
    rate = Rate.make(batch * seq / per_step, step_flops, per_step)
    log(f"{tag} ({n_params / 1e6:.0f}M params): {per_step * 1e3:.1f} ms/step at "
        f"batch {batch} x seq {seq} → {rate:.0f} tokens/s ({rate.mfu_note()}, "
        f"device-true; 6ND cross-check "
        f"{'skipped' if not cross_check else 'ok' if warn is None else 'FAILED'})")
    return rate


def bench_lm_32k() -> None:
    """Config 6, extreme-length leg: a FULL GPT-2-small train step at
    S=32768 on one chip — possible only because the loss is sequence-
    chunked (``training/trainer.chunked_lm_loss``: the (1, 32768, 50304)
    logits tensor alone is 6.6 GB f32, which OOM'd the dense loss; the
    flash kernel handles the attention, remat the block activations)."""
    import jax
    import jax.numpy as jnp
    import optax

    from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
    from distributed_ml_pytorch_tpu.training.trainer import chunked_lm_loss
    from distributed_ml_pytorch_tpu.utils.devtime import device_time
    from distributed_ml_pytorch_tpu.utils.flops import lm_train_flops_6nd

    S = 32768
    lm = TransformerLM(
        vocab_size=50304, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
        max_len=S, dtype=jnp.bfloat16, pos_encoding="rope", remat=True)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 50304, (1, S)),
                         jnp.int32)
    targets = jnp.asarray(np.random.default_rng(1).integers(0, 50304, (1, S)),
                          jnp.int32)
    params = lm.init(jax.random.key(0), tokens[:, :128])["params"]
    tx = optax.sgd(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: chunked_lm_loss(lm, p, tokens, targets, chunk=2048)
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    holder = {"p": params, "o": opt_state}

    def call():
        holder["p"], holder["o"], loss = step(
            holder["p"], holder["o"], tokens, targets)
        return loss

    t = device_time(call, calls=2, warmup=2)
    n_params = sum(p.size for p in jax.tree.leaves(holder["p"]))
    embed_params = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_flatten_with_path(holder["p"])[0]
        if any("embed" in str(getattr(k, "key", k)).lower() for k in path)
    )
    fl = lm_train_flops_6nd(
        n_params - embed_params, 1, S, lm.n_heads,
        lm.d_model // lm.n_heads, lm.n_layers, remat=True)
    from bench import Rate

    rate = Rate.make(S / t.per_call_s, fl, t.per_call_s)
    emit(6, "gpt2_small_seq32768_train_throughput", rate, "tokens/sec/chip",
         "1x tpu",
         "FULL-model single-chip training at 32k context (bf16, RoPE, "
         "remat, sequence-chunked loss — the dense loss OOMs on the 6.6 GB "
         "logits tensor); numerator is the analytic 6ND count incl. remat "
         "recompute (cost_analysis path not used for this leg)")


def bench_moe_lm(batch: int = 8, seq: int = 2048, n_long: int = 4,
                 trials: int = 2):
    """Single-chip Switch-MoE LM leg: same measurement discipline as
    bench_lm, on the MoE model family (GPT-2-small dims, 4 experts, top-1
    routing — ~4x the FFN params of the dense model at ~the dense FLOPs,
    the MoE bargain the EP sharding distributes)."""
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models.moe import MoETransformerLM

    moe = MoETransformerLM(
        vocab_size=50304, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
        n_experts=4, max_len=seq, dtype=jnp.bfloat16,
    )
    # cross_check=False: 6·N·D over ALL experts' params overcounts top-1
    # routed execution ~2-3x — an activated-params 6ND for MoE is future work
    return bench_lm(moe, batch=batch, seq=seq, n_long=n_long, trials=trials,
                    cross_check=False,
                    tag=f"moe-4e-seq{seq}")


def bench_decode(batch: int = 32, prompt_len: int = 128,
                 new_tokens: int = 256, kv_quant: bool = False):
    """Autoregressive decode of the GPT-2-small model — tokens/s plus the
    roofline that judges it (VERDICT r2 #4): each single-token step must
    read every parameter once (batch-amortized) and each sequence's K/V
    cache, so the decode ceiling is HBM bandwidth, not FLOPs. Reports
    bytes/step from the actual param dtypes + the average live cache
    length, and the achieved fraction of the chip's 819 GB/s. Timing is
    device-true (utils/devtime): the profiler's device spans for the
    prefill + scanned-generation programs."""
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import TransformerLM, generate
    from distributed_ml_pytorch_tpu.utils.devtime import device_time

    lm = TransformerLM(
        vocab_size=50304, d_model=768, n_heads=12, n_layers=12, d_ff=3072,
        max_len=prompt_len + new_tokens, dtype=jnp.bfloat16,
    )
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [
        jnp.asarray(np.random.default_rng(s).integers(
            0, lm.vocab_size, size=(batch, prompt_len)), jnp.int32)
        for s in range(8)
    ]
    calls = {"i": 0}

    def one_call():  # rotate prompts: identical dispatches can be memoized
        calls["i"] += 1
        return generate(lm, params, prompts[calls["i"] % len(prompts)],
                        new_tokens, kv_quant=kv_quant)

    # single-call traces: the 256-iteration scan emits thousands of inner
    # spans per call, and a multi-call window overflows the profiler buffer
    # (observed: 4 forced calls, one surviving top-level span)
    t1 = device_time(one_call, calls=1, warmup=2)
    t2 = device_time(one_call, calls=1, warmup=0)
    per_call = (t1.per_call_s + t2.per_call_s) / 2
    rate = batch * new_tokens / per_call

    # --- roofline: MANDATORY bytes per step, a lower bound on achieved
    # HBM bandwidth. Weights count at the compute dtype (XLA hoists the
    # one-time f32→bf16 conversion out of the scanned loop, so steady-state
    # steps read the bf16 copies — counting stored-f32 bytes measured an
    # impossible 111% at batch 8); K/V counts the average live cache read.
    n_params = sum(leaf.size for leaf in jax.tree.leaves(params))
    param_bytes = n_params * jnp.dtype(lm.dtype).itemsize
    d_model, n_layers = lm.d_model, lm.n_layers
    avg_len = prompt_len + new_tokens / 2  # cache grows as tokens emit
    if kv_quant:
        # int8 values + one f32 scale per (head, position) per K and V
        kv_bytes_per_step = batch * 2 * n_layers * avg_len * (
            d_model * 1 + lm.n_heads * 4)
    else:
        kv_bytes_per_step = batch * 2 * n_layers * d_model * avg_len * 2  # bf16 K+V
    bytes_per_step = param_bytes + kv_bytes_per_step
    steps_per_s = rate / batch
    achieved_bw = bytes_per_step * steps_per_s
    frac = achieved_bw / 819e9

    # the MEASURED roofline: what a pure streaming read actually sustains on
    # this chip (nameplate 819 GB/s is never reachable — measured 714-720
    # GB/s on 256 MB-1 GB sums, ~87% of nameplate). Decode efficiency is
    # judged against what the memory system demonstrably delivers.
    stream = jnp.ones((128 * 1024 * 1024,), jnp.bfloat16)  # 256 MB
    t_read = device_time(
        jax.jit(lambda x: jnp.sum(x, dtype=jnp.float32)), stream,
        calls=6, warmup=2)
    measured_bw = stream.size * 2 / t_read.per_call_s
    frac_measured = achieved_bw / measured_bw
    log(f"decode: {per_call * 1e3:.1f} ms per {new_tokens}-token generation "
        f"(batch {batch}, device-true) → {rate:.0f} tokens/s; "
        f"{bytes_per_step / 1e6:.0f} MB/step mandatory "
        f"({param_bytes / 1e6:.0f} bf16 params + {kv_bytes_per_step / 1e6:.0f} KV) "
        f"→ ≥{achieved_bw / 1e9:.0f} GB/s = {100 * frac:.0f}% of 819 GB/s "
        f"nameplate, {100 * frac_measured:.0f}% of the measured "
        f"{measured_bw / 1e9:.0f} GB/s streaming roofline")
    return rate, frac_measured, bytes_per_step


def bench_hostfed_resnet50(batch: int = 256, steps: int = 8, trials: int = 3):
    """Overlapped-input leg (VERDICT r1 #1): every step's batch starts in
    host RAM and is ``device_put`` while the device runs the previous step —
    the per-step trainer path a real data loader feeds. jax's async dispatch
    does the overlap: the host loop enqueues transfer(i+1) + step(i+1)
    before step(i) finishes; the closing loss fetch forces the chain.
    Returns None when the host link makes the leg meaningless (< 1 img/s).
    """
    import jax
    import jax.numpy as jnp

    from bench import Rate
    from distributed_ml_pytorch_tpu.models import get_resnet
    from distributed_ml_pytorch_tpu.training.trainer import (
        create_train_state,
        make_train_step,
    )
    from distributed_ml_pytorch_tpu.utils.flops import compiled_flops

    model = get_resnet("resnet50", num_classes=1000, dtype=jnp.bfloat16)
    state, tx = create_train_state(model, jax.random.key(0), lr=0.05,
                                   sample_shape=(1, 224, 224, 3))
    step = make_train_step(model, tx)
    rng = jax.random.key(1)
    # distinct host batches, pre-cast to bf16 on the host (what a real
    # loader would ship: half the bytes of f32 over the link)
    host = [np.random.default_rng(s).normal(
                size=(batch, 224, 224, 3)).astype(jnp.bfloat16)
            for s in range(4)]
    labels = jax.device_put(np.arange(batch, dtype=np.int32) % 1000)

    flops = compiled_flops(step, state, jax.ShapeDtypeStruct(
        (batch, 224, 224, 3), jnp.bfloat16), labels, rng)

    def run(n):
        nonlocal state
        t0 = time.perf_counter()
        loss = None
        for i in range(n):
            bx = jax.device_put(host[i % len(host)])
            state, loss = step(state, bx, labels, rng)
        float(loss)
        return time.perf_counter() - t0

    try:
        run(2)  # compile + warm
    except Exception as e:
        log(f"host-fed resnet50 leg failed: {e}")
        return None
    short = min(run(1) for _ in range(trials))
    long_ = min(run(steps) for _ in range(trials))
    per_step = (long_ - short) / (steps - 1)
    rate = Rate.make(batch / per_step, flops, per_step)
    log(f"host-fed resnet50: {per_step * 1e3:.1f} ms/step incl. host→device "
        f"batch transfer → {rate:.0f} img/s ({rate.mfu_note()})")
    if rate < 1.0:  # host link so slow the leg measures nothing but it
        log("host-fed leg suppressed (< 1 img/s — link-bound, not a "
            "framework measurement)")
        return None
    return rate


def ps_phase() -> None:
    # config 3 — 1 server + 4 workers, real processes, TCP transport
    from distributed_ml_pytorch_tpu.launch import launch_world

    n_workers = 4
    per_worker = 512  # this box exposes 1 core; 5 processes contend for it
    t0 = time.perf_counter()
    code = launch_world(
        n_workers + 1,
        ["--epochs", "1", "--synthetic-data",
         "--synthetic-train-size", str(per_worker),
         "--synthetic-test-size", "64",
         "--log-interval", "100000"],  # no mid-epoch eval in the timed window
    )
    dt = time.perf_counter() - t0
    if code != 0:
        log(f"config 3 FAILED with exit code {code}")
        return
    agg = n_workers * per_worker / dt
    emit(3, "async_ps_4worker_aggregate_throughput", agg, "images/sec",
         "5 cpu processes",
         f"{n_workers} workers x {per_worker} images in {dt:.1f}s wall, "
         "startup+compile included (the reference's launch pattern)")


_SHARD_RTT_SERVER_SRC = """
import sys
import numpy as np
from distributed_ml_pytorch_tpu.parallel.sharded_ps import make_shard_server
from distributed_ml_pytorch_tpu.utils.messaging import make_transport

shard, k, n, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3]), int(sys.argv[4])
t = make_transport(0, 2, port=port, kind="python", connect_timeout=120)
try:
    server = make_shard_server(params=np.zeros(n, np.float32), shard=shard,
                               n_shards=k, transport=t, n_workers=1)
    server.run()
finally:
    t.close()
"""


def bench_sharded_push_rtt(k: int, flat: "np.ndarray", rounds: int = 20,
                           warmup: int = 3):
    """Mean end-to-end push+pull round trip against ``k`` real TCP shard
    server processes (VERDICT r3 #7): one timed round = send every shard its
    slice of the full lr-pre-scaled gradient, request every slice back, and
    block until all ``k`` replies arrive. Returns seconds/roundtrip or None
    if a server process fails."""
    import subprocess
    import sys as _sys

    from distributed_ml_pytorch_tpu.launch import _free_port, cpu_platform_env
    from distributed_ml_pytorch_tpu.parallel.async_ps import Listener
    from distributed_ml_pytorch_tpu.parallel.sharded_ps import shard_ranges
    from distributed_ml_pytorch_tpu.utils.messaging import (
        MessageCode,
        make_transport,
        send_message,
    )

    n = flat.shape[0]
    ranges = shard_ranges(n, k)
    ports = [_free_port() for _ in range(k)]
    env = cpu_platform_env()
    env["PYTHONPATH"] = os.getcwd() + os.pathsep + env.get("PYTHONPATH", "")
    procs = [
        subprocess.Popen(
            [_sys.executable, "-c", _SHARD_RTT_SERVER_SRC,
             str(s), str(k), str(n), str(ports[s])],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for s in range(k)
    ]
    transports, listeners = [], []
    grad = np.full(n, -1e-3, np.float32)
    times = []
    try:
        transports = [
            make_transport(1, 2, port=p, kind="python", connect_timeout=120)
            for p in ports
        ]
        listeners = [Listener(transport=t) for t in transports]
        for listener in listeners:
            listener.start()
        for s, (lo, hi) in enumerate(ranges):  # install central params
            send_message(MessageCode.ParameterUpdate, flat[lo:hi],
                         transport=transports[s])
        for r in range(warmup + rounds):
            t0 = time.perf_counter()
            for s, (lo, hi) in enumerate(ranges):
                send_message(MessageCode.GradientUpdate, grad[lo:hi],
                             transport=transports[s])
            for s in range(k):
                send_message(MessageCode.ParameterRequest,
                             np.zeros(0, np.float32), transport=transports[s])
            deadline = time.perf_counter() + 120.0
            for s, listener in enumerate(listeners):
                while listener.take_latest() is None:
                    if time.perf_counter() > deadline:
                        raise TimeoutError(f"shard {s} reply never arrived")
                    time.sleep(0.0005)
            if r >= warmup:
                times.append(time.perf_counter() - t0)
        for s in range(k):
            send_message(MessageCode.WorkerDone, np.zeros(0, np.float32),
                         transport=transports[s])
    except (TimeoutError, OSError, ConnectionError) as e:
        log(f"sharded push-rtt k={k} FAILED: {e}")
        for p in procs:
            p.kill()
        return None
    finally:
        for listener in listeners:
            listener.stop()
        for t in transports:
            t.close()
    for p in procs:
        try:
            p.communicate(timeout=60)
        except subprocess.TimeoutExpired:
            p.kill()
    rtt = float(np.mean(times))
    log(f"sharded-PS e2e push round-trip, k={k}: {rtt * 1e3:.1f} ms mean "
        f"over {rounds} rounds ({n * 4 / 1e6:.1f} MB gradient split into "
        f"{k} slice(s); min {min(times) * 1e3:.1f} / max {max(times) * 1e3:.1f})")
    return rtt


def sharded_ps_phase() -> None:
    """Config 3, sharded-PS leg (VERDICT r2 #7): quantify the 1/k design
    claim of ``sharded_ps.py`` — per-shard server bandwidth and apply cost
    scale as 1/k — and measure the end-to-end world at k ∈ {1, 2, 4}.

    Two measurements, because this 1-core host confounds them when mixed:
    (a) real-process worlds (k shard servers + 2 workers over TCP):
        aggregate worker img/s — k+2 processes CONTEND for one core, so
        this validates the composed topology at each k rather than showing
        server-relief speedups (which need k hosts);
    (b) an in-process microbench of exactly the per-shard server work: the
        ``central += payload`` apply on an AlexNet-sized slice (N/k f32)
        — the bytes/push and apply seconds that each shard host is
        relieved of, the measurable substance of the 1/k claim.
    """
    from distributed_ml_pytorch_tpu.launch import launch_world
    from distributed_ml_pytorch_tpu.parallel.async_ps import ParameterServer
    from distributed_ml_pytorch_tpu.parallel.sharded_ps import shard_ranges

    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import get_model

    model = get_model("alexnet")
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params,
    )

    flat = np.asarray(ravel_model_params(params), np.float32)
    n = flat.shape[0]

    # (b) per-shard apply microbench
    from distributed_ml_pytorch_tpu.utils.messaging import MessageCode

    for k in (1, 2, 4):
        lo, hi = shard_ranges(n, k)[0]
        slice_vec = flat[lo:hi].copy()
        payload = np.random.default_rng(0).normal(size=hi - lo).astype(np.float32)
        server = ParameterServer(params=slice_vec)
        reps = 50
        t0 = time.perf_counter()
        for _ in range(reps):
            server.handle(1, MessageCode.GradientUpdate, payload)
        per_apply = (time.perf_counter() - t0) / reps
        emit(3, f"sharded_ps_per_shard_apply_k{k}", per_apply * 1e6,
             "microseconds/push", "1 cpu core",
             f"server-side `central += payload` on the {hi - lo:,}-element "
             f"slice ({(hi - lo) * 4 / 1e6:.1f} MB/push wire payload) — "
             f"the per-shard-host cost the 1/k design divides")

    # (c) END-TO-END push round-trip latency, k=1 vs k=2, same worker
    # (VERDICT r3 #7): one real worker process measures
    # push(GradientUpdate slices to all k shards) + pull(ParameterRequest)
    # + wait(all k replies) as one timed round trip over real TCP server
    # processes. This is the system-level form of the 1/k claim: each
    # shard serializes/applies/replies half the bytes at k=2. CAVEAT: all
    # k+1 processes share ONE core here, so server-side apply overlap
    # (the actual multi-host win) cannot show; what CAN show is the wire
    # + apply pipeline on half-size payloads per shard.
    for k in (1, 2):
        rtt = bench_sharded_push_rtt(k, flat)
        if rtt is not None:
            emit(3, f"sharded_ps_e2e_push_rtt_k{k}", rtt * 1e3,
                 "milliseconds/roundtrip", f"{k + 1} cpu processes, TCP",
                 f"mean steady-state push+pull round trip of the full "
                 f"{n * 4 / 1e6:.1f} MB gradient against {k} real shard "
                 f"server process(es); one shared core — see (b) for the "
                 "uncontended per-shard substance")

    # (a) real-process worlds
    per_worker = 384
    batch = 16
    for k in (1, 2, 4):
        t0 = time.perf_counter()
        code = launch_world(
            k + 2,
            ["--epochs", "1", "--synthetic-data",
             "--synthetic-train-size", str(per_worker),
             "--synthetic-test-size", "64",
             "--batch-size", str(batch),
             "--log-interval", "100000"],
            n_servers=k,
        )
        dt = time.perf_counter() - t0
        if code != 0:
            log(f"sharded_ps k={k} FAILED with exit code {code}")
            continue
        agg = 2 * per_worker / dt
        emit(3, f"sharded_ps_k{k}_aggregate_throughput", agg, "images/sec",
             f"{k + 2} cpu processes",
             f"2 workers x {per_worker} images against {k} shard server(s) "
             f"in {dt:.1f}s wall (startup+compile included); all processes "
             "share ONE core, so cross-k deltas here are contention, not "
             "server relief — see sharded_ps_per_shard_apply_k* for the "
             "1/k substance")


def elastic_phase() -> None:
    """Config 3, elastic-control-plane leg (ISSUE 3): steady-state worker
    throughput BEFORE / DURING / AFTER a coordinator-driven shard
    rebalance. One in-process fleet (coordinator + 2 elastic shard servers
    + 2 DownPour workers on LeNet); shard server 2 is silently crashed
    mid-run, the coordinator detects it by lease expiry and pushes a new
    map, workers drain + cut over + install the moved range. Windows are
    split on worker 1's step timeline: [warmup, crash), [crash, cutover),
    [cutover, end) — the DURING window prices what a rebalance costs the
    data plane (stale-map drops + the cutover drain), and AFTER shows
    throughput recovered with the fleet one server smaller."""
    import time as _time

    from distributed_ml_pytorch_tpu.coord.demo import elastic_scenario

    batch = 16
    crash_at = 24
    times: dict = {}
    cut: dict = {}

    def hook(j, step, opt):
        if j == 1:
            times[step] = _time.perf_counter()
            if opt.map_version >= 3 and "step" not in cut:
                cut["step"] = step  # first step on the post-crash map

    out = elastic_scenario(
        steps=72, n_workers=2, n_shards=2, crash_shard_at=crash_at,
        lease=0.4, step_hook=hook)
    if not out["ok"] or "step" not in cut:
        log(f"elastic_phase incomplete: ok={out['ok']} cutover={cut} "
            f"events={out['events'][-5:]}")
        return

    def rate(a, b):
        ts = [times[s] for s in range(a, b) if s in times]
        if len(ts) < 3:
            return None
        return batch * (len(ts) - 1) / (ts[-1] - ts[0])

    before = rate(4, crash_at)  # skip warmup/compile steps
    during = rate(crash_at, cut["step"] + 1)
    after = rate(cut["step"] + 1, 72)
    for name, value, win in (
        ("before", before, f"steps 4-{crash_at}"),
        ("during", during, f"steps {crash_at}-{cut['step']} (crash -> "
                           "lease expiry -> map adopted)"),
        ("after", after, f"steps {cut['step'] + 1}-72, 1 shard left"),
    ):
        if value is None:
            log(f"elastic_phase: window {name} too short to rate")
            continue
        emit(3, f"elastic_rebalance_throughput_{name}", value,
             "images/sec/worker", "in-process fleet, 1 core",
             f"worker-1 steady state {win}; coordinator lease 0.4s; "
             "LeNet batch 16, cadence 2/2 (coord/demo.elastic_scenario)")
    log(f"elastic_phase: map v{out['map_version']}, cutover at worker step "
        f"{cut['step']}, server stats {out['stats']}")


def recovery_phase() -> None:
    """Config 3, durability-plane leg (ISSUE 5): the full disaster-recovery
    drill — coordinator-aligned snapshot barrier, ALL shard servers killed
    silently mid-epoch, fleet restored from FleetManifest + per-shard WALs —
    priced as MTTR (kill → every restored shard serving pulls again), pure
    restore time (manifest load + checkpoint restore + WAL replay), and the
    replayed-update count, with the acked-vs-applied sequence accounting
    reported as the loss-freedom check."""
    import tempfile

    from distributed_ml_pytorch_tpu.coord.drill import (
        default_drill_plan,
        recovery_drill,
    )

    out = recovery_drill(
        base_dir=tempfile.mkdtemp(prefix="bench_drill_"), seed=0,
        plan=default_drill_plan(0))
    if not out["ok"] or out["mttr_s"] is None:
        log(f"recovery_phase incomplete: ok={out['ok']} "
            f"errors={out['errors']} events={out['events'][-5:]}")
        return
    acked = sum(sum(d.values()) for d in out["acked"].values())
    applied = sum(sum(d.values()) for d in out["applied"].values())
    emit(3, "recovery_mttr", out["mttr_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "kill ALL shards mid-epoch -> manifest+WAL restore -> every shard "
         f"serving pulls again; {out['replayed_updates']} WAL update(s) "
         f"replayed; acked={acked} <= applied={applied} (zero acked loss); "
         "2 workers + 2 shards, LeNet, coord/drill.recovery_drill")
    emit(3, "recovery_restore", out["restore_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "manifest load + checkpoint restore + WAL replay + dedup reseed "
         "for both shards (the MTTR component the durability plane owns)")
    log(f"recovery_phase: mttr {out['mttr_s'] * 1e3:.0f} ms, restore "
        f"{out['restore_s'] * 1e3:.0f} ms, replayed "
        f"{out['replayed_updates']}, chaos {out['chaos_counts']}")


def coordfail_phase() -> None:
    """Config 3, control-plane durability leg (ISSUE 17): the
    kill-the-COORDINATOR drill — snapshot barrier broadcast, arbiter
    crashed before the dones land, restarted from its own checkpoint+WAL
    — priced as control-plane MTTR (kill → every member re-attached to
    the successor epoch, grace window closed by traffic), the durable
    restore time (epoch bump + ckpt load + WAL replay), and the
    steps/tokens the fleet lost to the outage (zero is the claim:
    workers train fail-open on the last shard map throughout)."""
    import tempfile

    from distributed_ml_pytorch_tpu.coord.drill import coordfail_drill

    steps, n_workers, batch = 20, 2, 16
    out = coordfail_drill(
        base_dir=tempfile.mkdtemp(prefix="bench_coordfail_"), seed=0,
        steps=steps, kill_during="snapshot")
    if not out["ok"] or out["mttr_s"] is None:
        log(f"coordfail_phase incomplete: ok={out['ok']} "
            f"errors={out['errors']} violations={out['violations']} "
            f"events={out['events2'][-5:]}")
        return
    steps_done = sum(len(l) for l in out["losses"].values())
    steps_lost = steps * n_workers - steps_done
    tokens_lost = steps_lost * batch
    emit(3, "coordfail_mttr", out["mttr_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "kill the coordinator mid-snapshot-barrier -> restart from its "
         f"ckpt+WAL (epoch {out['epochs'][0]} -> {out['epochs'][1]}) -> "
         f"every member re-attached; {out['restored_members']} member(s) "
         f"restored, {len(out['evictions'])} evicted during the grace "
         f"window; {steps_lost} of {steps * n_workers} worker steps "
         f"({tokens_lost} samples) lost to the outage (fail-open); "
         "2 workers + 2 shards, LeNet, coord/drill.coordfail_drill")
    emit(3, "coordfail_restore", out["restore_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "persisted-epoch bump + checkpoint load + control-plane WAL "
         "replay (member table, map/snapshot clocks, park table, "
         "scheduler ledger) — the MTTR component the durable "
         "coordinator owns")
    log(f"coordfail_phase: mttr {out['mttr_s'] * 1e3:.0f} ms, restore "
        f"{out['restore_s'] * 1e3:.0f} ms, outage "
        f"{out['outage_s'] * 1e3:.0f} ms, steps lost {steps_lost}, "
        f"chaos {out['chaos_counts']}")


def gray_phase() -> None:
    """Config 3, gray-failure leg (ISSUE 20): the SAME windowed one-way
    partition (workers' pull requests toward shard 0 vanish, its renewals
    keep flowing) run twice — containment ON (GrayHealth detects on the
    workers' renew-tail link evidence, parks the victim, resumes it
    bit-identically) vs OFF (nobody acts; the episode drains only through
    retransmit back-off). Priced as goodput over the identical fixed
    script, detection latency (gray onset -> PROBATION), and containment
    MTTR (PROBATION -> parked). Detection latency is gated against
    ``gray_detection_latency_ceiling_s`` in bench_floors.json — a slower
    detector widens the window in which a gray node poisons the fleet."""
    import tempfile

    from distributed_ml_pytorch_tpu.coord.drill import gray_drill

    steps, n_workers = 170, 2
    on = gray_drill(
        base_dir=tempfile.mkdtemp(prefix="bench_gray_on_"), seed=0,
        steps=steps, n_workers=n_workers)
    if (not on["ok"] or on["detect_latency_s"] is None
            or on["containment_mttr_s"] is None
            or on["fixed_wall_s"] is None):
        log(f"gray_phase incomplete (containment leg): ok={on['ok']} "
            f"errors={on['errors']} violations={on['violations']}")
        return
    off = gray_drill(
        base_dir=tempfile.mkdtemp(prefix="bench_gray_off_"), seed=0,
        steps=steps, n_workers=n_workers, contain=False)
    if not off["ok"] or off["fixed_wall_s"] is None:
        log(f"gray_phase incomplete (unmanaged leg): ok={off['ok']} "
            f"errors={off['errors']} violations={off['violations']}")
        return
    fixed = steps * n_workers
    goodput_on = fixed / on["fixed_wall_s"]
    goodput_off = fixed / off["fixed_wall_s"]
    # raw steps/s barely moves either way — the workers fail OPEN to
    # purely-local SGD on a downed slice and keep stepping. What
    # containment protects is CENTRAL aggregation on the gray slice:
    # worker deltas the victim shard actually applied, per second.
    central_on = sum(on["applied"][0].values()) / on["wall_s"]
    central_off = sum(off["applied"][0].values()) / off["wall_s"]
    emit(3, "gray_victim_slice_goodput_contained", central_on,
         "applied updates/s", "in-process fleet, 1 core",
         "central aggregation rate on the GRAY slice with the ladder "
         "live — the PRICE of containment: the park window trades some "
         "episode throughput for a BOUNDED recovery (victim on "
         f"PROBATION in {on['detect_latency_s'] * 1e3:.0f} ms, parked, "
         f"resumed bit_identical={on['bit_identical']}, ladder cleared, "
         f"evictions={on['gray']['evictions']}) vs {central_off:.1f} "
         "applied/s unmanaged, where the grind is open-ended and the "
         "slice's pull freshness is gone for the whole episode; raw "
         f"worker steps/s {goodput_on:.1f} vs {goodput_off:.1f} over "
         f"the identical {fixed}-step fixed script (fail-open local SGD "
         "keeps raw stepping alive either way) — coord/drill.gray_drill")
    emit(3, "gray_victim_slice_goodput_unmanaged", central_off,
         "applied updates/s", "in-process fleet, 1 core",
         "the comparison leg: identical gray episode, suspicion pinned "
         "off — the victim slice grinds on retransmit back-off + open "
         "circuits for the whole episode while its deltas drift "
         "local-only")
    emit(3, "gray_detect_latency", on["detect_latency_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "gray onset (first chaos-matched pull) -> victim on PROBATION, "
         "confirmed over 2 suspicious ticks of renew-tail link evidence "
         "from both workers")
    emit(3, "gray_containment_mttr", on["containment_mttr_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "PROBATION -> checkpoint-parked via the gray-granted preempt "
         "path (snapshot barrier + WAL'd park ticket); the victim never "
         "lease-expires and is never revoked")
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_floors.json")) as fh:
        ceiling = json.load(fh)["gray_detection_latency_ceiling_s"]
    log(f"gray_phase: victim-slice {central_on:.1f} vs {central_off:.1f} "
        f"applied/s, raw {goodput_on:.1f} vs {goodput_off:.1f} steps/s "
        f"(contained vs unmanaged), detect "
        f"{on['detect_latency_s'] * 1e3:.0f} ms (ceiling {ceiling}s), "
        f"mttr {on['containment_mttr_s'] * 1e3:.0f} ms, chaos "
        f"{on['chaos_counts']}")
    if on["detect_latency_s"] > ceiling:
        raise RuntimeError(
            f"gray detection latency {on['detect_latency_s']:.2f}s "
            f"exceeds the {ceiling}s ceiling in bench_floors.json — the "
            "suspicion plane got slow enough to let a gray node poison "
            "the fleet for whole episodes")


def _serving_slot_rate() -> tuple:
    """Tokens/s one engine slot serves (a real ``ServingEngine`` burst,
    compile outside the timed window) plus its p50 TTFT — the measured
    rate ``sched_phase`` prices borrowed-slot serving goodput with."""
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import TransformerLM
    from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

    lm = TransformerLM(vocab_size=128, d_model=64, n_heads=2, n_layers=2,
                       d_ff=128, max_len=256)
    params = lm.init(jax.random.key(0),
                     jnp.zeros((1, 8), jnp.int32))["params"]
    engine = ServingEngine(lm, params, slots=4, cache_size=128)
    w = engine.submit(np.zeros(16, np.int32), 10)
    engine.run_until_idle()
    assert w.done
    engine.reset_metrics()
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    handles = [
        engine.submit(rng.integers(0, 128, size=16).astype(np.int32), 24)
        for _ in range(8)]
    engine.run_until_idle()
    burst = time.perf_counter() - t0
    tokens = sum(len(h.tokens) for h in handles)
    ttft = engine.slo_summary().get("ttft_ms") or {}
    return tokens / max(burst, 1e-9), ttft.get("p50")


def sched_phase() -> None:
    """Config 3, scheduler-plane leg (ISSUE 16): the multi-tenant
    day-in-the-life under seeded wire chaos. The ``FleetScheduler``
    preempts a LIVE training shard at the serving peak (snapshot barrier
    -> park under the FleetManifest), lends its slot to the serving
    tenant, and resumes it bit-for-bit off-peak (checkpoint +
    exactly-once WAL replay, rejoining as a newer incarnation). Priced as
    preempt/resume MTTR plus AGGREGATE GOODPUT — training steps in the
    loss corridor + serving tokens in SLO — for the shared-scheduler
    fleet vs two statically partitioned half-fleets over the same
    measured day."""
    import tempfile

    from distributed_ml_pytorch_tpu.coord.drill import (
        default_drill_plan,
        sched_drill,
    )

    out = sched_drill(base_dir=tempfile.mkdtemp(prefix="bench_sched_"),
                      seed=0, plan=default_drill_plan(0))
    s = out["sched"]
    if not out["ok"] or not s["preempt_mttr_s"] or not s["resume_mttr_s"]:
        log(f"sched_phase incomplete: ok={out['ok']} "
            f"violations={out['violations']} errors={out['errors']}")
        return
    preempt_mttr = s["preempt_mttr_s"][0]
    resume_mttr = s["resume_mttr_s"][0]
    emit(3, "sched_preempt_mttr", preempt_mttr * 1e3, "ms",
         "in-process fleet, 1 core",
         "serving demand spike -> snapshot barrier -> PreemptRequest -> "
         "live training shard parks under the FleetManifest and its slot "
         "is granted to the serving tenant; 2 workers + 2 shards under "
         "seeded wire chaos (coord/sched.FleetScheduler via "
         "coord/drill.sched_drill)")
    emit(3, "sched_resume_mttr", resume_mttr * 1e3, "ms",
         "in-process fleet, 1 core",
         "off-peak revoke -> ResumeRequest -> fresh server restores the "
         f"manifest checkpoint + replays {out['replayed_updates']} WAL "
         f"record(s) exactly once (bit-identical: {out['bit_identical']}) "
         "and rejoins as a newer incarnation of the same rank")

    # ---- aggregate goodput: shared scheduler vs static half-fleets ----
    wall = out["wall_s"]
    peak = out["peak_window_s"] or 0.0
    in_corridor = all(np.mean(l[-4:]) < np.mean(l[:4])
                      for l in out["losses"].values())
    train_steps = sum(len(l) for l in out["losses"].values())
    serve_rate, ttft_p50 = _serving_slot_rate()
    # shared day: both slots train off-peak; one is lent for the peak
    # window, and the transitions cost the measured MTTRs
    shared_train_slot_s = 2 * wall - peak
    shared_serve_s = max(0.0, peak - preempt_mttr)
    # static halves: one slot trains all day, one serves all day — but
    # serving only has live demand during the peak window, so the
    # dedicated slot's off-peak seconds produce no goodput
    static_train_slot_s = wall
    static_serve_s = peak
    shared_tokens = serve_rate * shared_serve_s
    static_tokens = serve_rate * static_serve_s
    # static training steps: linear-in-slot-seconds extrapolation from
    # the measured shared day (stated as such in the record)
    static_train_steps = (
        train_steps * static_train_slot_s / max(shared_train_slot_s, 1e-9))
    shared_useful = shared_train_slot_s + shared_serve_s - resume_mttr
    static_useful = static_train_slot_s + static_serve_s
    emit(3, "sched_goodput_uplift", shared_useful / static_useful, "x",
         "derived",
         "demand-weighted useful slot-seconds, shared FleetScheduler vs "
         "two statically partitioned half-fleets over the SAME measured "
         "day: static dedicates one slot to serving that only has live "
         "demand during the peak window, shared lends the training slot "
         "at peak (preempt) and takes it back off-peak (resume), paying "
         "only the measured MTTRs; serving tokens priced at a real "
         "ServingEngine's measured burst rate",
         extra={
             "day_s": round(wall, 2),
             "peak_window_s": round(peak, 2),
             "shared": {
                 "train_steps": train_steps,
                 "train_in_loss_corridor": bool(in_corridor),
                 "serve_tokens_in_slo": int(shared_tokens),
                 "useful_slot_s": round(shared_useful, 2),
             },
             "static": {
                 "train_steps_extrapolated": int(static_train_steps),
                 "serve_tokens_in_slo": int(static_tokens),
                 "useful_slot_s": round(static_useful, 2),
             },
             "serve_tokens_per_s": round(serve_rate, 1),
             "serve_ttft_p50_ms": ttft_p50,
         })
    log(f"sched_phase: preempt {preempt_mttr * 1e3:.0f} ms, resume "
        f"{resume_mttr * 1e3:.0f} ms, day {wall:.1f}s (peak {peak:.1f}s), "
        f"goodput uplift {shared_useful / static_useful:.2f}x, replayed "
        f"{out['replayed_updates']}, chaos {out['chaos_counts']}")


def mpmd_phase() -> None:
    """Config 3, MPMD-pipeline-plane leg (ISSUE 10): a 4-stage pipeline of
    fleet members over the reliable in-process wire. Leg 1 (steady state):
    tokens/s through the fault-free fleet plus the measured BUBBLE
    fraction (1 - sum of per-stage busy seconds / (stages x wall)). Leg 2
    (stage kill): the middle stage is killed mid-schedule and restarted
    from its per-stage checkpoint — stage-restart MTTR (vacancy ->
    replacement StageReady) with throughput before/during/after, and the
    applied-microbatch accounting reported as the no-double-apply check."""
    import tempfile

    from distributed_ml_pytorch_tpu.coord.stages import mpmd_scenario

    # all shape knobs passed EXPLICITLY so the rates below can never skew
    # against a changed scenario default
    steps, n_stages, M, mb, seq = 16, 4, 4, 4, 8
    shape = dict(n_stages=n_stages, n_microbatches=M, mb=mb, seq=seq)
    warm = mpmd_scenario(base_dir=tempfile.mkdtemp(prefix="bench_mpmd_"),
                         seed=0, steps=4, **shape)
    if not warm["ok"]:
        log(f"mpmd_phase warmup incomplete: {warm['errors']}")
        return
    out = mpmd_scenario(base_dir=tempfile.mkdtemp(prefix="bench_mpmd_"),
                        seed=0, steps=steps, **shape)
    if not out["ok"] or out["wall_s"] is None:
        log(f"mpmd_phase steady leg incomplete: ok={out['ok']} "
            f"errors={out['errors']}")
        return
    tok_per_step = M * mb * seq
    steady = tok_per_step * (steps - 1) / out["wall_s"]
    bubble = max(0.0, 1.0 - out["busy_s"] / (n_stages * out["wall_s"]))
    emit(3, "mpmd_pipeline_steady", steady, "tokens/sec",
         "in-process fleet, 1 core",
         f"{n_stages}-stage MPMD pipeline (per-stage compiled programs "
         f"over ReliableTransport), M={M} microbatches of {mb}x{seq} "
         "tokens; driver step cadence, fault-free "
         "(coord/stages.mpmd_scenario)")
    # the flight-recorder decomposition of that bubble (ISSUE 12): merge
    # the run's per-member dumps and attribute each stage's wall clock to
    # its exclusive serve-loop states — schema-gated so a malformed
    # attribution can never ship in the record
    attribution = None
    try:
        from distributed_ml_pytorch_tpu.analysis import timeline

        report = timeline.analyze(out["obs_dir"])
        attribution = check_bubble_attribution(
            report["bubble_attribution"])
        log(f"mpmd_phase: flight-recorder dumps in {out['obs_dir']} "
            f"(analyze anytime: make timeline TIMELINE_DIR={out['obs_dir']})")
    except (ValueError, OSError, KeyError) as e:
        log(f"mpmd_phase: bubble attribution unavailable: {e!r}")
    emit(3, "mpmd_bubble_fraction", bubble * 100.0, "%",
         "in-process fleet, 1 core",
         "1 - sum(stage busy s) / (stages x wall s) over the steady run — "
         "idle share of stage-seconds (schedule bubble + wire wait); "
         "bubble_attribution decomposes it per flight-recorder state "
         "(analysis/timeline.py over the run's obs dumps)",
         extra=({"bubble_attribution": attribution}
                if attribution is not None else None))

    kill_at = 6
    out = mpmd_scenario(base_dir=tempfile.mkdtemp(prefix="bench_mpmd_"),
                        seed=0, steps=steps, kill_stage=1,
                        kill_at_step=kill_at, snapshot_at_step=2, **shape)
    if not out["ok"] or out["stage_mttr_s"] is None:
        log(f"mpmd_phase kill leg incomplete: ok={out['ok']} "
            f"errors={out['errors']} events={out['events'][-5:]}")
        return
    emit(3, "mpmd_stage_restart_mttr", out["stage_mttr_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         f"middle stage killed at its step {kill_at} (silent; lease "
         "expiry detection) -> checkpoint restart -> StageReady; "
         "watermark-bounded replay refilled the in-flight microbatches "
         f"(applied accounting {'OK' if out['applied_ok'] else 'BROKEN'}: "
         "no microbatch applied twice)")

    # throughput before/during/after on the driver's step-completion
    # timeline (step_times[i] = completion instant of step i)
    ts = out["step_times"]

    def rate(a, b):
        if b - a < 2 or b > len(ts):
            return None
        return tok_per_step * (b - 1 - a) / (ts[b - 1] - ts[a])

    for name, value, win in (
        ("before", rate(1, kill_at), f"steps 1-{kill_at}"),
        ("during", rate(kill_at, kill_at + 4),
         f"steps {kill_at}-{kill_at + 4} (kill -> lease expiry -> "
         "restart -> replay)"),
        ("after", rate(kill_at + 4, steps), f"steps {kill_at + 4}-{steps}"),
    ):
        if value is None:
            log(f"mpmd_phase: window {name} too short to rate")
            continue
        emit(3, f"mpmd_stage_kill_throughput_{name}", value, "tokens/sec",
             "in-process fleet, 1 core",
             f"driver step-completion rate {win}; 4-stage pipeline, "
             "middle stage killed and restarted from its checkpoint")
    log(f"mpmd_phase: kill leg driver stats {out['driver_stats']}, "
        f"events {out['events'][-3:]}")


def health_phase() -> None:
    """Config 3, numerical-health leg (ISSUE 8): the immune-system scenario
    — 2 workers + 2 WAL'd shards behind the admission gate, one worker's
    push channel under seeded SDC (gate-slipping scale corruption, then
    NaN) — priced as the quarantine reject rate, the worker-observed nack
    round-trips, and the coordinator auto-rollback MTTR (watchdog trigger
    -> every shard restored + reported), alongside the drill's recovery
    numbers."""
    import tempfile

    from distributed_ml_pytorch_tpu.coord.health import health_scenario

    out = health_scenario(
        base_dir=tempfile.mkdtemp(prefix="bench_health_"), seed=0)
    if not out["ok"] or out["rollbacks"] < 1 or out["rollback_mttr_s"] is None:
        log(f"health_phase incomplete: ok={out['ok']} "
            f"rollbacks={out['rollbacks']} errors={out['errors']} "
            f"events={out['events'][-5:]}")
        return
    applied = sum(sum(d.values()) for d in out["applied"].values())
    quarantined = out["quarantined_total"]
    seen = applied + quarantined
    reject_rate = quarantined / seen if seen else 0.0
    nacks_heard = sum(out["worker_nacks"].values())
    emit(3, "health_reject_rate", 100.0 * reject_rate, "%",
         "in-process fleet, 1 core",
         f"admission gate: {quarantined} of {seen} arriving updates "
         f"quarantined (finiteness + per-worker EWMA z-score), every one "
         f"explicitly nacked ({out['nacks_sent_total']} UpdateNacks), zero "
         "in any WAL; 2 workers + 2 shards, one poisoned push channel, "
         "coord/health.health_scenario")
    emit(3, "health_nack_roundtrips", float(nacks_heard), "nacks",
         "in-process fleet, 1 core",
         "UpdateNacks that completed the round trip (server reject -> "
         "worker heard it, resynced by pulling fresh params and held its "
         f"in-flight update); {out['revoked_workers']} worker(s) "
         "reputation-revoked by the coordinator")
    emit(3, "health_rollback_mttr", out["rollback_mttr_s"] * 1e3, "ms",
         "in-process fleet, 1 core",
         "coordinator watchdog detects fleet loss divergence -> "
         "RollbackRequest barrier -> both shards restore the last good "
         "FleetManifest (ckpt + WAL capped at its apply seq) -> all "
         "RollbackDone reports in; workers drop accumulators and pull")
    log(f"health_phase: reject rate {100 * reject_rate:.1f}%, "
        f"{nacks_heard} nack round-trips, rollback mttr "
        f"{out['rollback_mttr_s'] * 1e3:.0f} ms, "
        f"revoked {out['revoked_workers']}, chaos {out['chaos_counts']}")


def _steady_rate_from_csv(path: str, batch: int):
    """Steady-state img/s from a trainer CSV's per-iteration timestamps:
    MEAN inter-step gap over the second half of the run (warmup/compile
    excluded by construction). Mean, not median: chunk-dispatched workers
    log a burst of per-step records at each chunk boundary, so the gap
    distribution is bimodal (≈0 within a burst, chunk-time at boundaries)
    and a median would see only the zeros; the tail mean is exactly
    (t_end − t_mid)/steps either way. Returns (img_per_sec, n_steps) or
    None."""
    import pandas as pd

    if not os.path.isfile(path):
        return None
    df = pd.read_csv(path)
    if len(df) < 8:
        return None
    gaps = pd.to_datetime(df["timestamp"]).diff().dt.total_seconds().iloc[1:]
    tail = gaps.iloc[len(gaps) // 2:]
    per_step = float(tail.mean())
    if per_step <= 0:
        return None
    return batch / per_step, len(df)


def ps_tpu_phase() -> None:
    """Config 3 (TPU leg, VERDICT r1 #2): the DownPour core with the real
    chip in the loop — CPU server + rank-1 worker pinned to the TPU — against
    the same recipe in single mode on the same chip. Both rates come from
    per-iteration CSV timestamps (``_steady_rate_from_csv``), so startup and
    compile are excluded and the delta isolates push/pull overhead (device→
    host ravel at the push cadence + install between steps; the per-step
    dispatch cost is identical in both legs)."""
    import tempfile

    from distributed_ml_pytorch_tpu.launch import launch_world, tpu_platform_env

    # A chip belongs to one process: the worker rank and the single-mode
    # comparison below are CHILDREN that need it, so this phase runs before
    # this process has touched jax (main() orders it first). Both children
    # are pinned to the TPU platform and fail without one — no skip.
    batch = 64
    data_args = [
        "--batch-size", str(batch),  # rate math below derives from this
        "--epochs", "2", "--synthetic-data",
        "--synthetic-train-size", "16384", "--synthetic-test-size", "64",
        "--log-interval", "100000",
    ]
    ps_rate = single_rate = None
    with tempfile.TemporaryDirectory() as td:
        code = launch_world(2, data_args + ["--log-dir", td], tpu_worker_rank=1)
        if code != 0:
            raise RuntimeError(
                f"ps-with-tpu-worker world failed with exit code {code}")
        got = _steady_rate_from_csv(os.path.join(td, "node1.csv"), batch)
        if got:
            ps_rate, n = got
            emit(3, "async_ps_tpu_worker_throughput", ps_rate,
                 "images/sec/chip", "cpu server + 1x tpu worker",
                 f"steady-state from {n} per-step CSV timestamps; "
                 "DownPour cadence 10/10 with chunked dispatch (one "
                 "compiled scan per between-comm run, VERDICT r2 #2)")
    with tempfile.TemporaryDirectory() as td:
        subprocess.run(
            [sys.executable, "-m", "distributed_ml_pytorch_tpu.training.cli",
             "--no-distributed", "--backend", "tpu",
             "--steps-per-dispatch", "10", "--log-dir", td] + data_args,
            env=tpu_platform_env(), check=True,
        )
        got = _steady_rate_from_csv(os.path.join(td, "tpu.csv"), batch)
        if got:
            single_rate, n = got
            emit(3, "single_mode_scanned_throughput", single_rate,
                 "images/sec/chip", "1x tpu",
                 f"same recipe at --steps-per-dispatch 10 (the chunk "
                 f"size the PS cadence implies), {n} per-step records "
                 "— the PS delta is protocol cost, not dispatch")
    if ps_rate and single_rate:
        emit(3, "async_ps_push_pull_overhead", 100 * (1 - ps_rate / single_rate),
             "percent", "derived",
             "throughput cost of the PS protocol for a TPU worker vs the "
             "same-chunk-size scanned single-mode recipe (one 9.9 MB accum "
             "fetch per push cadence) — see async_ps_chunked_device_cycle")
    # only now may this process take the chip itself
    _ps_device_cycle_phase(batch)


def _ps_device_cycle_phase(batch: int) -> None:
    """The DownPour worker's device-side ceiling: one cadence cycle of
    chunked dispatches (lengths 1+9 at cadence 10/10) with NO host fetch —
    what the chunk-dispatch rework actually bought, measured without the
    per-push device→host fetch."""
    import time

    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import get_model
    from distributed_ml_pytorch_tpu.parallel.async_ps import (
        default_downpour_tx,
        init_downpour_accumulator,
        make_downpour_chunk_step,
    )

    model = get_model("alexnet")
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    _, n, pad, accum = init_downpour_accumulator(params)
    tx = default_downpour_tx(0.008)
    opt_state = tx.init(params)
    chunk_step = make_downpour_chunk_step(model, tx, pad)
    rng = jax.random.key(1)
    rnd = np.random.default_rng(0)

    def mkbatch(length):
        return (
            np.asarray(rnd.normal(size=(length, batch, 32, 32, 3)), np.float32),
            np.asarray(rnd.integers(0, 10, (length, batch))),
        )

    bxs1, bys1 = mkbatch(1)
    bxs9, bys9 = mkbatch(9)
    dx1, dy1 = jax.device_put(bxs1), jax.device_put(bys1)
    dx9, dy9 = jax.device_put(bxs9), jax.device_put(bys9)
    losses = None
    for _ in range(2):  # compile both scan lengths + warm
        params, opt_state, accum, losses = chunk_step(
            params, opt_state, accum, dx1, dy1, rng, 0)
        params, opt_state, accum, losses = chunk_step(
            params, opt_state, accum, dx9, dy9, rng, 1)
    float(losses[-1])

    def cycle_rate(x1, y1, x9, y9, reps=10):
        nonlocal params, opt_state, accum, losses
        t0 = time.perf_counter()
        for _ in range(reps):
            params, opt_state, accum, losses = chunk_step(
                params, opt_state, accum, x1, y1, rng, 0)
            params, opt_state, accum, losses = chunk_step(
                params, opt_state, accum, x9, y9, rng, 1)
        float(losses[-1])  # trailing fetch forces the chain
        return (time.perf_counter() - t0) / reps

    per_cycle = cycle_rate(dx1, dy1, dx9, dy9)
    with_xfer = cycle_rate(bxs1, bys1, bxs9, bys9)
    emit(3, "async_ps_chunked_device_cycle", 10 * batch / per_cycle,
         "images/sec/chip",
         "1x tpu, device-resident input",
         f"one 10-step DownPour cadence cycle as two compiled chunk "
         f"dispatches, forced completion ({per_cycle * 1e3:.1f} ms/cycle); "
         f"with per-cycle host batch upload it is "
         f"{10 * batch / with_xfer:.0f} img/s ({with_xfer * 1e3:.0f} ms)")


def transport_phase() -> None:
    """Config 7 (native-runtime evidence): PS control-plane round-trip rate
    of the in-tree C++ transport vs the Python one, same wire format, same
    AlexNet-gradient-sized payload, echo server in a real separate process."""
    import subprocess
    import sys as _sys

    from distributed_ml_pytorch_tpu.launch import _free_port, cpu_platform_env
    from distributed_ml_pytorch_tpu.utils.messaging import MessageCode, make_transport

    payload = np.zeros(2_472_266, np.float32)  # raveled AlexNet size
    n_iter = 30
    server_src = (
        "import sys\n"
        "from distributed_ml_pytorch_tpu.utils.messaging import make_transport\n"
        "t = make_transport(0, 2, port=int(sys.argv[1]), kind=sys.argv[2])\n"
        f"for _ in range({n_iter} + 2):\n"
        "    sender, code, payload = t.recv(timeout=120)\n"
        "    t.send(code, payload, dst=sender)\n"
        "t.close()\n"
    )
    for kind in ("native", "python"):
        port = _free_port()
        srv = subprocess.Popen(
            [_sys.executable, "-c", server_src, port, kind],
            env=cpu_platform_env(),
        )
        t = None
        try:
            t = make_transport(1, 2, port=int(port), kind=kind, connect_timeout=120)
            for _ in range(2):  # warm both directions
                t.send(MessageCode.GradientUpdate, payload)
                t.recv(timeout=120)
            t0 = time.perf_counter()
            for _ in range(n_iter):
                t.send(MessageCode.GradientUpdate, payload)
                t.recv(timeout=120)
            dt = time.perf_counter() - t0
            rate = n_iter / dt
            mbps = 2 * payload.nbytes * rate / 1e6
            emit(7, f"ps_transport_roundtrip_{kind}", rate, "roundtrips/sec",
                 "2 processes, localhost TCP",
                 f"9.9 MB gradient payload echo ({mbps:.0f} MB/s both ways); "
                 "capability-extension evidence for the in-tree C++ transport")
        except Exception as e:
            log(f"transport bench ({kind}) failed: {e}")
        finally:
            if t is not None:
                t.close()
            if srv.poll() is None:
                srv.kill()
            srv.wait()


def reliability_phase() -> None:
    """Config 7, reliability-overhead leg (ISSUE 2 satellite): the same
    Python-TCP echo as ``transport_phase`` with the reliability layer on vs
    off — what the seq+CRC envelope, the ack frames and receiver dedup cost
    on the PS wire. The ack timeout is set well above one 9.9 MB transfer
    time on this rig so the measurement is protocol overhead, not spurious
    retransmits."""
    import subprocess
    import sys as _sys

    from distributed_ml_pytorch_tpu.launch import _free_port, cpu_platform_env
    from distributed_ml_pytorch_tpu.utils.messaging import (
        MessageCode,
        ReliableTransport,
        make_transport,
    )

    payload = np.zeros(2_472_266, np.float32)  # raveled AlexNet size
    n_iter = 20
    server_src = (
        "import sys\n"
        "from distributed_ml_pytorch_tpu.utils.messaging import (\n"
        "    ReliableTransport, make_transport)\n"
        "t = make_transport(0, 2, port=int(sys.argv[1]), kind='python')\n"
        "if sys.argv[2] != 'off':\n"
        "    t = ReliableTransport(t, ack_timeout=5.0, max_backoff=10.0,\n"
        "                          batched_acks=(sys.argv[2] == 'on'),\n"
        "                          legacy_envelope=(sys.argv[2] == 'legacy'))\n"
        f"for _ in range({n_iter} + 2):\n"
        "    sender, code, payload = t.recv(timeout=120)\n"
        "    t.send(code, payload, dst=sender)\n"
        "t.close()\n"
    )
    rates: dict = {}
    # interleaved best-of-4: on this 2-core rig one round's rate swings
    # 20-50% with background load, so each mode keeps its BEST round
    # (least interference) and the derived overhead compares bests
    for _round in range(4):
        for acks in ("off", "legacy", "on"):
            port = _free_port()
            srv = subprocess.Popen(
                [_sys.executable, "-c", server_src, port, acks],
                env=cpu_platform_env(),
            )
            t = None
            try:
                t = make_transport(1, 2, port=int(port), kind="python",
                                   connect_timeout=120)
                if acks != "off":
                    t = ReliableTransport(
                        t, ack_timeout=5.0, max_backoff=10.0,
                        batched_acks=(acks == "on"),
                        legacy_envelope=(acks == "legacy"))
                for _ in range(2):  # warm both directions
                    t.send(MessageCode.GradientUpdate, payload)
                    t.recv(timeout=120)
                iters = []
                for _ in range(n_iter):
                    t0 = time.perf_counter()
                    t.send(MessageCode.GradientUpdate, payload)
                    t.recv(timeout=120)
                    iters.append(time.perf_counter() - t0)
                # median-per-roundtrip: this shared 2-core host injects
                # 20-40 ms scheduler spikes into a handful of iterations;
                # a mean (total/n) would price the SCHEDULER, not the wire
                rates[acks] = max(rates.get(acks, 0.0),
                                  1.0 / float(np.median(iters)))
            except Exception as e:
                log(f"reliability bench (acks {acks}) failed: {e}")
            finally:
                if t is not None:
                    t.close()
                if srv.poll() is None:
                    srv.kill()
                srv.wait()
    for acks, rate in rates.items():
        mbps = 2 * payload.nbytes * rate / 1e6
        desc = {
            "off": "no reliability layer",
            "legacy": "the ISSUE-2 wire faithfully reproduced: full-frame "
                      "concatenate, tobytes+crc32 checksums, one ack per "
                      "frame (legacy_envelope=True)",
            "on": "ISSUE 7 adaptive wire: zero-copy checksums, "
                  "scatter/gather envelope, batched cumulative acks",
        }[acks]
        emit(7, f"ps_transport_roundtrip_python_acks_{acks}", rate,
             "roundtrips/sec", "2 processes, localhost TCP",
             f"9.9 MB gradient payload echo ({mbps:.0f} MB/s both "
             f"ways), median roundtrip, best of 4 rounds, {desc} "
             "(utils/messaging.ReliableTransport)")
    if "on" in rates and "off" in rates and "legacy" in rates:
        overhead = 100 * (1 - rates["on"] / rates["off"])
        before = 100 * (1 - rates["legacy"] / rates["off"])
        emit(7, "ps_reliability_layer_overhead", overhead,
             "percent", "derived",
             "roundtrip-rate cost of acks+checksum+dedup on the 9.9 MB PS "
             "echo (positive = reliability slower); the exactly-once apply "
             "guarantee under drop/dup/corrupt is what it buys "
             "(tests/test_chaos.py)")
        # ISSUE 7 acceptance: >= half of the ack tax recovered. Before =
        # the ISSUE-2 envelope measured TODAY on this rig (the wire got
        # ~5x faster since the 36.5% record, which makes the same absolute
        # CPU tax a LARGER fraction — same-day legs keep the comparison
        # honest); after = the adaptive wire.
        emit(7, "ps_reliability_ack_tax_recovered",
             100 * (before - overhead) / max(1e-9, before),
             "percent of legacy overhead", "derived",
             f"before/after on this rig today: legacy envelope costs "
             f"{before:.1f}% of raw rt/s (ISSUE-2 record: 36.5% on the "
             f"then-slower wire), adaptive wire costs {overhead:.1f}% — "
             "recovered by zero-copy u64-sum bulk checksums, sendv "
             "scatter/gather framing and batched cumulative acks")


def transport_microbench_phase() -> None:
    """Config 7, wire cost ladder (ISSUE 7 satellite): every layer of the
    unified transport stack priced on the same in-process echo — raw
    mailboxes, the reliability envelope with legacy per-frame acks, the
    adaptive batched-cumulative-ack path, WAL-style deferred acks released
    at a group boundary, and the chaos wrapper's bookkeeping (empty plan).
    One JSON line per rung, so a regression in any layer's overhead is a
    diffable number, not a feeling."""
    import threading

    from distributed_ml_pytorch_tpu.utils.chaos import ChaosPlan
    from distributed_ml_pytorch_tpu.utils.messaging import (
        MessageCode,
        ReliableTransport,
        make_world,
    )

    payload = np.zeros(2_472_266, np.float32)  # raveled AlexNet size
    n_iter = 20
    group_n = 8  # WAL-deferred leg: acks released every `group_n` applies

    def echo_run(make):
        """Round-trip rate through a 2-rank world built by ``make()``."""
        world, _ = make()
        a, b = world[0], world[1]
        stop = threading.Event()

        def server():
            applied = 0
            while not stop.is_set():
                msg = a.recv(timeout=0.5)
                if msg is None:
                    continue
                applied += 1
                commit = getattr(a, "ack_delivered", None)
                if commit is not None and not a.ack_on_delivery \
                        and applied % group_n == 0:
                    commit()  # the group-fsync boundary releases acks
                a.send(msg[1], msg[2], dst=1)

        t = threading.Thread(target=server, daemon=True)
        t.start()
        # the CLIENT defers acks too on the wal rung (both ends share
        # reliable_opts): release them at the same group cadence, or the
        # server's echo sends would hit their window once n_iter outgrows
        # it and wedge the bench
        b_commit = getattr(b, "ack_delivered", None)
        if b_commit is not None and getattr(b, "ack_on_delivery", True):
            b_commit = None
        echoes = 0

        def pump_once():
            nonlocal echoes
            assert b.recv(timeout=30) is not None
            echoes += 1
            if b_commit is not None and echoes % group_n == 0:
                b_commit()

        try:
            for _ in range(2):  # warm
                b.send(MessageCode.GradientUpdate, payload)
                pump_once()
            t0 = time.perf_counter()
            for _ in range(n_iter):
                b.send(MessageCode.GradientUpdate, payload)
                pump_once()
            return n_iter / (time.perf_counter() - t0)
        finally:
            stop.set()
            t.join(timeout=5)
            for side in (a, b):
                commit = getattr(side, "ack_delivered", None)
                if commit is not None:
                    commit()  # release any tail behind the group boundary
            for tr in world.values():
                tr.close()

    ladder = [
        ("raw", "in-process mailboxes, no wrapping",
         lambda: make_world(2)),
        ("reliable_per_frame_ack", "seq+checksum envelope, one ack/frame",
         lambda: make_world(2, reliable=True, reliable_opts={
             "ack_timeout": 5.0, "max_backoff": 10.0,
             "batched_acks": False})),
        ("reliable_batched_ack", "adaptive wire: cumulative acks + credit",
         lambda: make_world(2, reliable=True, reliable_opts={
             "ack_timeout": 5.0, "max_backoff": 10.0})),
        ("wal_deferred_ack", "acks withheld to a group boundary "
         f"(n={group_n}), cumulative release",
         lambda: make_world(2, reliable=True, reliable_opts={
             "ack_timeout": 5.0, "max_backoff": 10.0,
             "ack_on_delivery": False})),
        ("chaos_wrapped", "reliable+batched under FaultyTransport with an "
         "empty plan (pure wrapper cost)",
         lambda: make_world(2, reliable=True, plan=ChaosPlan(),
                            reliable_opts={"ack_timeout": 5.0,
                                           "max_backoff": 10.0})),
    ]
    base = None
    for name, desc, make in ladder:
        try:
            rate = echo_run(make)
        except Exception as e:  # noqa: BLE001 — one rung must not kill the rest
            log(f"transport microbench ({name}) failed: {e}")
            continue
        if base is None:
            base = rate
        emit(7, f"wire_ladder_{name}", rate, "roundtrips/sec",
             "1 process, in-process transport",
             f"9.9 MB echo; {desc}; "
             f"{100 * (1 - rate / base):.1f}% below the raw rung")


def compute_microbench_phase() -> None:
    """Per-fusion cost ladder for the conv epilogues (ISSUE 9): the fused
    Pallas ``relu_pool2`` / ``bias_relu`` kernels vs the unfused XLA chain,
    standalone, on the AlexNet conv-output shapes at the large-batch leg's
    scale — the compute-plane analog of ``transport_microbench_phase``.

    Off-TPU the fused entry points lower to the same XLA chain (recorded
    as ``xla-fallback``), so the phase still runs everywhere and prices
    the chain; the fused-vs-unfused comparison is only meaningful on the
    TPU rows. Timing is device-true on TPU (``utils/devtime``), wallclock
    elsewhere; repeat dispatches reuse one input.
    """
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.ops import fused_conv as fc
    from distributed_ml_pytorch_tpu.utils.devtime import device_time

    platform = jax.devices()[0].platform
    hw = f"1x {platform}"
    path = "pallas" if platform == "tpu" else "xla-fallback"
    on_tpu = platform == "tpu"
    calls = 10 if on_tpu else 3
    b = 256
    rng = np.random.default_rng(0)
    shapes = {  # AlexNet conv outputs feeding a relu->pool tail
        "conv1_tail": (b, 8, 8, 64),
        "conv2_tail": (b, 4, 4, 192),
        "conv5_tail": (b, 2, 2, 256),
    }

    def us(t):
        return t.per_call_s * 1e6

    for name, shape in shapes.items():
        x = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        ct = jnp.asarray(rng.normal(
            size=(shape[0], shape[1] // 2, shape[2] // 2, shape[3])
        ).astype(np.float32))
        variants = {
            "unfused": lambda v: fc.max_pool_2x2(jax.nn.relu(v)),
            "fused": fc.relu_pool2,
        }
        costs = {}
        for tag, fn in variants.items():
            fwd = jax.jit(fn)
            fwdbwd = jax.jit(lambda v, g, f=fn: jax.vjp(f, v)[1](g)[0])
            t_f = device_time(fwd, x, calls=calls, warmup=1)
            t_fb = device_time(fwdbwd, x, ct, calls=calls, warmup=1)
            costs[tag] = (us(t_f), us(t_fb))
            emit(1, f"conv_epilogue_{name}_{tag}_fwdbwd", us(t_fb),
                 "us/call", hw,
                 f"{name} {shape} relu->2x2pool {tag} "
                 f"({'pallas kernel' if tag == 'fused' and on_tpu else 'xla'}): "
                 f"fwd {us(t_f):.1f} us, fwd+bwd {us(t_fb):.1f} us "
                 f"({t_f.source}); fused path on this backend = {path}")
        log(f"  {name}: unfused fwd+bwd {costs['unfused'][1]:.1f} us vs "
            f"fused {costs['fused'][1]:.1f} us")

    # the elementwise bias+relu epilogue (conv3/conv4-shaped tail)
    x = jnp.asarray(rng.normal(size=(b * 4 * 4, 384)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(384,)).astype(np.float32))
    ct = jnp.asarray(rng.normal(size=x.shape).astype(np.float32))
    for tag, fn in {
        "unfused": lambda v, bb: jax.nn.relu(v + bb),
        "fused": fc.bias_relu,
    }.items():
        fwdbwd = jax.jit(
            lambda v, bb, g, f=fn: jax.vjp(f, v, bb)[1](g)[0])
        t_fb = device_time(fwdbwd, x, bias, ct, calls=calls, warmup=1)
        emit(1, f"conv_epilogue_bias_relu_{tag}_fwdbwd", us(t_fb), "us/call",
             hw, f"bias+relu on (4096, 384) {tag}: fwd+bwd {us(t_fb):.1f} us "
             f"({t_fb.source}); fused path on this backend = {path}")


def cpu_mesh_phase() -> None:
    """Virtual-device measurements, taken in a child on eight CPU devices:
    a process's device count is fixed when its backend starts, and by this
    phase the parent has started one (on a chip machine, the TPU's)."""
    from distributed_ml_pytorch_tpu.launch import cpu_platform_env

    proc = subprocess.run(
        [sys.executable, "-c", "import bench_all; bench_all.cpu_mesh_child()"],
        cwd=os.path.dirname(os.path.abspath(__file__)),
        env=cpu_platform_env(n_devices=8), stdout=subprocess.PIPE, text=True,
        check=True)
    for line in proc.stdout.splitlines():
        if line.startswith("{"):  # the child's emit() records
            RESULTS.append(json.loads(line))
        print(line, flush=True)


def cpu_mesh_child() -> None:
    """Body of :func:`cpu_mesh_phase`; the process's first use of jax."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_ml_pytorch_tpu.models import AlexNet, get_resnet
    from distributed_ml_pytorch_tpu.parallel.sync import (
        make_sync_train_step,
        replicate,
        shard_batch,
    )
    from distributed_ml_pytorch_tpu.runtime.mesh import force_cpu_devices, make_mesh
    from distributed_ml_pytorch_tpu.training.trainer import create_train_state
    from distributed_ml_pytorch_tpu.runtime import startup
    from distributed_ml_pytorch_tpu.utils.serialization import ravel_model_params

    force_cpu_devices(8)
    startup.enable_compile_cache()

    # config 2 — 2-device allreduce of the raveled AlexNet gradient vector
    mesh2 = make_mesh({"data": 2}, devices=jax.devices()[:2])
    model = AlexNet()
    params = model.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    flat = np.asarray(ravel_model_params(params))
    n_elems = flat.size
    per_device = np.stack([flat, -0.5 * flat])  # distinct values: real comms

    allreduce = jax.jit(
        jax.shard_map(
            lambda g: jax.lax.psum(g[0], "data"),
            mesh=mesh2, in_specs=P("data"), out_specs=P(),
        )
    )
    g = jax.device_put(per_device)
    jax.block_until_ready(allreduce(g))  # compile
    iters = 50
    t0 = time.perf_counter()
    for _ in range(iters):
        out = allreduce(g)
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    emit(2, "allreduce_2way_gradient_exchange_rate", iters / dt,
         "exchanges/sec", "2 virtual cpu devices",
         f"psum of the {n_elems}-elem raveled AlexNet gradient "
         f"({n_elems * 4 / 1e6:.1f} MB) — functional collective measurement; "
         "no second chip for an ICI number")

    # config 4 (8-way leg) — the actual sharded ResNet-18 sync-DP step
    mesh8 = make_mesh({"data": 8})
    r18 = get_resnet("resnet18")
    state, tx = create_train_state(r18, jax.random.key(0), lr=0.05)
    state = replicate(mesh8, state)
    step = make_sync_train_step(r18, tx, mesh8)
    rng = replicate(mesh8, jax.random.key(1))
    images, labels = make_batch(64)
    bx, by = shard_batch(mesh8, images, labels)
    state, loss = step(state, bx, by, rng)
    jax.block_until_ready(state.params)  # compile + first step
    iters = 5
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = step(state, bx, by, rng)
    jax.block_until_ready(state.params)
    dt = time.perf_counter() - t0
    emit(4, "resnet18_8way_dp_step_throughput", iters * 64 / dt, "images/sec",
         "8 virtual cpu devices",
         f"global batch 64 over 8-way psum DP, loss={float(loss):.3f} — "
         "functional validation of the sharded step, not TPU perf")


def multiprocess_psum_phase(n: int = 4, rounds: int = 20) -> None:
    """Config 2 at REAL-process scale (VERDICT r4 #7): n localhost processes
    psum the raveled AlexNet gradient vector over gloo — the cross-process
    analog of the in-process `allreduce_2way_gradient_exchange_rate` row.
    Subprocess-isolated so the phase runs under any parent backend."""
    import subprocess
    import sys as _sys
    import textwrap

    from distributed_ml_pytorch_tpu.launch import _free_port, cpu_platform_env

    worker = textwrap.dedent('''
        import sys, time
        proc, n, port, rounds = (int(sys.argv[1]), int(sys.argv[2]),
                                 sys.argv[3], int(sys.argv[4]))
        import jax
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        from distributed_ml_pytorch_tpu.runtime.mesh import (
            initialize_distributed)
        initialize_distributed(f"localhost:{port}", num_processes=n,
                               process_id=proc)
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from distributed_ml_pytorch_tpu.models import AlexNet
        from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh
        from distributed_ml_pytorch_tpu.utils.serialization import (
            ravel_model_params)

        model = AlexNet()
        params = model.init(jax.random.key(0),
                            jnp.zeros((1, 32, 32, 3)))["params"]
        flat = np.asarray(ravel_model_params(params))
        mesh = make_mesh({"data": n})
        # each process contributes a DISTINCT vector: real traffic, and the
        # psum result checks the collective actually reduced across ranks
        per = jax.make_array_from_process_local_data(
            NamedSharding(mesh, P("data")),
            ((proc + 1) * flat)[None, :])
        allreduce = jax.jit(jax.shard_map(
            lambda g: jax.lax.psum(g[0], "data"),
            mesh=mesh, in_specs=P("data"), out_specs=P()))
        out = allreduce(per)
        jax.block_until_ready(out)
        want = flat * (n * (n + 1) / 2)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-6)
        t0 = time.perf_counter()
        for _ in range(rounds):
            out = allreduce(per)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        print(f"PSUM-OK proc={proc} n_elems={flat.size} "
              f"rate={rounds / dt:.3f}", flush=True)
    ''')
    port = _free_port()
    env = cpu_platform_env()
    procs = [
        subprocess.Popen(
            [_sys.executable, "-c", worker, str(rank), str(n), port,
             str(rounds)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for rank in range(n)
    ]
    outs = [p.communicate(timeout=600)[0] for p in procs]
    rates, n_elems = [], 0
    for rank, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0 or f"PSUM-OK proc={rank}" not in out:
            log(f"multiprocess psum rank {rank} failed:\n{out[-2000:]}")
            return
        rates.append(float(out.split("rate=")[1].split()[0]))
        n_elems = int(out.split("n_elems=")[1].split()[0])
    # one exchange completes when the SLOWEST rank finishes its round
    rate = min(rates)
    emit(2, f"allreduce_{n}process_gloo_exchange_rate", rate,
         "exchanges/sec", f"{n} real processes, 1 core",
         f"psum of the {n_elems}-elem raveled AlexNet gradient "
         f"({n_elems * 4 / 1e6:.1f} MB) across {n} localhost processes over "
         "gloo, result verified = sum of all ranks; min-rank rate over "
         f"{rounds} rounds — the real-process analog of the in-process "
         "2-device row")


def wire_bytes_phase() -> None:
    """Config 7, compressed-wire ladder (ISSUE 14, ``--only wire_bytes``,
    ``make bench-wire-bytes``): dense vs int8 vs top-k bytes-on-wire per
    push and acked push round-trips/s on the real raveled-AlexNet PS push
    path — in-process transports + the reliability envelope + a real
    ``ParameterServer`` decoding every frame, so the codec's encode AND
    decode CPU are inside the measured loop (the honest per-push cost,
    labelled in-process; the 9.9 MB echo baseline for the same payload
    over real TCP is ``reliability_phase``). Bytes are exact frame
    arithmetic, not estimates."""
    import threading

    from distributed_ml_pytorch_tpu.parallel.async_ps import ParameterServer
    from distributed_ml_pytorch_tpu.utils.compress import (
        CompressingEncoder,
        make_codec,
    )
    from distributed_ml_pytorch_tpu.utils.messaging import (
        MessageCode,
        make_world,
    )

    n = 2_472_266  # raveled AlexNet size — the 9.9 MB dense frame
    rng = np.random.default_rng(0)
    n_iter = 12
    rates: dict = {}
    bytes_per_push: dict = {}
    for mode in ("dense", "int8", "topk"):
        world, t, stop = None, None, None
        try:
            # setup rides INSIDE the try: a failed rung (construction
            # included) logs and yields to the next mode, never kills
            # the whole table
            world, _log = make_world(
                2, reliable=True,
                reliable_opts={"ack_timeout": 5.0, "max_backoff": 10.0})
            ps = ParameterServer(params=np.zeros(n, np.float32),
                                 transport=world[0])
            stop = threading.Event()

            def serve():
                while not stop.is_set():
                    msg = world[0].recv(timeout=0.2)
                    if msg is None:
                        continue
                    ps.handle(msg[0], msg[1], msg[2])

            t = threading.Thread(target=serve, daemon=True)
            t.start()
            enc = (None if mode == "dense" else CompressingEncoder(
                n, make_codec(mode, block=1024, k_frac=0.01)))
            vec = rng.normal(scale=0.01, size=n).astype(np.float32)

            def push():
                if enc is None:
                    world[1].send(MessageCode.GradientUpdate, vec, dst=0)
                    return n * 4
                head, body = enc.encode_range(vec, 0, n)
                world[1].sendv(MessageCode.CompressedUpdate, (head, body),
                               dst=0)
                return int((head.size + body.size) * 4)
            push()  # warm both directions (+ the server's first decode)
            world[1].flush(timeout=60)
            t0 = time.perf_counter()
            nbytes = 0
            for _ in range(n_iter):
                nbytes = push()
                # flush per push: the rate includes the ack round trip,
                # matching the dense echo baseline's send+reply discipline
                world[1].flush(timeout=60)
            dt = time.perf_counter() - t0
            rates[mode] = n_iter / dt
            bytes_per_push[mode] = nbytes
            emit(7, f"ps_wire_bytes_per_push_{mode}", nbytes, "bytes",
                 "in-process, reliable envelope",
                 f"exact frame bytes of one {mode} push of the "
                 f"{n}-param vector (envelope header excluded: +36 B "
                 "either way); decoded server-side inside the loop")
            emit(7, f"ps_push_roundtrips_{mode}", rates[mode],
                 "pushes/sec", "in-process, reliable envelope",
                 f"acked {mode} pushes/s incl. encode + decode + apply "
                 f"({nbytes * rates[mode] / 1e6:.1f} MB/s on-wire); "
                 "dense TCP echo baseline: reliability_phase")
        except Exception as e:  # noqa: BLE001 — a failed rung must not
            log(f"wire_bytes bench ({mode}) failed: {e}")  # kill the table
        finally:
            if stop is not None:
                stop.set()
            if t is not None:
                t.join(timeout=10)
            for tr in (world or {}).values():
                tr.close()
    for mode in ("int8", "topk"):
        if mode in bytes_per_push and "dense" in bytes_per_push:
            emit(7, f"ps_wire_compression_ratio_{mode}",
                 bytes_per_push["dense"] / bytes_per_push[mode],
                 "x fewer bytes", "derived",
                 f"dense / {mode} bytes-on-wire per push (error-feedback "
                 "encoder, utils/compress.py); the acceptance bar is "
                 ">= 3x with convergence in the fault-free corridor "
                 "(tests/test_compress.py)")

    # --- ISSUE 18: the codec plane's OTHER hot wires, same discipline —
    # exact frame arithmetic from the registry, encode AND decode CPU
    # inside every timed loop. Rows: activations (pipeline codes 30/31),
    # delta pull replies (the real server's _reply_delta path), and the
    # serving migration's quantized KV lane.
    from distributed_ml_pytorch_tpu.utils import codecs
    from distributed_ml_pytorch_tpu.utils.compress import (
        CODEC_DENSE,
        CODEC_INT8,
    )

    def _codec_ladder(tag, code, x, head_floats, note, iters=20):
        """Price one plane's dense-vs-int8 rungs: exact bytes/frame and
        encode+decode frames/s; returns {mode: bytes}."""
        out = {}
        for mode, cid in (("dense", CODEC_DENSE), ("int8", CODEC_INT8)):
            try:
                t0 = time.perf_counter()
                for _ in range(iters):
                    got, body = codecs.encode_body(code, x, cid)
                    codecs.decode_body(code, got, body, x.size)
                dt = time.perf_counter() - t0
                nbytes = int((head_floats + body.size) * 4)
                out[mode] = nbytes
                emit(7, f"{tag}_wire_bytes_per_frame_{mode}", nbytes,
                     "bytes", "registry encode_body/decode_body",
                     f"exact frame bytes ({head_floats}-float head + "
                     f"body) of one {mode} {code.name} frame of "
                     f"{x.size} floats; {note}")
                emit(7, f"{tag}_codec_frames_per_s_{mode}", iters / dt,
                     "frames/sec", "registry encode_body/decode_body",
                     f"{mode} encode + decode round trips/s on one core "
                     f"({iters / dt * nbytes / 1e6:.1f} MB/s on-wire)")
            except Exception as e:  # noqa: BLE001 — one rung, one row
                log(f"wire_bytes codec ladder ({tag}/{mode}) failed: {e}")
        return out

    act = rng.normal(scale=2.0, size=8 * 128 * 256).astype(np.float32)
    act_bytes = _codec_ladder(
        "act", MessageCode.ActivationShip, act, 8,
        "the MPMD corridor acceptance holds the loss trajectory within "
        "tolerance of the uncompressed pipeline (tests/test_mpmd.py)")
    if {"dense", "int8"} <= set(act_bytes):
        emit(7, "act_wire_compression_ratio_int8",
             act_bytes["dense"] / act_bytes["int8"], "x fewer bytes",
             "derived", "dense / int8 bytes per activation frame "
             "(codes 30/31, parallel/mpmd.py); acceptance bar is >= 3x "
             "with the loss corridor held")

    kv = rng.normal(scale=0.5, size=1024 * 128).astype(np.float32)
    kv_bytes = _codec_ladder(
        "kv_migrate", MessageCode.KvMigrate, kv, 9,
        "the token lane of the same frame rides tok16 (exact), so "
        "migrated-stream token identity never depends on this rung")
    if {"dense", "int8"} <= set(kv_bytes):
        emit(7, "kv_migrate_compression_ratio_int8",
             kv_bytes["dense"] / kv_bytes["int8"], "x fewer bytes",
             "derived", "dense / int8 bytes per migrated KV lane "
             "(serving/fleet.py handoff; kv_quant recipe)")

    # delta pull replies: the REAL server reply path (ParameterRequest
    # with a held stamp -> _reply_delta -> Listener install), so the
    # bytes are what the server actually put on the wire
    world = None
    try:
        from distributed_ml_pytorch_tpu.parallel.async_ps import (
            Listener,
        )
        from distributed_ml_pytorch_tpu.utils.messaging import (
            InProcessTransport,
        )

        world = InProcessTransport.create_world(2)
        ps = ParameterServer(
            params=rng.normal(scale=0.01, size=n).astype(np.float32),
            transport=world[0])
        lst = Listener(transport=world[1])

        def delta_pull():
            before = ps.delta_reply_wire_floats
            ps.handle(1, MessageCode.ParameterRequest, lst.held_stamp())
            msg = world[1].recv(timeout=5.0)
            assert msg is not None
            lst.receive(msg[0], msg[1], msg[2])
            return (ps.delta_reply_wire_floats - before) * 4

        full_bytes = delta_pull()  # first pull: full dense install
        upd = rng.normal(scale=1e-4, size=n).astype(np.float32)
        n_pulls, delta_bytes, spent = 6, 0, 0.0
        for _ in range(n_pulls):
            ps.handle(1, MessageCode.GradientUpdate, upd)
            t0 = time.perf_counter()
            delta_bytes = delta_pull()
            spent += time.perf_counter() - t0
        emit(7, "pull_reply_bytes_full", full_bytes, "bytes",
             "in-process, real _reply_delta path",
             f"exact wire bytes of the full dense fallback install of "
             f"the {n}-param vector (version miss / restore / rebalance "
             "path)")
        emit(7, "pull_reply_bytes_delta_steady", delta_bytes, "bytes",
             "in-process, real _reply_delta path",
             "exact wire bytes of one steady-state top-k delta reply "
             "(server tracks the worker's last-pulled view; "
             "per-worker error feedback keeps the tracked mirror "
             "bitwise equal to the installed view)")
        emit(7, "pull_reply_roundtrips_delta", n_pulls / spent,
             "pulls/sec", "in-process, real _reply_delta path",
             f"steady-state delta pulls/s incl. encode + decode + "
             f"install ({delta_bytes * n_pulls / spent / 1e6:.1f} MB/s "
             "on-wire)")
        emit(7, "pull_reply_compression_ratio_delta",
             full_bytes / max(delta_bytes, 1), "x fewer bytes",
             "derived", "full / steady-state delta reply bytes "
             "(parallel/async_ps.py); acceptance bar is >= 4x with "
             "drill restores bit-exact (full fallback re-fences)")
    except Exception as e:  # noqa: BLE001 — one ladder, one table leg
        log(f"wire_bytes pull-reply ladder failed: {e}")
    finally:
        for tr in (world or {}).values():
            tr.close()


def lint_phase() -> None:
    """Price the static-analysis pass itself (ISSUE 19): one full
    distcheck run — parse plus every checker family, the interprocedural
    distflow pass included — raw (pre-suppression) findings counted.
    `make test` fronts tier-1 with `make lint`, so the pass staying cheap
    IS a product property; gated against ``lint_wall_clock_ceiling_s``
    in bench_floors.json (a ceiling, not a floor: slower regresses)."""
    from distributed_ml_pytorch_tpu.analysis import cli
    from distributed_ml_pytorch_tpu.analysis.core import load_package

    t0 = time.perf_counter()
    pkg = load_package(cli.default_root())
    parse_s = time.perf_counter() - t0
    raw = []
    for check in cli.CHECKERS:
        raw.extend(check(pkg))
    total_s = time.perf_counter() - t0
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_floors.json")) as fh:
        ceiling = json.load(fh)["lint_wall_clock_ceiling_s"]
    emit(8, "lint_full_pass_wall_clock", total_s, "s", "1-core host",
         f"full distcheck: parse {parse_s:.2f}s + {len(cli.CHECKERS)} "
         f"checker families over {len(pkg.files)} modules, {len(raw)} "
         f"raw findings pre-suppression; ceiling {ceiling}s")
    if total_s > ceiling:
        raise RuntimeError(
            f"lint wall clock {total_s:.2f}s exceeds the "
            f"{ceiling}s ceiling in bench_floors.json — a checker "
            "got expensive enough to tax every `make test` run")


#: phases addressable via ``--only`` (``make bench-wire`` runs the wire
#: legs without paying for the full table)
PHASES = {
    "tpu": lambda: tpu_phase(),
    "ps": lambda: ps_phase(),
    "sharded_ps": lambda: sharded_ps_phase(),
    "elastic": lambda: elastic_phase(),
    "recovery": lambda: recovery_phase(),
    "coordfail": lambda: coordfail_phase(),
    "gray": lambda: gray_phase(),
    "sched": lambda: sched_phase(),
    "health": lambda: health_phase(),
    "mpmd": lambda: mpmd_phase(),
    "ps_tpu": lambda: ps_tpu_phase(),
    "transport": lambda: transport_phase(),
    "reliability": lambda: reliability_phase(),
    "transport_microbench": lambda: transport_microbench_phase(),
    "wire_bytes": lambda: wire_bytes_phase(),
    "compute_microbench": lambda: compute_microbench_phase(),
    "lint": lambda: lint_phase(),
    "cpu_mesh": lambda: cpu_mesh_phase(),
    "multiprocess_psum": lambda: multiprocess_psum_phase(),
}


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--only", action="append", choices=sorted(PHASES),
        help="run only the named phase(s), in the given order (repeatable)")
    args = ap.parse_args(argv)
    from distributed_ml_pytorch_tpu.runtime import startup

    startup.enable_compile_cache()
    if args.only:
        if "ps_tpu" in args.only[1:]:
            ap.error("--only ps_tpu must be the first phase named: its "
                     "children need the chip, and a phase that ran before "
                     "it may have left this process holding it")
        for name in args.only:
            PHASES[name]()
        log(f"bench_all: {len(RESULTS)} measurements")
        return
    # FIRST, before this process touches jax: its children need the chip,
    # and a parent that has taken the chip keeps it until it exits
    ps_tpu_phase()
    tpu_phase()
    ps_phase()
    sharded_ps_phase()
    elastic_phase()
    recovery_phase()
    coordfail_phase()
    gray_phase()
    sched_phase()
    health_phase()
    mpmd_phase()
    transport_phase()
    reliability_phase()
    transport_microbench_phase()
    wire_bytes_phase()
    compute_microbench_phase()
    cpu_mesh_phase()
    # LAST: the 4 gloo subprocesses leave the 1-core host briefly saturated
    # as they tear down — running this before cpu_mesh_phase measured the
    # in-process 2-way psum at 0.8 exchanges/s vs 88.5 standalone
    multiprocess_psum_phase()
    log(f"bench_all: {len(RESULTS)} measurements")


if __name__ == "__main__":
    main()
