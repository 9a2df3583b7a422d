"""FLOPs accounting and MFU (model-FLOPs-utilization) reporting.

VERDICT r1 #1: every benchmark leg must report model FLOPs/step, achieved
TFLOP/s, and %-of-peak for the measured dtype — MFU is how single-chip
performance is judged, img/s alone says nothing about how much of the MXU
a leg leaves idle.

Design choices, stated so the numbers can be audited:

- FLOPs come from XLA itself: ``jitted.lower(...).compile().cost_analysis()
  ["flops"]`` — the compiler's count over the *optimized* HLO of the exact
  program being timed (including the optimizer update and any remat
  recomputation), not a hand-derived ``6ND`` estimate. This makes the
  numerator slightly generous for remat'd programs (recomputed FLOPs are
  counted as achieved) — noted per-leg where it applies. Conversely the
  count EXCLUDES FLOPs inside Pallas kernels (custom calls are opaque to
  cost_analysis), so for programs using the flash-attention kernel the
  reported TFLOP/s and MFU are FLOORS — the attention matmuls are real
  work the denominator's wall-clock paid for but the numerator omits.
- Peak is the device's dense systolic-array peak from a device-kind table
  (public TPU spec sheets). MFU follows the scaling-book convention:
  achieved FLOP/s divided by the bf16 peak regardless of the dtype
  actually used, with the dtype stated in each leg's note (TPU has no
  published dense-f32 rate — f32 matmuls run through the same MXU).

There is no reference counterpart — the reference publishes no numbers at
all (SURVEY.md §6) — this is the framework's own honesty harness.
"""

from __future__ import annotations

from typing import Optional

import jax

# Dense matmul peak FLOP/s per chip, by `device.device_kind`, from the
# public TPU spec tables. bf16 is the MXU-native rate; f32 entries exist
# only where the hardware documents a native f32 rate.
PEAK_FLOPS: dict[str, dict[str, float]] = {
    "TPU v2": {"bf16": 45e12},
    "TPU v3": {"bf16": 123e12},
    "TPU v4": {"bf16": 275e12},
    "TPU v5 lite": {"bf16": 197e12, "int8": 394e12},  # v5e
    "TPU v5": {"bf16": 459e12},                       # v5p
    "TPU v6 lite": {"bf16": 918e12, "int8": 1836e12},  # Trillium
}


def device_peak_flops(device=None, dtype: str = "bf16") -> Optional[float]:
    """Peak FLOP/s for ``device`` (default: first visible device) at
    ``dtype``. ``None`` only for a host CPU, which has no MXU peak to
    utilise; a TPU whose ``device_kind`` or dtype the table does not hold
    raises — a missing row must not make MFU quietly disappear."""
    device = device if device is not None else jax.devices()[0]
    if device.platform == "cpu":
        return None
    try:
        return PEAK_FLOPS[device.device_kind][dtype]
    except KeyError:
        raise KeyError(
            f"no {dtype} peak FLOP/s for device_kind {device.device_kind!r} "
            f"in utils/flops.PEAK_FLOPS (known: {sorted(PEAK_FLOPS)}); add "
            "the row, with its source, before reporting utilisation"
        ) from None


def compiled_flops(jitted, *args, **kwargs) -> Optional[float]:
    """XLA's FLOP count for one dispatch of ``jitted(*args, **kwargs)``.

    Lowers against shape/dtype abstractions of the arguments (never touching
    the concrete buffers, so donated/deleted inputs are safe) and reads the
    compiled executable's ``cost_analysis``. Returns None when the backend
    does not report flops.
    """
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
        if hasattr(x, "shape") else x,
        (args, kwargs),
    )
    a_args, a_kwargs = abstract
    try:
        analysis = jitted.lower(*a_args, **a_kwargs).compile().cost_analysis()
    except Exception:
        return None
    if not analysis:
        return None
    flops = analysis.get("flops")
    return float(flops) if flops and flops > 0 else None


def flash_attention_train_flops(batch: int, heads: int, seq: int,
                                head_dim: int, n_layers: int, *,
                                causal: bool = True,
                                remat: bool = False,
                                bwd_impl: str = "fused") -> float:
    """Analytic FLOPs of the Pallas flash-attention kernels for ONE train
    step — the piece ``cost_analysis`` cannot see (custom calls are opaque).

    Counted from the kernel structure (ops/attention.py): forward = 2
    matmuls over the S² score plane (QKᵀ, PV). Backward, fused (the round-3
    default): ONE kernel does 5 matmuls per block pair (recomputed S, dV,
    dP, dK, dQ-partial) → 7 total; split: 3 in the dQ kernel + 4 in dK/dV
    (S recomputed twice) → 9 total. ×2 FLOPs/MAC, halved for causal (dead
    blocks are skipped). Per-block remat reruns the forward kernel inside
    the backward (+2). Add this to the XLA count to turn an LM leg's MFU
    floor into the real numerator.
    """
    matmuls = (7 if bwd_impl == "fused" else 9) + (2 if remat else 0)
    per_layer = matmuls * 2 * batch * heads * seq * seq * head_dim
    if causal:
        per_layer /= 2
    return float(per_layer * n_layers)


def lm_train_flops_6nd(n_matmul_params: float, batch: int, seq: int,
                       heads: int, head_dim: int, n_layers: int, *,
                       causal: bool = True, remat: bool = False,
                       bwd_impl: str = "fused") -> float:
    """Scaling-book analytic train FLOPs for one LM step: ``6·N·D`` over the
    dense-matmul parameters (N excludes embedding tables — lookups are not
    matmuls; the lm_head IS one and must be inside ``n_matmul_params``)
    plus the attention S² kernel term. Remat recomputes the block forward:
    +2·N·D.

    This is the AUDIT CROSS-CHECK (VERDICT r2 #8) for the hybrid MFU
    numerator (XLA ``cost_analysis`` + analytic kernel FLOPs): the two
    counts come from independent methods, so bench legs assert they agree
    within ~15% (``check_flops_agreement``) — a silent miscount in either
    can no longer inflate MFU unnoticed.
    """
    dense_factor = 6.0 + (2.0 if remat else 0.0)
    dense = dense_factor * float(n_matmul_params) * batch * seq
    attn = flash_attention_train_flops(
        batch, heads, seq, head_dim, n_layers,
        causal=causal, remat=remat, bwd_impl=bwd_impl)
    return dense + attn


def check_flops_agreement(hybrid: Optional[float], analytic: float,
                          tol: float = 0.15) -> Optional[str]:
    """None when the hybrid numerator agrees with the 6ND-style analytic
    count within ``tol``; otherwise a warning string for the bench log."""
    if not hybrid or analytic <= 0:
        return None
    rel = abs(hybrid - analytic) / analytic
    if rel <= tol:
        return None
    return (f"FLOPs cross-check FAILED: hybrid numerator {hybrid:.3e} vs "
            f"analytic 6ND {analytic:.3e} ({100 * rel:.0f}% apart > "
            f"{100 * tol:.0f}%) — audit utils/flops.py before trusting MFU")


def utilization(flops_per_step: Optional[float], step_seconds: float,
                device=None) -> tuple[Optional[float], Optional[float]]:
    """(achieved TFLOP/s, MFU fraction vs bf16 peak) for a measured step
    time; either element is None when its ingredient is unavailable."""
    if not flops_per_step or step_seconds <= 0:
        return None, None
    achieved = flops_per_step / step_seconds
    peak = device_peak_flops(device)
    return achieved / 1e12, (achieved / peak if peak else None)
