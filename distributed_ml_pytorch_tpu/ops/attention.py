"""Attention kernels: differentiable Pallas flash attention + blockwise scan.

The reference has no attention at all (image CNNs only, SURVEY.md §5.7); this
module is the long-context foundation the TPU framework adds as first-class:

- ``flash_attention`` — a Pallas TPU kernel, DIFFERENTIABLE via
  ``jax.custom_vjp``: the O(S²) score matrix never touches HBM in either
  pass. Forward: grid over (batch·heads, query blocks, key blocks) with
  online-softmax statistics in VMEM scratch, emitting the per-row logsumexp
  as a residual. Backward (default ``bwd_impl="fused"``): ONE kernel over
  (bh, key block, query block) recomputing probabilities from the saved
  logsumexp once per block pair — dK/dV accumulate in VMEM scratch across
  the inner query sweep, dQ is emitted as per-key-block partials reduced by
  one XLA sum afterwards. A ``"split"`` two-kernel backward (dQ pass +
  dK/dV pass, scores recomputed twice) is kept for A/B. Causally-dead
  blocks are skipped.
- ``blockwise_attention`` — the same online-softmax recurrence written as a
  ``lax.scan`` over key blocks in plain JAX: used as the per-chunk compute
  inside ring attention (``parallel/ring.py``), whose carry interface
  (acc, m, l) it exposes; also the fallback where flash's block-divisibility
  constraints don't hold.
- ``auto_attention`` — the model-facing selector: the flash kernel on TPU
  when the shape fits its blocking, the scan otherwise.
- ``attention_reference`` — the naive softmax(QKᵀ)V for tests.

Measurements (v5 lite, causal bf16, b=8 h=12 S=2048 d=64, device spans
from the profiler via ``utils/devtime``): forward at the
default (1024, 1024) blocking runs 1.63 ms vs the blockwise scan's
10.2 ms (6.3×) and (128, 128)'s 10.7 ms. The fused backward brings
fwd+bwd to 4.49 ms — the backward alone is 1.75× the forward against
~2.5× in raw FLOPs, vs 3.7× for the split two-kernel backward (5.34 ms
total). Calibration against the installed JAX's own kernels on identical
shapes: legacy ``pallas.ops.tpu.flash_attention`` 1.49 ms fwd / 8.0 ms
fwd+bwd at its best blocking; ``splash_attention`` with its fused backward
1.63 ms / 4.49 ms — this kernel matches splash on both passes, so it sits
on the Mosaic ceiling for this shape. What got it there, in measured
order of importance: (1) one score recompute per block pair (the split
backward's second recompute cost ~0.9 ms); (2) lane-replicated (BQ, 128)
m/l statistics widened by whole-tile copies (``_rep_lanes``) — replacing
(BQ, 1) lane-broadcast shuffles cut ~0.9 ms from the forward at sub-1024
key blocks; (3) transposed (BK, BQ) scores in the backward so dV/dK are
plain NN contractions and lse/delta broadcast along sublanes; (4) log2-
space softmax and diagonal-only masking (small, ~2% each). At GPT-2-small
scale the scan-based step spent ~90% of its time in attention, so the
kernel, not the scan, is the training default on TPU (auto_attention).

Long-context sweep (S ∈ {2k, 8k, 32k}, device-true): beyond speed, the
scan's BACKWARD is O(S²) HBM — XLA's autodiff saves every per-block score
tensor, and at S=8192 (b2·h12) its gradient OOMs at 19.5 GB against the
chip's 15.75 GB. The flash backward recomputes probabilities from the saved
logsumexp instead, and the fused backward holds bwd ≈ 2.0× fwd at every
length: b2·h12·S8192 fwd 4.24 ms / fwd+bwd 12.7 ms (56.8 useful TFLOP/s);
b1·h12·S32768 fwd 29.9 ms / fwd+bwd 92.3 ms (62.5 TFLOP/s, 31.7% of bf16
peak — vs 157 ms for the round-2 split backward) where the scan cannot
compile at all. On this hardware the kernel is the only differentiable
attention at long context without rematerialization.

All take ``(batch, heads, seq, head_dim)`` and an optional causal mask.
``NEG_INF`` is a large-finite mask value rather than ``-inf`` so fully-masked
rows (which ring attention produces on not-yet-arrived chunks) stay NaN-free;
masked probabilities are explicitly zeroed so a fully-masked row yields
``acc = 0, l = 0`` (callers detect empty rows by ``l == 0``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_ml_pytorch_tpu.ops.fused_update import _interpret

NEG_INF = -1e30
LOG2_E = 1.4426950408889634  # scores are kept in log2 space inside the kernels
# ceiling on the fused backward's HBM dq-partials buffer; above it the
# buffer-free split backward is auto-selected (measured S=32k fused buffer:
# 3.2 GB on the 15.75 GB chip — comfortably under; 2× longer would not be)
FUSED_BWD_PARTIALS_CAP = 6 * 1024**3


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False
) -> jax.Array:
    """Naive softmax(QKᵀ/√d)V — the ground truth for kernel tests."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        s = jnp.where(mask, s, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


def init_softmax_state(q: jax.Array):
    """Empty online-softmax state ``(m, l, acc)`` for queries ``q``, in f32.

    Derived from ``q`` rather than built as fresh constants so the arrays
    carry ``q``'s device-varying type when traced inside ``shard_map`` (a
    constant init would fail lax.scan's carry-type check there).
    """
    l0 = (q[..., :1] * 0.0).astype(jnp.float32)
    m0 = l0 + NEG_INF
    acc0 = (q * 0.0).astype(jnp.float32)
    return m0, l0, acc0


def _online_update(m, l, acc, s, v_blk):
    """One online-softmax step: fold scores ``s`` (…q,k) and values ``v_blk``
    (…k,d) into the running (max, normalizer, accumulator). Entries at
    ``NEG_INF`` (masked) contribute exactly zero even when the whole row is
    masked (where exp(NEG_INF − NEG_INF) would otherwise be 1)."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(s > NEG_INF / 2, jnp.exp(s - m_new), 0.0)
    correction = jnp.exp(jnp.maximum(m - m_new, NEG_INF))
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    acc_new = acc * correction + jnp.einsum(
        "...qk,...kd->...qd", p, v_blk, preferred_element_type=jnp.float32
    )
    return m_new, l_new, acc_new


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_k: int = 512,
    q_offset=0,
    k_offset=0,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Differentiable online-softmax attention over key blocks (lax.scan).

    Returns ``(out, m, l)`` — the un-finalized accumulator statistics, always
    float32 regardless of input dtype — so ring attention can keep folding
    further key chunks in; finalize with ``finalize_attention`` (and cast back
    if needed). ``q_offset``/``k_offset`` are the global positions
    of element 0 of the local q/k chunks, which is what makes the causal mask
    correct when the sequence axis is sharded across devices.
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    block_k = min(block_k, sk)
    n_blocks = pl.cdiv(sk, block_k)
    pad = n_blocks * block_k - sk
    if pad:
        # padded keys are masked off via their out-of-range global position
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    scale = d**-0.5
    q_pos = q_offset + jnp.arange(sq)

    kb = k.reshape(b, h, n_blocks, block_k, d).transpose(2, 0, 1, 3, 4)
    vb = v.reshape(b, h, n_blocks, block_k, d).transpose(2, 0, 1, 3, 4)

    def body(carry, blk):
        m, l, acc = carry
        k_blk, v_blk, j = blk
        # accumulate scores and softmax statistics in f32 even for bf16
        # inputs (MXU takes bf16 operands natively; the accumulate is f32) —
        # matching the flash kernel's f32 scratch
        s = jnp.einsum(
            "bhqd,bhkd->bhqk", q, k_blk, preferred_element_type=jnp.float32
        ) * scale
        k_pos = k_offset + j * block_k + jnp.arange(block_k)
        valid = k_pos < k_offset + sk
        if causal:
            valid = valid[None, :] & (k_pos[None, :] <= q_pos[:, None])
        else:
            valid = jnp.broadcast_to(valid[None, :], (sq, block_k))
        s = jnp.where(valid, s, NEG_INF)
        return _online_update(m, l, acc, s, v_blk), None

    m0, l0, acc0 = init_softmax_state(q)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0), (kb, vb, jnp.arange(n_blocks))
    )
    return acc, m, l


def finalize_attention(acc: jax.Array, l: jax.Array) -> jax.Array:
    """Normalize the online-softmax accumulator into attention output."""
    return acc / jnp.maximum(l, 1e-30)


def _rep_lanes(x, width):
    """Widen a 128-lane-replicated (rows, 128) value to (rows, width) by
    whole-tile copies — never a lane-broadcast shuffle (see _flash_kernel)."""
    if width <= 128:
        return x[:, :width]
    return jnp.tile(x, (1, pl.cdiv(width, 128)))[:, :width]


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
    *, block_q: int, block_k: int, causal: bool
):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # keys strictly after the last query of this block contribute nothing
    live = (kj * block_k < (qi + 1) * block_q) if causal else (kj >= 0)
    # blocks wholly below the diagonal need no mask at all — only the
    # diagonal-straddling blocks pay the iota/compare/select VPU passes
    # (the per-step cost is VPU-bound at d=64: O(BQ·BK) vector work against
    # d-thin matmuls, so every elementwise pass over the score block counts)
    diag = ((kj + 1) * block_k - 1 > qi * block_q) if causal else None

    def _step(masked):
        q = q_ref[0]  # (BQ, D)
        d = q.shape[-1]
        k_blk = k_ref[0]  # (BK, D)
        v_blk = v_ref[0]
        # scores in log2 space: fold log2(e) into the 1/√d scale so the
        # softmax runs on exp2 — one fewer multiply pass over the score
        # block per step (the kernel is VPU-bound, so elementwise passes
        # are the currency; the lse residual is stored base-2 to match)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (d**-0.5 * LOG2_E)
        if masked:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        # m/l live lane-replicated at full 128-lane width so the (BQ, BK)
        # broadcasts below are TILE copies, not lane-broadcast shuffles —
        # a (BQ, 1) operand must be shuffled across lanes for every 128-wide
        # score tile, and that shuffle was ~60% of the whole kernel's time
        # (measured by ablation: matmul+DMA floor 0.62 ms vs 1.5 ms full)
        m_prev = m_ref[:]  # (BQ, 128)
        l_prev = l_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # no select guarding the exp: every flash row has ≥1 live key
        # (causal needs sq == sk, so the diagonal is always present), hence
        # m_new is finite and masked entries underflow to exactly 0 —
        # exp2(NEG_INF − m_new) = 0 in f32. (The scan keeps its guard: ring
        # attention feeds it fully-masked rows where m_new == NEG_INF.)
        p = jnp.exp2(s - _rep_lanes(m_new, block_k))
        correction = jnp.exp2(m_prev - m_new)
        l_new = l_prev * correction + jax.lax.broadcast_in_dim(
            jnp.sum(p, axis=-1), l_prev.shape, (0,))
        pv = jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = m_new
        l_ref[:] = l_new
        acc_ref[:] = acc_ref[:] * _rep_lanes(correction, d) + pv

    if causal:
        @pl.when(live & diag)
        def _step_diag():
            _step(True)

        @pl.when(live & jnp.logical_not(diag))
        def _step_interior():
            _step(False)
    else:
        @pl.when(live)
        def _step_full():
            _step(False)

    @pl.when(kj == n_k - 1)
    def _finalize():
        l_fin = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_fin).astype(o_ref.dtype)
        # per-row logsumexp — the backward's softmax residual. Stored
        # sublane-replicated ×8 so the output block is a legal (8, block_q)
        # TPU tile (rank-2 row vectors can't be blocked per-bh otherwise).
        lse = (m_ref[:, :1] + jnp.log2(l_fin))[:, 0]  # base-2, like the scores
        lse_ref[0] = jnp.broadcast_to(lse[None, :], (8, lse.shape[0]))


def _vma_struct(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-manual-axes set.

    Inside ``shard_map`` (with the default ``check_vma=True``) a
    ``pallas_call`` out_shape must DECLARE how the output varies across
    mesh axes — our outputs vary exactly like the kernel inputs (the
    batch/head/sequence shards). Declaring it keeps the checker ON, which
    matters beyond hygiene: ``check_vma=False`` also disables the
    automatic psum/pbroadcast insertion that makes gradients of
    REPLICATED shard_map operands correct (round 3 measured a dp×sp step
    silently producing wrong replicated-param grads under
    ``check_vma=False``). Outside shard_map ``vma`` is empty/absent and
    this degrades to a plain struct."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    """Forward pallas_call returning ``(out, lse)`` with flattened heads;
    ``lse`` is (bh, 8, sq) f32, replicated over the 8-sublane axis."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal
    )
    return pl.pallas_call(
        kernel,
        out_shape=(
            _vma_struct(q.shape, q.dtype, q),
            _vma_struct((bh, 8, sq), jnp.float32, q),
        ),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 8, block_q), lambda bh, i, j: (bh, 0, i), memory_space=pltpu.VMEM),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),  # running max (lane-replicated)
            pltpu.VMEM((block_q, 128), jnp.float32),  # running normalizer
            pltpu.VMEM((block_q, d), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
    )(q, k, v)


def _recompute_p(q, k_blk, qi, kj, lse, *, block_q, block_k, causal, scale):
    """Probabilities p = exp2(s₂ − lse₂) for one (q block, k block) pair — the
    backward pass's recomputation (scores never persisted; log2 space, with
    masked entries underflowing to exactly 0 against the finite lse)."""
    s = jax.lax.dot_general(
        q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (scale * LOG2_E)
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)
    return jnp.exp2(s - lse[:, None]), s


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
    *, block_q: int, block_k: int, causal: bool
):
    qi, kj = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = (kj * block_k < (qi + 1) * block_q) if causal else (kj >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0]
        d = q.shape[-1]
        scale = d**-0.5
        k_blk, v_blk, do = k_ref[0], v_ref[0], do_ref[0]
        p, _s = _recompute_p(q, k_blk, qi, kj, lse_ref[0, 0], block_q=block_q,
                             block_k=block_k, causal=causal, scale=scale)
        dp = jax.lax.dot_general(  # do @ vᵀ → (BQ, BK)
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(  # ds @ k → (BQ, D)
            ds.astype(k_blk.dtype), k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_k - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, block_q: int, block_k: int, causal: bool
):
    # grid: (bh, key block j, query block i) — q innermost so dk/dv accumulate
    kj, qi = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # query blocks entirely before this key block see none of it
    live = ((qi + 1) * block_q > kj * block_k) if causal else (qi >= 0)

    @pl.when(live)
    def _step():
        q = q_ref[0]
        d = q.shape[-1]
        scale = d**-0.5
        k_blk, v_blk, do = k_ref[0], v_ref[0], do_ref[0]
        p, _s = _recompute_p(q, k_blk, qi, kj, lse_ref[0, 0], block_q=block_q,
                             block_k=block_k, causal=causal, scale=scale)
        pt = p.astype(do.dtype)
        dv_acc[:] += jax.lax.dot_general(  # pᵀ @ do → (BK, D)
            pt, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta_ref[0, 0][:, None]) * scale
        dk_acc[:] += jax.lax.dot_general(  # dsᵀ @ q → (BK, D)
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_part_ref, dk_ref, dv_ref, dk_acc, dv_acc,
    *, block_q: int, block_k: int, causal: bool
):
    """One-pass backward: grid (bh, key block j, query block i), i innermost.

    Scores are recomputed ONCE per (i, j) block pair (the split kernels
    recomputed them twice — measured 6.7× fwd, vs ~2.5× in raw FLOPs).
    dK/dV accumulate in VMEM scratch across the inner query sweep. dQ cannot
    accumulate in scratch here (its block changes every inner step), so each
    grid step emits a per-key-block PARTIAL dq block into an (n_k, bh, sq, d)
    output that one XLA reduction folds afterwards — the same layout JAX's
    own fused splash-attention backward uses.

    Scores are built TRANSPOSED, (block_k, block_q): that makes dV = pᵀ·do
    and dK = dsᵀ·q plain non-transposed MXU contractions, and broadcasts
    the per-query lse/delta row vectors along lanes for free.
    """
    kj, qi = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # query blocks entirely before this key block see none of it
    live = ((qi + 1) * block_q > kj * block_k) if causal else (qi >= 0)
    # interior (fully-live) blocks skip the mask's VPU passes, as in forward
    diag = ((kj + 1) * block_k - 1 > qi * block_q) if causal else None

    def _step(masked):
        q = q_ref[0]
        d = q.shape[-1]
        scale = d**-0.5
        k_blk, v_blk, do = k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, :1]  # (1, BQ) — queries along lanes
        di = delta_ref[0, :1]
        s_t = jax.lax.dot_general(  # k @ qᵀ → (BK, BQ)
            k_blk, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * (scale * LOG2_E)  # log2 space, matching the stored lse
        if masked:
            k_pos = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
            s_t = jnp.where(k_pos <= q_pos, s_t, NEG_INF)
        # masked entries underflow to exactly 0 (lse finite per row) — no
        # select needed, as in the forward
        p_t = jnp.exp2(s_t - lse)
        dv_acc[:] += jax.lax.dot_general(  # pᵀ·do as plain (BK,BQ)@(BQ,D)
            p_t.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp_t = jax.lax.dot_general(  # v @ doᵀ → (BK, BQ)
            v_blk, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds_t = p_t * (dp_t - di) * scale
        dk_acc[:] += jax.lax.dot_general(  # dsᵀ·q as plain (BK,BQ)@(BQ,D)
            ds_t.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dq_part_ref[0, 0] = jax.lax.dot_general(  # ds·k → (BQ, D)
            ds_t.astype(k_blk.dtype), k_blk, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(live & diag)
        def _step_diag():
            _step(True)

        @pl.when(live & jnp.logical_not(diag))
        def _step_interior():
            _step(False)

        # dead pairs must still publish a (zero) dq partial
        @pl.when(jnp.logical_not(live))
        def _dead():
            dq_part_ref[0, 0] = jnp.zeros_like(dq_part_ref[0, 0])
    else:
        @pl.when(live)
        def _step_full():
            _step(False)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash(causal, blocks, bwd_blocks, interpret, bwd_impl, q, k, v):
    out, _lse = _flash_fwd(q, k, v, causal, blocks[0], blocks[1], interpret)
    return out


def _flash_fwd_rule(causal, blocks, bwd_blocks, interpret, bwd_impl, q, k, v):
    out, lse = _flash_fwd(q, k, v, causal, blocks[0], blocks[1], interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, blocks, bwd_blocks, interpret, bwd_impl, res, do):
    q, k, v, out, lse = res
    # delta_i = Σ_d do·o — one cheap fused XLA pass, shared by the kernels
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    return _flash_bwd_core(causal, bwd_blocks, interpret, bwd_impl,
                           q, k, v, lse, do, delta)


def _flash_bwd_core(causal, bwd_blocks, interpret, bwd_impl,
                    q, k, v, lse, do, delta):
    """Shared backward: ``delta`` is the natural-space per-row correction —
    rowsum(do·o) for the plain vjp, rowsum(do·o) − dlse when the logsumexp
    output also carries a cotangent (``ds = p·(dp − rowsum(do·o) + dlse)``,
    so the lse term folds into delta with no kernel changes)."""
    # backward blocking is swept independently of the forward's: on the v5e
    # the fused backward at (1024, 1024) runs ~19% faster than at the
    # fwd-shared (1024, 512) — see the module docstring's measurements
    block_q, block_k = bwd_blocks
    bh, sq, d = q.shape
    sk = k.shape[1]
    # broadcast into the same 8-sublane-replicated layout as lse
    delta = jnp.broadcast_to(delta[:, None, :], (bh, 8, sq))
    if bwd_impl == "fused":
        n_k = sk // block_k
        qspec = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0),
                             memory_space=pltpu.VMEM)
        kspec = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0),
                             memory_space=pltpu.VMEM)
        rowspec = pl.BlockSpec((1, 8, block_q), lambda bh, j, i: (bh, 0, i),
                               memory_space=pltpu.VMEM)
        dq_part, dk, dv = pl.pallas_call(
            functools.partial(_flash_bwd_fused_kernel, block_q=block_q,
                              block_k=block_k, causal=causal),
            out_shape=(
                _vma_struct((n_k, bh, sq, d), jnp.float32, q),
                _vma_struct(k.shape, k.dtype, k),
                _vma_struct(v.shape, v.dtype, v),
            ),
            grid=(bh, n_k, sq // block_q),
            in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
            out_specs=(
                pl.BlockSpec((1, 1, block_q, d),
                             lambda bh, j, i: (j, bh, i, 0),
                             memory_space=pltpu.VMEM),
                kspec, kspec,
            ),
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            interpret=interpret,
        )(q, k, v, do, lse, delta)
        dq = dq_part.sum(axis=0).astype(q.dtype)
        return dq, dk, dv
    # split impl: the round-2 two-kernel backward (scores recomputed twice) —
    # kept for A/B measurement and as a fallback with no dq-partials buffer
    qspec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0), memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0), memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((1, 8, block_q), lambda bh, i, j: (bh, 0, i), memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_q=block_q, block_k=block_k, causal=causal),
        out_shape=_vma_struct(q.shape, q.dtype, q),
        grid=(bh, sq // block_q, sk // block_k),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    # dK/dV: key blocks outermost, query blocks innermost (accumulation axis)
    qspec_t = pl.BlockSpec((1, block_q, d), lambda bh, j, i: (bh, i, 0), memory_space=pltpu.VMEM)
    kspec_t = pl.BlockSpec((1, block_k, d), lambda bh, j, i: (bh, j, 0), memory_space=pltpu.VMEM)
    rowspec_t = pl.BlockSpec((1, 8, block_q), lambda bh, j, i: (bh, 0, i), memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q, block_k=block_k, causal=causal),
        out_shape=(
            _vma_struct(k.shape, k.dtype, k),
            _vma_struct(v.shape, v.dtype, v),
        ),
        grid=(bh, sk // block_k, sq // block_q),
        in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, rowspec_t, rowspec_t],
        out_specs=(kspec_t, kspec_t),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _flash_lse(causal, blocks, bwd_blocks, interpret, bwd_impl, q, k, v):
    """Like ``_flash`` but also returns the per-row NATURAL logsumexp
    (bh, sq) — and is differentiable in BOTH outputs, which is what lets
    ring attention combine per-chunk kernel results outside the kernel."""
    out, lse2 = _flash_fwd(q, k, v, causal, blocks[0], blocks[1], interpret)
    return out, lse2[:, 0, :] * (1.0 / LOG2_E)


def _flash_lse_fwd_rule(causal, blocks, bwd_blocks, interpret, bwd_impl,
                        q, k, v):
    out, lse2 = _flash_fwd(q, k, v, causal, blocks[0], blocks[1], interpret)
    return (out, lse2[:, 0, :] * (1.0 / LOG2_E)), (q, k, v, out, lse2)


def _flash_lse_bwd_rule(causal, blocks, bwd_blocks, interpret, bwd_impl,
                        res, cts):
    q, k, v, out, lse2 = res
    do, dlse = cts
    # ds = p·(v·do − rowsum(do·o) + dlse): the lse cotangent enters as a
    # per-row shift of delta (∂lse/∂s = p), shared by every backward kernel
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta = delta - dlse.astype(jnp.float32)
    return _flash_bwd_core(causal, bwd_blocks, interpret, bwd_impl,
                           q, k, v, lse2, do, delta)


_flash_lse.defvjp(_flash_lse_fwd_rule, _flash_lse_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
    interpret: bool | None = None,
    bwd_impl: str | None = None,
) -> jax.Array:
    """Differentiable Pallas flash attention over (batch, heads, seq, head_dim).

    Block sizes default to the largest measured-good blocking that divides
    the sequence lengths — ``flash_block_choice`` for the forward and
    ``flash_bwd_block_choice`` for the backward (both prefer (1024, 1024)
    on aligned shapes, down to (128, 128); see the module docstring's
    sweep) — and a shape no candidate divides raises rather than falling
    back to an unswept clamp. Explicit blocks must divide exactly.
    Pad upstream for ragged sequences, or use ``auto_attention`` which falls
    back to the scan. ``causal`` requires ``sq == sk`` (the standard
    self-attention layout; the end-aligned decode mask is a different
    contract and is rejected rather than silently diverging).
    ``interpret=None`` compiles the kernel (Mosaic, TPU only) unless the
    caller is inside ``fused_update.force_pallas_interpret()`` — interpret
    mode is always an explicit request (the CPU tests make it), never
    something a non-TPU backend gets by accident. ``bwd_impl``: "fused" (one kernel, scores
    recomputed once per block pair) or "split" (the two-kernel dQ + dK/dV
    pair, scores recomputed twice, but no dq-partials buffer). The default
    ``None`` picks "fused" unless its (sk/block_k, b·h, sq, d) f32
    dq-partials buffer would exceed ``FUSED_BWD_PARTIALS_CAP`` bytes of HBM
    (beyond ~S=48k at GPT-2-small geometry), where the slower-but-lean
    split keeps long-context training compilable.
    """
    blocks, bwd_blocks, interpret, bwd_impl = _resolve_flash_config(
        q, k, causal, block_q, block_k, block_q_bwd, block_k_bwd,
        interpret, bwd_impl)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    out = _flash(causal, blocks, bwd_blocks, interpret, bwd_impl, qf, kf, vf)
    return out.reshape(b, h, sq, d)


def flash_attention_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    block_q_bwd: int | None = None,
    block_k_bwd: int | None = None,
    interpret: bool | None = None,
    bwd_impl: str | None = None,
) -> tuple[jax.Array, jax.Array]:
    """:func:`flash_attention` that also returns the per-row natural
    logsumexp ``(b, h, sq)`` f32 — differentiable in both outputs.

    This is the building block for cross-chunk combines (ring attention,
    decode-time chunked prefill): per-chunk ``(out_i, lse_i)`` pairs merge
    exactly as ``out = Σ out_i·exp(lse_i − lse)``, ``lse = logaddexp_i`` in
    plain XLA, and gradients flow because the lse cotangent folds into the
    backward's delta term (see ``_flash_lse_bwd_rule``). Same blocking
    rules and constraints as :func:`flash_attention`.
    """
    blocks, bwd_blocks, interpret, bwd_impl = _resolve_flash_config(
        q, k, causal, block_q, block_k, block_q_bwd, block_k_bwd,
        interpret, bwd_impl)
    b, h, sq, d = q.shape
    sk = k.shape[2]
    qf = q.reshape(b * h, sq, d)
    kf = k.reshape(b * h, sk, d)
    vf = v.reshape(b * h, sk, d)
    out, lse = _flash_lse(causal, blocks, bwd_blocks, interpret, bwd_impl,
                          qf, kf, vf)
    return out.reshape(b, h, sq, d), lse.reshape(b, h, sq)


def _resolve_flash_config(q, k, causal, block_q, block_k,
                          block_q_bwd, block_k_bwd, interpret, bwd_impl):
    """Default-resolution and validation shared by the flash entry points."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if causal and sq != sk:
        raise ValueError(f"causal flash_attention requires sq == sk, got {sq} != {sk}")
    # defaults derive PER SIDE so an explicit block for an odd length still
    # composes with a derived one for the other side (e.g. block_q=320 with
    # sq=320, sk=2048); only a side that actually needs a default can raise
    def _default(n, name):
        c = _side_block_choice(n)
        if c is None:
            raise ValueError(
                f"no flash blocking divides {name}={n}; pass an explicit "
                "block or pad the sequence (auto_attention falls back to "
                "the scan for such shapes)"
            )
        return c

    if block_q is None:
        block_q = _default(sq, "sq")
    if block_k is None:
        block_k = _default(sk, "sk")
    # backward defaults via flash_bwd_block_choice (square at short
    # sequences, (·, 2048) key blocks at sk == 8192 exactly — see its
    # docstring); an explicit forward block is the fallback for lengths no
    # candidate divides — it divides by definition
    if block_q_bwd is None or block_k_bwd is None:
        bwd_default = flash_bwd_block_choice(sq, sk)
        if block_q_bwd is None:
            block_q_bwd = bwd_default[0] if bwd_default else block_q
        if block_k_bwd is None:
            block_k_bwd = bwd_default[1] if bwd_default else block_k
    if sq % block_q or sk % block_k or sq % block_q_bwd or sk % block_k_bwd:
        raise ValueError(
            f"flash_attention needs seq multiples of block sizes, got "
            f"sq={sq}%{block_q}/{block_q_bwd}, sk={sk}%{block_k}/{block_k_bwd}"
        )
    if interpret is None:
        interpret = _interpret()
    if bwd_impl is None:
        partials = (sk // block_k_bwd) * b * h * sq * d * 4
        bwd_impl = "split" if partials > FUSED_BWD_PARTIALS_CAP else "fused"
    if bwd_impl not in ("fused", "split"):
        raise ValueError(f"bwd_impl must be 'fused' or 'split', got {bwd_impl!r}")
    return ((block_q, block_k), (block_q_bwd, block_k_bwd), interpret,
            bwd_impl)


def auto_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True) -> jax.Array:
    """Model-facing attention: the flash kernel when the backend and shapes
    allow, the differentiable blockwise scan otherwise.

    The decision is static (shapes + backend at trace time), so under jit
    exactly one path is compiled. The scan remains the path for non-TPU
    backends (interpret-mode pallas is orders slower than compiled XLA) and
    sequences not divisible by the kernel's minimum blocking; ring
    attention makes the same choice at chunk granularity (flash via the
    chunk-level lse combine on TPU, the (acc, m, l)-carry blockwise scan
    elsewhere — parallel/ring.py).
    """
    sq, sk = q.shape[2], k.shape[2]
    blocks = flash_block_choice(sq, sk)
    use_flash = (
        jax.default_backend() == "tpu"
        and blocks is not None
        and (not causal or sq == sk)
    )
    if use_flash:
        bq, bk = blocks
        return flash_attention(
            q, k, v, causal=causal, block_q=bq, block_k=bk, interpret=False
        )
    acc, _m, l = blockwise_attention(q, k, v, causal=causal)
    return finalize_attention(acc, l).astype(q.dtype)


def scan_attn_fn(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Causal attention via the blockwise scan, finalized — the non-Pallas
    formulation of ``auto_attention``'s fallback, usable anywhere XLA can
    partition (plain ops only)."""
    acc, _m, l = blockwise_attention(q, k, v, causal=True)
    return finalize_attention(acc, l).astype(q.dtype)


def make_sharded_attn_fn(mesh, batch_axes=("data",), head_axis=None,
                         local_attn=None):
    """Causal attention for GSPMD-partitioned train steps: a ``shard_map``
    island over (batch, heads).

    A ``pallas_call`` is an opaque custom call to XLA's SPMD partitioner
    (no partitioning rule), so it cannot sit directly inside a multi-device
    jit-with-shardings program. But attention is exactly parallel over the
    batch and head dimensions — so this wraps the whole attention in a
    ``shard_map`` whose per-device body is ordinary local code, where
    :func:`auto_attention` may legally pick the flash kernel (and still
    picks the scan off-TPU or for unblockable shapes). ``batch_axes``/
    ``head_axis`` must mirror how the enclosing step shards activations
    (tp: batch over data + heads over model; fsdp: batch over data;
    composite: batch over (data, fsdp) + heads over model), so the island
    adds no resharding — just a boundary the partitioner already agrees
    with. No collectives: in/out specs are identical and fully mapped.
    """
    from jax.sharding import PartitionSpec as P

    batch_entry = tuple(batch_axes) if not isinstance(batch_axes, str) else batch_axes
    spec = P(batch_entry, head_axis, None, None)
    local = local_attn or (lambda a, b, c: auto_attention(a, b, c, causal=True))

    def attn(q, k, v):
        # check_vma stays ON (round 3): the kernel's out_shapes declare
        # their varying axes (_vma_struct), so the checker passes — and
        # keeping it is what guarantees shard_map inserts the psums that
        # make replicated-operand gradients correct elsewhere
        f = jax.shard_map(
            local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        )
        return f(q, k, v)

    return attn


def gspmd_safe_lm(model, mesh, batch_axes=("data",), head_axis=None):
    """Give a model GSPMD-legal attention for a jit-with-shardings step.

    On a multi-device mesh the model default (:func:`auto_attention`, which
    may emit a ``pallas_call`` — illegal under pure GSPMD, see
    :func:`make_sharded_attn_fn`) is replaced by the shard_map island with
    the step's activation layout, so tp/ep/fsdp/composite keep the flash
    kernel's speed on real hardware. Models that already inject an
    ``attn_fn`` are left alone; on a 1-device mesh the direct kernel is
    safe and kept.
    """
    has_field = "attn_fn" in getattr(model, "__dataclass_fields__", {})
    if mesh.devices.size > 1 and has_field and model.attn_fn is None:
        return model.clone(
            attn_fn=make_sharded_attn_fn(mesh, batch_axes, head_axis)
        )
    return model


def _side_block_choice(n: int):
    """Largest v5e-swept block size dividing one sequence side, or None.
    THE single candidate list — every default-blocking path (forward,
    backward, per-side fallback in _resolve_flash_config) derives from it,
    so a future re-sweep edits exactly one tuple."""
    return next((c for c in (1024, 512, 256, 128) if n % c == 0), None)


def flash_block_choice(sq: int, sk: int):
    """Largest measured-good forward (block_q, block_k) dividing the sequence
    lengths, or None when no legal blocking exists (→ scan fallback).
    Preference order reflects the v5e sweep in the module docstring."""
    bq, bk = _side_block_choice(sq), _side_block_choice(sk)
    return None if bq is None or bk is None else (bq, bk)


def flash_bwd_block_choice(sq: int, sk: int):
    """Backward blocking: the fused backward's v5e sweep prefers square
    (1024, 1024) at short-to-mid sequences — larger key blocks amortize the
    per-(i, j) dq-partial write, and the kernel has no (block_q, block_k)
    score transpose asymmetry the forward has.

    At sk = 8192 exactly, (1024, 2048) wins twice over — the kernel itself
    is faster (measured 5.901 vs 6.155 ms fwd+bwd per GPT-2-small layer at
    S=8192, device-true) AND the dq-partials buffer has sk/2048 blocks
    instead of sk/1024, halving the partials reduction that follows the
    kernel (12 × 0.53 → 0.27 ms/step). The gate is deliberately exact:
    measured at sk=4096 the square blocking is faster (1.63 vs 1.68 ms),
    and at sk ≥ 16384 block_k 2048 fails to compile (scoped-vmem OOM in
    the fused backward: 16.43M > the 16M limit at S=32768; same class of
    failure b8 × S2048 hit in the round-3 sweep). block_k 4096 fails to
    compile even at sk=8192."""
    choice = flash_block_choice(sq, sk)
    if choice is not None and sk == 8192:
        return (choice[0], 2048)
    return choice
