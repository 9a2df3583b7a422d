"""Kernel tests: Pallas flat-axpy + flash attention vs. naive references.

Pallas kernels run in interpreter mode on the CPU test mesh, always by
explicit request (``interpret=True`` / ``force_pallas_interpret``): Mosaic
only compiles for a real TPU, and no wrapper picks interpret mode on its own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.ops import (
    attention_reference,
    blockwise_attention,
    downpour_accumulate,
    flash_attention,
    flat_axpy,
)
from distributed_ml_pytorch_tpu.ops.attention import finalize_attention
from distributed_ml_pytorch_tpu.ops.fused_update import force_pallas_interpret


@pytest.mark.parametrize("n", [128 * 256, 1000, 7])
def test_flat_axpy_pallas_matches_reference(n):
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=n).astype(np.float32))
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    with force_pallas_interpret():
        got = flat_axpy(y, x, -0.05)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(y) - 0.05 * np.asarray(x), rtol=1e-5, atol=1e-6
    )


def test_flat_axpy_fallback_path():
    y = jnp.arange(10, dtype=jnp.float32)
    x = jnp.ones(10, jnp.float32)
    np.testing.assert_allclose(np.asarray(flat_axpy(y, x, 2.0)), np.arange(10) + 2.0)


def test_downpour_accumulate_prescales_by_neg_lr():
    accum = jnp.zeros(5, jnp.float32)
    grads = jnp.ones(5, jnp.float32)
    out = downpour_accumulate(accum, grads, lr=0.1)
    np.testing.assert_allclose(np.asarray(out), -0.1 * np.ones(5), rtol=1e-6)


def _qkv(b=2, h=2, sq=256, sk=256, d=64, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda s: jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    return mk(sq), mk(sk), mk(sk)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_matches_reference(causal):
    q, k, v = _qkv()
    want = attention_reference(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sk", [256, 300])  # 300 exercises the ragged-pad path
def test_blockwise_attention_matches_reference(causal, sk):
    q, k, v = _qkv(sq=256 if causal else 128, sk=sk)
    if causal and sk != q.shape[2]:
        pytest.skip("causal is defined for sq == sk")
    want = attention_reference(q, k, v, causal=causal)
    acc, _m, l = blockwise_attention(q, k, v, causal=causal, block_k=128)
    got = finalize_attention(acc, l)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_blockwise_attention_bf16_accumulates_in_f32():
    q, k, v = _qkv(sq=128, sk=256)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    acc, m, l = blockwise_attention(qb, kb, vb, block_k=64)
    assert acc.dtype == jnp.float32 and l.dtype == jnp.float32
    got = finalize_attention(acc, l)
    want = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-2, rtol=3e-2)


def test_blockwise_attention_is_differentiable():
    q, k, v = _qkv(b=1, h=1, sq=128, sk=128, d=32)

    def loss(q, k, v):
        acc, _m, l = blockwise_attention(q, k, v, causal=True, block_k=64)
        return jnp.sum(finalize_attention(acc, l) ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
        assert float(jnp.abs(g).max()) > 0.0


def test_blockwise_attention_fully_masked_rows_are_empty():
    """A chunk whose keys are all causally after the queries must contribute
    nothing: acc == 0 and l == 0 (ring attention's not-yet-arrived case)."""
    q, k, v = _qkv(b=1, h=1, sq=64, sk=128)
    acc, _m, l = blockwise_attention(q, k, v, causal=True, q_offset=0, k_offset=64)
    assert float(jnp.abs(acc).max()) == 0.0
    assert float(jnp.abs(l).max()) == 0.0


def test_flash_attention_rejects_causal_cross_lengths():
    q, k, v = _qkv(sq=128, sk=256)
    with pytest.raises(ValueError):
        flash_attention(q, k, v, causal=True)


def test_blockwise_attention_offsets_shift_causal_mask():
    """With q_offset = sk (queries globally after all keys), causal masking
    must reduce to full attention over the keys — the invariant ring
    attention relies on for later-arriving chunks."""
    q, k, v = _qkv(b=1, h=1, sq=64, sk=128)
    acc, _m, l = blockwise_attention(q, k, v, causal=True, q_offset=128, k_offset=0)
    got = finalize_attention(acc, l)
    want = attention_reference(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bwd_impl", ["fused", "split"])
def test_flash_attention_gradients_match_reference(causal, bwd_impl):
    """Both backward implementations (the one-recompute fused kernel and the
    two-kernel split) must agree with autodiff through the dense reference —
    including with backward blocking different from the forward's (the
    production default) so the dq-partials layout is exercised."""
    rng = np.random.default_rng(5)
    b, h, s, d = 2, 2, 256, 64
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
               for _ in range(3))

    def f(q, k, v):
        return flash_attention(q, k, v, causal=causal, block_q=128,
                               block_k=128, block_q_bwd=256, block_k_bwd=128,
                               bwd_impl=bwd_impl, interpret=True).sum()

    def r(q, k, v):
        return attention_reference(q, k, v, causal=causal).sum()

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_lse_matches_reference(causal):
    """flash_attention_lse must return the dense output AND the per-row
    natural logsumexp of the scaled (masked) scores."""
    from distributed_ml_pytorch_tpu.ops.attention import flash_attention_lse

    rng = np.random.default_rng(11)
    b, h, s, d = 2, 2, 256, 64
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
               for _ in range(3))
    out, lse = flash_attention_lse(q, k, v, causal=causal,
                                   block_q=128, block_k=128, interpret=True)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d**-0.5
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(mask, scores, -jnp.inf)
    want_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attention_reference(q, k, v, causal=causal)),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("bwd_impl", ["fused", "split"])
def test_flash_attention_lse_cotangent_reaches_inputs(bwd_impl):
    """A loss that consumes BOTH outputs (as ring attention's combine does)
    must produce reference gradients — the dlse cotangent folds into the
    backward delta."""
    from distributed_ml_pytorch_tpu.ops.attention import flash_attention_lse

    rng = np.random.default_rng(12)
    b, h, s, d = 1, 2, 256, 64
    q, k, v = (jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
               for _ in range(3))

    def f(q, k, v):
        out, lse = flash_attention_lse(q, k, v, causal=True, block_q=128,
                                       block_k=128, bwd_impl=bwd_impl,
                                       interpret=True)
        return jnp.sum(out**2) + jnp.sum(jnp.sin(lse))

    def r(q, k, v):
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d**-0.5
        scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
        lse = jax.scipy.special.logsumexp(scores, axis=-1)
        return jnp.sum(out**2) + jnp.sum(jnp.sin(lse))

    got = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(r, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-2, atol=2e-2)


def test_flash_bwd_impl_auto_selects_split_at_extreme_length(monkeypatch):
    """Beyond FUSED_BWD_PARTIALS_CAP the lean split backward must be chosen
    so extreme-length gradients stay compilable (code-review r3 finding)."""
    from distributed_ml_pytorch_tpu.ops import attention as A

    chosen = []
    real = A._flash

    def spy(causal, blocks, bwd_blocks, interpret, bwd_impl, q, k, v):
        chosen.append(bwd_impl)
        return real(causal, blocks, bwd_blocks, interpret, bwd_impl, q, k, v)

    monkeypatch.setattr(A, "_flash", spy)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 256, 64)), jnp.float32)
               for _ in range(3))
    A.flash_attention(q, k, v, causal=True, interpret=True)
    assert chosen[-1] == "fused"
    monkeypatch.setattr(A, "FUSED_BWD_PARTIALS_CAP", 1)  # force the cap
    A.flash_attention(q, k, v, causal=True, interpret=True)
    assert chosen[-1] == "split"


def test_flash_block_choice_prefers_large_and_falls_back():
    from distributed_ml_pytorch_tpu.ops.attention import flash_block_choice

    assert flash_block_choice(2048, 2048) == (1024, 1024)
    assert flash_block_choice(512, 256) == (512, 256)
    assert flash_block_choice(384, 384) == (128, 128)
    assert flash_block_choice(200, 512) is None  # no divisor → scan path


def test_auto_attention_matches_reference_off_tpu():
    """On the CPU test backend auto_attention takes the scan path and must
    equal the dense reference (the flash path's numerics are covered by the
    kernel tests above)."""
    from distributed_ml_pytorch_tpu.ops.attention import auto_attention

    rng = np.random.default_rng(6)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, 128, 32)), jnp.float32)
               for _ in range(3))
    got = auto_attention(q, k, v, causal=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_gspmd_safe_lm_pins_sharded_island_on_multidevice_mesh():
    """GSPMD step factories must not embed a bare pallas custom call (no
    SPMD partitioning rule) — models with a default attn_fn get a
    shard_map attention island on multi-device meshes, stay untouched on
    1-device meshes, and injected attn_fns are never overridden."""
    from distributed_ml_pytorch_tpu.models import TransformerLM
    from distributed_ml_pytorch_tpu.models.moe import MoETransformerLM
    from distributed_ml_pytorch_tpu.ops.attention import gspmd_safe_lm
    from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

    mesh8 = make_mesh({"data": 8})
    mesh1 = make_mesh({"data": 1}, devices=jax.devices()[:1])
    for cls in (TransformerLM, MoETransformerLM):
        m = cls(vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64)
        pinned = gspmd_safe_lm(m, mesh8)
        assert pinned is not m and pinned.attn_fn is not None
        assert gspmd_safe_lm(m, mesh1) is m
        injected = m.clone(attn_fn=attention_reference)
        assert gspmd_safe_lm(injected, mesh8).attn_fn is attention_reference


def test_sharded_attn_island_matches_reference():
    """The shard_map attention island (batch over data, heads over model)
    must reproduce dense causal attention exactly — attention is parallel
    over (batch, heads), so sharding adds nothing numerically."""
    from distributed_ml_pytorch_tpu.ops.attention import make_sharded_attn_fn
    from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"data": 2, "model": 4})
    attn = make_sharded_attn_fn(mesh, batch_axes=("data",), head_axis="model")
    rng = np.random.default_rng(8)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 4, 128, 32)), jnp.float32)
               for _ in range(3))
    got = jax.jit(attn)(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and it is differentiable end-to-end (the GSPMD step trains through it)
    g = jax.jit(jax.grad(lambda q: attn(q, k, v).sum()))(q)
    assert np.isfinite(np.asarray(g)).all()


def test_flash_bwd_block_choice_gates_long_key_blocks():
    """The (·, 2048) backward key block applies at sk == 8192 EXACTLY:
    measured faster there (and it halves the dq-partials reduce), but
    slower at 4096 and scoped-vmem-OOM at >= 16384 (see the docstring's
    measurements) — the gate must not widen silently."""
    from distributed_ml_pytorch_tpu.ops.attention import (
        flash_bwd_block_choice,
    )

    assert flash_bwd_block_choice(8192, 8192) == (1024, 2048)
    assert flash_bwd_block_choice(2048, 2048) == (1024, 1024)
    assert flash_bwd_block_choice(4096, 4096) == (1024, 1024)
    assert flash_bwd_block_choice(16384, 16384) == (1024, 1024)
    assert flash_bwd_block_choice(32768, 32768) == (1024, 1024)


def test_flash_bwd_2048_key_block_grads_match_reference():
    """The sk=8192 backward blocking computes the same gradients as the
    square blocking (interpret mode, small head count)."""
    rng = np.random.default_rng(11)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 8192, 8)), jnp.float32)
               for _ in range(3))

    def loss(blocks):
        def f(q, k, v):
            return flash_attention(
                q, k, v, causal=True, block_q_bwd=blocks[0],
                block_k_bwd=blocks[1], interpret=True).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    g_a = loss((1024, 1024))
    g_b = loss((1024, 2048))
    for a, b in zip(g_a, g_b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_flash_attention_default_blocks_adapt_to_sequence():
    """Default (unspecified) blocks must derive from flash_block_choice so
    lengths like 1536 — divisible by 512 but not 1024 — still work."""
    rng = np.random.default_rng(7)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 1, 1536, 8)), jnp.float32)
               for _ in range(3))
    got = flash_attention(q, k, v, causal=True, interpret=True)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-4)


def test_sharded_attn_island_runs_pallas_kernel():
    """The island's purpose is hosting the Pallas kernel under shard_map —
    exercised here with interpret-mode flash (the CPU analog of the TPU
    path auto_attention takes), guarding against shard_map/pallas interop
    regressions (e.g. the check_vma rejection of custom-call bodies)."""
    from distributed_ml_pytorch_tpu.ops.attention import make_sharded_attn_fn
    from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

    mesh = make_mesh({"data": 2, "model": 4})
    attn = make_sharded_attn_fn(
        mesh, batch_axes=("data",), head_axis="model",
        local_attn=lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=True),
    )
    rng = np.random.default_rng(9)
    q, k, v = (jnp.asarray(rng.normal(size=(2, 4, 128, 32)), jnp.float32)
               for _ in range(3))
    got = jax.jit(attn)(q, k, v)
    want = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    g = jax.jit(jax.grad(lambda q: attn(q, k, v).sum()))(q)
    assert np.isfinite(np.asarray(g)).all()
