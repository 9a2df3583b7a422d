"""Ring attention on the 8-device virtual CPU mesh vs. full attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributed_ml_pytorch_tpu.ops import attention_reference
from distributed_ml_pytorch_tpu.ops.fused_update import force_pallas_interpret
from distributed_ml_pytorch_tpu.parallel.ring import make_ring_attention
from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh({"seq": 8})


def _qkv(b=2, h=2, s=256, d=32, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, s, d)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(seq_mesh, causal):
    q, k, v = _qkv()
    fn = make_ring_attention(seq_mesh, "seq", causal=causal, block_k=16)
    got = fn(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)


def test_ring_attention_output_stays_sharded(seq_mesh):
    q, k, v = _qkv()
    spec = P(None, None, "seq", None)
    q = jax.device_put(q, NamedSharding(seq_mesh, spec))
    out = make_ring_attention(seq_mesh, "seq")(q, k, v)
    assert out.sharding.spec == spec


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_is_differentiable(seq_mesh, causal):
    q, k, v = _qkv(b=1, h=1, s=64, d=16)
    fn = make_ring_attention(seq_mesh, "seq", causal=causal, block_k=8)

    def ring_loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_matches_full(seq_mesh, causal):
    """The flash-kernel ring (chunk-level logsumexp combine) must agree with
    dense attention — interpret-mode flash on the CPU mesh, the analog of
    the TPU path where local chunks fit the kernel blocking."""
    q, k, v = _qkv(s=1024, d=32)  # s_local = 128 = min flash block
    fn = make_ring_attention(seq_mesh, "seq", causal=causal, impl="flash")
    with force_pallas_interpret():
        got = fn(q, k, v)
    want = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=1e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_attention_is_differentiable(seq_mesh, causal):
    """Gradients through the flash ring: the per-chunk lse outputs carry
    cotangents (the combine weights depend on them), exercising the
    dlse→delta folding in the kernel backward."""
    q, k, v = _qkv(b=1, h=1, s=1024, d=16, seed=3)
    fn = make_ring_attention(seq_mesh, "seq", causal=causal, impl="flash")

    def ring_loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    def ref_loss(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=causal) ** 2)

    with force_pallas_interpret():
        got = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=5e-4, rtol=5e-3)


def test_ring_attention_long_context_smoke(seq_mesh):
    """8k tokens over 8 devices — each device only ever holds 1k."""
    q, k, v = _qkv(b=1, h=1, s=8192, d=32)
    out = make_ring_attention(seq_mesh, "seq", causal=True, block_k=256)(q, k, v)
    assert out.shape == (1, 1, 8192, 32)
    assert bool(jnp.isfinite(out).all())
