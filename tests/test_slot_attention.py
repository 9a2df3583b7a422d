"""The per-slot read of a decode step's big K/V caches (``ops/slot_attention``)
against the loop it replaces under a pool's ``vmap``.

``bounded_cache_attention`` called plainly is the loop over chunks of the
allocation as far as one bound. Mapped over a pool's lanes, its batching rule
hands every lane's ring base to one Pallas kernel that reads each slot's rows
and no others; here the kernel runs in interpret mode. Both compute one
attention, so they agree to float32 rounding wherever their weights round
alike: in float32, and in bfloat16 where every score's maximum is known
before the first chunk (the self term's), so that neither read rescales
weights it has already rounded.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models import transformer as tm
from distributed_ml_pytorch_tpu.ops import slot_attention as sa
from distributed_ml_pytorch_tpu.ops.fused_update import force_pallas_interpret

ROWS, T = 384, 4
#: a slot of length 0 (an idle one), a block exactly, a row past one, the
#: whole allocation, and lengths inside the first and the second block
LENGTHS = (0, 128, 129, ROWS, 37, 255)


@pytest.fixture
def kernel_here(monkeypatch):
    """The kernel in interpret mode, in blocks of 128 rows (the smallest that
    fills the lanes: a tiny cache's block would hold the whole allocation),
    with JAX's caches cleared on entry and exit: a traced program remembers
    which side of the rule it took."""
    monkeypatch.setattr(sa, "BLOCK_BYTES", 1)
    jax.clear_caches()
    with force_pallas_interpret():
        yield
    jax.clear_caches()


def pool_operands(heads, head_dim, dtype, dominant_self, seed=0):
    """One lane a slot, batch 1 in each, as ``SlotKVPool`` maps them."""
    S = len(LENGTHS)
    k = jax.random.split(jax.random.key(seed), 7)
    lane = lambda key, *shape: jax.random.normal(key, (S, 1, heads) + shape)
    q, v = lane(k[0], 1, head_dim).astype(dtype), lane(k[1], 1, head_dim).astype(dtype)
    cache_k, cache_v = (lane(key, ROWS, head_dim).astype(dtype) for key in k[2:4])
    ring_v = lane(k[4], T, head_dim).astype(dtype)
    scale = jnp.sqrt(jnp.float32(head_dim))
    s_ring = lane(k[5], 1, T) * scale
    s_self = lane(k[6], 1) * scale
    if dominant_self:
        # the self term holds every row's largest score (no more than that:
        # the cache's weights stay of order one)
        scores = jnp.einsum("sbhqd,sbhrd->sbhqr", q.astype(jnp.float32),
                            cache_k.astype(jnp.float32))
        s_self = jnp.maximum(jnp.max(scores, axis=-1), jnp.max(s_ring, axis=-1))
    ring_base = jnp.asarray(LENGTHS, jnp.int32)
    return dict(q=q, v=v, s_ring=s_ring, s_self=s_self, scale=scale, ring_base=ring_base,
                ring_v=ring_v, cache_k=cache_k, cache_v=cache_v)


def mapped(read, ops, bound, turned):
    """``read`` mapped over the lanes as the pool maps the model: the bound
    closed over, so one scalar for the pool; with ``turned`` the caches are
    handed over in the view a TPU keeps 64-wide heads in."""
    def lane(q, v, s_ring, s_self, ring_base, ring_v, cache_k, cache_v):
        return read(bound, q, v, s_ring, s_self, ops["scale"], ring_base, ring_v,
                    cache_k, cache_v, None, None)

    return jax.vmap(lane)(*(ops[n] for n in (
        "q", "v", "s_ring", "s_self", "ring_base", "ring_v", "cache_k", "cache_v")))


@pytest.mark.parametrize("dtype,dominant_self",
                         [(jnp.float32, False), (jnp.bfloat16, True)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("heads,head_dim,turned",
                         [(3, 64, True), (2, 128, False)],
                         ids=["head-64-rows-minor", "head-128-as-written"])
def test_the_kernel_reads_what_the_loop_reads(kernel_here, heads, head_dim, turned,
                                              dtype, dominant_self):
    """A pool of ragged lengths: every slot's output is the loop's over the
    whole allocation, to float32 rounding, and the kernel is what ran."""
    ops = pool_operands(heads, head_dim, dtype, dominant_self)
    bound = jnp.asarray(ROWS, jnp.int32)
    per_slot = partial(tm.bounded_cache_attention, dtype=dtype, turned=turned)
    loop = partial(tm._bounded_read, dtype=dtype, turned=turned)
    jaxpr = jax.make_jaxpr(lambda b: mapped(per_slot, ops, b, turned))(bound)
    assert "pallas_call" in str(jaxpr)
    got = mapped(per_slot, ops, bound, turned)
    want = mapped(loop, ops, bound, turned)
    assert got.shape == want.shape == (len(LENGTHS), 1, heads, 1, head_dim)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("bound", [0, 128, ROWS])
def test_the_pools_bound_is_not_read(kernel_here, bound):
    """Each slot reads as far as its own ring base whatever the pool's bound
    says; an idle slot (length 0) gets the ring and self terms alone, finite,
    which the pool then discards."""
    ops = pool_operands(2, 128, jnp.float32, False, seed=1)
    per_slot = partial(tm.bounded_cache_attention, dtype=jnp.float32, turned=False)
    got = np.asarray(mapped(per_slot, ops, jnp.asarray(bound, jnp.int32), False))
    want = np.asarray(mapped(partial(tm._bounded_read, dtype=jnp.float32, turned=False),
                             ops, jnp.asarray(ROWS, jnp.int32), False))
    np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    assert np.isfinite(got).all()
    idle = np.asarray(mapped(partial(tm._bounded_read, dtype=jnp.float32, turned=False),
                             ops, jnp.asarray(0, jnp.int32), False))[0]
    np.testing.assert_allclose(got[0], idle, rtol=2e-6, atol=2e-6)


def test_int8_caches_take_the_loop(kernel_here):
    """The kernel takes no per-row scales: an int8 pool's lanes run the loop,
    as ``vmap``'s own rule would run it."""
    ops = pool_operands(2, 64, jnp.float32, False, seed=2)
    q8 = {n: jnp.clip(jnp.round(ops[n] * 20), -127, 127).astype(jnp.int8)
          for n in ("cache_k", "cache_v")}
    scales = jax.random.uniform(jax.random.key(9), (len(LENGTHS), 1, 2, ROWS)) / 20

    def run(read):
        def lane(q, v, s_ring, s_self, ring_base, ring_v, ck, cv, sk, sv):
            return read(jnp.asarray(ROWS, jnp.int32), q, v, s_ring, s_self, ops["scale"],
                        ring_base, ring_v, ck, cv, sk, sv)
        return jax.vmap(lane)(ops["q"], ops["v"], ops["s_ring"], ops["s_self"],
                              ops["ring_base"], ops["ring_v"], q8["cache_k"], q8["cache_v"],
                              scales, scales * 2)

    per_slot = partial(tm.bounded_cache_attention, dtype=jnp.float32, turned=False)
    loop = partial(tm._bounded_read, dtype=jnp.float32, turned=False)
    assert "pallas_call" not in str(jax.make_jaxpr(lambda: run(per_slot))())
    np.testing.assert_allclose(np.asarray(run(per_slot)), np.asarray(run(loop)),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("lengths", [[0, 0, 0], [1, 128, 129, 384], [300, 0, 5]])
def test_the_work_list_holds_the_live_pairs_in_slot_order(lengths):
    """``(count, slot, block)``: a pair for every block that holds a row of
    its slot, slot by slot, and none for a slot of length 0."""
    count, slot, block = sa.work_list(jnp.asarray(lengths, jnp.int32), 128, 384)
    n = int(count[0])
    assert slot.shape == block.shape == (len(lengths) * 3,)
    pairs = list(zip(np.asarray(slot)[:n].tolist(), np.asarray(block)[:n].tolist()))
    assert pairs == [(s, b) for s, length in enumerate(lengths)
                     for b in range(-(-length // 128))]


@pytest.mark.parametrize("rows,heads,head_dim,want", [
    (1024, 20, 64, 128),    # gpt2-large: 320 KB a block
    (1536, 30, 128, 128),   # Olmo-Hybrid-7B's full layers: the smallest tile
    (1024, 2, 64, 1024),    # a small model: the whole allocation fits a block
    (96, 4, 8, 96),         # no multiple of 128 divides it: one block
])
def test_a_block_fills_whole_lane_tiles(rows, heads, head_dim, want):
    assert sa.kv_block_rows(rows, heads, head_dim, 2) == want


def test_a_pool_decodes_through_the_kernel_what_generate_decodes(kernel_here):
    """Requests of ragged lengths through ``ServingEngine``, the kernel doing
    every attention layer's big-cache read (one kernel call a layer in the
    scanned step, and no loop over chunks of the allocation): each request
    gets the tokens a standalone ``generate()`` gives it."""
    from distributed_ml_pytorch_tpu.models.generate import generate
    from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
    from distributed_ml_pytorch_tpu.serving.cache import _decode_block_jit
    from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

    model = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                          max_len=256)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(model, params, slots=3, cache_size=256, decode_block=4,
                        prefill_bucket=8)
    assert eng.pool.slot_block_rows == 128
    S = eng.pool.slots
    jaxpr = jax.make_jaxpr(_decode_block_jit, static_argnums=(0,))(
        eng.pool.dec, eng.pool.params, eng.pool.cache, jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.uint32), jnp.zeros(S, jnp.float32),
        jnp.zeros(S, jnp.int32), jnp.ones(S, jnp.float32), jnp.ones(S, bool))

    def primitives(jaxpr):
        for eqn in jaxpr.eqns:
            yield eqn.primitive.name
            if eqn.primitive.name != "pallas_call":  # not into the kernel's own body
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from primitives(sub)

    found = list(primitives(jaxpr.jaxpr))
    # one kernel a layer, and no loop over chunks of the allocation
    assert found.count("pallas_call") == 2 and found.count("while") == 0
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 64, size=n) for n in (5, 140, 30)]
    reqs = [eng.submit(p, 20) for p in prompts]
    eng.run_until_idle()
    for p, req in zip(prompts, reqs):
        want = generate(model, params, jnp.asarray(p, jnp.int32)[None], 20)
        assert req.tokens == np.asarray(want)[0, len(p):].tolist()
