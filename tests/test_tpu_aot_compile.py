"""Every Pallas kernel in ``ops/`` compiles for a TPU v5e — checked with no chip.

libtpu can describe a v5e topology to JAX on a machine that has none, and
``jit(f).lower(<shapes placed on those devices>).compile()`` then runs Mosaic
and XLA's TPU backend ahead of time. So "the kernel compiles" stays true on
every tier-1 run instead of being learned on the next chip run: a block shape
off the (8, 128) tiling, a scoped-VMEM overflow or an op Mosaic does not lower
fails here. Execution (that the compiled kernels give the right numbers) is
``chip_smoke.py``'s part.

``jax.default_backend`` is patched to say ``"tpu"`` so the repo's own
dispatch takes the branch it takes on the chip; every program must show
``tpu_custom_call`` in its compiled text. Kernel-only programs compile in
seconds; the whole-step programs are behind the ``slow`` marker.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

#: the three AlexNet conv outputs that feed a relu -> 2x2 pool tail, per image
ALEXNET_TAILS = [(8, 8, 64), (4, 4, 192), (2, 2, 256)]


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that cannot describe a v5e
        pytest.skip(f"libtpu cannot give a v5e topology here: {e!r}")
    assert len(topo.devices) == 4 and topo.devices[0].platform == "tpu"
    return topo.devices


@pytest.fixture
def chip_dispatch(monkeypatch):
    """The repo's ``jax.default_backend() == "tpu"`` branches, with no chip."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def on(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def assert_kernels(text: str, at_least: int = 1) -> None:
    n = text.count(CUSTOM_CALL)
    assert n >= at_least, f"{n} tpu_custom_call(s) in the compiled text"


def test_flat_axpy_compiles(v5e, chip_dispatch):
    from distributed_ml_pytorch_tpu.ops import flat_axpy

    one = SingleDeviceSharding(v5e[0])
    vec = on(one, (2_472_320,))  # raveled AlexNet, padded to 128 lanes
    assert_kernels(compiled_text(lambda y, x: flat_axpy(y, x, -0.008), vec, vec))


@pytest.mark.parametrize("batch", [64, 256, 1024])
@pytest.mark.parametrize("tail", ALEXNET_TAILS, ids=lambda t: "x".join(map(str, t)))
def test_conv_epilogues_compile_forward_and_gradient(v5e, chip_dispatch,
                                                     batch, tail):
    from distributed_ml_pytorch_tpu.ops.fused_conv import bias_relu, relu_pool2

    one = SingleDeviceSharding(v5e[0])
    x = on(one, (batch, *tail))
    b = on(one, (tail[-1],))

    def both(op):
        def f(x, b):
            y, pull = jax.vjp(op, x, b)
            return (y,) + pull(y)
        return f

    # forward + backward kernel each
    assert_kernels(compiled_text(both(relu_pool2), x, b), 2)
    assert_kernels(compiled_text(both(bias_relu), x, b), 2)


@pytest.mark.parametrize("shape", [(8, 12, 2048, 64), (1, 12, 8192, 64),
                                   (1, 16, 4096, 128)],
                         ids=lambda s: "b{}h{}s{}d{}".format(*s))
@pytest.mark.parametrize("bwd_impl", ["fused", "split"])
def test_flash_attention_compiles_forward_and_gradient(v5e, shape, bwd_impl):
    from distributed_ml_pytorch_tpu.ops.attention import flash_attention

    one = SingleDeviceSharding(v5e[0])
    q = on(one, shape, jnp.bfloat16)

    def f(q, k, v):
        out, pull = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            bwd_impl=bwd_impl), q, k, v)
        return (out,) + pull(out)

    # forward + one fused backward kernel, or + the dQ and dK/dV pair
    assert_kernels(compiled_text(f, q, q, q), 2 if bwd_impl == "fused" else 3)


@pytest.mark.parametrize("form", ["chunked", "step"])
def test_gated_delta_rule_compiles_at_the_published_sizes(v5e, form):
    """``ops/gated_delta.py`` is ``jax.numpy`` (no kernel to find in the
    text): what is held is that XLA's TPU backend takes both forms at
    Olmo-Hybrid-7B's sizes (30 heads, key 96, value 192): the chunkwise
    prefill of a 1,024-token bucket with its batched inverse by doubling, and
    one decode step over 32 slots."""
    from distributed_ml_pytorch_tpu.ops.gated_delta import (
        gated_delta_chunked,
        gated_delta_step,
    )

    one = SingleDeviceSharding(v5e[0])
    h, dk, dv = 30, 96, 192
    if form == "chunked":
        b, t = 1, 1024
        args = [on(one, (b, t, h, dk)), on(one, (b, t, h, dk)), on(one, (b, t, h, dv)),
                on(one, (b, t, h)), on(one, (b, t, h)), on(one, (b, h, dv, dk)),
                on(one, (), jnp.int32)]
        compiled = jax.jit(gated_delta_chunked).lower(*args).compile()
    else:
        s = 32
        args = [on(one, (s, h, dk)), on(one, (s, h, dk)), on(one, (s, h, dv)),
                on(one, (s, h)), on(one, (s, h)), on(one, (s, h, dv, dk))]
        compiled = jax.jit(gated_delta_step).lower(*args).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# ---------------------------------------------------------- whole programs


@pytest.mark.slow
@pytest.mark.parametrize("accum", [False, True], ids=["plain", "microbatch256"])
def test_alexnet_fused_epilogue_scan_step_compiles(v5e, chip_dispatch, accum):
    """``bench.py``'s large legs: the b1024 ``fused_epilogue=True`` scan."""
    from distributed_ml_pytorch_tpu.models import AlexNet
    from distributed_ml_pytorch_tpu.training.trainer import (
        create_train_state,
        make_scan_accum_train_step,
        make_scan_train_step,
    )

    one = SingleDeviceSharding(v5e[0])
    model = AlexNet(num_classes=10, fused_epilogue=True)
    # the init runs for real, here on the CPU: the unfused model has the
    # same parameter tree and no kernel to refuse
    state, tx = create_train_state(AlexNet(num_classes=10), jax.random.key(0),
                                   lr=0.008, sample_shape=(1, 32, 32, 3))
    step = (make_scan_accum_train_step(model, tx, 256,
                                       effective_update_batch=64)
            if accum else make_scan_train_step(model, tx))
    abstract = jax.tree.map(lambda x: on(one, x.shape, x.dtype), state)
    text = step.lower(
        abstract, on(one, (4, 1024, 32, 32, 3)),
        on(one, (4, 1024), jnp.int32),
        jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one),
    ).compile().as_text()
    assert_kernels(text, 6)  # three relu->pool tails, forward and backward


@pytest.mark.slow
@pytest.mark.parametrize("batch,seq", [(8, 2048), (1, 8192)])
def test_gpt2_small_train_step_compiles(v5e, chip_dispatch, batch, seq):
    """``examples.train_lm --mode single`` at GPT-2-small width: 12 layers of
    flash attention, forward and backward."""
    import optax

    from distributed_ml_pytorch_tpu.models import TransformerLM
    from distributed_ml_pytorch_tpu.parallel.fsdp import (
        _state_shardings,
        make_fsdp_lm_train_step,
    )
    from distributed_ml_pytorch_tpu.training.trainer import TrainState

    mesh = Mesh(np.array(v5e[:1]), ("data",))
    lm = TransformerLM(vocab_size=50304, d_model=768, n_heads=12, n_layers=12,
                       d_ff=3072, max_len=seq, dtype=jnp.bfloat16,
                       pos_encoding="rope")
    tx = optax.sgd(0.05)
    shapes = jax.eval_shape(
        lambda key: TrainState.create(
            lm.init(key, jnp.zeros((1, 8), jnp.int32))["params"], tx),
        jax.random.key(0))
    shardings = _state_shardings(mesh, shapes, "data")
    step = make_fsdp_lm_train_step(lm, tx, mesh, shardings)
    state = jax.tree.map(lambda x, s: on(s, x.shape, x.dtype), shapes, shardings)
    tokens = on(NamedSharding(mesh, P("data", None)), (batch, seq), jnp.int32)
    assert_kernels(step.lower(state, tokens, tokens).compile().as_text(), 24)


@pytest.mark.slow
def test_ring_flash_attention_compiles_on_four_chips(v5e, chip_dispatch):
    """``parallel/ring.py``'s flash ring over the 2x2 topology, forward and
    gradient — the branch no CPU mesh ever takes."""
    from distributed_ml_pytorch_tpu.parallel.ring import make_ring_attention

    mesh = Mesh(np.array(v5e), ("seq",))
    ring = make_ring_attention(mesh, "seq", causal=True)  # impl: the default
    q = on(NamedSharding(mesh, P(None, None, "seq", None)),
           (2, 12, 4 * 1024, 64), jnp.bfloat16)

    def f(q, k, v):
        out, pull = jax.vjp(ring, q, k, v)
        return (out,) + pull(out)

    assert_kernels(compiled_text(f, q, q, q), 2)


@pytest.mark.parametrize("head_dim,rows", [(64, 1024), (128, 1536)],
                         ids=["head-64", "head-128"])
def test_bounded_cache_read_copies_no_cache_leaf(v5e, chip_dispatch, head_dim, rows):
    """The pool's decode block at the two serving cells' head size and cache
    rows (32 slots, two ``TransformerLM`` layers, the rest small). Each
    attention layer reads its big caches by ONE call of the per-slot kernel
    (``ops/slot_attention``, reached through the read's batching rule under
    the pool's ``vmap``) and by no loop over chunks of the allocation. The
    device keeps a cache of 64-wide heads with its rows along the lanes, and a
    kernel takes its operands in the layout their shape has by default: handed
    over the wrong way round, every big cache is copied whole (21 MB each
    here, twice that padded). The compiled program holds no temporary of that
    order, and its one conditional is the sampler's: the read multiplies no
    code."""
    from distributed_ml_pytorch_tpu.models.generate import (
        _decode_model,
        _fuse_qkv_params,
        init_cache,
    )
    from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
    from distributed_ml_pytorch_tpu.serving.cache import _decode_block_jit

    jax.clear_caches()  # a program traced off the chip took the other side of the rule
    one = SingleDeviceSharding(v5e[0])
    heads, slots, layers = 4, 32, 2
    lm = TransformerLM(vocab_size=512, d_model=heads * head_dim, n_heads=heads,
                       n_layers=layers, d_ff=256, max_len=rows, dtype=jnp.bfloat16)
    dec = _decode_model(lm, rows, decode_block=16)
    placed = lambda tree, lead=(): jax.tree.map(
        lambda a: on(one, lead + a.shape, a.dtype), tree)
    params = placed(jax.eval_shape(lambda: _fuse_qkv_params(jax.tree.map(
        lambda a: a.astype(jnp.bfloat16),
        lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]))))
    pool = placed(jax.eval_shape(
        lambda: init_cache(lm, 1, rows, decode_block=16)), (slots,))
    leaf = 2 * slots * heads * rows * head_dim  # one big cache, bytes
    vec = lambda dt: on(one, (slots,), dt)
    compiled = _decode_block_jit.lower(
        dec, params, pool, vec(jnp.int32), vec(jnp.int32), vec(jnp.uint32),
        vec(jnp.float32), vec(jnp.int32), vec(jnp.float32),
        vec(jnp.bool_)).compile()
    jax.clear_caches()
    assert compiled.memory_analysis().temp_size_in_bytes < leaf
    text = compiled.as_text()
    assert text.count(" conditional(") == 1
    assert text.count(CUSTOM_CALL) == layers
    # the scan and the merge's four scatter loops; no read's loop
    assert text.count(" while(") == 1 + 4


def test_latent_moe_pool_programs_compile_at_the_published_widths(v5e, chip_dispatch):
    """``LatentMoELM``'s admission and decode block through the pool's two
    programs, every width as ``kanana-2-30b-a3b`` publishes it (128 experts of
    768, 32 heads, latent 512 + rope 64), one dense and one expert layer, a
    small vocabulary and pool. The routed experts of both programs are grouped
    products by the Pallas grouped-matmul kernel, three an expert layer: the
    admission's over its bucket's pairs, the decode block's ONE set over the
    whole pool's 24 x 6 pairs padded to two tiles of 128 (the expert layer's
    batching rule), which reads the experts the live slots chose where they
    lie and no others. So the decode block holds no temporary of the order of
    a layer's routed weights (a gather of each slot's experts would be 24 x 6 x
    9.4 MB, a copy of the layer 1.2 GB), no ``ragged-dot`` (the compiler's own
    rewrite, which ``moe_group_roofline.history`` would read as an
    admission's), no scatter loop beyond the merge's, and one loop a layer for
    the bounded read of the latent rows."""
    import json
    import os

    from benchmarks.reference import deepseek_v3_mla_moe as ref
    from distributed_ml_pytorch_tpu.models.generate import _decode_model, init_cache
    from distributed_ml_pytorch_tpu.models.latent_moe import LatentMoELM
    from distributed_ml_pytorch_tpu.serving.cache import _admit_jit, _decode_block_jit

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "configs", "kanana-2-30b-a3b.json")
    with open(path) as fh:
        cfg = dict(json.load(fh), num_hidden_layers=2, vocab_size=1024)
    one = SingleDeviceSharding(v5e[0])
    slots, rows, bucket = 24, 512, 256  # 24 x 6 pairs: two row tiles, as the cell's 64 x 6 are three
    lm = LatentMoELM.from_config(cfg, dtype=jnp.bfloat16)
    dec = _decode_model(lm, rows, decode_block=16)
    placed = lambda tree, lead=(): jax.tree.map(
        lambda a: on(one, lead + a.shape, a.dtype), tree)
    params = placed(jax.eval_shape(lambda k: ref.make_params(k, cfg, jnp.bfloat16),
                                   jax.random.key(0)))
    pool = placed(jax.eval_shape(lambda: init_cache(lm, 1, rows, decode_block=16)), (slots,))
    vec, scalar = (lambda dt: on(one, (slots,), dt)), (lambda dt: on(one, (), dt))
    admit = _admit_jit.lower(
        dec, params, pool, scalar(jnp.int32), on(one, (1, bucket), jnp.int32), scalar(jnp.int32),
        scalar(jnp.uint32), scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.float32),
        scalar(jnp.int32)).compile()
    # three grouped products an expert layer, the Pallas kernel and not the
    # compiler's own rewrite of ragged_dot (tiles of 512 rows)
    assert_kernels(admit.as_text(), 3)
    assert "ragged-dot" not in admit.as_text()
    decode = _decode_block_jit.lower(
        dec, params, pool, vec(jnp.int32), vec(jnp.int32), vec(jnp.uint32), vec(jnp.float32),
        vec(jnp.int32), vec(jnp.float32), vec(jnp.bool_)).compile()
    expert = 2 * 3 * 2048 * 768  # one expert's weights, bytes
    assert decode.memory_analysis().temp_size_in_bytes < 24 * expert
    text = decode.as_text()
    assert_kernels(text, 3)
    assert "ragged-dot" not in text
    # the scan, the merge's scatter loop a layer, the bounded read's loop a layer,
    # and in the expert layer the kernel's own group metadata: the bisection of a
    # 128-entry histogram over the row tiles (megablox ``make_group_metadata``,
    # once for the three products; every admission has the same one)
    assert text.count(" while(") == 1 + 2 + 2 + 1
