"""Autoregressive decoding: KV-cache incremental attention must match the
full causal forward, and generation must be deterministic and well-shaped."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models.generate import generate, init_cache
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM


def tiny_lm():
    return TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64
    )


def trained_ish_params(model, seed=0):
    return model.init(jax.random.key(seed), jnp.zeros((1, 8), jnp.int32))["params"]


def test_incremental_decode_matches_full_forward():
    """Prefill + token-by-token cached decode must reproduce the full causal
    forward's logits at every position."""
    model = tiny_lm()
    params = trained_ish_params(model)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, size=(2, 10)), jnp.int32
    )

    full_logits = model.apply({"params": params}, tokens)  # [2, 10, 64]

    dec = model.clone(decode=True, cache_size=10, attn_fn=None)
    cache = init_cache(model, 2, 10)
    got = []
    for t in range(10):
        logits, mutated = dec.apply(
            {"params": params, "cache": cache},
            tokens[:, t : t + 1],
            jnp.full((2, 1), t, jnp.int32),
            mutable=["cache"],
        )
        cache = mutated["cache"]
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full_logits), rtol=2e-4, atol=2e-5)


def test_prefill_block_matches_full_forward():
    """Multi-token prefill writes the cache identically to token-by-token."""
    model = tiny_lm()
    params = trained_ish_params(model)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(2, 8)), jnp.int32
    )
    full_logits = model.apply({"params": params}, tokens)

    dec = model.clone(decode=True, cache_size=8, attn_fn=None)
    cache = init_cache(model, 2, 8)
    logits, _ = dec.apply(
        {"params": params, "cache": cache},
        tokens,
        jnp.arange(8)[None, :],
        mutable=["cache"],
    )
    np.testing.assert_allclose(np.asarray(logits), np.asarray(full_logits), rtol=2e-4, atol=2e-5)


def test_greedy_generate_is_deterministic_and_shaped():
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    out1 = generate(model, params, prompt, max_new_tokens=7)
    out2 = generate(model, params, prompt, max_new_tokens=7)
    assert out1.shape == (2, 10)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    np.testing.assert_array_equal(np.asarray(out1[:, :3]), np.asarray(prompt))


def test_greedy_matches_naive_rollout():
    """Cached greedy decode must pick the same tokens as re-running the full
    forward on the growing sequence each step (the O(n^2)-per-token oracle)."""
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray([[7, 8, 9, 10]], jnp.int32)
    fast = generate(model, params, prompt, max_new_tokens=5)

    seq = prompt
    for _ in range(5):
        logits = model.apply({"params": params}, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(seq))


def test_temperature_sampling_reproducible_and_varied():
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    a = generate(model, params, prompt, 8, temperature=1.0, rng=jax.random.key(3))
    b = generate(model, params, prompt, 8, temperature=1.0, rng=jax.random.key(3))
    c = generate(model, params, prompt, 8, temperature=1.0, rng=jax.random.key(4))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c)), "rng had no effect"


def test_zero_new_tokens_returns_prompt_unchanged():
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray([[1, 2, 3]], jnp.int32)
    out = generate(model, params, prompt, max_new_tokens=0)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(prompt))


def test_decode_rejects_injected_attn_fn():
    model = tiny_lm().clone(decode=True, cache_size=8, attn_fn=lambda q, k, v: q)
    with pytest.raises(ValueError, match="attn_fn"):
        model.init(jax.random.key(0), jnp.zeros((1, 1), jnp.int32))


def test_temperature_requires_rng_and_max_len_enforced():
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    with pytest.raises(ValueError, match="rng"):
        generate(model, params, prompt, 4, temperature=0.7)
    with pytest.raises(ValueError, match="max_len"):
        generate(model, params, prompt, 63)  # 2 + 63 > max_len 64


def test_blocked_decode_matches_unblocked_scan():
    """Runs long enough to use the ring-buffered block path (>= DECODE_BLOCK
    steps, spanning several merge boundaries) must pick exactly the same
    greedy tokens as the plain one-token scan. Exactness is a CPU contract
    (this suite's platform): on the MXU the blocked concat-softmax and the
    fused QKV matmul reorder low-bit f32 accumulation, which legitimately
    flips near-ties of a random-init model (see generate.py's numerics
    contract)."""
    from distributed_ml_pytorch_tpu.models.generate import (
        DECODE_BLOCK,
        _decode_model,
        _generate_jit,
    )

    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=128
    )
    params = trained_ish_params(model)
    prompt = jnp.asarray(
        np.random.default_rng(2).integers(0, 64, size=(2, 5)), jnp.int32
    )
    n = 2 * DECODE_BLOCK + 3  # crosses two merge boundaries + a padded tail
    blocked = generate(model, params, prompt, n)

    total = 5 + n
    cache = init_cache(model, 2, total)
    ref = _generate_jit(
        _decode_model(model, total), n, 0.0, 0, 1.0,
        params, cache, prompt, jax.random.key(0)
    )
    np.testing.assert_array_equal(np.asarray(blocked), np.asarray(ref))


def test_single_token_prompt_long_generation_correct():
    """A (B, 1) prompt must NOT take the blocked path (its prefill would be
    indistinguishable from a decode step and the prompt's K/V would be
    orphaned in the ring — found by review); it must match the naive
    rollout exactly."""
    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=64
    )
    params = trained_ish_params(model)
    prompt = jnp.asarray([[7], [13]], jnp.int32)
    fast = generate(model, params, prompt, 20)

    seq = prompt
    for _ in range(20):
        logits = model.apply({"params": params}, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(seq))


def test_blocked_decode_cache_has_rings():
    """The blocked clone's cache carries per-layer rings; the plain clone's
    does not (the standalone one-token module contract is unchanged)."""
    model = tiny_lm()
    plain = init_cache(model, 1, 32)
    ringed = init_cache(model, 1, 32, decode_block=8)
    flat_plain = {"/".join(str(k) for k in p): v.shape
                  for p, v in jax.tree_util.tree_leaves_with_path(plain)}
    assert not any("ring" in k for k in flat_plain)
    flat_ring = {jax.tree_util.keystr(p): v.shape
                 for p, v in jax.tree_util.tree_leaves_with_path(ringed)}
    rings = [s for k, s in flat_ring.items() if "ring_k" in k]
    assert len(rings) == model.n_layers and all(s[2] == 8 for s in rings)


def test_quantize_kv_roundtrip_error_bounded():
    from distributed_ml_pytorch_tpu.models.transformer import quantize_kv

    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(2, 4, 16, 32)) * 0.3,
        jnp.bfloat16,
    )
    q8, scale = quantize_kv(x)
    assert q8.dtype == jnp.int8 and scale.shape == (2, 4, 16)
    deq = np.asarray(q8, np.float32) * np.asarray(scale)[..., None]
    err = np.abs(deq - np.asarray(x, np.float32))
    # absmax/127 per key is the quantization step; error <= half a step
    # plus bf16 rounding slack
    bound = np.asarray(scale)[..., None] * 0.51 + 1e-6
    assert (err <= bound).all()


def test_kv_quant_decode_deterministic_and_prefill_exact():
    """int8-cache decode must be deterministic, stay in-vocab, and agree
    with the exact-cache path on the FIRST generated token (the quantized
    prefill attends with the in-hand exact K/V, so prompt logits carry no
    quantization noise). Later tokens may legitimately drift on a
    random-init model whose logits have near-ties."""
    model = TransformerLM(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=128
    )
    params = trained_ish_params(model)
    prompt = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, size=(2, 6)), jnp.int32
    )
    exact = generate(model, params, prompt, 40)
    q1 = generate(model, params, prompt, 40, kv_quant=True)
    q2 = generate(model, params, prompt, 40, kv_quant=True)
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    assert q1.shape == exact.shape
    assert int(q1.max()) < 64 and int(q1.min()) >= 0
    np.testing.assert_array_equal(np.asarray(q1[:, 6]), np.asarray(exact[:, 6]))


def test_kv_quant_fallback_to_plain_scan_warns():
    """kv_quant=True on a shape the blocked path can't take (here: too few
    new tokens to fill one block) must be AUDIBLE — the plain scan keeps
    the exact full-size cache, not the halved int8 footprint the caller
    sized for (ADVICE r4)."""
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray(
        np.random.default_rng(5).integers(0, 64, size=(1, 6)), jnp.int32
    )
    with pytest.warns(UserWarning, match="kv_quant.*fall"):
        out = generate(model, params, prompt, 4, kv_quant=True)
    assert out.shape == (1, 10)


def test_fuse_qkv_params_only_rewrites_attn_named_modules():
    """The fused-QKV rewrite is anchored on the module NAME 'attn' plus the
    {q,k,v,o} child keys — a non-attention module that happens to have
    those child names must pass through untouched (ADVICE r4)."""
    from distributed_ml_pytorch_tpu.models.generate import _fuse_qkv_params

    k = jnp.ones((4, 4))
    attn = {"q": {"kernel": k}, "k": {"kernel": k}, "v": {"kernel": k},
            "o": {"kernel": k}}
    impostor = {"q": {"kernel": k}, "k": {"kernel": k}, "v": {"kernel": k},
                "o": {"kernel": k}, "extra": {"kernel": k}}
    tree = {"block_0": {"attn": attn, "lookup": impostor}}
    out = _fuse_qkv_params(tree)
    assert set(out["block_0"]["attn"]) == {"qkv", "o"}
    assert out["block_0"]["attn"]["qkv"]["kernel"].shape == (4, 12)
    assert set(out["block_0"]["lookup"]) == set(impostor)  # untouched


def test_kv_quant_cache_is_int8_with_scales():
    model = tiny_lm()
    cache = init_cache(model, 2, 32, decode_block=8, kv_quant=True)
    leaves = {jax.tree_util.keystr(p): v
              for p, v in jax.tree_util.tree_leaves_with_path(cache)}
    big = [v for k, v in leaves.items() if "cached_k" in k]
    scales = [v for k, v in leaves.items() if "scale_k" in k]
    assert big and all(v.dtype == jnp.int8 for v in big)
    assert scales and all(
        v.dtype == jnp.float32 and v.shape == (2, 4, 32) for v in scales)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "kv_quant"])
def test_ring_decode_step_is_one_softmax_over_the_visible_keys(kv_quant):
    """One single-token step of the ring-buffered cached attention, on a
    cache placed mid-block (``ring_base`` 32, five ring rows written),
    against ONE softmax over ``[big[:ring_base], ring[:t], self]`` with
    the int8 cache dequantised here: the module's three-part arithmetic,
    its masks and its scale folding are held to the plain definition.
    Rows the step must not see (big cache from ``ring_base`` on, ring
    from ``t`` on) hold values that would swamp the result."""
    from distributed_ml_pytorch_tpu.models.transformer import (
        MultiHeadAttention,
        quantize_kv,
    )

    b, h, C, T, d, ring_base, t = 3, 4, 40, 16, 32, 32, 5
    dt = jnp.float32 if kv_quant else jnp.bfloat16
    mha = MultiHeadAttention(d_model=h * d, n_heads=h, dtype=dt, decode=True,
                             cache_size=C, decode_block=T, kv_quant=kv_quant)
    rng = np.random.default_rng(0)
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    x = normal(b, 1, h * d).astype(dt)
    params = mha.init(jax.random.key(0), x)["params"]

    stale_big = (jnp.arange(C) >= ring_base)[None, None, :, None]
    stale_ring = (jnp.arange(T) >= t)[None, None, :, None]
    big_k = jnp.where(stale_big, 50.0, normal(b, h, C, d))
    big_v = jnp.where(stale_big, 50.0, normal(b, h, C, d))
    ring_k = jnp.where(stale_ring, 50.0, normal(b, h, T, d)).astype(dt)
    ring_v = jnp.where(stale_ring, 50.0, normal(b, h, T, d)).astype(dt)
    cache = {"ring_k": ring_k, "ring_v": ring_v,
             "cursor": jnp.asarray(ring_base + t, jnp.int32),
             "ring_base": jnp.asarray(ring_base, jnp.int32)}
    if kv_quant:
        (cache["cached_k"], cache["scale_k"]) = quantize_kv(big_k)
        (cache["cached_v"], cache["scale_v"]) = quantize_kv(big_v)
        seen_k = cache["cached_k"] * cache["scale_k"][..., None]
        seen_v = cache["cached_v"] * cache["scale_v"][..., None]
    else:
        cache["cached_k"], cache["cached_v"] = big_k.astype(dt), big_v.astype(dt)
        seen_k, seen_v = cache["cached_k"], cache["cached_v"]

    got, mutated = mha.apply({"params": params, "cache": cache}, x,
                             mutable=["cache"])

    heads = lambda name: (x @ params[name]["kernel"].astype(dt)).reshape(
        b, 1, h, d).transpose(0, 2, 1, 3).astype(jnp.float32)
    q, k, v = heads("q"), heads("k"), heads("v")
    f32 = lambda a: a.astype(jnp.float32)
    keys = jnp.concatenate(
        [f32(seen_k)[:, :, :ring_base], f32(ring_k)[:, :, :t], k], axis=2)
    values = jnp.concatenate(
        [f32(seen_v)[:, :, :ring_base], f32(ring_v)[:, :, :t], v], axis=2)
    assert keys.shape == (b, h, ring_base + t + 1, d)
    probs = jax.nn.softmax(
        jnp.einsum("bhsd,bhkd->bhsk", q, keys) / np.sqrt(d), axis=-1)
    attended = jnp.einsum("bhsk,bhkd->bhsd", probs, values)
    want = (attended.transpose(0, 2, 1, 3).reshape(b, 1, h * d).astype(dt)
            @ params["o"]["kernel"].astype(dt))

    tol = dict(rtol=1e-5, atol=1e-5) if kv_quant else dict(rtol=3e-2, atol=3e-2)
    np.testing.assert_allclose(np.asarray(f32(got)), np.asarray(f32(want)), **tol)
    after = mutated["cache"]
    assert int(after["cursor"]) == ring_base + t + 1
    assert int(after["ring_base"]) == ring_base
    np.testing.assert_array_equal(  # the step's K lands in ring row t only
        np.asarray(f32(after["ring_k"])),
        np.asarray(f32(jnp.where((jnp.arange(T) == t)[None, None, :, None],
                                 k.astype(dt), ring_k))))
    np.testing.assert_array_equal(np.asarray(after["cached_k"]),
                                  np.asarray(cache["cached_k"]))


def test_tp_sharded_decode_matches_single_device():
    """Greedy TP decode on a 2x4 dp x tp mesh must be bit-identical to the
    single-device path — same compiled program, shardings propagated."""
    from distributed_ml_pytorch_tpu.models.generate import generate_tp
    from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray(
        np.random.default_rng(1).integers(0, 64, size=(2, 6)), jnp.int32
    )
    want = generate(model, params, prompt, 8)
    mesh = make_mesh({"data": 2, "model": 4})
    got = generate_tp(model, params, prompt, 8, mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_decode_rejects_indivisible_heads():
    from distributed_ml_pytorch_tpu.models.generate import generate_tp
    from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh

    model = TransformerLM(
        vocab_size=64, d_model=30, n_heads=3, n_layers=1, d_ff=64, max_len=64
    )
    params = model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    mesh = make_mesh({"data": 1, "model": 2}, devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="divisible"):
        generate_tp(model, params, jnp.zeros((1, 2), jnp.int32), 4, mesh)


def test_sample_tokens_topk_restricts_support():
    from distributed_ml_pytorch_tpu.models.generate import sample_tokens

    rng = np.random.default_rng(4)
    logits = jnp.asarray(rng.normal(size=(4, 64)), jnp.float32)
    topset = np.argsort(np.asarray(logits), axis=-1)[:, -5:]
    for i in range(50):
        toks = np.asarray(sample_tokens(logits, jax.random.key(i),
                                        temperature=1.0, top_k=5))
        for b in range(4):
            assert toks[b] in topset[b]


def test_sample_tokens_topk1_and_tiny_topp_equal_greedy():
    from distributed_ml_pytorch_tpu.models.generate import sample_tokens

    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(3, 32)), jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    for i in range(10):
        k1 = np.asarray(sample_tokens(logits, jax.random.key(i),
                                      temperature=0.7, top_k=1))
        p0 = np.asarray(sample_tokens(logits, jax.random.key(i),
                                      temperature=0.7, top_p=1e-9))
        np.testing.assert_array_equal(k1, greedy)
        np.testing.assert_array_equal(p0, greedy)


def test_sample_tokens_topp_keeps_nucleus_only():
    from distributed_ml_pytorch_tpu.models.generate import sample_tokens

    # 0.5/0.3/0.1/0.1 distribution: the 0.75-nucleus is {0, 1} with a solid
    # float margin on both sides (0.5 < 0.75 ≤ 0.8 — an exact-boundary
    # threshold like 0.8 would flip on cumsum rounding across backends)
    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.1, 0.1]], jnp.float32))
    seen = set()
    for i in range(100):
        seen.add(int(sample_tokens(logits, jax.random.key(i),
                                   temperature=1.0, top_p=0.75)[0]))
    assert seen == {0, 1}


def test_sample_tokens_topk_topp_combined_restricts_support():
    """top-k AND top-p together: nucleus truncation applies to the
    POST-top-k RENORMALIZED distribution, so the combined support can be
    strictly smaller than either filter alone. With p = (0.5, 0.3, 0.12,
    0.08) and all-distinct logits (ties at the k-th logit are kept by
    contract, so distinctness matters): top_k=3 alone keeps {0, 1, 2};
    top_p=0.85 alone keeps {0, 1, 2} (exclusive mass before token 2 is
    0.8 < 0.85, before token 3 is 0.92); combined, the top-3 renormalize
    to (0.543, 0.326, 0.130) and the mass before token 2 becomes
    0.870 >= 0.85 — support {0, 1}, smaller than both."""
    from distributed_ml_pytorch_tpu.models.generate import sample_tokens

    logits = jnp.log(jnp.asarray([[0.5, 0.3, 0.12, 0.08]], jnp.float32))
    combined, k_only, p_only = set(), set(), set()
    for i in range(150):
        combined.add(int(sample_tokens(
            logits, jax.random.key(i), temperature=1.0, top_k=3,
            top_p=0.85)[0]))
        k_only.add(int(sample_tokens(
            logits, jax.random.key(i), temperature=1.0, top_k=3)[0]))
        p_only.add(int(sample_tokens(
            logits, jax.random.key(i), temperature=1.0, top_p=0.85)[0]))
    assert combined == {0, 1}
    assert k_only == {0, 1, 2}
    assert p_only == {0, 1, 2}


def test_generate_with_topk_topp_runs_and_stays_in_vocab():
    model = tiny_lm()
    params = trained_ish_params(model)
    prompt = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    out = generate(model, params, prompt, 8, temperature=0.9,
                   rng=jax.random.key(0), top_k=10, top_p=0.9)
    assert out.shape == (1, 12)
    assert int(out.max()) < 64 and int(out.min()) >= 0
    out2 = generate(model, params, prompt, 8, temperature=0.9,
                    rng=jax.random.key(0), top_k=10, top_p=0.9)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
