"""``HybridLM`` (gated-delta-rule layers among full-attention ones) against
the plain reference ``benchmarks/reference/olmo_hybrid.py`` on seeded weights
at a small size: the whole forward pass, prefill then decode through the
cache, ``generate()`` on both of its paths, what ``from_config`` refuses, and
the scopes the device trace is read by."""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as ref
from distributed_ml_pytorch_tpu.models.generate import (
    _decode_model,
    generate,
    init_cache,
    uses_block_decode,
)
from distributed_ml_pytorch_tpu.models.hybrid import HybridLM
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
from distributed_ml_pytorch_tpu.serving.cache import SlotKVPool, _admit_jit, _decode_block_jit

#: hidden 64, two periods of (3 linear + 1 full), 2 heads, key 8 / value 16,
#: convolution 4, vocabulary 128; float32 so that greedy tokens are exact
CONFIG_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_olmo_hybrid_config.json")
with open(CONFIG_PATH) as _fh:
    CONFIG = json.load(_fh)
TOL = 5e-5  # float32 sums in another order; the logits are about 0.6 wide


@pytest.fixture(scope="module")
def lm_and_params():
    return HybridLM.from_config(CONFIG), ref.make_params(jax.random.key(1), CONFIG)


def tokens(n, seed=2):
    return jax.random.randint(jax.random.key(seed), (n,), 0, CONFIG["vocab_size"])


def test_the_reference_makes_the_tree_the_model_declares(lm_and_params):
    lm, params = lm_and_params
    declared = jax.eval_shape(lambda: lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    shapes = lambda tree: jax.tree.map(lambda a: a.shape, tree)
    assert shapes(declared["params"]) == shapes(params)
    assert set(params["layer_3"]) == {"attn", "mixer_norm", "mlp", "mlp_norm"}
    assert set(params["layer_4"]) == {"gdn", "mixer_norm", "mlp", "mlp_norm"}


def test_the_whole_forward_pass_gives_the_references_logits(lm_and_params):
    lm, params = lm_and_params
    seq = tokens(150)
    with jax.default_matmul_precision("highest"):
        got = lm.apply({"params": params}, seq[None])[0]
    want = ref.sequence_logits(params, seq, CONFIG)
    assert float(jnp.abs(want).max()) > 0.3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL, rtol=0)


@pytest.mark.parametrize("decode_block,steps", [(0, 12), (4, 4)])
def test_prefill_then_decode_through_the_cache_gives_the_references_full_pass(
        lm_and_params, decode_block, steps):
    """Logits, not tokens: a prompt of 70 (past one chunk) prefilled in one
    call, then single-token steps, each row against the reference's one pass
    over the whole sequence; without the ring, and with it for the one block
    a caller may run before it merges the ring."""
    lm, params = lm_and_params
    seq, p = tokens(70 + steps, seed=5), 70
    dec = _decode_model(lm, 96, decode_block=decode_block)
    cache = init_cache(lm, 1, 96, decode_block=decode_block)
    want = ref.sequence_logits(params, seq, CONFIG)
    with jax.default_matmul_precision("highest"):
        logits, mut = dec.apply({"params": params, "cache": cache}, seq[None, :p], mutable=["cache"])
        rows = [logits[0]]
        for t in range(p, p + steps):
            logits, mut = dec.apply({"params": params, "cache": mut["cache"]},
                                    seq[None, t:t + 1], mutable=["cache"])
            rows.append(logits[0])
    np.testing.assert_allclose(np.asarray(jnp.concatenate(rows)), np.asarray(want), atol=TOL, rtol=0)
    leaves = mut["cache"]["layer_0"]["gdn"]
    assert leaves["state"].dtype == jnp.float32 and leaves["state"].shape == (1, 2, 16, 8)
    assert leaves["conv_tail"].shape == (1, 3, 64) and int(leaves["prefill_len"]) == 0


@pytest.mark.parametrize("new_tokens", [12, 40])
def test_generate_picks_the_references_best_tokens(lm_and_params, new_tokens):
    """12 new tokens take ``generate()``'s plain scan, 40 its blocked path."""
    lm, params = lm_and_params
    prompt = tokens(37, seed=9)
    assert uses_block_decode(lm, 37, new_tokens)[0] == (new_tokens == 40)
    out = generate(lm, params, prompt[None], new_tokens)[0]
    best = ref.sequence_logits(params, out, CONFIG).argmax(-1)
    assert out.shape == (37 + new_tokens,) and bool((out[:37] == prompt).all())
    assert (np.asarray(best[36:-1]) == np.asarray(out[37:])).all()


def test_a_second_prefill_continues_from_the_state_and_the_tail(lm_and_params):
    lm, params = lm_and_params
    seq = tokens(100, seed=4)
    dec = _decode_model(lm, 128)
    run = lambda cache, part: dec.apply({"params": params, "cache": cache}, part[None],
                                        mutable=["cache"])
    with jax.default_matmul_precision("highest"):
        first, mut = run(init_cache(lm, 1, 128), seq[:33])
        second, _ = run(mut["cache"], seq[33:])
    want = ref.sequence_logits(params, seq, CONFIG)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([first[0], second[0]])),
                               np.asarray(want), atol=TOL, rtol=0)


def test_prefill_len_keeps_padding_out_of_state_and_tail(lm_and_params):
    """The protocol the slot pool uses: ``prefill_len`` set on a fresh cache,
    the prompt right-padded. State and tail are those of the unpadded prompt,
    and the leaf is consumed."""
    lm, params = lm_and_params
    from distributed_ml_pytorch_tpu.serving.cache import replace_cache_leaves

    prompt = tokens(21, seed=6)
    padded = jnp.zeros(64, prompt.dtype).at[:21].set(prompt)
    dec, fresh = _decode_model(lm, 96), init_cache(lm, 1, 96)
    _, plain = dec.apply({"params": params, "cache": fresh}, prompt[None], mutable=["cache"])
    _, told = dec.apply({"params": params, "cache": replace_cache_leaves(fresh, {"prefill_len": 21})},
                        padded[None], mutable=["cache"])
    _, untold = dec.apply({"params": params, "cache": fresh}, padded[None], mutable=["cache"])
    for layer in ("layer_0", "layer_6"):
        a, b, c = (m["cache"][layer]["gdn"] for m in (plain, told, untold))
        np.testing.assert_allclose(np.asarray(b["state"]), np.asarray(a["state"]), atol=1e-5)
        np.testing.assert_allclose(np.asarray(b["conv_tail"]), np.asarray(a["conv_tail"]), atol=1e-6)
        assert float(jnp.abs(c["conv_tail"] - a["conv_tail"]).max()) > 1e-4
        assert int(b["prefill_len"]) == 0
        assert float(jnp.abs(c["state"] - a["state"]).max()) > 1e-3  # the padding was folded in


@pytest.mark.parametrize("key,value", [
    ("hidden_act", "gelu"), ("attention_bias", True), ("tie_word_embeddings", True),
    ("num_key_value_heads", 1), ("linear_num_value_heads", 4), ("num_hidden_layers", 7),
    ("rope_parameters", {"rope_theta": 10000.0}), ("layer_types", ["sliding_attention"] * 8),
])
def test_from_config_refuses_what_the_class_cannot_run(key, value):
    with pytest.raises(ValueError):
        lm = HybridLM.from_config(dict(CONFIG, **{key: value}))
        lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))  # a layer kind is met here


def test_from_config_reads_the_published_keys():
    lm = HybridLM.from_config(CONFIG, dtype=jnp.bfloat16)
    assert (lm.d_model, lm.n_heads, lm.d_ff, lm.vocab_size) == (64, 2, 96, 128)
    assert (lm.linear_heads, lm.linear_key_dim, lm.linear_value_dim, lm.conv_kernel) == (2, 8, 16, 4)
    assert lm.layer_types == tuple(CONFIG["layer_types"]) and lm.max_len == 512
    assert lm.allow_neg_eigval and lm.norm_eps == 1e-6 and lm.dtype == jnp.bfloat16


def test_qk_norm_is_an_option_that_adds_nothing_when_off():
    """``TransformerLM``'s tree and programs are what they were."""
    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_len=64)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    assert set(params["block_0"]["attn"]) == {"q", "k", "v", "o"}


# ------------------------------------------------------------------ scopes
SCOPES = re.compile(r"gdn/(?:recur|chunk|conv|gate_norm)")


def pool_programs(lm, params):
    pool = SlotKVPool(lm, params, slots=2, cache_size=64, decode_block=4)
    vec = lambda dt, fill=0: jnp.full((2,), fill, dt)
    decode = _decode_block_jit.lower(
        pool.dec, pool.params, pool.cache, vec(jnp.int32), vec(jnp.int32), vec(jnp.uint32),
        vec(jnp.float32), vec(jnp.int32), vec(jnp.float32, 1), vec(jnp.bool_, True))
    scalar = lambda dt, fill=0: jnp.asarray(fill, dt)
    admit = _admit_jit.lower(
        pool.dec, pool.params, pool.cache, scalar(jnp.int32), jnp.zeros((1, 32), jnp.int32),
        scalar(jnp.int32, 20), scalar(jnp.uint32), scalar(jnp.float32), scalar(jnp.int32),
        scalar(jnp.float32, 1), scalar(jnp.int32))
    return decode.as_text(debug_info=True), admit.as_text(debug_info=True)


def test_the_lowered_programs_carry_the_scopes_the_trace_is_read_by(lm_and_params):
    decode, admit = pool_programs(*lm_and_params)
    assert set(SCOPES.findall(decode)) == {"gdn/recur", "gdn/conv", "gdn/gate_norm"}
    assert set(SCOPES.findall(admit)) == {"gdn/chunk", "gdn/conv", "gdn/gate_norm"}
    # under the module names the metrics' patterns spell
    assert "layer_0/gdn/gdn/recur" in decode and "layer_3/attn/" in decode
    assert "layer_0/gdn/gdn/chunk" in admit


def test_a_model_without_recurrent_layers_has_none_of_them():
    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=1, d_ff=64, max_len=64)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    decode, admit = pool_programs(lm, params)
    assert not SCOPES.findall(decode + admit) and "prefill_len" not in decode + admit
