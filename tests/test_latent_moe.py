"""``LatentMoELM`` (latent attention, dropless routed experts) against its
plain reference ``benchmarks/reference/deepseek_v3_mla_moe.py`` at a tiny size
on the CPU, seeded float32 weights, logits and not tokens: the cached absorbed
path through ``SlotKVPool``, absorbed against expanded, the grouped routed sum
(a call's own rows, and a pool's live lanes under ``vmap``) against the
reference's loop over experts, padding and idle slots, the selection bias, and
the counts of the published sizes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import latent_moe_counts as counts
from benchmarks.reference import deepseek_v3_mla_moe as ref
from distributed_ml_pytorch_tpu.models import moe
from distributed_ml_pytorch_tpu.models.generate import generate
from distributed_ml_pytorch_tpu.models.latent_moe import LatentAttention, LatentMoELM
from distributed_ml_pytorch_tpu.serving.cache import SlotKVPool
from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "tiny_kanana2_config.json")) as _fh:
    CONFIG = json.load(_fh)  # 3 layers (1 dense), 8 experts top-2, 1 shared, 4 heads, latent 32 + rope 8
with open(os.path.join(os.path.dirname(HERE), "benchmarks", "configs", "kanana-2-30b-a3b.json")) as _fh:
    REAL = json.load(_fh)
BLOCK = 4


@pytest.fixture(scope="module")
def lm_and_params():
    return LatentMoELM.from_config(CONFIG), ref.make_params(jax.random.key(3), CONFIG)


def prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, CONFIG["vocab_size"], size=n).astype(np.int32)


def reference_gap(params, p, tokens):
    """Per served token: the reference's best logit less the served token's,
    in one full float32 pass over prompt + served tokens."""
    seq = jnp.asarray(np.concatenate([p, np.asarray(tokens, np.int32)]))
    stats = ref.served_token_stats(params, seq, CONFIG)
    rows = slice(len(p) - 1, len(p) - 1 + len(tokens))
    return np.asarray(stats["best"] - stats["served"])[rows]


# ------------------------------------------------- (a) the pool, the reference
def test_prefill_and_forty_steps_through_the_pool_pick_the_references_best(lm_and_params):
    """Two slots at different lengths, then a third request in a slot that was
    used: every served token is the reference's best to float32 rounding."""
    lm, params = lm_and_params
    eng = ServingEngine(lm, params, slots=2, cache_size=128, decode_block=BLOCK, prefill_bucket=16)
    first = [eng.submit(prompt(37, 1), 41), eng.submit(prompt(5, 2), 24)]
    eng.run_until_idle()
    reused = eng.submit(prompt(21, 3), 41)
    eng.run_until_idle()
    for req in first + [reused]:
        assert len(req.tokens) == req.max_new_tokens
        assert reference_gap(params, req.prompt, req.tokens).max() < 1e-4
    assert reused.slot in {r.slot for r in first}


def test_the_full_pass_gives_the_references_logits(lm_and_params):
    lm, params = lm_and_params
    seq = prompt(50, 4)
    got = lm.apply({"params": params}, jnp.asarray(seq)[None])[0]
    np.testing.assert_allclose(got, ref.logits(params, jnp.asarray(seq), CONFIG), atol=2e-5)


@pytest.mark.parametrize("new", [8, 40])  # the plain scan, the ring-buffered blocks
def test_generate_decodes_what_the_pool_serves(lm_and_params, new):
    lm, params = lm_and_params
    p = prompt(11, 5)
    eng = ServingEngine(lm, params, slots=1, cache_size=64, decode_block=BLOCK, prefill_bucket=8)
    req = eng.submit(p, new)
    eng.run_until_idle()
    assert np.asarray(generate(lm, params, jnp.asarray(p)[None], new))[0, len(p):].tolist() == req.tokens


# --------------------------------------------------- (b) absorbed = expanded
@pytest.mark.parametrize("decode_block", [0, 8])
def test_the_absorbed_step_equals_the_expanded_pass_on_the_same_rows(decode_block):
    """Nine rows prefilled (expanded, writing latent rows), seven decoded
    (absorbed, reading them: from the big cache alone, or cache and ring)."""
    mixer = LatentAttention(64, 4, 16, 8, 16, 32, rope_theta=1e4)
    x = jax.random.normal(jax.random.key(0), (1, 16, 64))
    pos = jnp.arange(16)[None]
    params = mixer.init(jax.random.key(1), x, pos)["params"]
    whole = mixer.apply({"params": params}, x, pos)
    dec = mixer.clone(decode=True, cache_size=32, decode_block=decode_block)
    cache = jax.tree.map(jnp.zeros_like, dec.init(jax.random.key(1), x[:, :1], pos[:, :1])["cache"])
    out, mut = dec.apply({"params": params, "cache": cache}, x[:, :9], pos[:, :9], mutable=["cache"])
    np.testing.assert_allclose(out, whole[:, :9], atol=1e-5)
    for t in range(9, 16):
        out, mut = dec.apply({"params": params, "cache": mut["cache"]}, x[:, t:t + 1],
                             pos[:, t:t + 1], mutable=["cache"])
        np.testing.assert_allclose(out[:, 0], whole[:, t], atol=1e-5)
    assert mut["cache"]["cached_latent"].shape == (1, 1, 32, 40)


# ------------------------------- (c) the routed sum, of a call and of a pool
def skewed(params):
    """A router under which expert 0 is chosen by nearly every row and expert
    5 by none (the bias moves the choice only)."""
    bias = jnp.zeros(8).at[0].set(1.0).at[5].set(-1.0)
    return dict(params, router=dict(params["router"], bias=bias))


@pytest.mark.parametrize("rows, decode", [(64, False), (1, True)])
def test_the_routed_sum_equals_the_references_loop_over_experts(lm_and_params, rows, decode):
    p = skewed(lm_and_params[1]["layer_1"]["moe"])
    x = jax.random.normal(jax.random.key(rows), (1, rows, 64))
    layer = moe.DroplessExperts(64, 32, 8, 2, 1, CONFIG["routed_scaling_factor"], decode=decode)
    variables = {"params": p, "cache": {"prefill_len": jnp.zeros((), jnp.int32)}} if decode \
        else {"params": p}
    got, counted = layer.apply(variables, x, mutable=["cache", moe.COUNTERS])
    want, chosen = ref._experts(x[0], p, CONFIG, False)
    np.testing.assert_allclose(got[0], want, atol=1e-5)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=8)
    assert counted[moe.COUNTERS]["expert_choices"].tolist() == load.tolist()
    if rows > 1:
        assert load[5] == 0 and load[0] > rows // 2 and load.sum() == 2 * rows


def routed_reference(x, p):
    """The reference's loop over experts for rows ``x``, without its shared expert."""
    whole, _ = ref._experts(x, p, CONFIG, False)
    shared = jax.tree.map(lambda a: a.astype(jnp.float32), p["shared"])
    return whole - ref._gated_ffn(x, shared["gate"]["kernel"], shared["up"]["kernel"],
                                  shared["down"]["kernel"], False)


LIVE = np.array([True, False, True, True, False, True, False, True])


@pytest.mark.parametrize("how", ["plain call", "vmap over lanes", "vmap in scan under jit",
                                 "vmap, nobody says who is live"])
def test_the_routed_sum_of_a_pool_is_the_references_on_the_live_rows(lm_and_params, how):
    """The expert layer without its shared expert, as a prefill calls it (eight
    rows of one sequence) and as a pool does (eight lanes of one row under
    ``vmap``, three of them idle, told through ``kv_read/live``): the
    reference's loop over experts on the live rows, exactly 0 on the others."""
    p = skewed(lm_and_params[1]["layer_2"]["moe"])
    routed = {k: v for k, v in p.items() if k != "shared"}
    layer = moe.DroplessExperts(64, 32, 8, 2, 0, CONFIG["routed_scaling_factor"], decode=True)
    x = jax.random.normal(jax.random.key(9), (8, 64))
    cache = {"prefill_len": jnp.zeros((), jnp.int32)}

    def lane(row, live):
        hint = {} if live is None else {moe.KV_READ: {"live": live}}
        return layer.apply({"params": routed, "cache": cache, **hint}, row[None, None],
                           mutable=["cache"])[0][0, 0]

    live = LIVE
    if how == "plain call":
        live = np.ones(8, bool)
        got = layer.apply({"params": routed, "cache": cache}, x[None], mutable=["cache"])[0][0]
    elif how == "vmap over lanes":
        got = jax.vmap(lane)(x, jnp.asarray(live))
    elif how == "vmap in scan under jit":
        steps = jax.jit(lambda x, live: jax.lax.scan(
            lambda c, _: (c, jax.vmap(lane)(c, live)), x, None, length=2)[1])
        first, got = steps(x, jnp.asarray(live))
        np.testing.assert_array_equal(first, got)
    else:
        live = np.ones(8, bool)
        got = jax.vmap(lambda row: lane(row, None))(x)
    np.testing.assert_allclose(got[live], routed_reference(x, p)[live], atol=1e-5)
    assert not np.asarray(got[~live]).any()


def test_an_expert_only_idle_lanes_chose_has_an_empty_group(monkeypatch):
    """The group sizes the batching rule hands to ``grouped_dot`` count the
    live lanes' pairs and no others, in one call for the whole pool: idle
    lanes steered to expert 0 leave its group empty, so the TPU's kernel never
    fetches it. With every lane live the same call counts them all."""
    seen = []
    dot = moe.grouped_dot
    note = lambda n: lambda sizes: seen.append((n, np.asarray(sizes)))
    monkeypatch.setattr(moe, "grouped_dot", lambda rows, w, sizes, dt: (
        jax.debug.callback(note(rows.shape[0]), sizes), dot(rows, w, sizes, dt))[1])
    k = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(k[0], (8, 1, 16))
    w_gate, w_up = (jax.random.normal(kk, (8, 16, 32)) / 4 for kk in k[1:3])
    w_down = jax.random.normal(k[3], (8, 32, 16)) / 6
    idx = np.stack([[[1 + i % 7, 1 + (i + 3) % 7]] for i in range(8)])
    idx[~LIVE] = [[0, 3]]
    weights = jnp.full((8, 1, 2), 0.5)
    pooled = lambda live: jax.vmap(moe.pooled_experts, in_axes=(0, 0, 0, 0, None, None, None))(
        x, jnp.asarray(idx), weights, jnp.asarray(live)[:, None], w_gate, w_up, w_down)
    got = pooled(LIVE)
    jax.effects_barrier()
    assert [rows for rows, _ in seen] == [moe.GROUP_TILE_ROWS] * 3   # one call, whole tiles
    assert all(sizes.tolist() == np.bincount(idx[LIVE].ravel(), minlength=8).tolist()
               for _, sizes in seen)
    assert seen[0][1][0] == 0 and seen[0][1].sum() == 2 * LIVE.sum()
    assert not np.asarray(got[~LIVE]).any() and np.asarray(got[LIVE]).all()
    del seen[:]
    pooled(np.ones(8, bool))
    jax.effects_barrier()
    assert seen[0][1][0] == 3 and seen[0][1].sum() == 16
    # lanes with experts of their own share nothing: the rule maps the plain sum
    own = jax.vmap(moe.pooled_experts, in_axes=(0, 0, 0, 0, 0, None, None))(
        x, jnp.asarray(idx), weights, jnp.asarray(LIVE)[:, None],
        jnp.broadcast_to(w_gate, (8,) + w_gate.shape), w_up, w_down)
    np.testing.assert_allclose(own, got, atol=1e-6)


# ----------------------------------------- (d) padding and idle slots
def test_padding_and_idle_slots_change_no_real_row_and_no_counter(lm_and_params):
    lm, params = lm_and_params
    served, counted = [], []
    for slots, bucket in ((1, 1), (4, 32)):
        eng = ServingEngine(lm, params, slots=slots, cache_size=96, decode_block=BLOCK,
                            prefill_bucket=bucket)
        req = eng.submit(prompt(13, 6), 22)
        eng.run_until_idle()
        summary = eng.slo_summary()
        served.append(req.tokens)
        counted.append(summary["model_counters"])
    assert served[0] == served[1]
    assert counted[0] == counted[1]
    prefill = counted[0]["prefill"]
    assert sorted(prefill) == ["layer_1/moe/expert_choices", "layer_2/moe/expert_choices"]
    assert all(sum(c["sum"]) == 13 * 2 and c["events"] == 1 for c in prefill.values())
    decode = counted[0]["decode"]["layer_1/moe/expert_choices"]
    # 21 tokens in 6 blocks of 4 steps, one active slot choosing 2 experts a step
    assert decode["events"] == 24 and sum(decode["sum"]) == 48 and decode["nonzero_mean"] == 2.0


def test_a_model_that_counts_nothing_reports_no_counters():
    from distributed_ml_pytorch_tpu.models.transformer import TransformerLM

    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=2, n_layers=1, d_ff=64, max_len=64)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))["params"]
    eng = ServingEngine(lm, params, slots=2, cache_size=32, decode_block=BLOCK, prefill_bucket=4)
    eng.submit(prompt(5, 7) % 64, 6)
    eng.run_until_idle()
    summary = eng.slo_summary()
    assert summary["model_counters"] == {"prefill": {}, "decode": {}}
    assert summary["pool"]["latent_bytes_per_slot"] == 0 < summary["pool"]["kv_bytes_per_slot"]


# -------------------------------------------------- (e) the selection bias
def test_the_bias_changes_a_chosen_set_and_never_a_weight(lm_and_params):
    p = lm_and_params[1]["layer_1"]["moe"]["router"]
    x = jax.random.normal(jax.random.key(11), (256, 64))
    assert float(jnp.abs(p["bias"]).max()) > 0
    idx_b, w_b, scores = moe.route_topk_sigmoid(x, p["kernel"], p["bias"], 2, 2.448)
    idx_0, w_0, _ = moe.route_topk_sigmoid(x, p["kernel"], jnp.zeros(8), 2, 2.448)
    moved = np.asarray(jnp.sort(idx_b, -1) != jnp.sort(idx_0, -1)).any(-1)
    assert 0 < moved.sum() < len(moved) // 2          # some tokens, not most
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx_b), axis=-1)
    np.testing.assert_allclose(w_b, picked / picked.sum(-1, keepdims=True) * 2.448, rtol=1e-6)
    same = ~moved
    np.testing.assert_allclose(np.sort(w_b, -1)[same], np.sort(w_0, -1)[same], rtol=1e-6)


# ----------------------------------------------------- (f) sizes and bytes
def test_parameters_of_the_published_model_and_of_the_cut():
    published = dict(REAL, num_hidden_layers=REAL["published"]["num_hidden_layers"])
    assert counts.total_params(published) == 30_670_815_104
    assert counts.total_params(REAL) == 4_429_613_312
    assert counts.layer_params(REAL, dense=False) == 640_029_312
    assert counts.layer_params(REAL, dense=True) == 64_098_816
    tree = jax.eval_shape(lambda k: ref.make_params(k, CONFIG), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == counts.total_params(CONFIG)
    lm = LatentMoELM.from_config(CONFIG)
    init = jax.eval_shape(lambda k: lm.init(k, jnp.zeros((1, 4), jnp.int32))["params"],
                          jax.random.key(0))
    assert jax.tree.map(lambda a: a.shape, init) == jax.tree.map(lambda a: a.shape, tree)


def test_a_slot_holds_1152_bytes_a_token_a_layer_at_the_published_sizes():
    import jax.numpy as jnp

    lm = LatentMoELM.from_config(REAL, dtype=jnp.bfloat16)
    pool = SlotKVPool(lm, None, slots=1, cache_size=32, decode_block=16)
    sizes = pool.slot_bytes()
    assert counts.latent_row_bytes(REAL) == 1152 and counts.per_head_row_bytes(REAL) == 20480
    assert sizes["latent_bytes_per_slot"] == (32 + 16) * REAL["num_hidden_layers"] * 1152
    assert sizes["kv_bytes_per_slot"] == sizes["latent_bytes_per_slot"]
    assert sizes["state_bytes_per_slot"] == 0


def test_from_config_refuses_what_it_does_not_run():
    with pytest.raises(ValueError, match="n_group"):
        LatentMoELM.from_config(dict(CONFIG, n_group=2))
    with pytest.raises(ValueError, match="q_lora_rank"):
        LatentMoELM.from_config(dict(CONFIG, q_lora_rank=64))


# ------------------------------------------------------------------- the CLI
def test_serve_cli_builds_the_model_from_a_published_configuration(tmp_path, capsys):
    from distributed_ml_pytorch_tpu.serving.cli import main

    dump = tmp_path / "metrics.json"
    rc = main(["--model-config", os.path.join(HERE, "tiny_kanana2_config.json"), "--demo", "3",
               "--slots", "2", "--cache-size", "64", "--decode-block", "4",
               "--prefill-bucket", "8", "--metrics-dump", str(dump)])
    assert rc == 0 and "serving demo complete" in capsys.readouterr().out
    metrics = json.loads(dump.read_text())
    # (64 + 4) rows x 3 layers x (32 + 8) float32 values
    assert metrics["engine.pool"]["latent_bytes_per_slot"] == 68 * 3 * 40 * 4
    assert metrics["engine.pool"]["state_bytes_per_slot"] == 0
    counted = metrics["engine.model_counters"]["decode"]["layer_1/moe/expert_choices"]
    assert counted["events"] > 0 and 2.0 <= counted["nonzero_mean"] <= 4.0
    assert sum(metrics["engine.kv_read"]["by_rows"].values()) == metrics["engine.kv_read"]["blocks"] > 0


def test_the_tpus_grouped_kernel_gives_the_ragged_products_numbers(monkeypatch):
    """``grouped_dot`` takes the Pallas grouped-matmul kernel on a TPU for
    bfloat16 shapes that tile, ``jax.lax.ragged_dot`` elsewhere. Here the
    kernel runs in interpret mode with the backend said to be a TPU: groups of
    0, 1, 127, 128 and 256 rows, against the ragged product."""
    from functools import partial

    from jax.experimental.pallas.ops.tpu.megablox import gmm

    sizes = jnp.asarray([0, 1, 127, 128, 256], jnp.int32)
    rows = jax.random.normal(jax.random.key(0), (512, 256)).astype(jnp.bfloat16)
    w = (jax.random.normal(jax.random.key(1), (5, 256, 128)) / 16).astype(jnp.bfloat16)
    want = moe.grouped_dot(rows, w, sizes, jnp.float32)        # the CPU: ragged_dot
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(moe, "_gmm", partial(gmm, interpret=True))
    got = moe.grouped_dot(rows, w, sizes, jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-2, rtol=2e-2)
    # shapes the kernel does not tile, and float32, stay with the ragged product
    monkeypatch.setattr(moe, "_gmm", None)
    assert moe.grouped_dot(rows[:500], w, sizes.at[4].add(-12), jnp.float32).shape == (500, 128)
    assert moe.grouped_dot(rows.astype(jnp.float32), w, sizes, jnp.float32).shape == (512, 128)
    assert moe._whole_or_tile(768) == 768 and moe._whole_or_tile(6144) == 2048


def test_the_counts_ride_spans_of_their_own_in_a_profile(lm_and_params, tmp_path):
    """A model that counts writes ``serve.prefill.counters`` inside
    ``serve.prefill`` and ``serve.decode.counters`` inside ``serve.decode``,
    each counter's sum and its entries that were not 0 as attributes, read
    back through the benchmark's own loader."""
    import glob

    from benchmarks import program_trace

    eng = ServingEngine(*lm_and_params, slots=2, cache_size=64, decode_block=BLOCK, prefill_bucket=8)
    eng.submit(prompt(9, 8), 6)
    eng.run_until_idle()  # compiled before the profile
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level, opts.python_tracer_level = 1, 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.submit(prompt(9, 8), 6)
        eng.run_until_idle()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))[-1]
    by_name = {}
    for s in program_trace.load_spans(path):
        by_name.setdefault(s[0], []).append(s)
    admitted, = by_name["serve.prefill.counters"]
    assert admitted[4]["layer_1_moe_expert_choices_sum"] == 9 * 2
    assert 1 <= admitted[4]["layer_2_moe_expert_choices_nonzero"] <= 8
    blocks = by_name["serve.decode.counters"]
    assert len(blocks) == 2 and all(b[4]["layer_1_moe_expert_choices_sum"] == BLOCK * 2 for b in blocks)
    inside = lambda c, p: p[1] <= c[1] and c[1] + c[2] <= p[1] + p[2]
    assert inside(admitted, by_name["serve.prefill"][0])
    assert all(any(inside(b, d) for d in by_name["serve.decode"]) for b in blocks)


def test_a_latent_pools_decode_block_lowers_to_the_text_it_had(lm_and_params):
    """The latent read (``bounded_latent_attention``) is not the per-slot read
    of per-head K/V caches: a latent pool's decode block lowers to the text it
    lowered to before that read existed (sha256 of ``Lowered.as_text()``,
    commit 2813c68), so nothing of it moves."""
    import hashlib

    from distributed_ml_pytorch_tpu.serving.cache import _decode_block_jit

    lm, params = lm_and_params
    pool = SlotKVPool(lm, params, slots=3, cache_size=96, decode_block=BLOCK)
    shapes = lambda tree: jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    vec = lambda dt: jax.ShapeDtypeStruct((3,), dt)
    lowered = _decode_block_jit.lower(
        pool.dec, shapes(pool.params), shapes(pool.cache), vec(jnp.int32), vec(jnp.int32),
        vec(jnp.uint32), vec(jnp.float32), vec(jnp.int32), vec(jnp.float32), vec(jnp.bool_))
    assert pool.slot_block_rows is None
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest() == (
        "ad330e8ade0015b60b15eb9063c0a3333f43c717f5733ac7a643d948de4a5436")
