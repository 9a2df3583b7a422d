"""``HybridLM`` through ``ServingEngine``: the schedule that was argued for
K/V rows under a mask, proved for a recurrent state that every step rewrites.
The oracle is the unpadded, unbatched ``generate()`` (itself held to the
plain reference in ``tests/test_hybrid_lm.py``); float32, so tokens are exact."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as ref
from distributed_ml_pytorch_tpu.models.generate import generate
from distributed_ml_pytorch_tpu.models.hybrid import HybridLM
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

BUCKET, BLOCK = 32, 4
CONFIG_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "tiny_olmo_hybrid_config.json")
with open(CONFIG_PATH) as _fh:
    CONFIG = json.load(_fh)  # hidden 64, two periods of (3 linear + 1 full), float32


@pytest.fixture(scope="module")
def lm_and_params():
    return HybridLM.from_config(CONFIG), ref.make_params(jax.random.key(1), CONFIG)


def make_engine(lm_and_params, **kw):
    kw = {"slots": 3, "cache_size": 128, "decode_block": BLOCK, "prefill_bucket": BUCKET, **kw}
    return ServingEngine(*lm_and_params, **kw)


def prompt(n, seed=0):
    return np.random.default_rng([seed, n]).integers(0, CONFIG["vocab_size"], size=n).astype(np.int32)


def oracle(lm_and_params, p, new):
    lm, params = lm_and_params
    return np.asarray(generate(lm, params, jnp.asarray(p)[None], new))[0, len(p):].tolist()


@pytest.mark.parametrize("n", [3, 17, 64, 65])
def test_a_padded_prefill_serves_what_the_unpadded_generate_gives(lm_and_params, n):
    """Prompts short of, at and past a bucket's edge (and a chunk's: 64 and
    65): the padding reaches neither the state nor the convolution's tail."""
    eng = make_engine(lm_and_params)
    p = prompt(n)
    req = eng.submit(p, 11)
    eng.run_until_idle()
    assert req.tokens == oracle(lm_and_params, p, 11)
    tokens = eng.slo_summary()["prefill_tokens"]
    assert tokens == {"real": n, "padded": -(-n // BUCKET) * BUCKET - n}


def test_a_slots_second_occupant_is_untouched_by_its_first(lm_and_params):
    eng = make_engine(lm_and_params, slots=1)
    first, second = prompt(40, seed=1), prompt(9, seed=2)
    a, b = eng.submit(first, 9), eng.submit(second, 10)
    eng.run_until_idle()
    assert a.slot == b.slot == 0 and b.active_at_admit == 0
    assert a.tokens == oracle(lm_and_params, first, 9)
    assert b.tokens == oracle(lm_and_params, second, 10)


def test_requests_that_finish_mid_block_beside_idle_and_active_slots(lm_and_params):
    """Six requests over three slots with lengths that end inside a block:
    slots go idle (and "decode garbage" into their state) while neighbours run,
    finished requests keep stepping to the block's end, and freed slots are
    taken again. Every stream is its own ``generate()``."""
    eng = make_engine(lm_and_params)
    work = [(prompt(n, seed=3), new) for n, new in
            ((5, 6), (33, 7), (12, 15), (20, 2), (7, 10), (64, 5))]
    reqs = [eng.submit(p, new) for p, new in work]
    eng.run_until_idle()
    assert any(r.active_at_admit > 0 for r in reqs)
    for (p, new), r in zip(work, reqs):
        assert (len(r.tokens) - 1) % BLOCK != 0 or new == 5
        assert r.tokens == oracle(lm_and_params, p, new)
    assert eng.slo_summary()["completed"] == 6


def test_an_idle_slot_beside_an_active_one_stays_harmless(lm_and_params):
    """One request alone in a pool of three: two slots step from a zero state
    all along; a later request admitted into one of them is exact."""
    eng = make_engine(lm_and_params)
    a = eng.submit(prompt(10, seed=4), 13)
    eng.run_until_idle()
    b = eng.submit(prompt(11, seed=5), 9)
    c = eng.submit(prompt(6, seed=6), 9)
    eng.run_until_idle()
    for r, (n, seed, new) in zip((a, b, c), ((10, 4, 13), (11, 5, 9), (6, 6, 9))):
        assert r.tokens == oracle(lm_and_params, prompt(n, seed=seed), new)


def test_a_resumed_request_continues_token_for_token(lm_and_params):
    """Stream migration: prompt + the tokens so far prefilled again on another
    engine with ``gen_offset``; the recurrent state is rebuilt by the prefill."""
    p, new, cut = prompt(19, seed=7), 14, 6
    whole = oracle(lm_and_params, p, new)
    eng = make_engine(lm_and_params)
    resumed = eng.submit(np.concatenate([p, np.asarray(whole[:cut], np.int32)]), new - cut,
                         gen_offset=cut)
    eng.run_until_idle()
    assert resumed.tokens == whole[cut:]
    sampled = dict(temperature=0.8, top_k=20, seed=5)
    a_eng = make_engine(lm_and_params)
    a = a_eng.submit(p, new, **sampled)
    a_eng.run_until_idle()
    b_eng = make_engine(lm_and_params)
    b = b_eng.submit(np.concatenate([p, np.asarray(a.tokens[:cut], np.int32)]), new - cut,
                     gen_offset=cut, **sampled)
    b_eng.run_until_idle()
    assert b.tokens == a.tokens[cut:]


def test_kv_lane_ships_the_state_with_the_rows(lm_and_params):
    eng = make_engine(lm_and_params)
    req = eng.submit(prompt(21, seed=8), 30)
    eng.step()
    lane = eng.kv_lane(req.request_id)
    sizes = eng.pool.slot_bytes()
    # float32 wire: K/V (float32 here) and state 4 bytes a value, all of both
    assert lane.size * 4 == sizes["kv_bytes_per_slot"] + sizes["state_bytes_per_slot"]
    state = np.asarray(eng.pool.cache["layer_0"]["gdn"]["state"][req.slot]).ravel()
    assert np.abs(state).max() > 0
    assert any(np.array_equal(lane[i:i + state.size], state)
               for i in range(0, lane.size - state.size + 1, 8))
    eng.run_until_idle()
    assert eng.kv_lane(req.request_id) is None


def test_slo_summary_says_what_a_slot_holds(lm_and_params):
    eng = make_engine(lm_and_params)
    pool = eng.slo_summary()["pool"]
    # 2 full layers x (K + V) x 2 heads x 32 x (128 rows + ring of 4) x 4 bytes
    assert pool["kv_bytes_per_slot"] == 2 * 2 * 2 * 32 * (128 + BLOCK) * 4
    # 6 linear layers x (state 2 x 16 x 8 float32 + tail 3 x 64 float32)
    assert pool["state_bytes_per_slot"] == 6 * (2 * 16 * 8 * 4 + 3 * 64 * 4)
    assert eng.slo_summary()["prefill_tokens"] == {"real": 0, "padded": 0}
    eng.submit(prompt(5), 2)
    eng.run_until_idle()
    eng.reset_metrics()
    assert eng.slo_summary()["prefill_tokens"] == {"real": 0, "padded": 0}


def test_an_attention_only_model_holds_no_state():
    lm = TransformerLM(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64, max_len=128)
    params = lm.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServingEngine(lm, params, slots=2, cache_size=64, decode_block=BLOCK, prefill_bucket=8)
    assert eng.slo_summary()["pool"]["state_bytes_per_slot"] == 0
    with pytest.raises(ValueError, match="cache rows") as err:
        eng.submit(np.arange(4), 100)
    assert "recurrent" not in str(err.value)


def test_an_oversized_request_is_told_which_layers_the_rows_bound(lm_and_params):
    eng = make_engine(lm_and_params, cache_size=64)
    with pytest.raises(ValueError, match="cache rows .* in each full-attention layer"):
        eng.submit(prompt(40), 40)


# ------------------------------------------------------------------- the CLI
def test_serve_cli_builds_the_model_from_a_published_configuration(tmp_path, capsys):
    from distributed_ml_pytorch_tpu.serving.cli import main

    dump = tmp_path / "metrics.json"
    rc = main(["--model-config", CONFIG_PATH, "--demo", "3", "--slots", "2", "--cache-size", "64",
               "--decode-block", "4", "--prefill-bucket", "8", "--metrics-dump", str(dump)])
    assert rc == 0 and "serving demo complete" in capsys.readouterr().out
    metrics = json.loads(dump.read_text())
    assert metrics["engine.pool"]["state_bytes_per_slot"] == 6 * (2 * 16 * 8 * 4 + 3 * 64 * 4)
    assert metrics["engine.prefill_tokens"]["real"] > 0
    # the decode blocks by the K/V rows their steps read, in chunks of a
    # quarter of the 64: the full-attention layers of this pool too
    read = metrics["engine.kv_read"]
    assert list(read["by_rows"]) == ["16", "32", "48", "64"]
    assert sum(read["by_rows"].values()) == read["blocks"] > 0
    assert 0.25 <= read["rows_share_mean"] < 1.0


def test_serve_cli_refuses_a_model_type_it_cannot_build(tmp_path):
    from distributed_ml_pytorch_tpu.serving.cli import main

    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(CONFIG, model_type="gpt2")))
    with pytest.raises(SystemExit):
        main(["--model-config", str(path), "--demo", "1"])
