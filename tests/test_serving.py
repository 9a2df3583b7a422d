"""Continuous-batching serving engine: parity with generate(), scheduling,
admission control, and the slot pool's exactness contract.

The load-bearing property is ARRIVAL-ORDER-INDEPENDENT EXACTNESS: whatever
mix of requests shares the slot pool, each request's output must be
token-identical (CPU) to a standalone ``generate()`` with the same
``(params, prompt, rng)`` — slots are independent vmap lanes over the same
attention module, so sharing a batch must never leak between requests.
"""

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models.generate import (
    generate,
    sample_tokens,
    sample_tokens_dynamic,
)
from distributed_ml_pytorch_tpu.models.transformer import (
    KV_READ,
    TransformerLM,
    kv_read_chunk,
)
from distributed_ml_pytorch_tpu.serving.cache import (
    SlotKVPool,
    find_cache_leaf,
    kv_read_hint,
    kv_read_ladder,
)
from distributed_ml_pytorch_tpu.serving.engine import (
    QueueFullError,
    ServingEngine,
)

VOCAB = 64


def tiny_lm():
    return TransformerLM(
        vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_len=128
    )


@pytest.fixture(scope="module")
def lm_and_params():
    model = tiny_lm()
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(lm_and_params, **kw):
    model, params = lm_and_params
    kw.setdefault("slots", 3)
    kw.setdefault("cache_size", 96)
    kw.setdefault("decode_block", 4)
    kw.setdefault("prefill_bucket", 8)
    return ServingEngine(model, params, **kw)


def ref_tokens(model, params, prompt, max_new, **kw):
    """Standalone generate() continuation for one request (the oracle)."""
    out = generate(model, params, jnp.asarray(prompt, jnp.int32)[None],
                   max_new, **kw)
    return np.asarray(out)[0, len(prompt):].tolist()


def prompts_rng(seed=0):
    return np.random.default_rng(seed)


def test_single_request_greedy_matches_generate(lm_and_params):
    model, params = lm_and_params
    eng = make_engine(lm_and_params)
    prompt = prompts_rng(1).integers(0, VOCAB, size=5)
    req = eng.submit(prompt, 20)
    eng.run_until_idle()
    assert req.done and len(req.tokens) == 20
    assert req.tokens == ref_tokens(model, params, prompt, 20)


def test_mixed_arrival_parity_and_midflight_admission(lm_and_params):
    """The acceptance-criterion test: a late request is admitted while an
    earlier one is mid-decode, and EVERY request still matches its
    standalone generate() output exactly."""
    model, params = lm_and_params
    eng = make_engine(lm_and_params)
    rng = prompts_rng(2)
    pa = rng.integers(0, VOCAB, size=6)
    pb = rng.integers(0, VOCAB, size=3)
    pc = rng.integers(0, VOCAB, size=9)

    ra = eng.submit(pa, 30)
    eng.step()  # admits A, decodes one block
    eng.step()
    assert not ra.done and len(ra.tokens) > 1  # A is mid-decode
    rb = eng.submit(pb, 9)
    rc = eng.submit(pc, 17)
    eng.run_until_idle()

    assert rb.active_at_admit >= 1  # B joined while A held a slot
    for req, prompt, n in ((ra, pa, 30), (rb, pb, 9), (rc, pc, 17)):
        assert req.done and len(req.tokens) == n
        assert req.tokens == ref_tokens(model, params, prompt, n), (
            f"request {req.request_id} diverged from standalone generate()")


def test_sampled_request_matches_generate_rng(lm_and_params):
    """Temperature/top-k/top-p requests must reproduce generate()'s exact
    token stream for the same seed — the per-slot fold_in key schedule is
    part of the engine's contract, not just greedy argmax."""
    model, params = lm_and_params
    eng = make_engine(lm_and_params)
    prompt = prompts_rng(3).integers(0, VOCAB, size=4)
    req = eng.submit(prompt, 18, temperature=0.8, top_k=7, top_p=0.9, seed=11)
    other = eng.submit(prompts_rng(4).integers(0, VOCAB, size=7), 12)
    eng.run_until_idle()
    want = ref_tokens(model, params, prompt, 18, temperature=0.8,
                      top_k=7, top_p=0.9, rng=jax.random.key(11))
    assert req.tokens == want
    assert other.done and len(other.tokens) == 12


def test_parity_independent_of_arrival_order(lm_and_params):
    """Same request set, two arrival orders -> identical per-request
    outputs (and equal to running each alone)."""
    model, params = lm_and_params
    rng = prompts_rng(5)
    reqs = [(rng.integers(0, VOCAB, size=int(rng.integers(2, 10))),
             int(rng.integers(5, 22))) for _ in range(4)]
    outs = []
    for order in (range(4), reversed(range(4))):
        eng = make_engine(lm_and_params)
        handles = {}
        for i in order:
            prompt, n = reqs[i]
            handles[i] = eng.submit(prompt, n)
            eng.step()  # interleave admission with decode
        eng.run_until_idle()
        outs.append({i: handles[i].tokens for i in range(4)})
    assert outs[0] == outs[1]
    for i, (prompt, n) in enumerate(reqs):
        assert outs[0][i] == ref_tokens(model, params, prompt, n)


def test_prefill_bucketing_is_exact(lm_and_params):
    """Right-padding prompts to the prefill bucket must not change a single
    token (padded K/V is causally invisible and cursor-rewound)."""
    model, params = lm_and_params
    prompt = prompts_rng(6).integers(0, VOCAB, size=5)
    outs = []
    for bucket in (1, 8):
        eng = make_engine(lm_and_params, prefill_bucket=bucket)
        req = eng.submit(prompt, 13)
        eng.run_until_idle()
        outs.append(req.tokens)
    assert outs[0] == outs[1] == ref_tokens(model, params, prompt, 13)


def test_single_token_prompt_pads_past_decode_discriminator(lm_and_params):
    """A 1-token prompt must still prefill correctly: inside the blocked
    module ``s == 1`` means a DECODE step, so admission pads the prompt to
    at least 2 even at prefill_bucket=1 — and stays exact vs generate()."""
    model, params = lm_and_params
    eng = make_engine(lm_and_params, prefill_bucket=1)
    prompt = np.asarray([7], np.int32)
    req = eng.submit(prompt, 14)
    eng.run_until_idle()
    assert req.tokens == ref_tokens(model, params, prompt, 14)


def test_kv_quant_pool_deterministic_and_in_vocab(lm_and_params):
    """int8 slot caches: deterministic, shape-correct, in-vocab; and the
    first generated token matches the exact-cache engine (prefill logits
    carry no quantization noise — the single-prefill contract holds for
    every fresh slot admission)."""
    outs = []
    prompt = prompts_rng(7).integers(0, VOCAB, size=6)
    for quant in (True, True, False):
        eng = make_engine(lm_and_params, kv_quant=quant)
        req = eng.submit(prompt, 15)
        eng.run_until_idle()
        outs.append(req.tokens)
    assert outs[0] == outs[1]
    assert len(outs[0]) == 15
    assert all(0 <= t < VOCAB for t in outs[0])
    assert outs[0][0] == outs[2][0]


def test_queue_backpressure_raises(lm_and_params):
    eng = make_engine(lm_and_params, slots=1, max_queue=2)
    prompt = np.arange(4)
    eng.submit(prompt, 6)
    eng.submit(prompt, 6)
    with pytest.raises(QueueFullError):
        eng.submit(prompt, 6)
    eng.run_until_idle()
    summary = eng.slo_summary()
    assert summary["rejected"] == 1 and summary["completed"] == 2


def test_submit_rejects_oversized_request(lm_and_params):
    eng = make_engine(lm_and_params, cache_size=32)
    with pytest.raises(ValueError, match="cache rows"):
        eng.submit(np.arange(4), 40)
    with pytest.raises(ValueError):
        eng.submit(np.arange(4), 0)


def test_cancel_queued_and_active(lm_and_params):
    eng = make_engine(lm_and_params, slots=1)
    ra = eng.submit(np.arange(5), 25)
    rb = eng.submit(np.arange(3), 10)
    eng.step()  # A active, B queued
    assert eng.cancel(rb.request_id)
    eng.step()
    assert eng.cancel(ra.request_id)
    eng.run_until_idle()
    assert ra.done and ra.cancelled and len(ra.tokens) < 25
    assert rb.done and rb.cancelled and rb.tokens == []
    assert not eng.cancel(12345)


def test_eos_token_truncates_stream(lm_and_params):
    model, params = lm_and_params
    prompt = prompts_rng(8).integers(0, VOCAB, size=5)
    full = ref_tokens(model, params, prompt, 20)
    eos = full[4]  # force an early stop at a token greedy decode emits
    eng = make_engine(lm_and_params)
    req = eng.submit(prompt, 20, eos_token=eos)
    eng.run_until_idle()
    stop = full.index(eos)
    assert req.tokens == full[: stop + 1]


def test_max_new_tokens_one_completes_at_admission(lm_and_params):
    model, params = lm_and_params
    prompt = prompts_rng(9).integers(0, VOCAB, size=6)
    eng = make_engine(lm_and_params)
    req = eng.submit(prompt, 1)
    eng.run_until_idle()
    assert req.done and req.tokens == ref_tokens(model, params, prompt, 1)
    # the slot freed at admission must be swept like any evicted slot
    assert eng.pool.live_lengths().max() == 0


def test_slot_reuse_after_completion_is_clean(lm_and_params):
    """A recycled slot must give the same output as a fresh engine — no
    leakage from the previous occupant's cache."""
    model, params = lm_and_params
    eng = make_engine(lm_and_params, slots=1)
    p1 = prompts_rng(10).integers(0, VOCAB, size=7)
    p2 = prompts_rng(11).integers(0, VOCAB, size=4)
    eng.submit(p1, 12)
    eng.run_until_idle()
    req = eng.submit(p2, 16)  # reuses the single slot
    eng.run_until_idle()
    assert req.tokens == ref_tokens(model, params, p2, 16)


def test_slo_summary_reports_percentiles(lm_and_params):
    eng = make_engine(lm_and_params)
    for seed in range(3):
        eng.submit(prompts_rng(seed).integers(0, VOCAB, size=4), 9)
    eng.run_until_idle()
    s = eng.slo_summary()
    assert s["completed"] == 3
    assert s["ttft_ms"] is not None and s["ttft_ms"]["count"] == 3
    assert set(s["ttft_ms"]) >= {"count", "mean", "p50", "p90", "p99", "max"}
    assert s["tpot_ms"]["count"] == 3 and s["tpot_ms"]["p50"] > 0
    assert 0 < s["slot_occupancy"] <= 1
    assert s["queue_depth"]["max"] >= 0


def test_live_lengths_track_slot_progress(lm_and_params):
    eng = make_engine(lm_and_params)
    eng.submit(np.arange(1, 6), 20)
    eng.step()
    lens = eng.pool.live_lengths()
    assert lens.shape == (3,)
    assert lens.max() == 5 + eng.pool.decode_block  # prompt + one block
    eng.run_until_idle()
    assert eng.pool.live_lengths().max() == 0  # everything evicted + reset


#: batches of (temperature, top_k, top_p) rows, one for each amount of work
#: the sampler's conditional can choose and for the mixes between them
SAMPLER_BATCHES = {
    "all_greedy": [(0.0, 0, 1.0), (0.0, 5, 1.0), (-1.0, 0, 0.5), (0.0, 3, 0.7)],
    "temperature_only": [(1.0, 0, 1.0), (0.7, 0, 1.0), (1.3, 0, 1.0)],
    "top_k_only": [(0.7, 5, 1.0), (0.5, 1, 1.0), (1.1, VOCAB + 10, 1.0)],
    "top_p_only": [(0.7, 0, 0.9), (1.0, 0, 0.5), (0.9, 0, 0.0)],
    "mixed_with_greedy": [(0.0, 0, 1.0), (0.8, 0, 1.0), (0.0, 4, 0.6),
                          (1.2, 0, 0.8), (0.6, 3, 1.0)],
    "k_and_p_together": [(1.3, 8, 0.85), (0.9, VOCAB + 10, 0.5), (0.6, 3, 0.7)],
    "temperature_beside_greedy": [(0.0, 0, 1.0), (0.9, 0, 1.0), (0.0, 7, 0.3)],
}


@pytest.mark.parametrize("batch", list(SAMPLER_BATCHES))
def test_sample_tokens_dynamic_matches_scalar_rowwise(batch):
    """The traced-params sampler must agree bit-for-bit with sample_tokens
    for every configuration a request can carry (greedy, temp-only, top-k,
    top-p, combined), in whatever batch it sits — this equivalence is what
    lets one compiled block program serve heterogeneous sampling params,
    and it must hold in each tier of the sampler's conditional: the batch as
    a whole decides how much of the work runs, never what a row gets."""
    rows = SAMPLER_BATCHES[batch]
    n = len(rows)
    logits = jnp.asarray(
        np.random.default_rng(len(batch)).normal(size=(n, VOCAB)) * 2.0,
        jnp.float32)
    keys = jax.vmap(jax.random.key)(jnp.arange(100, 100 + n, dtype=jnp.uint32))
    temps, ks, ps = (jnp.asarray(col) for col in zip(*rows))
    got = sample_tokens_dynamic(logits, keys, temps, ks, ps, jnp.ones(n, bool))
    assert got.shape == (n,) and got.dtype == jnp.int32
    for row, (t, k, p) in enumerate(rows):
        want = sample_tokens(
            logits[row][None], keys[row], temperature=t, top_k=k, top_p=p)[0]
        assert int(got[row]) == int(want), (batch, row, t, k, p)
        alone = sample_tokens_dynamic(
            logits[row][None], keys[row][None], temps[row][None],
            ks[row][None], ps[row][None], jnp.ones(1, bool))[0]
        assert int(alone) == int(want), (batch, row, t, k, p)


def test_sampler_tier_follows_the_rows_somebody_reads(monkeypatch):
    """The conditional's index is 0 while no live row has a temperature, 1
    while none of those asks for top-k or top-p, else 2. ``live`` names the
    rows whose token is read: a dead row's stale top-p must not make the
    batch sort, and a live row's token does not depend on ``live`` at all."""
    taken, switch = [], jax.lax.switch
    monkeypatch.setattr(  # eager calls: the index is a concrete scalar
        jax.lax, "switch",
        lambda index, *rest: taken.append(int(index)) or switch(index, *rest))
    rng = np.random.default_rng(7)
    logits = jnp.asarray(rng.normal(size=(3, VOCAB)), jnp.float32)
    keys = jax.vmap(jax.random.key)(jnp.arange(3, dtype=jnp.uint32))
    temps, ks, ps = (jnp.asarray([0.0, 0.9, 0.7]), jnp.asarray([0, 0, 4]),
                     jnp.asarray([1.0, 1.0, 0.6]))
    every = sample_tokens_dynamic(logits, keys, temps, ks, ps, jnp.ones(3, bool))
    assert taken == [2]
    for live, tier in (([True, True, True], 2), ([False, False, True], 2),
                       ([True, True, False], 1), ([True, False, False], 0),
                       ([False, False, False], 0)):
        got = sample_tokens_dynamic(
            logits, keys, temps, ks, ps, jnp.asarray(live))
        assert taken[-1] == tier, live
        assert [int(got[r]) for r in range(3) if live[r]] == [
            int(every[r]) for r in range(3) if live[r]], live


def test_sample_tokens_dynamic_heterogeneous_rows():
    """A batch mixing greedy and differently-truncated sampled rows equals
    running each row separately."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, VOCAB)), jnp.float32)
    keys = jax.vmap(jax.random.key)(jnp.arange(4, dtype=jnp.uint32))
    temps = jnp.asarray([0.0, 0.8, 1.2, 0.6])
    ks = jnp.asarray([0, 5, 0, 3])
    ps = jnp.asarray([1.0, 1.0, 0.8, 0.7])
    batched = sample_tokens_dynamic(logits, keys, temps, ks, ps, jnp.ones(4, bool))
    for row in range(4):
        alone = sample_tokens_dynamic(
            logits[row][None], keys[row][None], temps[row][None],
            ks[row][None], ps[row][None], jnp.ones(1, bool))[0]
        assert int(batched[row]) == int(alone)


# --- the sampler does what the ACTIVE rows ask for ----------------------------

def test_free_slots_stale_sampling_params_cost_and_change_nothing(lm_and_params):
    """The engine never clears a slot's temperature / top-k / top-p at
    eviction. The decode block masks the sampler's tier by ``active``, so
    what a free slot still holds is not read: the block returns for every
    slot, the free ones' garbage included, what it returns with those
    parameters cleared (a free row is sampled only if the tier was raised).
    The same parameters on an ACTIVE slot do change that slot's tokens."""
    def block(stale, active):
        eng = make_engine(lm_and_params)
        eng.submit(np.arange(1, 6), 30)
        eng.step()  # slot 0 is live and one block in; slots 1, 2 never used
        temps, ks, ps = eng._temps.copy(), eng._top_ks.copy(), eng._top_ps.copy()
        if stale:
            temps[1:], ks[2], ps[1] = (5.0, 3.0), 3, 0.5
        return eng.pool.decode_block_step(
            eng._tok, eng._n_gen, eng._seeds, temps, ks, ps,
            np.asarray(active, bool))

    only_first = [True, False, False]
    clean = block(False, only_first)
    np.testing.assert_array_equal(block(True, only_first), clean)
    everyone = block(True, [True, True, True])
    np.testing.assert_array_equal(everyone[0], clean[0])
    assert (everyone[1:] != block(False, [True, True, True])[1:]).any()


def test_sampler_counter_and_greedy_tokens_beside_a_top_p_request(lm_and_params):
    """``slo_summary()["sampler"]`` counts the decode blocks whose ACTIVE
    requests made the sampler draw, and filter: nonzero while a top-p
    request is live, and unchanged over the all-greedy blocks after its
    eviction though its slot's parameters are still in the engine's mirror.
    A greedy request's tokens are the same alone and beside it."""
    model, params = lm_and_params
    prompt = prompts_rng(5).integers(0, VOCAB, size=6)
    alone = make_engine(lm_and_params)
    want = alone.submit(prompt, 30)
    alone.run_until_idle()
    assert alone.slo_summary()["sampler"] == {
        "blocks": 8, "sampled_blocks": 0, "filtered_blocks": 0}

    eng = make_engine(lm_and_params)
    greedy = eng.submit(prompt, 30)
    sampled = eng.submit(prompts_rng(6).integers(0, VOCAB, size=7), 6,
                         temperature=0.8, top_p=0.7, seed=3)
    while eng._slot_req[1] is not sampled:
        eng.step()
    while eng._slot_req[1] is sampled:  # ... until its eviction
        eng.step()
    # six tokens are an admission and two blocks of four; the round that
    # evicted it went on to decode the first all-greedy block
    after = eng.slo_summary()["sampler"]
    assert after == {"blocks": 3, "sampled_blocks": 2, "filtered_blocks": 2}
    assert eng._top_ps[1] < 1.0 and eng._temps[1] > 0.0  # stale, in a free slot
    eng.run_until_idle()
    end = eng.slo_summary()["sampler"]
    assert end["blocks"] > after["blocks"]
    assert end["filtered_blocks"] == after["filtered_blocks"]
    assert end["sampled_blocks"] == after["sampled_blocks"]
    assert greedy.tokens == want.tokens
    assert sampled.tokens == ref_tokens(
        model, params, sampled.prompt, 6, temperature=0.8, top_p=0.7,
        rng=jax.random.key(3))

    # a temperature alone draws and does not filter
    eng.reset_metrics()
    eng.submit(prompt, 5, temperature=0.9, seed=1)
    eng.run_until_idle()
    assert eng.slo_summary()["sampler"] == {
        "blocks": 1, "sampled_blocks": 1, "filtered_blocks": 0}


# --- the ring append (a select, not a per-slot scatter) ----------------------

def _equations(jaxpr):
    """Every equation of ``jaxpr``, those of its sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _scatter_operands(jaxpr):
    """Shape of the operand each scatter in ``jaxpr`` writes into."""
    return [eqn.invars[0].aval.shape for eqn in _equations(jaxpr)
            if eqn.primitive.name.startswith("scatter")]


def test_decode_scan_appends_without_scatter(lm_and_params):
    """A ``dynamic_update_slice`` at a per-slot offset under the pool's
    ``vmap`` is a scatter, and the TPU compiler runs a scatter as a
    sequential loop over the slots: two such loops a layer in every decode
    step. The step's ring append is a select, so the scanned step scatters
    into no cache leaf; the once-a-block merge, outside the scan, and the
    sampler's mask over the logits, inside the one branch of its conditional
    that filters, are other mechanisms and stay."""
    # what the walk has to catch: a batched offset turns the write into a scatter
    ring = jnp.zeros((3, 1, 4, 4, 8))
    batched = jax.make_jaxpr(jax.vmap(
        lambda r, k, t: jax.lax.dynamic_update_slice(r, k, (0, 0, t, 0))))(
            ring, jnp.ones((3, 1, 4, 1, 8)), jnp.arange(3))
    assert _scatter_operands(batched.jaxpr) == [ring.shape]

    pool = make_engine(lm_and_params).pool
    step = _scanned_step(pool)
    assert any(eqn.primitive.name == "dot_general" for eqn in _equations(step))
    *_, filtered = _sampler_branches(step)
    assert _scatter_operands(filtered) == [(pool.slots, VOCAB)]
    assert len(_scatter_operands(step)) == 1  # that one, and no other


def _scanned_step(pool):
    """The jaxpr of one step of ``_decode_block_jit``'s scan for ``pool``."""
    from distributed_ml_pytorch_tpu.serving.cache import _decode_block_jit

    S = pool.slots
    jaxpr = jax.make_jaxpr(_decode_block_jit, static_argnums=(0,))(
        pool.dec, pool.params, pool.cache,
        jnp.zeros(S, jnp.int32), jnp.zeros(S, jnp.int32),
        jnp.zeros(S, jnp.uint32), jnp.zeros(S, jnp.float32),
        jnp.zeros(S, jnp.int32), jnp.ones(S, jnp.float32),
        jnp.ones(S, bool)).jaxpr
    scans = [eqn for eqn in _equations(jaxpr) if eqn.primitive.name == "scan"]
    assert len(scans) == 1, "the decode block is one scan over its steps"
    (step,) = jax.core.jaxprs_in_params(scans[0].params)
    return step


def _sampler_branches(step):
    """The branches of the step's sampler, in the order of its index: all
    greedy, a draw, a filter and a draw. It is found by what it is, a
    conditional of three branches whose first is an argmax: a step may hold
    other conditionals."""
    found = [
        [branch.jaxpr for branch in eqn.params["branches"]]
        for eqn in _equations(step) if eqn.primitive.name == "cond"
        and len(eqn.params["branches"]) == 3]
    found = [branches for branches in found if _count(branches[0], "argmax")]
    assert len(found) == 1, "the step samples once"
    return found[0]


def _count(jaxpr, *primitives):
    return sum(eqn.primitive.name in primitives for eqn in _equations(jaxpr))


def test_decode_step_sorts_and_draws_only_in_the_branches_that_need_to(
        lm_and_params):
    """A decode step of an all-greedy pool pays for an argmax: the sort, the
    cumulative sum and the random bits each sit inside a branch of ONE
    conditional outside the per-row ``vmap`` (under ``vmap`` a conditional
    is a select and every side runs), and the branch an all-greedy batch
    takes holds none of them."""
    step = _scanned_step(make_engine(lm_and_params).pool)
    greedy, drawn, filtered = _sampler_branches(step)
    costly = ("sort", "cumsum", "random_bits")
    assert _count(greedy, *costly) == 0
    assert _count(greedy, "argmax") == 1
    assert _count(drawn, "sort", "cumsum") == 0
    assert _count(drawn, "random_bits") == 1
    assert (_count(filtered, "sort"), _count(filtered, "cumsum"),
            _count(filtered, "random_bits")) == (1, 1, 1)
    # and nowhere else in the step
    assert _count(step, *costly) == _count(drawn, *costly) + _count(
        filtered, *costly)


RING_T = 16


@pytest.mark.parametrize("lanes", [1, 4])
@pytest.mark.parametrize("kv_quant", [False, True], ids=["bf16", "kv_quant"])
@pytest.mark.parametrize("t", [0, 7, RING_T - 1])
def test_ring_append_matches_dynamic_update_slice(t, kv_quant, lanes):
    """One single-token apply leaves in each lane's ring exactly what
    ``dynamic_update_slice`` of its K/V row at its own offset gives, bit
    for bit, alone and under ``vmap`` with a different ``t`` in every lane."""
    from flax import linen as nn

    from distributed_ml_pytorch_tpu.models.transformer import (
        MultiHeadAttention,
    )

    d_model, heads, T = 32, 4, RING_T
    attn = MultiHeadAttention(
        d_model, heads, dtype=jnp.bfloat16, decode=True, cache_size=64,
        decode_block=T, kv_quant=kv_quant)
    rng = np.random.default_rng(100 * t + 10 * kv_quant + lanes)
    variables = attn.init(jax.random.key(1), jnp.zeros((1, 1, d_model)))
    params = variables["params"]
    ts = [(t + 5 * lane) % T for lane in range(lanes)]  # all different
    base = 8  # a prompt of 8 rows sits in the big cache

    def lane_cache(t_lane):
        cache = dict(variables["cache"])
        for name in ("ring_k", "ring_v"):  # stale rows of the last block
            cache[name] = jnp.asarray(
                rng.normal(size=cache[name].shape), jnp.bfloat16)
        cache["ring_base"] = jnp.asarray(base, jnp.int32)
        cache["cursor"] = jnp.asarray(base + t_lane, jnp.int32)
        return cache

    caches = [lane_cache(t_lane) for t_lane in ts]
    xs = [jnp.asarray(rng.normal(size=(1, 1, d_model)), jnp.float32)
          for _ in ts]

    def apply(cache, x):
        _, mutated = attn.apply(
            {"params": params, "cache": cache}, x, mutable=["cache"])
        return mutated["cache"]

    if lanes == 1:
        got = [apply(caches[0], xs[0])]
    else:
        stacked = jax.vmap(apply)(
            jax.tree.map(lambda *leaves: jnp.stack(leaves), *caches),
            jnp.stack(xs))
        got = [jax.tree.map(lambda leaf: leaf[i], stacked)
               for i in range(lanes)]

    def row(name, x):  # the lane's K or V row, as the module computes it
        dense = nn.Dense(d_model, use_bias=False, dtype=jnp.bfloat16)
        y = dense.apply({"params": params[name]}, x)
        return y.reshape(1, 1, heads, d_model // heads).transpose(0, 2, 1, 3)

    for t_lane, before, x, after in zip(ts, caches, xs, got):
        for ring, proj in (("ring_k", "k"), ("ring_v", "v")):
            want = jax.lax.dynamic_update_slice(
                before[ring], row(proj, x), (0, 0, t_lane, 0))
            assert after[ring].dtype == jnp.bfloat16
            np.testing.assert_array_equal(
                np.asarray(after[ring]), np.asarray(want))
        assert int(after["cursor"]) == base + t_lane + 1


# --- a decode step reads the rows the pool holds live (ISSUE 33) -------------


READ_KINDS = ["plain", "kv_quant", "hybrid"]


@pytest.fixture(scope="module")
def hybrid_and_params():
    from benchmarks.reference import olmo_hybrid as ref
    from distributed_ml_pytorch_tpu.models.hybrid import HybridLM

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tiny_olmo_hybrid_config.json")
    with open(path) as fh:
        config = json.load(fh)  # full-attention layers with QK-norm, float32
    return HybridLM.from_config(config), ref.make_params(jax.random.key(1), config)


@pytest.fixture
def read_engine(request, lm_and_params, hybrid_and_params):
    """``make(**kw)`` builds an engine of the requested kind (96 rows, read
    in chunks of 24), and ``oracle(prompt, n)`` is its generate()."""
    kind = request.param
    model, params = hybrid_and_params if kind == "hybrid" else lm_and_params
    quant = kind == "kv_quant"

    def make(**kw):
        kw = {"slots": 3, "cache_size": 96, "decode_block": 4,
              "prefill_bucket": 8, "kv_quant": quant, **kw}
        return ServingEngine(model, params, **kw)

    make.oracle = lambda prompt, n: ref_tokens(
        model, params, prompt, n, kv_quant=quant)
    make.vocab = model.vocab_size
    return make


def test_the_ladder_is_whole_chunks_of_the_allocation():
    """One rule: a chunk is a quarter of the allocation, rounded up, and a
    read stops after whole chunks or at the allocation's end."""
    assert kv_read_ladder(1024) == (256, 512, 768, 1024)
    assert kv_read_ladder(1536) == (384, 768, 1152, 1536)
    assert kv_read_ladder(96) == (24, 48, 72, 96)
    assert kv_read_ladder(100) == (25, 50, 75, 100)
    assert kv_read_ladder(5) == (2, 4, 5) and kv_read_ladder(1) == (1,)
    for rows in (1, 5, 96, 100, 1024, 1536):
        assert kv_read_ladder(rows)[0] == kv_read_chunk(rows)


def _big_cache_rows(eqn, pool, head_dim):
    """Rows of the big cache that a ``dot_general`` has for an operand, or
    None when neither operand is a big cache or a piece of one."""
    for var in eqn.invars:
        shape = var.aval.shape
        if (len(shape) == 5 and shape[0] == pool.slots and shape[4] == head_dim
                and shape[3] not in (1, pool.decode_block)):  # a row, a ring
            return shape[3]
    return None


@pytest.mark.parametrize("read_engine", READ_KINDS, indirect=True)
def test_decode_step_reads_the_big_caches_in_a_loop_the_pool_bounds(
        read_engine):
    """Every attention layer's two products over its big cache sit in the
    body of ONE loop a layer and read a chunk of 24 rows there; outside
    those loops no product has a big cache, or a piece of one, for an
    operand, so nothing reads the allocation. A loop's trip count is one
    scalar for the pool: its condition works on scalars alone (a per-slot
    bound under the pool's ``vmap`` would make it an ``any`` over the slots
    with every carry a select) and compares the counter with the chunks that
    hold the longest ACTIVE slot."""
    pool = read_engine().pool
    chunk = kv_read_chunk(pool.cache_size)
    head_dim = find_cache_leaf(pool.cache, "cached_k").shape[-1]
    attn_layers = sum(
        "cached_k" in path[-1].key
        for path, _ in jax.tree_util.tree_leaves_with_path(pool.cache))
    step = _scanned_step(pool)
    loops = [eqn for eqn in _equations(step) if eqn.primitive.name == "while"]
    assert len(loops) == attn_layers >= 2 and chunk == 24
    for loop in loops:
        body, cond = loop.params["body_jaxpr"].jaxpr, loop.params["cond_jaxpr"].jaxpr
        reads = [_big_cache_rows(e, pool, head_dim) for e in _equations(body)
                 if e.primitive.name == "dot_general"]
        assert reads == [chunk, chunk]  # K and V
        assert all(v.aval.shape == () for e in cond.eqns for v in e.outvars)
    in_loops = sum(_count(loop.params["body_jaxpr"].jaxpr, "dot_general")
                   for loop in loops)
    outside = [_big_cache_rows(e, pool, head_dim) for e in _equations(step)
               if e.primitive.name == "dot_general"]
    assert len([r for r in outside if r is not None]) == in_loops
    assert outside.count(None) > 0  # the projections, the MLPs, the head


def _step_logits(pool, bound=None):
    """One decode step's logits for every slot of ``pool`` as it stands: the
    big caches read whole (the module as every other caller runs it), or as
    far as ``bound`` rows in whole chunks."""
    hint = {} if bound is None else {
        KV_READ: kv_read_hint(pool.cache, jnp.asarray(bound, jnp.int32))}

    def lane(lane_cache, tok, pos):
        logits, _ = pool.dec.apply(
            {"params": pool.params, "cache": lane_cache, **hint},
            tok[None, None], pos[None, None], mutable=["cache"])
        return logits[0, -1]

    toks = jnp.arange(1, pool.slots + 1, dtype=jnp.int32)
    return np.asarray(jax.vmap(lane)(
        pool.cache, toks, find_cache_leaf(pool.cache, "cursor")))


@pytest.mark.parametrize("longest", [47, 48, 49], ids=["under", "at", "over"])
@pytest.mark.parametrize("read_engine", READ_KINDS, indirect=True)
def test_a_read_bounded_at_a_ladder_edge_gives_the_whole_reads_logits(
        read_engine, longest):
    """The longest active slot one row under, at, and one row over the edge
    at 48 rows (the pool reads 48, 48 and 72): every slot's logits by that
    read are those of the read over all 96 rows, to float32 rounding, and
    the same token leads."""
    pool = read_engine().pool
    rng = prompts_rng(longest)
    for slot, n in enumerate((longest, 7, 13)):
        bucket = -(-n // 8) * 8
        prompt = np.zeros(bucket, np.int32)
        prompt[:n] = rng.integers(0, read_engine.vocab, size=n)
        pool.admit(slot, prompt, n)
    whole, bounded = _step_logits(pool), _step_logits(pool, longest)
    np.testing.assert_allclose(bounded, whole, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(bounded.argmax(-1), whole.argmax(-1))
    assert _block(pool, [True, True, True])[1] == (48 if longest <= 48 else 72)


@pytest.mark.parametrize("read_engine", READ_KINDS, indirect=True)
def test_a_request_decoded_across_a_ladder_edge_matches_generate(read_engine):
    """A prompt of 40 and 21 tokens in blocks of four starts its blocks at
    rows 40, 44, 48, 52 and 56: three read 48 rows and two 72, and the
    tokens are generate()'s."""
    eng = read_engine(slots=1)
    prompt = prompts_rng(33).integers(0, read_engine.vocab, size=40)
    req = eng.submit(prompt, 21)
    eng.run_until_idle()
    assert req.tokens == read_engine.oracle(prompt, 21)
    # an allocation of 96 rows is one block of the per-slot read (no multiple of
    # 128 divides it), which an int8 pool's steps do not take
    assert eng.slo_summary()["kv_read"] == {
        "blocks": 5, "rows_share_mean": (3 * 48 + 2 * 72) / (5 * 96),
        "by_rows": {24: 0, 48: 3, 72: 2, 96: 0},
        "slot_rows_share_mean": 0.0 if eng.pool.kv_quant else 1.0}


@pytest.mark.parametrize("read_engine", READ_KINDS, indirect=True)
def test_the_last_member_serves_a_slot_one_block_short_of_the_allocation(
        read_engine):
    """A request whose last block starts at ``cache_size - decode_block``:
    the blocks past row 72 read all 96, and the tokens are generate()'s."""
    eng = read_engine(slots=1)
    prompt = prompts_rng(34).integers(0, read_engine.vocab, size=56)
    req = eng.submit(prompt, 41)  # blocks start at 56, 60, ... 92
    eng.run_until_idle()
    assert eng.pool.last_read_rows == 96
    assert req.tokens == read_engine.oracle(prompt, 41)
    assert eng.slo_summary()["kv_read"]["by_rows"] == {24: 0, 48: 0, 72: 5, 96: 5}


def test_an_allocation_that_is_no_multiple_of_the_chunk_counts_no_row_twice(
        lm_and_params):
    """100 rows in chunks of 25 divide; 90 rows in chunks of 23 do not, and
    the last chunk starts at row 67 and overlaps the third: a request that
    runs into it gets generate()'s tokens."""
    model, params = lm_and_params
    eng = make_engine(lm_and_params, slots=1, cache_size=90, decode_block=2)
    assert eng.pool.read_ladder == (23, 46, 69, 90)
    prompt = prompts_rng(35).integers(0, VOCAB, size=60)
    req = eng.submit(prompt, 29)  # blocks start at 60, 62, ... 86
    eng.run_until_idle()
    assert req.tokens == ref_tokens(model, params, prompt, 29)
    assert eng.slo_summary()["kv_read"]["by_rows"] == {23: 0, 46: 0, 69: 5, 90: 9}


def _block(pool, active):
    S = pool.slots
    toks = pool.decode_block_step(
        np.ones(S, np.int32), np.ones(S, np.int32), np.zeros(S, np.uint32),
        np.zeros(S, np.float32), np.zeros(S, np.int32), np.ones(S, np.float32),
        np.asarray(active, bool))
    return toks, pool.last_read_rows


def test_only_active_slots_raise_the_bound(lm_and_params):
    """An inactive slot that still holds a long sequence does not raise the
    bound, whether the engine freed it or not; a pool with no active slot
    reads no row of any big cache and returns."""
    pool = make_engine(lm_and_params).pool
    long, short = np.arange(1, 61, dtype=np.int32), np.arange(1, 9, dtype=np.int32)
    pool.admit(0, np.pad(long, (0, 4)), 60)
    pool.admit(1, short, 8)
    assert _block(pool, [True, True, False])[1] == 72   # 60 rows -> 3 chunks
    assert _block(pool, [False, True, False])[1] == 24  # slot 0 not asked for
    toks, rows = _block(pool, [False, False, False])
    assert rows == 0 and toks.shape == (3, 4)
    # the inactive slots were re-zeroed on exit, as always: nothing left to raise
    assert pool.live_lengths().tolist() == [0, 0, 0]


def test_a_long_request_admitted_between_two_blocks_is_not_cut_short(
        lm_and_params):
    """Block N serves a short request alone and reads 24 rows; a request of
    60 rows is admitted before block N + 1, which reads 72 for every slot;
    both get generate()'s tokens, and after the long one's eviction the
    short one's blocks read fewer rows again."""
    model, params = lm_and_params
    eng = make_engine(lm_and_params)
    short = eng.submit(prompts_rng(40).integers(0, VOCAB, size=5), 30)
    eng.step()
    assert eng.pool.last_read_rows == 24
    long = eng.submit(prompts_rng(41).integers(0, VOCAB, size=60), 6)
    eng.step()
    assert eng.pool.last_read_rows == 72
    eng.run_until_idle()
    assert eng.pool.last_read_rows == 48  # the short one's last block: row 29
    assert short.tokens == ref_tokens(model, params, short.prompt, 30)
    assert long.tokens == ref_tokens(model, params, long.prompt, 6)


def test_kv_read_counter_follows_the_requests_lengths(lm_and_params):
    """``slo_summary()["kv_read"]``: one entry of the histogram a member of
    the ladder, as many blocks as were dispatched, and the mean share of the
    allocation what the requests' lengths say: a request alone with a prompt
    of ``p`` reads, in its block ``j``, the member that holds ``p + 4 j``."""
    eng = make_engine(lm_and_params, slots=1)
    ladder = eng.pool.read_ladder
    empty = eng.slo_summary()["kv_read"]
    assert empty == {"blocks": 0, "rows_share_mean": 0.0,
                     "by_rows": dict.fromkeys(ladder, 0), "slot_rows_share_mean": 0.0}
    want = []
    for p, new in ((5, 10), (70, 9)):  # short, then long, one at a time
        eng.submit(prompts_rng(p).integers(0, VOCAB, size=p), new)
        eng.run_until_idle()
        blocks = -(-(new - 1) // 4)
        want += [min(m for m in ladder if m >= p + 4 * j) for j in range(blocks)]
    read = eng.slo_summary()["kv_read"]
    assert want == [24, 24, 24, 72, 96]
    assert read["blocks"] == len(want) == eng.slo_summary()["sampler"]["blocks"]
    assert list(read["by_rows"]) == list(ladder)
    assert read["by_rows"] == {m: want.count(m) for m in ladder}
    assert read["rows_share_mean"] == pytest.approx(sum(want) / (len(want) * 96))
    eng.reset_metrics()
    assert eng.slo_summary()["kv_read"] == empty


def test_kv_read_counter_is_the_devices_count_by_the_hosts_rule(lm_and_params):
    """Requests that overlap: after every block the count the device returned
    is the member that holds the longest ACTIVE slot's block start, taken
    from the lengths the pool itself reports."""
    eng = make_engine(lm_and_params)
    ladder = eng.pool.read_ladder
    rng = prompts_rng(50)
    for p, new in ((70, 12), (6, 25), (50, 9), (30, 5), (3, 14)):
        eng.submit(rng.integers(0, VOCAB, size=p), new)
    seen = []
    while eng.step():
        active = [r is not None for r in eng._slot_req]
        if not any(active):
            continue
        start = eng.pool.live_lengths()[active].max() - eng.pool.decode_block
        assert eng.pool.last_read_rows == min(m for m in ladder if m >= start)
        seen.append(eng.pool.last_read_rows)
    read = eng.slo_summary()["kv_read"]
    assert read["blocks"] == len(seen) and set(seen) == set(ladder)
    assert read["by_rows"] == {m: seen.count(m) for m in ladder}


def test_slot_rows_counter_follows_each_active_slots_own_length(lm_and_params):
    """``kv_read.slot_rows_share_mean``: a block's share of ALL slots' rows is,
    over the slots ACTIVE at its dispatch, each one's ring base rounded up to
    the pool's row block (``slot_block_rows``), over slots x ``cache_size``;
    the engine reckons it from its requests' lengths, and it agrees with the
    lengths the pool reports after every block. Never more than the bound's
    share; a pool whose steps read no other way counts 0."""
    eng = make_engine(lm_and_params)
    assert eng.pool.slot_block_rows == 96  # no multiple of 128 divides 96
    eng.pool.slot_block_rows = 24  # the arithmetic at blocks a tiny cache has not
    rng = prompts_rng(51)
    for p, new in ((70, 12), (6, 25), (50, 9), (30, 5), (3, 14)):
        eng.submit(rng.integers(0, VOCAB, size=p), new)
    T, shares = eng.pool.decode_block, []
    while eng.step():
        if eng.slo_summary()["kv_read"]["blocks"] == len(shares):
            continue  # a round without a decode block
        active = [r is not None for r in eng._slot_req]
        bases = eng.pool.live_lengths()[active] - T
        shares.append(sum(min(-(-int(b) // 24) * 24, 96) for b in bases) / (3 * 96))
    read = eng.slo_summary()["kv_read"]
    assert read["blocks"] == len(shares) > 5
    assert read["slot_rows_share_mean"] == pytest.approx(np.mean(shares))
    assert 0 < read["slot_rows_share_mean"] < read["rows_share_mean"]
    eng.reset_metrics()
    assert eng.slo_summary()["kv_read"]["slot_rows_share_mean"] == 0.0
    assert make_engine(lm_and_params, kv_quant=True).pool.slot_block_rows is None


# sha256 of ``Lowered.as_text()`` on the tree before the bounded read (commit
# 495d1d4): callers that give the attention module no bound lower to the
# program they lowered to then. A change that MEANS to alter blocked
# generate() or the prefill replaces these with the new texts' hashes.
PARENT_LOWERED = {
    "generate-plain":
        "b5754411b6fc199e9db3e432b33053e9f75b04354792df413987577b8a0e4059",
    "generate-kv_quant":
        "79ed734f2a4e0d55d8815b4529060149eeb0a44aa9f6a6ebe29b682c89ba56ac",
    "generate-hybrid":
        "d1d2743e9985ed27cbc2e4154668ca5aac07280adca238ff40a195700f90d5ad",
    "admit-plain":
        "69a6fa971246edce5ece8770ab6c3b350f1ddd2e5b8338d89f7a709c57a5be9e",
    "admit-kv_quant":
        "1eca788a9dc958a78c24a2675d728b092bde01b1958f5724a3dbf4a9b7ebb2bd",
    "admit-hybrid":
        "a359b0ec62f938419c816ea0ac8c55c82ede45fb7469b55b82e7b9d08d8e296a",
}


def _lowered_sha(program, kind, lm_and_params, hybrid_and_params):
    from importlib import import_module

    gen = import_module("distributed_ml_pytorch_tpu.models.generate")
    from distributed_ml_pytorch_tpu.serving.cache import _admit_jit

    model, params = hybrid_and_params if kind == "hybrid" else lm_and_params
    quant = kind == "kv_quant"
    shapes = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree)
    if program == "generate":
        blocked, padded = gen.uses_block_decode(model, 5, 40)
        assert blocked
        cache = jax.eval_shape(lambda: gen.init_cache(
            model, 2, padded, decode_block=gen.DECODE_BLOCK, kv_quant=quant))
        dec = gen._decode_model(
            model, padded, decode_block=gen.DECODE_BLOCK, kv_quant=quant)
        lowered = gen._generate_blocked_jit.lower(
            dec, 40, 0.0, 0, 1.0, shapes(params), cache,
            jax.ShapeDtypeStruct((2, 5), jnp.int32),
            jax.eval_shape(lambda: jax.random.key(0)))
    else:
        pool = SlotKVPool(model, params, slots=3, cache_size=96,
                          decode_block=4, kv_quant=quant)
        scalar = lambda dt: jax.ShapeDtypeStruct((), dt)
        lowered = _admit_jit.lower(
            pool.dec, shapes(pool.params), shapes(pool.cache),
            scalar(jnp.int32), jax.ShapeDtypeStruct((1, 8), jnp.int32),
            scalar(jnp.int32), scalar(jnp.uint32), scalar(jnp.float32),
            scalar(jnp.int32), scalar(jnp.float32), scalar(jnp.int32))
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()


@pytest.mark.parametrize("kind", READ_KINDS)
@pytest.mark.parametrize("program", ["generate", "admit"])
def test_callers_that_give_no_bound_lower_to_the_parents_text(
        program, kind, lm_and_params, hybrid_and_params):
    got = _lowered_sha(program, kind, lm_and_params, hybrid_and_params)
    assert got == PARENT_LOWERED[f"{program}-{kind}"]
