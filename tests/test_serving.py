"""Continuous-batching serving engine: parity with generate(), scheduling,
admission control, and the slot pool's exactness contract.

The load-bearing property is ARRIVAL-ORDER-INDEPENDENT EXACTNESS: whatever
mix of requests shares the slot pool, each request's output must be
token-identical (CPU) to a standalone ``generate()`` with the same
``(params, prompt, rng)`` — slots are independent vmap lanes over the same
attention module, so sharing a batch must never leak between requests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models.generate import (
    generate,
    sample_tokens,
    sample_tokens_dynamic,
)
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
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
    """The branches of the scanned step's one conditional, the sampler's, in
    the order of its index: all greedy, a draw, a filter and a draw."""
    conds = [eqn for eqn in _equations(step) if eqn.primitive.name == "cond"]
    assert len(conds) == 1, "the step's one conditional is the sampler's"
    return [branch.jaxpr for branch in conds[0].params["branches"]]


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
