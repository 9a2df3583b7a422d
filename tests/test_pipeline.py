"""GPipe pipeline parallelism: the S-stage microbatch schedule must be
numerically identical to the single-stage (plain sequential) forward."""

import jax
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from distributed_ml_pytorch_tpu.parallel.pipeline import (
    PipelineLMConfig,
    create_pp_train_state,
    make_pp_train_step,
    microbatch,
)
from distributed_ml_pytorch_tpu.parallel.seq_parallel import next_token_targets


def cfg4():
    return PipelineLMConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64, max_len=128
    )


def stage_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("stage",))


def make_batch(batch=8, seq=16, seed=0):
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, 64, size=(batch, seq)).astype(np.int32)
    return tokens, next_token_targets(tokens)


def run_steps(n_stages, n_micro, n_steps=2):
    cfg = cfg4()
    mesh = stage_mesh(n_stages)
    tx = optax.sgd(0.1)
    state = create_pp_train_state(cfg, jax.random.key(0), tx, mesh)
    step = make_pp_train_step(cfg, tx, mesh, n_microbatches=n_micro)
    tokens, targets = make_batch()
    tok_mb, tgt_mb = microbatch(tokens, targets, n_micro)
    losses = []
    for _ in range(n_steps):
        state, loss = step(state, tok_mb, tgt_mb)
        losses.append(float(loss))
    return losses, jax.device_get(state.params)


def test_pipeline_matches_single_stage():
    """The 4-stage pipeline equals the single-stage reference — loss AND
    updated params — via the MPMD per-stage-compiled step."""
    from distributed_ml_pytorch_tpu.parallel.mpmd import MpmdLocal

    ref_losses, ref_params = run_steps(n_stages=1, n_micro=1)
    tokens, targets = make_batch()
    pp = MpmdLocal(cfg4(), 4, 4, 0.1, jax.random.key(0))
    tok_mb, tgt_mb = tokens.reshape(4, 2, 16), targets.reshape(4, 2, 16)
    pp_losses = [pp.step(tok_mb, tgt_mb) for _ in range(2)]
    np.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(ref_params),
                    jax.tree.leaves(pp.full_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5,
                                   atol=1e-6)


def test_shard_map_pipeline_matches_single_stage():
    """The shard_map schedule's version of the same parity."""
    ref_losses, ref_params = run_steps(n_stages=1, n_micro=1)
    pp_losses, pp_params = run_steps(n_stages=4, n_micro=4)
    np.testing.assert_allclose(pp_losses, ref_losses, rtol=2e-5)
    for a, b in zip(jax.tree.leaves(ref_params), jax.tree.leaves(pp_params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-5, atol=1e-6)


def test_pipeline_microbatch_count_does_not_change_loss():
    l1, _ = run_steps(n_stages=2, n_micro=2, n_steps=1)
    l2, _ = run_steps(n_stages=2, n_micro=8, n_steps=1)
    np.testing.assert_allclose(l1, l2, rtol=2e-5)


def test_pp_state_blocks_sharded_over_stages():
    cfg = cfg4()
    mesh = stage_mesh(4)
    state = create_pp_train_state(cfg, jax.random.key(0), optax.sgd(0.1, momentum=0.9), mesh)
    leaf = jax.tree.leaves(state.params["blocks"])[0]
    assert leaf.sharding.spec[0] == "stage"
    mom = jax.tree.leaves(state.opt_state[0].trace["blocks"])[0]
    assert mom.sharding.spec[0] == "stage"
    # replicated pieces stay replicated
    assert state.params["head"]["kernel"].sharding.spec == P()


def test_pp_rejects_indivisible_layers():
    cfg = PipelineLMConfig(n_layers=3)
    with pytest.raises(ValueError, match="divide evenly"):
        create_pp_train_state(cfg, jax.random.key(0), optax.sgd(0.1), stage_mesh(2))


def test_microbatch_rejects_indivisible_batch():
    tokens, targets = make_batch(batch=6)
    with pytest.raises(ValueError, match="microbatches"):
        microbatch(tokens, targets, 4)


def test_interleaved_schedule_matches_gpipe_loss_and_grads():
    """The interleaved (virtual-stage) schedule computes the SAME function
    as GPipe — identical loss and identical parameter updates (modulo the
    documented layer-storage permutation) — only the execution order and
    bubble differ."""
    from distributed_ml_pytorch_tpu.parallel.pipeline import (
        interleave_layer_order,
    )

    cfg = PipelineLMConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=8, d_ff=64, max_len=128
    )
    S, v, M = 4, 2, 4
    mesh = stage_mesh(S)
    tx = optax.sgd(0.1)
    tokens, targets = make_batch(batch=M * 2, seq=16)
    tmb, gmb = microbatch(tokens, targets, M)

    state_g = create_pp_train_state(cfg, jax.random.key(0), tx, mesh)
    step_g = make_pp_train_step(cfg, tx, mesh, n_microbatches=M)
    _, loss_g = step_g(state_g, tmb, gmb)

    order = interleave_layer_order(cfg.n_layers, S, v)
    state_i = create_pp_train_state(cfg, jax.random.key(0), tx, mesh)
    state_i = state_i.replace(
        params={**state_i.params,
                "blocks": jax.tree.map(lambda x: x[order],
                                       state_i.params["blocks"])})
    step_i = make_pp_train_step(cfg, tx, mesh, n_microbatches=M,
                                schedule="interleaved", virtual_stages=v)
    new_i, loss_i = step_i(state_i, tmb, gmb)

    np.testing.assert_allclose(float(loss_i), float(loss_g), rtol=1e-5)

    # one more GPipe step to get its updated blocks; the interleaved update
    # must equal it under the same permutation
    new_g, _ = step_g(create_pp_train_state(cfg, jax.random.key(0), tx, mesh),
                      tmb, gmb)
    for a, b in zip(jax.tree.leaves(
            jax.tree.map(lambda x: x[order], new_g.params["blocks"])),
            jax.tree.leaves(new_i.params["blocks"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_interleaved_schedule_wrap_fifo_depths():
    """M > S exercises the wrap FIFO (D = M − S > 0); M == S the direct
    hand-off — both must agree with GPipe."""
    from distributed_ml_pytorch_tpu.parallel.pipeline import (
        interleave_layer_order,
    )

    cfg = PipelineLMConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64, max_len=128
    )
    S, v = 2, 2
    mesh = stage_mesh(S)
    tx = optax.sgd(0.1)
    order = interleave_layer_order(cfg.n_layers, S, v)
    for M in (2, 6):  # D = 0 and D = 4
        tokens, targets = make_batch(batch=M * 2, seq=16, seed=M)
        tmb, gmb = microbatch(tokens, targets, M)
        state = create_pp_train_state(cfg, jax.random.key(1), tx, mesh)
        _, loss_g = make_pp_train_step(cfg, tx, mesh, n_microbatches=M)(
            state, tmb, gmb)
        state_i = create_pp_train_state(cfg, jax.random.key(1), tx, mesh)
        state_i = state_i.replace(
            params={**state_i.params,
                    "blocks": jax.tree.map(lambda x: x[order],
                                           state_i.params["blocks"])})
        _, loss_i = make_pp_train_step(
            cfg, tx, mesh, n_microbatches=M, schedule="interleaved",
            virtual_stages=v)(state_i, tmb, gmb)
        np.testing.assert_allclose(float(loss_i), float(loss_g), rtol=1e-5)


def test_interleaved_rejects_too_few_microbatches():
    cfg = PipelineLMConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=8, d_ff=64, max_len=128
    )
    mesh = stage_mesh(4)
    with pytest.raises(ValueError, match="n_microbatches >= n_stages"):
        make_pp_train_step(cfg, optax.sgd(0.1), mesh, n_microbatches=2,
                           schedule="interleaved", virtual_stages=2)


def test_1f1b_schedule_timetable_properties():
    """Structural proof of the 1F1B memory claim: simulate oneF1B_tick_roles
    over every (tick, stage) — the EXACT function the compiled step traces —
    and check (a) every microbatch runs F then B exactly once per stage,
    (b) a stage never does two units in one tick, (c) backward hand-offs
    arrive exactly one tick after their producer, and (d) the S-slot
    arrivals ring (the schedule's ONLY activation storage, vs GPipe's
    all-M-live profile) serves every forward and backward read correctly:
    a slot is written at arrival (F(s−1,m)+1), reread at F(s,m) and at
    B(s,m), and never overwritten while live."""
    from distributed_ml_pytorch_tpu.parallel.pipeline import oneF1B_tick_roles

    for S, M in [(2, 4), (4, 8), (4, 4), (3, 7), (4, 2), (1, 3)]:
        T = 2 * (M + S - 1)
        F = {}
        B = {}
        for s in range(S):
            ring = {}  # slot -> parked microbatch (live = not yet backward'd)
            peak = 0
            for t in range(T):
                m_f, m_b = oneF1B_tick_roles(t, s, S, M)
                assert not (m_f >= 0 and m_b >= 0), (S, M, s, t)
                if s > 0:
                    # the compiled step's arrival-detection call, verbatim
                    m_a, _ = oneF1B_tick_roles(t - 1, s - 1, S, M)
                    if m_a >= 0:
                        assert ring.get(m_a % S) is None, "overwrote live slot"
                        ring[m_a % S] = m_a
                        peak = max(peak, sum(v is not None for v in ring.values()))
                if m_f >= 0:
                    assert (s, m_f) not in F, "double forward"
                    F[(s, m_f)] = t
                    if s > 0:  # stage 0 recomputes its embedding input
                        assert ring.get(m_f % S) == m_f, "fwd read wrong slot"
                if m_b >= 0:
                    assert (s, m_b) not in B, "double backward"
                    B[(s, m_b)] = t
                    if s > 0:
                        assert ring.get(m_b % S) == m_b, "bwd read wrong slot"
                        ring[m_b % S] = None  # freed: backward consumed it
            if s > 0:
                # ≤ S parked activations ever (the ring IS the memory bound)
                assert peak <= min(S, M) and peak >= 1, (S, M, s, peak)
        for s in range(S):
            for m in range(M):
                assert (s, m) in F and (s, m) in B
                assert B[(s, m)] > F[(s, m)]
                if s > 0:
                    # fwd hand-off arrives one tick after the producer but
                    # may rest in the arrivals ring before consumption
                    # (warmup→steady boundary); never consumed before sent
                    assert F[(s, m)] >= F[(s - 1, m)] + 1
                if s < S - 1:
                    assert B[(s, m)] == B[(s + 1, m)] + 1  # bwd hand-off: exact
        assert max(B.values()) == T - 1  # schedule is tight


def test_1f1b_matches_gpipe_loss_and_grads():
    """The 1F1B and GPipe execution orders compute the same function:
    identical loss and identical parameter updates. Asserted via the MPMD
    per-stage-compiled step (the shard_map comparison keeps its own test
    below) — with the per-microbatch work depth-first (bounded
    activations) vs all-forwards-then-backwards."""
    from distributed_ml_pytorch_tpu.parallel.mpmd import MpmdLocal

    tokens, targets = make_batch()
    tok_mb, tgt_mb = tokens.reshape(4, 2, 16), targets.reshape(4, 2, 16)
    g = MpmdLocal(cfg4(), 4, 4, 0.1, jax.random.key(0))
    f = MpmdLocal(cfg4(), 4, 4, 0.1, jax.random.key(0), schedule="1f1b")
    np.testing.assert_allclose(f.step(tok_mb, tgt_mb),
                               g.step(tok_mb, tgt_mb), rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g.full_params()),
                    jax.tree.leaves(f.full_params())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-8)


def test_shard_map_1f1b_matches_gpipe_loss_and_grads():
    """schedule='1f1b' computes the same function as GPipe on the
    shard_map plane: identical loss and identical parameter updates (the
    hand-built backward against AD)."""
    cfg = PipelineLMConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=8, d_ff=64, max_len=128
    )
    S, M = 4, 8
    mesh = stage_mesh(S)
    tx = optax.sgd(0.1)
    tokens, targets = make_batch(batch=M * 2, seq=16)
    tmb, gmb = microbatch(tokens, targets, M)

    step_g = make_pp_train_step(cfg, tx, mesh, n_microbatches=M)
    new_g, loss_g = step_g(create_pp_train_state(cfg, jax.random.key(0), tx, mesh),
                           tmb, gmb)
    step_f = make_pp_train_step(cfg, tx, mesh, n_microbatches=M, schedule="1f1b")
    new_f, loss_f = step_f(create_pp_train_state(cfg, jax.random.key(0), tx, mesh),
                           tmb, gmb)

    np.testing.assert_allclose(float(loss_f), float(loss_g), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(new_g.params), jax.tree.leaves(new_f.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_1f1b_m_equals_s_and_m_less_than_s():
    """Edge cadences: M == S and M < S (all-warmup, no steady state) must
    still match GPipe."""
    cfg = PipelineLMConfig(
        vocab_size=32, d_model=16, n_heads=2, n_layers=4, d_ff=32, max_len=64
    )
    S = 4
    mesh = stage_mesh(S)
    tx = optax.sgd(0.05)
    for M in (4, 2):
        tokens, targets = make_batch(batch=M * 2, seq=8)
        tmb, gmb = microbatch(tokens, targets, M)
        _, loss_g = make_pp_train_step(cfg, tx, mesh, n_microbatches=M)(
            create_pp_train_state(cfg, jax.random.key(1), tx, mesh), tmb, gmb)
        _, loss_f = make_pp_train_step(
            cfg, tx, mesh, n_microbatches=M, schedule="1f1b")(
            create_pp_train_state(cfg, jax.random.key(1), tx, mesh), tmb, gmb)
        np.testing.assert_allclose(float(loss_f), float(loss_g), rtol=1e-5)


@pytest.mark.slow  # ~20 s/case on this host (two compiled worlds per case)
@pytest.mark.parametrize("sched,kw", [
    ("gpipe", {}), ("interleaved", {"virtual_stages": 2}), ("1f1b", {}),
])
def test_dp_pp_composite_matches_pure_pp(sched, kw):
    """dp x pp on a (data=2, stage=2) mesh must produce the same loss and
    post-update params as pure pp on the identical global batch — for every
    schedule. Catches both the batch-sharding spec and the grad
    normalization (AD auto-psums param cotangents over the data axis; a
    naive pmean left grads exactly 2x at dp=2 during development)."""
    cfg = PipelineLMConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                           d_ff=64, max_len=64)
    tx = optax.sgd(0.1)
    M, mb, seq = 4, 8, 16
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(M, mb, seq)).astype(np.int32)
    targets = rng.integers(0, 64, size=(M, mb, seq)).astype(np.int32)

    mesh_pp = Mesh(np.array(jax.devices()[:2]), ("stage",))
    st = create_pp_train_state(cfg, jax.random.key(0), tx, mesh_pp)
    st1, loss_ref = make_pp_train_step(
        cfg, tx, mesh_pp, n_microbatches=M, schedule=sched, **kw
    )(st, tokens, targets)

    mesh_dp = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                   ("data", "stage"))
    st_dp = create_pp_train_state(cfg, jax.random.key(0), tx, mesh_dp)
    st2, loss_dp = make_pp_train_step(
        cfg, tx, mesh_dp, n_microbatches=M, schedule=sched,
        data_axis="data", **kw
    )(st_dp, tokens, targets)

    assert abs(float(loss_ref) - float(loss_dp)) < 1e-5
    for a, b in zip(jax.tree.leaves(st1.params), jax.tree.leaves(st2.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # two compiled worlds per case
@pytest.mark.parametrize("sched,kw", [
    ("gpipe", {}), ("interleaved", {"virtual_stages": 2}), ("1f1b", {}),
])
def test_pp_tp_composite_matches_pure_pp(sched, kw):
    """pp x tp on a (stage=2, model=2) mesh — Megatron sharding inside each
    stage — must produce the same loss and post-update params as pure pp
    running the SAME schedule on the identical batch. Float tolerance, not
    bitwise: the tp block's psums reassociate the o/down contraction."""
    cfg = PipelineLMConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                           d_ff=64, max_len=64)
    tx = optax.sgd(0.1)
    M, mb, seq = 4, 8, 16
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(M, mb, seq)).astype(np.int32)
    targets = rng.integers(0, 64, size=(M, mb, seq)).astype(np.int32)

    mesh_pp = Mesh(np.array(jax.devices()[:2]), ("stage",))
    st = create_pp_train_state(cfg, jax.random.key(0), tx, mesh_pp)
    st1, loss_ref = make_pp_train_step(
        cfg, tx, mesh_pp, n_microbatches=M, schedule=sched, **kw
    )(st, tokens, targets)

    mesh_tp = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                   ("stage", "model"))
    st_tp = create_pp_train_state(cfg, jax.random.key(0), tx, mesh_tp,
                                  model_axis="model")
    st2, loss_tp = make_pp_train_step(
        cfg, tx, mesh_tp, n_microbatches=M, schedule=sched,
        model_axis="model", **kw
    )(st_tp, tokens, targets)

    assert abs(float(loss_ref) - float(loss_tp)) < 1e-5
    for a, b in zip(jax.tree.leaves(st1.params), jax.tree.leaves(st2.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-5, atol=1e-6)


@pytest.mark.slow
def test_dp_pp_tp_2x2x2_matches_pure_pp():
    """The full composite: dp x pp x tp on a (data=2, stage=2, model=2)
    mesh — the canonical deep-LM 3-D layout — must match pure pp on the
    identical global batch (loss and updated params)."""
    cfg = PipelineLMConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                           d_ff=64, max_len=64)
    tx = optax.sgd(0.1)
    M, mb, seq = 4, 8, 16
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 64, size=(M, mb, seq)).astype(np.int32)
    targets = rng.integers(0, 64, size=(M, mb, seq)).astype(np.int32)

    mesh_pp = Mesh(np.array(jax.devices()[:2]), ("stage",))
    st = create_pp_train_state(cfg, jax.random.key(0), tx, mesh_pp)
    st1, loss_ref = make_pp_train_step(cfg, tx, mesh_pp, n_microbatches=M)(
        st, tokens, targets)

    mesh3 = Mesh(np.array(jax.devices()[:8]).reshape(2, 2, 2),
                 ("data", "stage", "model"))
    st3 = create_pp_train_state(cfg, jax.random.key(0), tx, mesh3,
                                model_axis="model")
    st2, loss3 = make_pp_train_step(
        cfg, tx, mesh3, n_microbatches=M, data_axis="data",
        model_axis="model")(st3, tokens, targets)

    assert abs(float(loss_ref) - float(loss3)) < 1e-5
    for a, b in zip(jax.tree.leaves(st1.params), jax.tree.leaves(st2.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-5, atol=1e-6)


def test_pp_tp_state_megatron_sharded():
    """pp x tp state: q/k/v column-, o row-, MLP up column-/down row-sharded
    over model WITHIN the stage shard; down bias and LNs model-replicated."""
    from distributed_ml_pytorch_tpu.parallel.pipeline import pp_param_specs

    cfg = PipelineLMConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                           d_ff=64, max_len=64)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("stage", "model"))
    state = create_pp_train_state(cfg, jax.random.key(0),
                                  optax.sgd(0.1, momentum=0.9), mesh,
                                  model_axis="model")
    blocks = state.params["blocks"]
    assert blocks["attn"]["q"]["kernel"].sharding.spec == P(
        "stage", None, "model")
    assert blocks["attn"]["o"]["kernel"].sharding.spec == P(
        "stage", "model", None)
    assert blocks["Dense_0"]["kernel"].sharding.spec == P(
        "stage", None, "model")
    assert blocks["Dense_0"]["bias"].sharding.spec == P("stage", "model")
    assert blocks["Dense_1"]["kernel"].sharding.spec == P(
        "stage", "model", None)
    assert blocks["Dense_1"]["bias"].sharding.spec == P("stage", None)
    assert blocks["LayerNorm_0"]["scale"].sharding.spec == P("stage", None)
    # optimizer momentum mirrors the params (path-based specs)
    mom = state.opt_state[0].trace["blocks"]["attn"]["q"]["kernel"]
    assert mom.sharding.spec == P("stage", None, "model")
    # replicated pieces stay replicated
    assert state.params["head"]["kernel"].sharding.spec == P()
    # and the spec function exposes the same rules standalone
    specs = pp_param_specs(state.params, "stage", "model")
    assert specs["blocks"]["attn"]["v"]["kernel"] == P("stage", None, "model")


def test_pp_tp_rejects_indivisible_dims():
    cfg = PipelineLMConfig(vocab_size=64, d_model=30, n_heads=3, n_layers=4,
                           d_ff=64, max_len=64)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("stage", "model"))
    with pytest.raises(ValueError, match="n_heads"):
        create_pp_train_state(cfg, jax.random.key(0), optax.sgd(0.1), mesh,
                              model_axis="model")
    with pytest.raises(ValueError, match="n_heads"):
        make_pp_train_step(cfg, optax.sgd(0.1), mesh, n_microbatches=2,
                           model_axis="model")
    with pytest.raises(ValueError, match="model_axis"):
        make_pp_train_step(
            PipelineLMConfig(n_layers=4), optax.sgd(0.1),
            Mesh(np.array(jax.devices()[:2]), ("stage",)),
            n_microbatches=2, model_axis="model")


def test_dp_pp_rejects_unknown_data_axis():
    cfg = PipelineLMConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=2,
                           d_ff=64, max_len=64)
    mesh = Mesh(np.array(jax.devices()[:2]), ("stage",))
    with pytest.raises(ValueError, match="data_axis"):
        make_pp_train_step(cfg, optax.sgd(0.1), mesh, n_microbatches=2,
                           data_axis="data")
