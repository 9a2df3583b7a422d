"""ISSUE 2 chaos suite: deterministic fault injection + reliable delivery.

Three layers of property, all in-process (real subprocess kill-tests live in
tests/test_ps_fault_injection.py):

- unit: FaultyTransport's seeded per-channel decisions (drop/dup/reorder/
  corrupt/delay/partition) and ReliableTransport's seq/CRC/ack/dedup;
- system: the ISSUE acceptance scenario — async-PS training under
  drop=0.1 + dup=0.05 on two workers converges into the fault-free loss
  corridor with a byte-identical fault log across runs; scripted crash,
  failure-detector reap, rejoin, and server crash→restart (checkpoint +
  version) all exercised without spawning processes;
- serving: streams stay token-identical to standalone ``generate()`` under
  injected frame loss (client-driven resume), silent clients are reaped.

Fast seeded cases carry the ``chaos`` marker and run in tier-1
(``make chaos`` selects just them); long soak variants are additionally
``slow``.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models import LeNet
from distributed_ml_pytorch_tpu.parallel.async_ps import (
    Asynchronous,
    ParameterServer,
)
from distributed_ml_pytorch_tpu.utils.chaos import (
    ChaosLog,
    ChaosPlan,
    FaultRule,
    FaultyTransport,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    InProcessTransport,
    MessageCode,
    ReliableTransport,
)
from distributed_ml_pytorch_tpu.utils.serialization import ravel_model_params

pytestmark = pytest.mark.chaos

# the shared lock_witness fixture (tests/conftest.py) arms the acceptance
# scenario below as a concurrency validator under DISTCHECK_WITNESS=1

# ---------------------------------------------------------------------------
# unit: FaultyTransport
# ---------------------------------------------------------------------------

def _pump_all(t, n=1000):
    out = []
    while True:
        m = t.recv(timeout=0.05)
        if m is None or len(out) >= n:
            return out
        out.append(m)


def test_faulty_transport_decisions_are_seeded_and_channel_local():
    """Same plan + same per-channel send sequence → identical fault log and
    identical deliveries, run-to-run."""
    plan = ChaosPlan([FaultRule(drop=0.3, dup=0.2)], seed=11)

    def run():
        world = InProcessTransport.create_world(2)
        fw, log = FaultyTransport.wrap_world(world, plan)
        for i in range(50):
            fw[1].send(MessageCode.GradientUpdate, np.full(3, i, np.float32))
            fw[1].send(MessageCode.ParameterRequest, np.zeros(0, np.float32))
        got = [int(m[2][0]) for m in _pump_all(fw[0])
               if m[1] == MessageCode.GradientUpdate]
        return got, log.lines()

    got_a, log_a = run()
    got_b, log_b = run()
    assert log_a == log_b and log_a  # byte-identical, and faults DID fire
    assert got_a == got_b
    assert len(got_a) < 60  # drops happened
    assert "drop" in log_a and "dup" in log_a


def test_chaos_plan_json_roundtrip_is_exact():
    """ISSUE 13: counterexamples from the bounded model checker travel as
    ChaosPlan JSON — the round trip must be identity across all three
    rule families, and unknown fields must fail loudly (a typo'd field
    silently weakening a replayed counterexample is the one wrong
    answer)."""
    from distributed_ml_pytorch_tpu.utils.chaos import (
        SDCRule,
        WeatherRule,
        plan_from_json,
        plan_to_json,
    )

    plan = ChaosPlan(
        rules=[FaultRule(src=1, dst=0, code=int(MessageCode.ReliableFrame),
                         drop=1.0, after=2, until=3),
               FaultRule(dup=0.5, delay=0.01, delay_p=0.25)],
        seed=41,
        weather=[WeatherRule(src=0, latency=0.002, jitter=0.001,
                             bandwidth=1e6)],
        sdc=[SDCRule(code=int(MessageCode.GradientUpdate), p=1.0,
                     kind="scale", factor=-2.0, skip=6)])
    data = plan_to_json(plan)
    assert plan_from_json(json.loads(json.dumps(data))) == plan
    # defaults are omitted from the wire form, not round-tripped as noise
    assert "weather" not in plan_to_json(ChaosPlan(seed=7))
    with pytest.raises(ValueError, match="unknown ChaosPlan fields"):
        plan_from_json({"seed": 0, "ruels": []})
    with pytest.raises(ValueError, match="unknown FaultRule fields"):
        plan_from_json({"rules": [{"dorp": 1.0}]})


def test_fault_rule_windows_and_code_match():
    """`after`/`until` schedule a rule to a channel-index window, and a
    code-scoped rule leaves other codes untouched."""
    plan = ChaosPlan(
        [FaultRule(code=int(MessageCode.GradientUpdate), drop=1.0,
                   after=2, until=4)],
        seed=0)
    world = InProcessTransport.create_world(2)
    fw, log = FaultyTransport.wrap_world(world, plan)
    for i in range(6):
        fw[1].send(MessageCode.GradientUpdate, np.full(1, i, np.float32))
        fw[1].send(MessageCode.Heartbeat, np.zeros(0, np.float32))
    grads = [int(m[2][0]) for m in _pump_all(fw[0])
             if m[1] == MessageCode.GradientUpdate]
    assert grads == [0, 1, 4, 5]  # sends #2 and #3 dropped
    assert log.counts() == {"drop": 2}


def test_one_way_partition_and_heal():
    world = InProcessTransport.create_world(2)
    fw, log = FaultyTransport.wrap_world(world, ChaosPlan())
    fw[1].partition(0)
    fw[1].send(MessageCode.GradientUpdate, np.ones(1, np.float32))
    fw[0].send(MessageCode.ParameterUpdate, np.ones(1, np.float32), dst=1)
    assert fw[0].recv(timeout=0.1) is None      # 1→0 severed
    assert fw[1].recv(timeout=0.5) is not None  # 0→1 unaffected (one-way)
    fw[1].heal(0)
    fw[1].send(MessageCode.GradientUpdate, np.ones(1, np.float32))
    assert fw[0].recv(timeout=0.5) is not None
    assert log.counts() == {"partition-drop": 1}


def test_scripted_crash_and_restart():
    world = InProcessTransport.create_world(2)
    fw, _log = FaultyTransport.wrap_world(world, ChaosPlan())
    fw[0].crash()
    with pytest.raises(ConnectionError):
        fw[1].send(MessageCode.GradientUpdate, np.ones(1, np.float32))
    with pytest.raises(ConnectionError):
        fw[0].send(MessageCode.ParameterUpdate, np.ones(1, np.float32), dst=1)
    assert fw[0].recv(timeout=0.05) is None  # a crashed endpoint hears nothing
    fw[0].restart()
    fw[1].send(MessageCode.GradientUpdate, np.ones(1, np.float32))
    assert fw[0].recv(timeout=0.5) is not None


def test_reorder_swaps_adjacent_frames():
    plan = ChaosPlan([FaultRule(reorder=1.0, until=1)], seed=3)
    world = InProcessTransport.create_world(2)
    fw, log = FaultyTransport.wrap_world(world, plan)
    for i in range(3):
        fw[1].send(MessageCode.GradientUpdate, np.full(1, i, np.float32))
    got = [int(m[2][0]) for m in _pump_all(fw[0])]
    assert got == [1, 0, 2]  # frame #0 held, released after #1
    assert log.counts() == {"reorder-hold": 1}


def test_delay_holds_then_delivers():
    plan = ChaosPlan([FaultRule(delay=0.3, delay_p=1.0, until=1)], seed=5)
    world = InProcessTransport.create_world(2)
    fw, log = FaultyTransport.wrap_world(world, plan)
    t0 = time.monotonic()
    fw[1].send(MessageCode.GradientUpdate, np.full(1, 7, np.float32))
    msg = fw[0].recv(timeout=2)
    assert msg is not None and int(msg[2][0]) == 7
    assert time.monotonic() - t0 >= 0.25
    assert log.counts() == {"delay": 1}


def test_corrupt_changes_bytes():
    plan = ChaosPlan([FaultRule(corrupt=1.0)], seed=9)
    world = InProcessTransport.create_world(2)
    fw, log = FaultyTransport.wrap_world(world, plan)
    payload = np.arange(4, dtype=np.float32)
    fw[1].send(MessageCode.GradientUpdate, payload)
    fw[1].send(MessageCode.ParameterRequest, np.zeros(0, np.float32))
    got = _pump_all(fw[0])
    assert len(got) == 2
    assert not np.array_equal(got[0][2], payload)     # corrupted in flight
    assert got[1][2].size == 1                        # empty frame grew garbage
    assert log.counts() == {"corrupt": 2}


# ---------------------------------------------------------------------------
# unit: ReliableTransport
# ---------------------------------------------------------------------------

def test_reliable_exactly_once_under_drop_dup_corrupt():
    """The tentpole's delivery contract: under wire-level drops, duplicates
    and corruption, every frame is delivered exactly once, uncorrupted."""
    world = InProcessTransport.create_world(2)
    plan = ChaosPlan([FaultRule(drop=0.3, dup=0.2, corrupt=0.2)], seed=7)
    fw, _log = FaultyTransport.wrap_world(world, plan)
    a = ReliableTransport(fw[0], ack_timeout=0.05)
    b = ReliableTransport(fw[1], ack_timeout=0.05)
    got, stop = [], threading.Event()

    def rx():
        while not stop.is_set():
            m = a.recv(timeout=0.2)
            if m is not None:
                got.append(m)

    t = threading.Thread(target=rx)
    t.start()
    n = 40
    try:
        for i in range(n):
            b.send(MessageCode.GradientUpdate, np.full(8, i, np.float32))
        assert b.flush(timeout=60), b.stats
        deadline = time.monotonic() + 10
        while len(got) < n and time.monotonic() < deadline:
            time.sleep(0.02)
    finally:
        stop.set()
        t.join(timeout=5)
    assert sorted(int(m[2][0]) for m in got) == list(range(n))
    assert all(np.all(m[2] == m[2][0]) for m in got)  # no corrupt delivery
    assert a.stats["delivered"] == n
    a.close()
    b.close()


def test_reliable_passthrough_and_heartbeat_skip():
    """Plain frames from an unwrapped peer pass through; heartbeats skip the
    envelope (no ack, no retry state)."""
    world = InProcessTransport.create_world(2)
    rel = ReliableTransport(world[0], ack_timeout=0.05)
    world[1].send(MessageCode.GradientUpdate, np.ones(2, np.float32))
    msg = rel.recv(timeout=1)
    assert msg is not None and msg[1] == MessageCode.GradientUpdate
    assert rel.stats["passthrough"] == 1

    rel2 = ReliableTransport(world[1], ack_timeout=0.05)
    rel2.send(MessageCode.Heartbeat, np.zeros(0, np.float32))
    msg = rel.recv(timeout=1)
    assert msg is not None and msg[1] == MessageCode.Heartbeat
    with rel2._lock:
        assert not rel2._pending  # heartbeats are fire-and-forget
    rel.close()
    rel2.close()


def test_reliable_declares_peer_dead_after_retries():
    world = InProcessTransport.create_world(2)
    world[0].close()  # the peer will never ack
    b = ReliableTransport(world[1], ack_timeout=0.02, max_backoff=0.05,
                          max_retries=3)
    b.send(MessageCode.GradientUpdate, np.ones(2, np.float32))
    deadline = time.monotonic() + 5
    while not b.stats["gave_up"] and time.monotonic() < deadline:
        time.sleep(0.02)
    assert b.stats["gave_up"] == 1
    with pytest.raises(ConnectionError):
        b.send(MessageCode.GradientUpdate, np.ones(2, np.float32))
    b.close()


# ---------------------------------------------------------------------------
# system: the acceptance scenario (async PS under chaos, deterministic log)
# ---------------------------------------------------------------------------

_MODEL = LeNet()
_STEPS = 16
_BATCH = 16


@pytest.fixture(scope="module")
def ps_fixture():
    from distributed_ml_pytorch_tpu.data import load_cifar10
    from distributed_ml_pytorch_tpu.training.trainer import cross_entropy_loss

    x, y, *_ = load_cifar10(n_train=256, n_test=32, synthetic=True)

    @jax.jit
    def grad_fn(p, bx, by, rng):
        def loss_fn(q):
            logits = _MODEL.apply({"params": q}, bx, train=True,
                                  rngs={"dropout": rng})
            return cross_entropy_loss(logits, by)

        return jax.value_and_grad(loss_fn)(p)

    params0 = _MODEL.init(jax.random.key(0), jnp.zeros((1, 32, 32, 3)))["params"]
    return x, y, grad_fn, params0


def _run_ps_world(ps_fixture, plan=None, n_workers=2, reliable=False,
                  n_push=4, n_pull=4):
    """One in-process 1-server/N-worker DownPour run; returns
    (per-worker losses, chaos log or None, server)."""
    x, y, grad_fn, params0 = ps_fixture
    world = InProcessTransport.create_world(n_workers + 1)
    log = None
    if plan is not None:
        world, log = FaultyTransport.wrap_world(world, plan)
    if reliable:
        world = {r: ReliableTransport(t, ack_timeout=0.05)
                 for r, t in world.items()}
    server = ParameterServer(
        params=np.asarray(ravel_model_params(params0)),
        transport=world[0], n_workers=n_workers)
    server_thread = threading.Thread(target=server.run,
                                     kwargs={"timeout": 180})
    server_thread.start()
    results = {}

    def worker(rank):
        params = jax.tree.map(jnp.asarray, params0)
        opt = Asynchronous(params, lr=0.05, n_push=n_push, n_pull=n_pull,
                           transport=world[rank])
        rng = jax.random.key(rank)
        losses = []
        for step in range(_STEPS):
            sel = np.random.default_rng(rank * 100 + step).integers(
                0, len(x), _BATCH)
            loss, grads = grad_fn(params, x[sel], y[sel],
                                  jax.random.fold_in(rng, step))
            params = opt.step(params, grads)
            losses.append(float(loss))
        opt.finish()
        results[rank] = losses

    threads = [threading.Thread(target=worker, args=(r,))
               for r in range(1, n_workers + 1)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    server_thread.join(timeout=60)
    assert not server_thread.is_alive(), "server did not shut down"
    for t in world.values():
        t.close()
    return results, log, server


# the ISSUE acceptance plan: drop=0.1 + dup=0.05 on the three DownPour data
# codes; WorkerDone/Heartbeat are untouched control traffic (faulting the
# shutdown handshake tests nothing DownPour claims to tolerate)
_ACCEPTANCE_PLAN = ChaosPlan(
    [FaultRule(code=int(c), drop=0.10, dup=0.05)
     for c in (MessageCode.GradientUpdate, MessageCode.ParameterRequest,
               MessageCode.ParameterUpdate)],
    seed=42)


def test_async_ps_chaos_deterministic_and_converges(ps_fixture, lock_witness):
    """THE acceptance test (ISSUE 2): drop=0.1 + dup=0.05, 2 workers,
    in-process transport, 3 runs in a row — training reaches the fault-free
    loss corridor and the fault log is byte-identical across runs."""
    clean, _, _ = _run_ps_world(ps_fixture, plan=None)
    clean_final = np.mean([np.mean(l[-6:]) for l in clean.values()])

    logs, finals = [], []
    for _run in range(3):
        results, log, server = _run_ps_world(ps_fixture, plan=_ACCEPTANCE_PLAN)
        assert np.isfinite(server.central).all()
        logs.append(log.lines())
        finals.append(np.mean([np.mean(l[-6:]) for l in results.values()]))
        for losses in results.values():
            assert np.mean(losses[-6:]) < np.mean(losses[:6]), losses
    assert logs[0] and logs[0] == logs[1] == logs[2], (
        "fault log not byte-identical across runs")
    # at this cadence the plan must actually have fired both fault kinds
    assert "drop" in logs[0] and "dup" in logs[0]
    for final in finals:
        assert abs(final - clean_final) < 0.45, (final, clean_final)


def test_async_ps_reliable_applies_each_push_exactly_once(ps_fixture):
    """With the reliability layer negotiated on every rank, the server
    applies each GradientUpdate exactly once even though the wire drops,
    duplicates and corrupts frames (corrupt applied raw would poison the
    central vector; CRC + retry must launder it)."""
    plan = ChaosPlan([FaultRule(drop=0.15, dup=0.10, corrupt=0.10)], seed=13)
    results, _, server = _run_ps_world(ps_fixture, plan=plan, reliable=True)
    # per worker: pushes fire on idx 0,4,8,12 plus the finish() flush
    expected = 2 * (len(range(0, _STEPS, 4)) + 1)
    assert server.message_counts[MessageCode.GradientUpdate] == expected
    assert np.isfinite(server.central).all()
    for losses in results.values():
        assert np.mean(losses[-6:]) < np.mean(losses[:6])


def test_worker_crash_is_detected_reaped_and_rejoin_resumes(ps_fixture):
    """A worker that goes silent mid-epoch is declared failed (its slot no
    longer blocks termination), and a rejoining replacement adopts the
    server's central params and completes the world."""
    x, y, grad_fn, params0 = ps_fixture
    world = InProcessTransport.create_world(3)
    fw, _log = FaultyTransport.wrap_world(world, ChaosPlan())
    server = ParameterServer(
        params=np.asarray(ravel_model_params(params0)),
        transport=fw[0], n_workers=2, worker_timeout=1.0)
    server_thread = threading.Thread(target=server.run,
                                     kwargs={"timeout": 120})
    server_thread.start()

    # worker 1: healthy (heartbeats carry liveness while it waits), and it
    # finishes only after the victim is reaped — so the server's clean exit
    # genuinely required the failure detector
    from distributed_ml_pytorch_tpu.utils.failure import HeartbeatSender

    hb = HeartbeatSender(fw[1], interval=0.2)
    hb.start()
    release = threading.Event()

    def healthy():
        params = jax.tree.map(jnp.asarray, params0)
        opt = Asynchronous(params, lr=0.05, n_push=2, n_pull=2,
                           transport=fw[1])
        for step in range(6):
            sel = np.random.default_rng(step).integers(0, len(x), _BATCH)
            _loss, grads = grad_fn(params, x[sel], y[sel],
                                   jax.random.fold_in(jax.random.key(1), step))
            params = opt.step(params, grads)
        release.wait(60)
        opt.finish()

    h = threading.Thread(target=healthy)
    h.start()

    # worker 2: pushes once, then crashes (scripted — stops speaking)
    params_v = jax.tree.map(jnp.asarray, params0)
    victim = Asynchronous(params_v, lr=0.05, n_push=1, n_pull=1,
                          transport=fw[2])
    sel = np.random.default_rng(99).integers(0, len(x), _BATCH)
    _loss, grads = grad_fn(params_v, x[sel], y[sel], jax.random.key(2))
    victim.step(params_v, grads)
    victim._flusher.drain()
    victim.listener.stop()
    fw[2].crash()

    deadline = time.monotonic() + 30
    while 2 not in server.failed_workers and time.monotonic() < deadline:
        time.sleep(0.05)
    assert 2 in server.failed_workers, "silent worker never declared failed"

    # rejoin: a replacement on the victim's rank adopts the central params
    fw[2].restart()
    rejoiner = Asynchronous(jax.tree.map(jnp.asarray, params0), lr=0.05,
                            n_push=2, n_pull=2, transport=fw[2],
                            rejoin=True, install_timeout=30.0)
    assert rejoiner.listener.wait_for_update(30.0), "rejoin pull unanswered"
    params = jax.tree.map(jnp.asarray, params0)
    for step in range(4):
        sel = np.random.default_rng(7 + step).integers(0, len(x), _BATCH)
        _loss, grads = grad_fn(params, x[sel], y[sel],
                               jax.random.fold_in(jax.random.key(3), step))
        params = rejoiner.step(params, grads)
    rejoiner.finish()
    release.set()
    h.join(timeout=120)
    hb.stop()
    server_thread.join(timeout=60)
    assert not server_thread.is_alive(), "server did not exit after rejoin"
    assert 2 not in server.failed_workers  # the rejoin cleared the reap
    for t in fw.values():
        t.close()


def test_server_crash_restart_restores_vector_and_version(ps_fixture, tmp_path):
    """Satellite: the ParameterServer crash→restart path end-to-end over a
    transport — a restarted server resumes the persisted central vector AND
    version, and a rejoining worker pulls the restored state."""
    _x, _y, _grad_fn, params0 = ps_fixture
    flat = np.asarray(ravel_model_params(params0))
    world = InProcessTransport.create_world(2)
    server = ParameterServer(params=flat.copy(), transport=world[0],
                             n_workers=1, ckpt_dir=str(tmp_path),
                             ckpt_every=1)
    delta = np.random.default_rng(0).normal(size=flat.shape).astype(np.float32)
    for _ in range(3):
        server.handle(1, MessageCode.GradientUpdate, delta)
    server.save_checkpoint()
    del server  # the crash

    restarted = ParameterServer(params=flat.copy(), transport=world[0],
                                n_workers=1, ckpt_dir=str(tmp_path))
    assert restarted.maybe_restore()
    np.testing.assert_allclose(restarted.central, flat + 3 * delta,
                               rtol=1e-4, atol=1e-5)
    assert restarted.staleness.version == 3       # the version survived
    assert restarted._push_count == 3
    # a reattaching worker pulls exactly the restored vector
    restarted.handle(1, MessageCode.ParameterRequest, np.zeros(0, np.float32))
    msg = world[1].recv(timeout=5)
    assert msg is not None and msg[1] == MessageCode.ParameterUpdate
    np.testing.assert_allclose(msg[2], restarted.central, rtol=1e-6)
    # and a fresh (non-rejoin) install cannot stomp the restored state
    restarted.handle(1, MessageCode.ParameterUpdate, np.zeros_like(flat))
    np.testing.assert_allclose(restarted.central, flat + 3 * delta,
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# soak variants (slow): heavier fault mix, longer runs
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_reliable_ps_survives_heavy_chaos(ps_fixture):
    """Soak: reliability layer + heavy chaos (drop/dup/corrupt/reorder on
    every code incl. the envelope) still yields exactly-once application
    and convergence."""
    plan = ChaosPlan(
        [FaultRule(drop=0.25, dup=0.15, corrupt=0.15, reorder=0.10)],
        seed=1234)
    results, _, server = _run_ps_world(
        ps_fixture, plan=plan, reliable=True, n_push=2, n_pull=2)
    expected = 2 * (len(range(0, _STEPS, 2)) + 1)
    assert server.message_counts[MessageCode.GradientUpdate] == expected
    assert np.isfinite(server.central).all()
    for losses in results.values():
        assert np.mean(losses[-6:]) < np.mean(losses[:6])


@pytest.mark.slow
def test_soak_chaos_log_three_seeds_deterministic(ps_fixture):
    """Soak: determinism is a property of the machinery, not one lucky
    seed — three different plans each produce byte-identical logs twice."""
    for seed in (1, 2, 3):
        plan = ChaosPlan(
            [FaultRule(code=int(c), drop=0.2, dup=0.1)
             for c in (MessageCode.GradientUpdate,
                       MessageCode.ParameterRequest,
                       MessageCode.ParameterUpdate)],
            seed=seed)
        _, log_a, _ = _run_ps_world(ps_fixture, plan=plan)
        _, log_b, _ = _run_ps_world(ps_fixture, plan=plan)
        assert log_a.lines() == log_b.lines() and log_a.lines()


# ---------------------------------------------------------------------------
# serving: streams under chaos (the acceptance test's serving half)
# ---------------------------------------------------------------------------

SERVE_VOCAB = 64


@pytest.fixture(scope="module")
def lm_and_params():
    from distributed_ml_pytorch_tpu.models.transformer import TransformerLM

    model = TransformerLM(vocab_size=SERVE_VOCAB, d_model=32, n_heads=4,
                          n_layers=2, d_ff=64, max_len=128)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def _serve_world(lm_and_params, plan=None, **frontend_kw):
    from distributed_ml_pytorch_tpu.serving.engine import ServingEngine
    from distributed_ml_pytorch_tpu.serving.frontend import ServingFrontend

    model, params = lm_and_params
    engine = ServingEngine(model, params, slots=2, cache_size=64,
                           decode_block=4, prefill_bucket=8)
    world = InProcessTransport.create_world(2)
    log = None
    hub = world[0]
    if plan is not None:
        log = ChaosLog()
        hub = FaultyTransport(world[0], plan, log=log)
    frontend = ServingFrontend(engine, hub, **frontend_kw)
    thread = threading.Thread(target=frontend.serve_forever, daemon=True)
    thread.start()
    return engine, world, frontend, thread, log


def _teardown_serve(world, frontend, thread):
    frontend.stop()
    thread.join(timeout=10)
    for t in world.values():
        t.close()


def test_serving_stream_token_identical_under_frame_loss(lm_and_params):
    """Acceptance (serving half): with seeded loss injected on StreamTokens
    frames, the client-driven resume protocol recovers every gap and the
    collected stream is token-identical to a standalone generate()."""
    from distributed_ml_pytorch_tpu.models.generate import generate
    from distributed_ml_pytorch_tpu.serving.frontend import ServingClient

    model, params = lm_and_params
    # seed 29 drops stream frames #0, #1, #3, #4 on this channel — the
    # resume path must recover the very first frame and mid-stream gaps
    plan = ChaosPlan(
        [FaultRule(code=int(MessageCode.StreamTokens), drop=0.3)], seed=29)
    engine, world, frontend, thread, log = _serve_world(lm_and_params,
                                                        plan=plan)
    try:
        client = ServingClient(world[1], resume_after=0.25)
        prompt = np.random.default_rng(0).integers(0, SERVE_VOCAB, size=5)
        tokens = client.generate(prompt, 14, timeout=120.0)
        want = np.asarray(
            generate(model, params, jnp.asarray(prompt, jnp.int32)[None], 14)
        )[0, 5:].tolist()
        assert tokens == want
        # a sampled stream survives loss identically (per-request rng rides
        # the submit frame, not the stream)
        tokens_s = client.generate(prompt, 10, temperature=0.8, top_k=8,
                                   seed=3, timeout=120.0)
        want_s = np.asarray(generate(
            model, params, jnp.asarray(prompt, jnp.int32)[None], 10,
            temperature=0.8, top_k=8, rng=jax.random.key(3)))[0, 5:].tolist()
        assert tokens_s == want_s
        assert log.counts().get("drop", 0) > 0, "no frame loss ever injected"
    finally:
        _teardown_serve(world, frontend, thread)


def test_serving_silent_client_is_reaped_and_state_freed(lm_and_params):
    """Satellite (stream-state leak): a client that submits and then goes
    silent past the deadline gets its request cancelled, slot evicted, and
    route/history freed — nothing leaks engine-side."""
    from distributed_ml_pytorch_tpu.serving.frontend import ServingFrontend
    from distributed_ml_pytorch_tpu.serving.engine import ServingEngine
    from distributed_ml_pytorch_tpu.utils.messaging import (
        MessageCode as MC,
    )
    from distributed_ml_pytorch_tpu.serving.frontend import encode_submit

    model, params = lm_and_params
    engine = ServingEngine(model, params, slots=2, cache_size=64,
                           decode_block=4, prefill_bucket=8)
    world = InProcessTransport.create_world(2)
    frontend = ServingFrontend(engine, world[0], client_deadline=0.2,
                               done_ttl=0.2)
    try:
        # no serve loop: drive scheduling by hand so the timeline is exact
        world[1].send(MC.SubmitRequest,
                      encode_submit(1, np.arange(4), 40), dst=0)
        deadline = time.monotonic() + 5
        while not frontend._routes and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(frontend._routes) == 1
        time.sleep(0.3)  # client goes silent past the deadline
        frontend._sweep(time.monotonic())
        assert frontend.reaped == 1
        engine.run_until_idle()  # cancellation drains queue + slots
        assert all(r is None for r in engine._slot_req)
        with engine._lock:
            assert not engine._queue
        # the done route ages out after done_ttl — history freed too
        time.sleep(0.3)
        frontend._sweep(time.monotonic())
        assert not frontend._routes and not frontend._by_client
    finally:
        frontend.stop()
        for t in world.values():
            t.close()


def test_serving_client_reading_one_stream_keeps_its_others_alive(
        lm_and_params):
    """Liveness belongs to the client, not to each request: a client that
    submitted two requests and is acking only the first (it reads streams
    one after the other, as ``serving.cli --demo`` does) must not have the
    second reaped as abandoned."""
    from distributed_ml_pytorch_tpu.serving.engine import ServingEngine
    from distributed_ml_pytorch_tpu.serving.frontend import (
        ServingFrontend,
        encode_submit,
    )
    from distributed_ml_pytorch_tpu.utils.messaging import (
        MessageCode as MC,
    )

    model, params = lm_and_params
    engine = ServingEngine(model, params, slots=2, cache_size=64,
                           decode_block=4, prefill_bucket=8)
    world = InProcessTransport.create_world(2)
    frontend = ServingFrontend(engine, world[0], client_deadline=0.3)
    try:
        for rid in (1, 2):
            world[1].send(MC.SubmitRequest,
                          encode_submit(rid, np.arange(4), 40), dst=0)
        deadline = time.monotonic() + 5
        while len(frontend._routes) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert len(frontend._routes) == 2
        time.sleep(0.2)
        world[1].send(MC.StreamAck, np.asarray([1, 0], np.float32), dst=0)
        time.sleep(0.2)  # request 2 itself: 0.4s of silence > the deadline
        frontend._sweep(time.monotonic())
        assert frontend.reaped == 0
        time.sleep(0.4)  # now the CLIENT is silent past the deadline
        frontend._sweep(time.monotonic())
        assert frontend.reaped == 2
    finally:
        frontend.stop()
        for t in world.values():
            t.close()


def test_serving_reconnect_and_resume_by_request_id(lm_and_params):
    """A client that consumed part of a stream and went away (reconnect)
    reattaches by request id and receives exactly the remainder."""
    from distributed_ml_pytorch_tpu.models.generate import generate
    from distributed_ml_pytorch_tpu.serving.frontend import ServingClient

    model, params = lm_and_params
    engine, world, frontend, thread, _ = _serve_world(lm_and_params)
    try:
        prompt = np.random.default_rng(1).integers(0, SERVE_VOCAB, size=6)
        want = np.asarray(
            generate(model, params, jnp.asarray(prompt, jnp.int32)[None], 12)
        )[0, 6:].tolist()

        first_client = ServingClient(world[1], resume_after=0.25)
        rid = first_client.submit(prompt, 12)
        it = first_client.stream(rid, timeout=60.0)
        head = [next(it) for _ in range(3)]
        it.close()  # the client vanishes mid-stream

        # ...and reconnects (same transport rank) later, resuming by id
        second_client = ServingClient(world[1], resume_after=0.25)
        second_client.resume_from(rid, n_have=len(head))
        tail = list(second_client.stream(rid, timeout=60.0, n_have=len(head)))
        assert head + tail == want
    finally:
        _teardown_serve(world, frontend, thread)


def test_serving_resume_unknown_request_rejected(lm_and_params):
    from distributed_ml_pytorch_tpu.serving.frontend import (
        RequestRejected,
        ServingClient,
    )

    engine, world, frontend, thread, _ = _serve_world(lm_and_params)
    try:
        client = ServingClient(world[1], resume_after=0.25)
        client.resume_from(12345, n_have=0)
        with pytest.raises(RequestRejected):
            list(client.stream(12345, timeout=20.0))
    finally:
        _teardown_serve(world, frontend, thread)


@pytest.mark.slow
def test_soak_serving_heavy_loss_many_requests(lm_and_params):
    """Soak: heavier loss (incl. dup + reorder on stream frames), several
    interleaved greedy/sampled requests — all streams exact."""
    from distributed_ml_pytorch_tpu.models.generate import generate
    from distributed_ml_pytorch_tpu.serving.frontend import ServingClient

    model, params = lm_and_params
    plan = ChaosPlan(
        [FaultRule(code=int(MessageCode.StreamTokens), drop=0.4, dup=0.2,
                   reorder=0.2)],
        seed=99)
    engine, world, frontend, thread, log = _serve_world(lm_and_params,
                                                        plan=plan)
    try:
        client = ServingClient(world[1], resume_after=0.25)
        rng = np.random.default_rng(4)
        jobs = []
        for i in range(5):
            prompt = rng.integers(0, SERVE_VOCAB, size=int(rng.integers(2, 8)))
            sampled = bool(i % 2)
            kw = (dict(temperature=0.7, top_k=8, seed=i) if sampled else {})
            rid = client.submit(prompt, 10, **kw)
            jobs.append((rid, prompt, kw))
        for rid, prompt, kw in jobs:
            got = list(client.stream(rid, timeout=180.0))
            gen_kw = dict(kw)
            if gen_kw:
                gen_kw["rng"] = jax.random.key(gen_kw.pop("seed"))
            want = np.asarray(generate(
                model, params, jnp.asarray(prompt, jnp.int32)[None], 10,
                **gen_kw))[0, len(prompt):].tolist()
            assert got == want, (rid, got, want)
        assert log.counts().get("drop", 0) > 0
    finally:
        _teardown_serve(world, frontend, thread)
