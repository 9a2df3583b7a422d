"""C1/C2/M1 async parameter-server tests: server message semantics, DownPour
cadence parity (push/pull every n steps, lr-pre-scaled accumulator), and a
full in-process 1-server/2-worker topology — the single-host cluster
simulation the reference does with localhost processes (SURVEY.md §4)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models import LeNet
from distributed_ml_pytorch_tpu.parallel.async_ps import (
    Asynchronous,
    DownpourSGD,
    ParameterServer,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    InProcessTransport,
    MessageCode,
)
from distributed_ml_pytorch_tpu.utils.serialization import ravel_model_params


def _lenet_params(seed=0):
    model = LeNet()
    return model, model.init(jax.random.key(seed), jnp.zeros((1, 32, 32, 3)))["params"]


def test_server_gradient_update_adds():
    _, params = _lenet_params()
    flat = np.asarray(ravel_model_params(params))
    server = ParameterServer(params=flat)
    delta = np.random.default_rng(0).normal(size=flat.shape).astype(np.float32)
    server.handle(1, MessageCode.GradientUpdate, delta)
    np.testing.assert_allclose(server.central, flat + delta, rtol=1e-6)


def test_server_checkpoint_cadence_and_restore(tmp_path):
    """Central params persist every ckpt_every pushes (atomic write) and a
    fresh server adopts them — PS preemption safety: workers recover by
    rejoining, only the server's state would otherwise be lost."""
    _, params = _lenet_params()
    flat = np.asarray(ravel_model_params(params))
    server = ParameterServer(params=flat, ckpt_dir=str(tmp_path), ckpt_every=2)
    delta = np.random.default_rng(1).normal(size=flat.shape).astype(np.float32)

    server.handle(1, MessageCode.GradientUpdate, delta)
    assert not (tmp_path / "ps_central.npy").exists()  # cadence not reached
    server.handle(2, MessageCode.GradientUpdate, delta)
    assert (tmp_path / "ps_central.npy").exists()

    fresh = ParameterServer(params=flat, ckpt_dir=str(tmp_path))
    assert fresh.maybe_restore()
    np.testing.assert_allclose(fresh.central, flat + 2 * delta, rtol=1e-6)


def test_server_restore_rejects_wrong_model(tmp_path):
    _, params = _lenet_params()
    flat = np.asarray(ravel_model_params(params))
    server = ParameterServer(params=flat, ckpt_dir=str(tmp_path))
    server.save_checkpoint()
    other = ParameterServer(params=flat[:100].copy(), ckpt_dir=str(tmp_path))
    with pytest.raises(ValueError, match="wrong --model"):
        other.maybe_restore()


def test_restored_server_survives_fresh_worker_install(tmp_path):
    """A resumed server must NOT be stomped by a non-rejoin worker's
    construction-time ParameterUpdate install — it answers with the
    authoritative (restored) params instead."""
    from distributed_ml_pytorch_tpu.utils.messaging import InProcessTransport

    _, params = _lenet_params()
    flat = np.asarray(ravel_model_params(params))
    saved = flat + 7.0
    writer = ParameterServer(params=saved.copy(), ckpt_dir=str(tmp_path))
    writer.save_checkpoint()

    world = InProcessTransport.create_world(2)
    server = ParameterServer(
        params=flat, transport=world[0], ckpt_dir=str(tmp_path)
    )
    assert server.maybe_restore()

    fresh_init = np.zeros_like(flat)
    server.handle(1, MessageCode.ParameterUpdate, fresh_init)
    np.testing.assert_allclose(server.central, saved, rtol=1e-6)  # not stomped
    # and the worker got the restored params back
    sender, code, payload = world[1].recv(timeout=5)
    assert code == MessageCode.ParameterUpdate
    np.testing.assert_allclose(payload, saved, rtol=1e-6)


def test_server_restore_without_checkpoint_is_noop(tmp_path):
    _, params = _lenet_params()
    flat = np.asarray(ravel_model_params(params))
    server = ParameterServer(params=flat, ckpt_dir=str(tmp_path))
    assert not server.maybe_restore()
    np.testing.assert_allclose(server.central, flat)


def test_server_parameter_request_replies():
    world = InProcessTransport.create_world(2)
    _, params = _lenet_params()
    server = ParameterServer(params=np.asarray(ravel_model_params(params)), transport=world[0])
    server.handle(1, MessageCode.ParameterRequest, np.zeros(0, np.float32))
    msg = world[1].recv(timeout=2)
    assert msg is not None
    sender, code, payload = msg
    assert sender == 0 and code == MessageCode.ParameterUpdate
    np.testing.assert_array_equal(payload, server.central)


def test_server_parameter_update_installs():
    _, params = _lenet_params()
    server = ParameterServer(params=np.asarray(ravel_model_params(params)))
    new = np.arange(server.central.size, dtype=np.float32)
    server.handle(2, MessageCode.ParameterUpdate, new)
    np.testing.assert_array_equal(server.central, new)


def test_downpour_alias():
    assert DownpourSGD is Asynchronous  # M4 contract


def test_worker_cadence_and_accumulator():
    """Message pattern parity with Asynchronous.py:42-70 for n_push=3, n_pull=2."""
    world = InProcessTransport.create_world(2)
    _, params = _lenet_params()
    opt = Asynchronous(params, lr=0.1, n_push=3, n_pull=2, transport=world[1])
    try:
        # construction sends the initial ParameterUpdate (:34)
        msg = world[0].recv(timeout=2)
        assert msg[1] == MessageCode.ParameterUpdate

        grads = jax.tree.map(jnp.ones_like, params)
        flat_ones = np.ones_like(np.asarray(ravel_model_params(params)))

        codes_per_step = []
        for _ in range(6):
            before = opt.idx
            params = opt.step(params, grads)
            codes = []
            while True:
                m = world[0].recv(timeout=0.05)
                if m is None:
                    break
                codes.append((m[1], m[2]))
            codes_per_step.append([c for c, _ in codes])
            for c, payload in codes:
                if c == MessageCode.GradientUpdate:
                    # lr-pre-scaled accumulation: pushes carry -lr * sum(grads)
                    steps_since_push = 3
                    if before == 0:
                        steps_since_push = 1  # first push fires on step 0
                    np.testing.assert_allclose(
                        payload, -0.1 * steps_since_push * flat_ones, rtol=1e-5
                    )
        # idx%2==0 → pull on steps 0,2,4; idx%3==0 → push on steps 0,3
        assert codes_per_step[0] == [MessageCode.ParameterRequest, MessageCode.GradientUpdate]
        assert codes_per_step[1] == []
        assert codes_per_step[2] == [MessageCode.ParameterRequest]
        assert codes_per_step[3] == [MessageCode.GradientUpdate]
        assert codes_per_step[4] == [MessageCode.ParameterRequest]
        assert codes_per_step[5] == []
    finally:
        opt.listener.stop()


def test_worker_installs_server_push_between_steps():
    world = InProcessTransport.create_world(2)
    _, params = _lenet_params()
    opt = Asynchronous(params, lr=0.0, n_push=100, n_pull=100, transport=world[1])
    try:
        world[0].recv(timeout=2)  # drain initial ParameterUpdate
        pushed = np.full(np.asarray(ravel_model_params(params)).size, 3.25, np.float32)
        world[1]._boxes[1].put((0, MessageCode.ParameterUpdate, pushed))
        # wait until the listener thread deposits it
        for _ in range(100):
            if opt.listener._latest is not None:
                break
            threading.Event().wait(0.02)
        grads = jax.tree.map(jnp.zeros_like, params)
        params = opt.step(params, grads)
        flat_after = np.asarray(ravel_model_params(params))
        np.testing.assert_allclose(flat_after, pushed, rtol=1e-6)
    finally:
        opt.listener.stop()


def test_full_ps_topology_in_process():
    """1 server + 2 workers training LeNet on synthetic data, in-process
    transports, real jitted steps — convergence + clean shutdown."""
    from distributed_ml_pytorch_tpu.data import load_cifar10
    from distributed_ml_pytorch_tpu.training.trainer import cross_entropy_loss

    world = InProcessTransport.create_world(3)
    model, params0 = _lenet_params()
    server = ParameterServer(
        params=np.asarray(ravel_model_params(params0)), transport=world[0], n_workers=2
    )
    server_thread = threading.Thread(target=server.run, kwargs={"timeout": 120})
    server_thread.start()

    x, y, *_ = load_cifar10(n_train=256, n_test=32, synthetic=True)
    results = {}

    @jax.jit
    def grad_fn(p, bx, by, rng):
        def loss_fn(q):
            logits = model.apply({"params": q}, bx, train=True, rngs={"dropout": rng})
            return cross_entropy_loss(logits, by)

        return jax.value_and_grad(loss_fn)(p)

    def worker(rank):
        _, params = _lenet_params(seed=0)
        opt = Asynchronous(params, lr=0.05, n_push=4, n_pull=4, transport=world[rank])
        rng = jax.random.key(rank)
        losses = []
        for step in range(24):
            sel = np.random.default_rng(rank * 100 + step).integers(0, len(x), 32)
            loss, grads = grad_fn(params, x[sel], y[sel], jax.random.fold_in(rng, step))
            params = opt.step(params, grads)
            losses.append(float(loss))
        opt.finish()
        results[rank] = losses

    threads = [threading.Thread(target=worker, args=(r,)) for r in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    server_thread.join(timeout=30)
    assert not server_thread.is_alive(), "server did not shut down after WorkerDone x2"

    for rank in (1, 2):
        losses = results[rank]
        assert np.mean(losses[-6:]) < np.mean(losses[:6]), (rank, losses)
    assert server.message_counts[MessageCode.GradientUpdate] >= 2
    assert server.message_counts[MessageCode.ParameterRequest] >= 2
    assert np.isfinite(server.central).all()


def test_push_flusher_overlaps_order_and_drain():
    """The flusher (VERDICT r4 #5) must (a) return from enqueue without
    waiting for the send, (b) preserve FIFO order across pushes, and
    (c) complete every pending send on drain()."""
    import time

    from distributed_ml_pytorch_tpu.parallel.async_ps import PushFlusher

    sent, gate = [], threading.Event()

    def slow_send(arr):
        gate.wait(5)  # the wire is slow; enqueue must not care
        sent.append(int(arr[0]))

    fl = PushFlusher(slow_send)
    t0 = time.perf_counter()
    for i in range(fl.MAX_IN_FLIGHT):  # up to the bound: non-blocking
        fl.enqueue(jnp.full((8,), i, jnp.float32))
    enq_time = time.perf_counter() - t0
    assert enq_time < 1.0, f"enqueue blocked on the send ({enq_time:.2f}s)"
    assert sent == []  # nothing sent while the wire is blocked
    gate.set()
    fl.drain()
    assert sent == list(range(fl.MAX_IN_FLIGHT))  # FIFO, all landed
    fl.stop()


def test_push_flusher_stop_reports_a_push_stuck_on_the_wire(
        monkeypatch, capsys):
    """A send stalled on a silently dead peer must not pass for a clean
    stop: ``stop()`` returns after its bounded wait and says on stderr
    that a push is stuck (the ten seconds are cut short here)."""
    from distributed_ml_pytorch_tpu.parallel.async_ps import PushFlusher

    picked_up, wire_dead = threading.Event(), threading.Event()

    def stalled_send(arr):
        picked_up.set()
        wire_dead.wait(60)

    fl = PushFlusher(stalled_send)
    fl.enqueue(jnp.zeros((4,), jnp.float32))
    assert picked_up.wait(10)
    join = fl._thread.join
    monkeypatch.setattr(fl._thread, "join", lambda timeout: join(0.2))
    fl.stop()
    assert "a push is stuck" in capsys.readouterr().err
    wire_dead.set()  # the peer answers after all: the thread ends cleanly
    join(10)
    assert not fl._thread.is_alive()


def test_push_flusher_survives_send_failure_and_still_drains():
    """A failing fetch/send must drop THAT push (degrade-never-crash, the
    _send contract) — not kill the thread and deadlock drain()/finish()."""
    from distributed_ml_pytorch_tpu.parallel.async_ps import PushFlusher

    sent, fail_first = [], [True]

    def flaky_send(arr):
        if fail_first[0]:
            fail_first[0] = False
            raise RuntimeError("wire exploded")
        sent.append(int(arr[0]))

    fl = PushFlusher(flaky_send)
    fl.enqueue(jnp.full((4,), 0, jnp.float32))  # lost to the failure
    fl.enqueue(jnp.full((4,), 1, jnp.float32))
    fl.drain()  # must NOT hang
    assert sent == [1]
    fl.stop()
