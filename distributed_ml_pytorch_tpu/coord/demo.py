"""The elastic acceptance scenario as reusable in-process machinery.

One function, :func:`elastic_scenario`, stands up the full control plane in
one process — coordinator + N elastic shard servers + M DownPour workers,
every data-plane world optionally wrapped in the chaos layer — and runs the
ISSUE 3 script: workers train, a late worker may JOIN mid-run, a shard
server may be CRASHED mid-run (silent death: its lease expires, the
coordinator rebalances, the survivors resize and the workers cut over), and
training runs to completion. It returns everything the acceptance criteria
judge: per-worker loss curves, the coordinator's decision log, per-server
stats, and the final shard-map version.

``tests/test_coord.py`` drives it three times with identical seeds for the
fault-free-corridor check; ``coord/cli.py --demo`` runs it once as a
self-contained demo.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

import numpy as np

from distributed_ml_pytorch_tpu.coord.coordinator import Coordinator
from distributed_ml_pytorch_tpu.coord.elastic import ElasticShardServer
from distributed_ml_pytorch_tpu.coord.member import CoordClient
from distributed_ml_pytorch_tpu.utils.messaging import InProcessTransport

#: coordinator-world rank layout: rank 0 is the coordinator, shard server i
#: is rank 1+i, worker j (1-based) is rank 1+n_shards+j-1
def _shard_rank(i: int) -> int:
    return 1 + i


def _worker_rank(n_shards: int, j: int) -> int:
    return n_shards + j


class ElasticWorld:
    """All the transports of one in-process elastic fleet.

    Shard server ``i`` owns PS star world ``i`` (it is rank 0 there; worker
    ``j`` is rank ``j``); everyone holds a rank in the coordination world.
    Worlds are sized for ``max_workers`` up front so late joiners have
    mailboxes (and chaos wrappers) from the start — elasticity of the
    MEMBERSHIP, not of the queue allocation.
    """

    def __init__(self, n_shards: int, max_workers: int,
                 plan=None, log=None):
        from distributed_ml_pytorch_tpu.utils.chaos import (
            ChaosLog,
            FaultyTransport,
        )

        self.n_shards = n_shards
        self.max_workers = max_workers
        self.coord_world = InProcessTransport.create_world(
            1 + n_shards + max_workers)
        self.shard_worlds = []
        self.log = log
        if plan is not None and log is None:
            self.log = ChaosLog()
        for _i in range(n_shards):
            world = InProcessTransport.create_world(1 + max_workers)
            if plan is not None:
                world, _ = FaultyTransport.wrap_world(world, plan, log=self.log)
            self.shard_worlds.append(world)

    def worker_factory(self, j: int):
        """The worker-side transport factory: shard-map entries name the
        server's coordinator rank; resolve it to this worker's transport in
        that server's PS world."""
        def factory(entry):
            return self.shard_worlds[entry.server_id - 1][j]

        return factory

    def close(self) -> None:
        for world in self.shard_worlds:
            for t in world.values():
                t.close()
        for t in self.coord_world.values():
            t.close()


def elastic_scenario(
    *,
    seed: int = 0,
    steps: int = 16,
    n_workers: int = 2,
    n_shards: int = 2,
    join_worker_at: Optional[int] = None,
    join_worker_steps: int = 8,
    crash_shard_at: Optional[int] = None,
    plan=None,
    lease: float = 0.6,
    lr: float = 0.05,
    n_push: int = 2,
    n_pull: int = 2,
    batch: int = 16,
    slow_worker: Optional[int] = None,
    slow_factor: float = 0.0,
    step_sleep: float = 0.0,
    speculation: bool = False,
    fixture=None,
    step_hook=None,
) -> Dict:
    """Run the elastic script (see module docstring). Returns a summary
    dict: ``losses`` per worker, ``events`` (coordinator log), ``stats``
    per server, ``map_version``, ``ok``.

    ``join_worker_at`` / ``crash_shard_at`` are step indices of worker 1's
    loop at which the extra worker joins / shard server ``n_shards - 1`` is
    silently crashed. ``fixture`` may supply ``(x, y, grad_fn, params0)``
    (the tests share a module-scoped jitted one); otherwise a LeNet set is
    built here.
    """
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.parallel.sharded_ps import (
        ShardedAsynchronous,
    )
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params,
    )

    if fixture is not None:
        x, y, grad_fn, params0 = fixture
    else:
        x, y, grad_fn, params0 = _default_fixture(seed)
    flat0 = np.asarray(ravel_model_params(params0), np.float32)
    n_params = int(flat0.shape[0])

    max_workers = n_workers + (1 if join_worker_at is not None else 0)
    world = ElasticWorld(n_shards, max_workers, plan=plan)
    coord = Coordinator(
        world.coord_world[0], n_params, lease=lease,
        speculation=speculation)
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 300}, daemon=True)
    coord_thread.start()

    servers, server_threads = [], []
    for i in range(n_shards):
        client = CoordClient(
            world.coord_world[_shard_rank(i)], "shard",
            renew_interval=lease / 4)
        srv = ElasticShardServer(
            server_id=_shard_rank(i), n_params=n_params,
            transport=world.shard_worlds[i][0], coord=client,
            init_params=flat0)
        servers.append(srv)
        t = threading.Thread(target=srv.run, kwargs={"timeout": 300},
                             daemon=True)
        t.start()
        server_threads.append(t)

    losses: Dict[int, list] = {}
    final_versions: Dict[int, int] = {}
    spec_tasks: Dict[int, list] = {}
    join_evt = threading.Event()
    crash_evt = threading.Event()
    errors: list = []

    def run_worker(j: int, my_steps: int, rejoin: bool) -> None:
        try:
            _run_worker(j, my_steps, rejoin)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            errors.append((j, repr(e)))

    def _run_worker(j: int, my_steps: int, rejoin: bool) -> None:
        tasks: list = []
        spec_tasks[j] = tasks
        client = CoordClient(
            world.coord_world[_worker_rank(n_shards, j)], "worker",
            renew_interval=lease / 4,
            on_speculate=lambda tid, victim, frm: tasks.append(
                (tid, victim, frm)))
        m = client.join(timeout=30)
        assert m is not None and m.entries, "worker never got a shard map"
        factory = world.worker_factory(j)
        params = jax.tree.map(jnp.asarray, params0)
        opt = ShardedAsynchronous(
            params, lr=lr, n_push=n_push, n_pull=n_pull,
            transports=[factory(e) for e in m.entries],
            coord=client, transport_factory=factory, shard_map=m,
            rejoin=rejoin)
        rng = jax.random.key(100 + j)
        my_losses = losses.setdefault(j, [])
        for step in range(my_steps):
            sel = np.random.default_rng(j * 1000 + step).integers(
                0, len(x), batch)
            loss, grads = grad_fn(params, x[sel], y[sel],
                                  jax.random.fold_in(rng, step))
            # progress (step EWMA incl. the scripted sleep below) reports
            # itself: ShardedAsynchronous.step feeds the coord client
            params = opt.step(params, grads)
            my_losses.append(float(loss))
            if step_sleep > 0:
                # pace the loop so lease-clock events (crash detection,
                # rebalance broadcast) land while training is still RUNNING
                # — the acceptance property is continuation, not survival
                time.sleep(step_sleep)
            if slow_worker == j and slow_factor > 0:
                time.sleep(slow_factor)
            if step_hook is not None:
                step_hook(j, step, opt)
            if j == 1:
                if join_worker_at is not None and step == join_worker_at:
                    join_evt.set()
                if crash_shard_at is not None and step == crash_shard_at:
                    crash_evt.set()
        final_versions[j] = opt.map_version
        opt.finish()
        client.close()

    worker_threads = [
        threading.Thread(target=run_worker, args=(j, steps, False),
                         daemon=True)
        for j in range(1, n_workers + 1)
    ]
    for t in worker_threads:
        t.start()

    if join_worker_at is not None:
        join_evt.wait(timeout=120)
        jt = threading.Thread(
            target=run_worker,
            args=(max_workers, join_worker_steps, True), daemon=True)
        jt.start()
        worker_threads.append(jt)

    if crash_shard_at is not None:
        crash_evt.wait(timeout=120)
        victim = servers[n_shards - 1]
        # a SILENT crash: the serve loop dies and the lease renewals stop,
        # but no CoordLeave is sent — the coordinator must *detect* it
        victim.crash()
        if hasattr(world.shard_worlds[n_shards - 1][0], "crash"):
            world.shard_worlds[n_shards - 1][0].crash()

    for t in worker_threads:
        t.join(timeout=300)
    alive = [t for t in worker_threads if t.is_alive()]
    for srv in servers:
        srv.stop()
    for t in server_threads:
        t.join(timeout=30)
    coord.stop()
    coord_thread.join(timeout=30)
    world.close()

    return {
        "ok": not alive and not errors,
        "errors": errors,
        "stuck_workers": len(alive),
        "losses": losses,
        "worker_map_versions": final_versions,
        "events": list(coord.events),
        "stats": {srv.server_id: dict(srv.stats) for srv in servers},
        "spec_tasks": spec_tasks,
        "map_version": coord.shard_map.version,
        "final_map": coord.shard_map,
        "servers": servers,
        "chaos_counts": world.log.counts() if world.log else {},
    }


def _default_fixture(seed: int):
    """LeNet + synthetic CIFAR + a jitted grad fn (the test suite passes a
    module-scoped equivalent instead, to pay the compile once)."""
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.data import load_cifar10
    from distributed_ml_pytorch_tpu.models import LeNet
    from distributed_ml_pytorch_tpu.training.trainer import cross_entropy_loss

    model = LeNet()
    x, y, *_ = load_cifar10(n_train=256, n_test=32, synthetic=True)

    @jax.jit
    def grad_fn(p, bx, by, rng):
        def loss_fn(q):
            logits = model.apply({"params": q}, bx, train=True,
                                 rngs={"dropout": rng})
            return cross_entropy_loss(logits, by)

        return jax.value_and_grad(loss_fn)(p)

    params0 = model.init(
        jax.random.key(seed), jnp.zeros((1, 32, 32, 3)))["params"]
    return x, y, grad_fn, params0


def elastic_demo(seed: int = 0) -> Dict:
    """One self-contained pass of the acceptance script (``--demo``)."""
    from distributed_ml_pytorch_tpu.utils.chaos import ChaosPlan, FaultRule

    plan = ChaosPlan([FaultRule(drop=0.05, dup=0.02)], seed=seed)
    out = elastic_scenario(
        seed=seed, steps=16, n_workers=2, n_shards=2,
        join_worker_at=6, join_worker_steps=8, crash_shard_at=10,
        plan=plan)
    first = {j: round(float(np.mean(l[:4])), 3)
             for j, l in out["losses"].items()}
    last = {j: round(float(np.mean(l[-4:])), 3)
            for j, l in out["losses"].items()}
    return {
        "ok": out["ok"] and out["map_version"] >= 2,
        "map_version": out["map_version"],
        "first_losses": first,
        "last_losses": last,
        "coordinator_events": out["events"],
        "server_stats": out["stats"],
        "chaos": out["chaos_counts"],
    }
