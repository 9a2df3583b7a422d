"""Disaster-recovery drills as first-class machinery (ISSUE 5 tentpole).

A recovery path that is not continuously exercised is a recovery path that
does not exist. :func:`recovery_drill` stands up the full stack in one
process — coordinator + N elastic shard servers (WAL + checkpoints on disk)
+ M DownPour workers, the PS stars under ``FaultyTransport`` chaos and the
``ReliableTransport`` envelope — and runs the ISSUE 5 script:

1. train; at a scripted step, drive a **coordinator-aligned snapshot
   barrier** (``SnapshotRequest``/``SnapshotDone`` → ``FleetManifest``);
2. keep training past the snapshot (so acked updates exist that ONLY the
   write-ahead logs hold);
3. **kill a shard subset — by default all of them — silently** mid-epoch
   (the in-process analog of SIGKILL: serve loops die without checkpoint,
   leave, or WAL flush; their endpoints raise like dead sockets);
4. **restore** from manifest + WAL: fresh server objects re-install their
   ranges from the manifest's shard map, replay their logs past the
   checkpoint, and re-seed their transports' dedup state; workers' pending
   reliable retries and cadence probes reconnect the fleet;
5. run to completion and **prove** the recovery: per-(worker, shard)
   sequence accounting — every acked ``GradientUpdate`` is in the
   restored server's applied counts (``acked <= applied``, zero acked
   loss) — plus convergence into the fault-free corridor and a
   byte-identical chaos log across repeats.

Determinism contract: the injected wire faults are restricted to channels
whose send sequences are pure functions of the (seeded, step-indexed)
training script — worker 1's pull channel, with kill/restore driven
synchronously from worker 1's own step hook — so the fault log renders
byte-identically run after run (``tests/test_drill.py`` asserts it 3x).
``GradientUpdate`` frames ride the reliability envelope and are never
faulted directly: their loss-freedom must come from WAL + deferred acks,
not from luck.

``make drill`` runs the drill suite.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from distributed_ml_pytorch_tpu.coord.coordinator import Coordinator
from distributed_ml_pytorch_tpu.coord.elastic import ElasticShardServer
from distributed_ml_pytorch_tpu.coord.manifest import (
    MANIFEST_NAME,
    FleetManifest,
)
from distributed_ml_pytorch_tpu.coord.member import CoordClient
from distributed_ml_pytorch_tpu.utils.chaos import (
    ChaosLog,
    ChaosPlan,
    FaultRule,
    FaultyTransport,
    GrayRule,
)
from distributed_ml_pytorch_tpu.utils.messaging import (
    InProcessTransport,
    MessageCode,
    ReliableTransport,
)

#: codes that go PLAIN (outside the reliability envelope) in drill worlds.
#: Pulls and replies are periodic, idempotent and cadence-driven — the
#: staleness channel DownPour tolerates by design — which makes them both
#: safe to fault and DETERMINISTIC to fault: their per-channel send indices
#: are a pure function of the step script, so the chaos log is
#: byte-identical across repeats.
DRILL_UNRELIABLE = (
    MessageCode.Heartbeat,
    MessageCode.LeaseRenew,
    MessageCode.ParameterRequest,
    MessageCode.ParameterUpdate,
)


def default_drill_plan(seed: int = 0) -> ChaosPlan:
    """Wire noise on worker 1's pull channel only (src=1 → server rank 0).

    Worker 1 is the thread that drives kill/restore synchronously from its
    own step hook, so its outage window is step-exact and its channel
    indices replay identically; other workers' timing floats free of the
    script, so faulting their channels would make the log race-dependent.
    """
    return ChaosPlan(
        [FaultRule(src=1, dst=0, code=int(MessageCode.ParameterRequest),
                   drop=0.2, dup=0.1)],
        seed=seed)


def _default_fixture(seed: int):
    from distributed_ml_pytorch_tpu.coord.demo import (
        _default_fixture as fixture,
    )

    return fixture(seed)


def _wait_for(predicate, timeout: float, what: str, poll: float = 0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = predicate()
        if got:
            return got
        time.sleep(poll)
    raise TimeoutError(f"drill: timed out after {timeout:.0f}s waiting for "
                       f"{what}")


def recovery_drill(
    *,
    base_dir: str,
    seed: int = 0,
    steps: int = 18,
    snapshot_at: Optional[int] = 6,
    kill_at: Optional[int] = 10,
    outage_steps: int = 2,
    kill_shards: Optional[Sequence[int]] = None,
    n_workers: int = 2,
    n_shards: int = 2,
    plan: Optional[ChaosPlan] = None,
    lease: float = 5.0,
    lr: float = 0.05,
    n_push: int = 2,
    n_pull: int = 2,
    batch: int = 16,
    wal_group_n: int = 4,
    fixture=None,
    compress: str = "",
    server_opt: str = "",
) -> Dict:
    """Run one kill-and-recover drill (see module docstring).

    ``snapshot_at`` / ``kill_at`` / the restore (``kill_at + outage_steps``)
    are step indices of worker 1's loop, driven synchronously from its step
    hook. ``kill_shards`` selects the victim subset (shard indices; default
    = ALL shards). ``kill_at=None`` runs the fault-free corridor baseline.
    Per-shard state (checkpoint + WAL) lives under ``base_dir/shard<i>``,
    the fleet manifest under ``base_dir``.

    ``compress`` (ISSUE 14) runs the workers' pushes over the compressed
    ``CompressedUpdate`` wire (int8/topk + error feedback) — the drill
    then proves restore replays DECODED deltas exactly once and that the
    WAL records carry the codec id. ``server_opt`` gives every shard a
    ZeRO-style sharded optimizer whose per-range state must survive the
    kill + manifest restore + WAL replay.
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
    victims = (list(range(n_shards)) if kill_shards is None
               else sorted(set(int(i) for i in kill_shards)))

    # --- worlds: coordination star (plain) + one chaos-wrapped PS star per
    # shard, all stars sharing one fault log; each star owns its own crash
    # state so a subset kill stays a subset ------------------------------
    log = ChaosLog()
    the_plan = plan if plan is not None else ChaosPlan(seed=seed)
    coord_world = InProcessTransport.create_world(1 + n_shards + n_workers)
    star_chaos: List[Dict[int, FaultyTransport]] = []
    for i in range(n_shards):
        world = InProcessTransport.create_world(1 + n_workers)
        hub = FaultyTransport(world[0], the_plan, log=log)
        star = {0: hub}
        for r in range(1, 1 + n_workers):
            star[r] = hub.sibling(world[r])
        star_chaos.append(star)

    def make_server_transport(i: int) -> ReliableTransport:
        return ReliableTransport(
            star_chaos[i][0], ack_timeout=0.05, max_backoff=0.25,
            max_retries=120, unreliable_codes=DRILL_UNRELIABLE,
            ack_on_delivery=False)

    rel_workers: List[Dict[int, ReliableTransport]] = []
    for i in range(n_shards):
        rel_workers.append({
            j: ReliableTransport(
                star_chaos[i][j], ack_timeout=0.05, max_backoff=0.25,
                max_retries=120, unreliable_codes=DRILL_UNRELIABLE)
            for j in range(1, 1 + n_workers)})

    manifest_path = os.path.join(base_dir, MANIFEST_NAME)
    coord = Coordinator(
        coord_world[0], n_params, lease=lease, speculation=False,
        manifest_dir=base_dir)
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 600}, daemon=True)
    coord_thread.start()

    def make_optimizer():
        if not server_opt:
            return None
        from distributed_ml_pytorch_tpu.parallel.optplane import (
            ShardedOptimizer,
        )

        # momentum 0.5: strong enough that lost/duplicated state would
        # visibly change the replayed trajectory, tame enough to converge
        return ShardedOptimizer(server_opt, 0, 0, lr=1.0, momentum=0.5)

    def start_server(i: int) -> ElasticShardServer:
        client = CoordClient(coord_world[1 + i], "shard",
                             renew_interval=lease / 4)
        srv = ElasticShardServer(
            server_id=1 + i, n_params=n_params,
            transport=make_server_transport(i), coord=client,
            init_params=flat0, ckpt_dir=os.path.join(base_dir, f"shard{i}"),
            ckpt_every=0, wal=True, wal_group_n=wal_group_n,
            optimizer=make_optimizer())
        t = threading.Thread(target=srv.run, kwargs={"timeout": 600},
                             daemon=True)
        t.start()
        return srv

    servers: List[ElasticShardServer] = [start_server(i)
                                         for i in range(n_shards)]
    retired_servers: List[ElasticShardServer] = []
    _wait_for(lambda: len(coord.shard_map.entries) == n_shards, 60,
              "all shard servers to join the map")

    timings: Dict[str, float] = {}
    losses: Dict[int, list] = {}
    opts: Dict[int, object] = {}
    errors: list = []
    restored_info = {"replayed": 0, "manifest": None, "replayed_codecs": []}
    restored_evt = threading.Event()
    if kill_at is None:
        restored_evt.set()  # corridor baseline: nothing to wait out

    def kill_fleet() -> None:
        timings["killed"] = time.monotonic()
        for i in victims:
            servers[i].crash()
            star_chaos[i][0].crash()

    def restore_fleet() -> None:
        t0 = time.monotonic()
        manifest = FleetManifest.load(manifest_path)  # refuses bad manifests
        restored_info["manifest"] = manifest.to_dict()
        for i in victims:
            star_chaos[i][0].restart()
            old = servers[i]
            detach = getattr(old.transport, "detach", None)
            if detach is not None:
                detach()  # the dead life's wrapper; its endpoint lives on
            retired_servers.append(old)
            client = CoordClient(coord_world[1 + i], "shard",
                                 renew_interval=lease / 4)
            srv = ElasticShardServer(
                server_id=1 + i, n_params=n_params,
                transport=make_server_transport(i), coord=client,
                init_params=flat0,
                ckpt_dir=os.path.join(base_dir, f"shard{i}"),
                ckpt_every=0, wal=True, wal_group_n=wal_group_n,
                optimizer=make_optimizer())
            srv.restore_from_manifest(manifest)
            restored_info["replayed"] += srv.ps.replayed_updates
            # codec provenance of the surviving log (ISSUE 14): captured
            # at restore time, before any later checkpoint truncates it —
            # a compressed run's replayed records must say they were
            # compressed (the WAL logs decoded deltas + codec ids)
            recs, _stats = srv.ps.wal.replay()
            restored_info["replayed_codecs"].extend(
                r.codec for r in recs)
            servers[i] = srv
            t = threading.Thread(target=srv.run, kwargs={"timeout": 600},
                                 daemon=True)
            t.start()
        timings["restored"] = time.monotonic()
        timings["restore_s"] = timings["restored"] - t0

    def step_hook(j: int, step: int) -> None:
        if j != 1:
            # every other worker pauses at the kill step until the fleet is
            # restored, so the WHOLE fleet (not just the scripting worker)
            # trains across the outage; this couples only thread timing on
            # unfaulted channels, so the chaos log stays deterministic
            if kill_at is not None and step == kill_at:
                restored_evt.wait(300)
            return
        if snapshot_at is not None and step == snapshot_at:
            coord.trigger_snapshot()
            _wait_for(lambda: os.path.exists(manifest_path)
                      and coord.manifests_written > 0, 60,
                      "the snapshot barrier to publish a manifest")
        if kill_at is not None:
            if step == kill_at:
                kill_fleet()
            elif step == kill_at + outage_steps:
                try:
                    restore_fleet()
                finally:
                    restored_evt.set()  # waiting workers resume even if
                    # the restore itself failed (the error surfaces)

    def run_worker(j: int) -> None:
        try:
            _run_worker(j)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            errors.append((j, repr(e)))

    def _run_worker(j: int) -> None:
        client = CoordClient(coord_world[n_shards + j], "worker",
                             renew_interval=lease / 4)
        m = client.join(timeout=30)
        assert m is not None and m.entries, "worker never got a shard map"
        factory = lambda entry: rel_workers[entry.server_id - 1][j]
        params = jax.tree.map(jnp.asarray, params0)
        opt = ShardedAsynchronous(
            params, lr=lr, n_push=n_push, n_pull=n_pull,
            transports=[factory(e) for e in m.entries],
            coord=client, transport_factory=factory, shard_map=m,
            compress=compress or None)
        opts[j] = opt
        rng = jax.random.key(100 + j)
        my_losses = losses.setdefault(j, [])
        for step in range(steps):
            sel = np.random.default_rng(j * 1000 + step).integers(
                0, len(x), batch)
            loss, grads = grad_fn(params, x[sel], y[sel],
                                  jax.random.fold_in(rng, step))
            params = opt.step(params, grads)
            my_losses.append(float(loss))
            step_hook(j, step)
        opt.finish()
        client.close()

    # MTTR watcher: "recovered" = every restored shard has answered a pull
    # again (message_counts starts at 0 on the fresh server objects)
    def watch_recovery() -> None:
        while "killed" not in timings:
            if watch_stop.wait(0.02):
                return
        while not watch_stop.is_set():
            if "restored" in timings and all(
                servers[i].ps.message_counts.get(
                    MessageCode.ParameterRequest, 0) > 0
                for i in victims
            ):
                timings["recovered"] = time.monotonic()
                return
            watch_stop.wait(0.02)

    watch_stop = threading.Event()
    watcher = None
    if kill_at is not None:
        watcher = threading.Thread(target=watch_recovery, daemon=True)
        watcher.start()

    worker_threads = [threading.Thread(target=run_worker, args=(j,),
                                       daemon=True)
                      for j in range(1, n_workers + 1)]
    for t in worker_threads:
        t.start()
    for t in worker_threads:
        t.join(timeout=600)
    stuck = [t for t in worker_threads if t.is_alive()]
    watch_stop.set()
    if watcher is not None:
        watcher.join(timeout=10)
    for srv in servers:
        srv.stop()
    time.sleep(0.05)
    coord.stop()
    coord_thread.join(timeout=30)

    # ---- sequence accounting: every acked push must be in the (restored)
    # server's applied counts. Elastic workers stamp their pushes with the
    # map version (ShardPush, ISSUE 6); legacy GradientUpdate acks are
    # counted too so the invariant is code-agnostic. --------------------
    acked: Dict[int, Dict[int, int]] = {}
    applied: Dict[int, Dict[int, int]] = {}
    for i in range(n_shards):
        acked[i] = {j: (rel_workers[i][j].acked_count(
            0, MessageCode.ShardPush) + rel_workers[i][j].acked_count(
            0, MessageCode.GradientUpdate) + rel_workers[i][j].acked_count(
            0, MessageCode.CompressedUpdate))
            for j in range(1, 1 + n_workers)}
        applied[i] = {j: servers[i].ps.applied_by_sender.get(j, 0)
                      for j in range(1, 1 + n_workers)}
    accounting_ok = all(
        acked[i][j] <= applied[i][j]
        for i in range(n_shards) for j in range(1, 1 + n_workers))

    for star in rel_workers:
        for t in star.values():
            t.close()
    for srv in servers:
        close = getattr(srv.transport, "close", None)
        if close is not None:
            close()
    for t in coord_world.values():
        t.close()

    mttr = (timings["recovered"] - timings["killed"]
            if "recovered" in timings and "killed" in timings else None)
    return {
        "ok": not stuck and not errors and accounting_ok,
        "errors": errors,
        "stuck_workers": len(stuck),
        "losses": losses,
        "acked": acked,
        "applied": applied,
        "accounting_ok": accounting_ok,
        "replayed_updates": restored_info["replayed"],
        "replayed_codecs": restored_info["replayed_codecs"],
        "manifest": restored_info["manifest"],
        "chaos_lines": log.lines(),
        "chaos_counts": log.counts(),
        "events": list(coord.events),
        "stats": {srv.server_id: dict(srv.stats) for srv in servers},
        "mttr_s": mttr,
        "restore_s": timings.get("restore_s"),
        "servers": servers,
    }


def sched_drill(
    *,
    base_dir: str,
    seed: int = 0,
    steps: int = 56,
    peak_at: int = 6,
    offpeak_at: int = 46,
    require_manifest: bool = True,
    n_workers: int = 2,
    n_shards: int = 2,
    plan: Optional[ChaosPlan] = None,
    lease: float = 2.0,
    lr: float = 0.05,
    n_push: int = 2,
    n_pull: int = 2,
    batch: int = 16,
    wal_group_n: int = 4,
    fixture=None,
    step_sleep: float = 0.05,
) -> Dict:
    """One multi-tenant preempt/park/resume drill (ISSUE 16).

    The full stack of :func:`recovery_drill` — coordinator + elastic WAL'd
    shards + DownPour workers under chaos — plus a :class:`FleetScheduler`
    with a training tenant (owns every shard slot) and a higher-priority
    serving tenant, and an **agent** member that actuates grants/resumes.
    The script, driven from worker 1's step hook like the recovery drill:

    1. at ``peak_at`` the serving tenant's demand spikes; the scheduler
       preempts the training tenant's last slot: snapshot barrier →
       ``PreemptRequest`` → the victim shard commits its WAL and parks
       (workers keep pushing THROUGH the barrier→park window, so acked
       deltas exist that only the WAL holds);
    2. workers observe the park and ``hold_shard`` the victim's range —
       their slice degrades to purely-local SGD (held, not lost);
    3. at ``offpeak_at`` demand drops; the grant is revoked and the agent
       restores the parked member from the manifest + exactly-once WAL
       replay, rejoining as a newer incarnation of the same rank;
    4. workers release the hold and push to the revived shard; the drill
       PROVES the round-trip: restored state bit-identical to the parked
       server's (params, apply_seq, per-sender applied counts), acked <=
       applied per (worker, shard), and a deterministic chaos log.

    ``require_manifest=False`` is the ``park_without_manifest`` mutation's
    real-stack surface: the scheduler parks without driving the barrier,
    and the resume finds no manifest to restore from — the violation the
    ``sched`` model's counterexample predicts. Violations are returned in
    ``out["violations"]`` (empty = the protocol held).
    """
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.coord.sched import FleetScheduler
    from distributed_ml_pytorch_tpu.coord.tenants import (
        TENANT_SERVING,
        TENANT_TRAINING,
        Tenant,
        TenantRegistry,
    )
    from distributed_ml_pytorch_tpu.parallel.sharded_ps import (
        ShardedAsynchronous,
    )
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params,
    )

    assert n_shards >= 2, "sched_drill needs a survivor shard (n_shards >= 2)"
    if fixture is not None:
        x, y, grad_fn, params0 = fixture
    else:
        x, y, grad_fn, params0 = _default_fixture(seed)
    flat0 = np.asarray(ravel_model_params(params0), np.float32)
    n_params = int(flat0.shape[0])
    # the victim is the training tenant's LAST slot (the scheduler's
    # _pick_victim order) — shard n_shards-1, never the chaos-faulted
    # star 0, so the fault log stays a pure function of the step script
    victim = n_shards - 1
    victim_sid = 1 + victim

    TRAIN_ID, SERVE_ID = 1, 2

    log = ChaosLog()
    the_plan = plan if plan is not None else ChaosPlan(seed=seed)
    agent_rank = 1 + n_shards + n_workers
    coord_world = InProcessTransport.create_world(2 + n_shards + n_workers)
    # Chaos rides star 0 ONLY (one shared log). Every star reuses the same
    # rank numbering, so a (src=1, dst=0, ParameterRequest) rule would
    # otherwise fault the VICTIM star's pull channel too — and that
    # channel's send count ends exactly when the worker observes the park,
    # which is coordinator-thread timing, not step script. Scoping the
    # plan to star 0 (whose shard is never parked) keeps the log a pure
    # function of the step script, so repeats are byte-identical.
    star_chaos: List[Dict[int, FaultyTransport]] = []
    for i in range(n_shards):
        world = InProcessTransport.create_world(1 + n_workers)
        hub = FaultyTransport(
            world[0], the_plan if i == 0 else ChaosPlan(seed=seed), log=log)
        star = {0: hub}
        for r in range(1, 1 + n_workers):
            star[r] = hub.sibling(world[r])
        star_chaos.append(star)

    def make_server_transport(i: int) -> ReliableTransport:
        return ReliableTransport(
            star_chaos[i][0], ack_timeout=0.05, max_backoff=0.25,
            max_retries=120, unreliable_codes=DRILL_UNRELIABLE,
            ack_on_delivery=False)

    rel_workers: List[Dict[int, ReliableTransport]] = []
    for i in range(n_shards):
        rel_workers.append({
            j: ReliableTransport(
                star_chaos[i][j], ack_timeout=0.05, max_backoff=0.25,
                max_retries=120, unreliable_codes=DRILL_UNRELIABLE)
            for j in range(1, 1 + n_workers)})

    manifest_path = os.path.join(base_dir, MANIFEST_NAME)
    coord = Coordinator(
        coord_world[0], n_params, lease=lease, speculation=False,
        manifest_dir=base_dir)
    registry = TenantRegistry()
    registry.register(Tenant(TRAIN_ID, "train", kind=TENANT_TRAINING,
                             priority=1, demand=n_shards,
                             min_slots=n_shards - 1))
    registry.register(Tenant(SERVE_ID, "serve", kind=TENANT_SERVING,
                             priority=5, demand=0))
    sched = FleetScheduler(
        coord, registry=registry, require_manifest=require_manifest,
        actuator_rank=agent_rank, preempt_timeout=60.0, resume_timeout=60.0)
    for i in range(n_shards):
        sched.register_member_slot(1 + i, TRAIN_ID)
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 600}, daemon=True)
    coord_thread.start()

    def start_server(i: int) -> ElasticShardServer:
        client = CoordClient(coord_world[1 + i], "shard",
                             renew_interval=lease / 4)
        srv = ElasticShardServer(
            server_id=1 + i, n_params=n_params,
            transport=make_server_transport(i), coord=client,
            init_params=flat0, ckpt_dir=os.path.join(base_dir, f"shard{i}"),
            ckpt_every=0, wal=True, wal_group_n=wal_group_n)
        t = threading.Thread(target=srv.run, kwargs={"timeout": 600},
                             daemon=True)
        t.start()
        return srv

    servers: List[ElasticShardServer] = [start_server(i)
                                         for i in range(n_shards)]
    retired_servers: List[ElasticShardServer] = []
    _wait_for(lambda: len(coord.shard_map.entries) == n_shards, 60,
              "all shard servers to join the map")

    # --- the node agent: grants/resumes land here over the wire ---------
    violations: List[str] = []
    grants: List[tuple] = []
    resumed_info = {"replayed": 0, "bit_identical": None,
                    "apply_seq_parked": None, "apply_seq_restored": None}
    resume_failed = threading.Event()
    resume_jobs: List[tuple] = []
    resume_ready = threading.Event()
    agent = CoordClient(coord_world[agent_rank], "agent",
                        renew_interval=lease / 4)

    def on_slot_grant(grant_id, tenant_id, action, slot_id):
        grants.append((grant_id, tenant_id, action, slot_id))

    def on_resume(grant_id, rank, snapshot_id):
        resume_jobs.append((grant_id, rank, snapshot_id))
        resume_ready.set()

    agent.on_slot_grant = on_slot_grant
    agent.on_resume = on_resume
    agent.join(timeout=30)

    def do_resume(grant_id: int, rank: int, snapshot_id: int) -> None:
        i = rank - 1
        old = servers[i]
        try:
            if snapshot_id <= 0 or not os.path.exists(manifest_path):
                raise FileNotFoundError(
                    f"no manifest for snapshot {snapshot_id}")
            manifest = FleetManifest.load(manifest_path)
            detach = getattr(old.transport, "detach", None)
            if detach is not None:
                detach()
            client = CoordClient(coord_world[1 + i], "shard",
                                 renew_interval=lease / 4)
            srv = ElasticShardServer(
                server_id=1 + i, n_params=n_params,
                transport=make_server_transport(i), coord=client,
                init_params=flat0,
                ckpt_dir=os.path.join(base_dir, f"shard{i}"),
                ckpt_every=0, wal=True, wal_group_n=wal_group_n)
            srv.restore_from_manifest(manifest)
            resumed_info["replayed"] += srv.ps.replayed_updates
            # bit-for-bit proof BEFORE any new traffic: the restored
            # range + apply_seq + per-sender counts must equal the parked
            # server's in-memory state (checkpoint + exact WAL replay)
            lo, hi = old.lo, old.hi
            resumed_info["apply_seq_parked"] = old.ps._apply_seq
            resumed_info["apply_seq_restored"] = srv.ps._apply_seq
            identical = (
                np.array_equal(np.asarray(old.ps.central[lo:hi]),
                               np.asarray(srv.ps.central[lo:hi]))
                and srv.ps._apply_seq == old.ps._apply_seq
                and dict(srv.ps.applied_by_sender)
                == dict(old.ps.applied_by_sender))
            resumed_info["bit_identical"] = identical
            if not identical:
                violations.append(
                    f"resume of rank {rank} not bit-identical: parked "
                    f"apply_seq {old.ps._apply_seq} vs restored "
                    f"{srv.ps._apply_seq}")
            retired_servers.append(old)
            servers[i] = srv
            threading.Thread(target=srv.run, kwargs={"timeout": 600},
                             daemon=True).start()
        except Exception as e:  # noqa: BLE001 — the violation IS the result
            violations.append(
                f"resume lost acked state: rank {rank} parked without a "
                f"usable manifest ({e!r})")
            resume_failed.set()

    def agent_loop() -> None:
        while not agent_stop.is_set():
            if not resume_ready.wait(0.05):
                continue
            resume_ready.clear()
            while resume_jobs:
                do_resume(*resume_jobs.pop(0))

    agent_stop = threading.Event()
    agent_thread = threading.Thread(target=agent_loop, daemon=True)
    agent_thread.start()

    timings: Dict[str, float] = {}
    losses: Dict[int, list] = {}
    opts: Dict[int, object] = {}
    errors: list = []
    hold_evt = threading.Event()
    release_evt = threading.Event()
    held = {j: False for j in range(1, 1 + n_workers)}

    def _follow(j: int) -> None:
        # non-blocking per-step reactions every worker applies: hold the
        # victim's range once it parks, release once it is back
        if hold_evt.is_set() and not release_evt.is_set() and not held[j]:
            opts[j].hold_shard(victim_sid)
            held[j] = True
        if release_evt.is_set() and held[j] and not resume_failed.is_set():
            opts[j].release_shard(victim_sid)
            held[j] = False

    def step_hook(j: int, step: int) -> None:
        time.sleep(step_sleep)  # pace ALL workers so wall-clock scheduler
        # decisions land inside the step script, not after it
        if j != 1:
            if step == offpeak_at:
                release_evt.wait(300)
            _follow(j)
            return
        if step == peak_at:
            timings["peak"] = time.monotonic()
            registry.set_demand(SERVE_ID, 1)
        if peak_at < step < offpeak_at and not hold_evt.is_set() \
                and sched.preempts_done > 0:
            hold_evt.set()
        if step == offpeak_at:
            _wait_for(lambda: sched.preempts_done > 0
                      or sched.preempts_aborted > 0, 120,
                      "the preempt to park the victim")
            hold_evt.set()
            _follow(1)
            timings["offpeak"] = time.monotonic()
            registry.set_demand(SERVE_ID, 0)
            _wait_for(lambda: sched.resumes_done > 0
                      or resume_failed.is_set(), 120,
                      "the resume to settle")
            release_evt.set()
        _follow(1)

    def run_worker(j: int) -> None:
        try:
            _run_worker(j)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            errors.append((j, repr(e)))
            release_evt.set()  # never strand the other workers

    def _run_worker(j: int) -> None:
        client = CoordClient(coord_world[n_shards + j], "worker",
                             renew_interval=lease / 4)
        m = client.join(timeout=30)
        assert m is not None and m.entries, "worker never got a shard map"
        factory = lambda entry: rel_workers[entry.server_id - 1][j]
        params = jax.tree.map(jnp.asarray, params0)
        opt = ShardedAsynchronous(
            params, lr=lr, n_push=n_push, n_pull=n_pull,
            transports=[factory(e) for e in m.entries],
            coord=client, transport_factory=factory, shard_map=m)
        opts[j] = opt
        rng = jax.random.key(100 + j)
        my_losses = losses.setdefault(j, [])
        for step in range(steps):
            sel = np.random.default_rng(j * 1000 + step).integers(
                0, len(x), batch)
            loss, grads = grad_fn(params, x[sel], y[sel],
                                  jax.random.fold_in(rng, step))
            params = opt.step(params, grads)
            my_losses.append(float(loss))
            step_hook(j, step)
        opt.finish()
        client.close()

    worker_threads = [threading.Thread(target=run_worker, args=(j,),
                                       daemon=True)
                      for j in range(1, n_workers + 1)]
    timings["day_start"] = time.monotonic()
    for t in worker_threads:
        t.start()
    for t in worker_threads:
        t.join(timeout=600)
    timings["day_end"] = time.monotonic()
    stuck = [t for t in worker_threads if t.is_alive()]
    agent_stop.set()
    agent_thread.join(timeout=10)
    for srv in servers:
        srv.stop()
    time.sleep(0.05)
    agent.close()
    coord.stop()
    coord_thread.join(timeout=30)

    # ---- per-(worker, shard) sequence accounting: every acked push is in
    # the (possibly parked-and-resumed) server's applied counts ----------
    acked: Dict[int, Dict[int, int]] = {}
    applied: Dict[int, Dict[int, int]] = {}
    for i in range(n_shards):
        acked[i] = {j: (rel_workers[i][j].acked_count(
            0, MessageCode.ShardPush) + rel_workers[i][j].acked_count(
            0, MessageCode.GradientUpdate) + rel_workers[i][j].acked_count(
            0, MessageCode.CompressedUpdate))
            for j in range(1, 1 + n_workers)}
        applied[i] = {j: servers[i].ps.applied_by_sender.get(j, 0)
                      for j in range(1, 1 + n_workers)}
        for j in range(1, 1 + n_workers):
            if acked[i][j] > applied[i][j]:
                violations.append(
                    f"acked delta lost: shard {i} worker {j}: acked "
                    f"{acked[i][j]} > applied {applied[i][j]}")
    violations.extend(sched.ledger.audit())

    for star in rel_workers:
        for t in star.values():
            t.close()
    for srv in servers:
        close = getattr(srv.transport, "close", None)
        if close is not None:
            close()
    for t in coord_world.values():
        t.close()

    return {
        "ok": (not stuck and not errors and not violations
               and sched.preempts_done > 0),
        "violations": violations,
        "errors": errors,
        "stuck_workers": len(stuck),
        "losses": losses,
        "acked": acked,
        "applied": applied,
        "replayed_updates": resumed_info["replayed"],
        "bit_identical": resumed_info["bit_identical"],
        "grants": grants,
        "sched": sched.summary(),
        "events": list(coord.events),
        "chaos_lines": log.lines(),
        "chaos_counts": log.counts(),
        "held_pushes": {j: getattr(opts.get(j), "held_pushes", 0)
                        for j in sorted(opts)},
        # day geometry for the bench's goodput accounting: total day
        # wall-clock and the measured peak window (demand-spike -> demand
        # drop, i.e. the seconds the borrowed slot served)
        "wall_s": timings["day_end"] - timings["day_start"],
        "peak_window_s": (timings["offpeak"] - timings["peak"]
                          if "peak" in timings and "offpeak" in timings
                          else None),
        "servers": servers,
    }


def default_gray_plan(seed: int = 0, n_workers: int = 2,
                      gray_from: int = 30, gray_until: int = 58) -> ChaosPlan:
    """A windowed ONE-WAY partition on every worker's pull channel toward
    shard server 0 (the gray victim): requests with per-channel send
    indices in ``[gray_from, gray_until)`` vanish; replies were never
    provoked, renewals never touched. Because every rule is INDEX-windowed
    and pulls are cadence-driven, the chaos log is a pure function of the
    window — byte-identical across repeats no matter how detection and
    containment timing float."""
    rules = [GrayRule(kind="partition", src=j, dst=0,
                      code=int(MessageCode.ParameterRequest),
                      after=gray_from, until=gray_until)
             for j in range(1, 1 + n_workers)]
    return ChaosPlan(seed=seed, gray=tuple(rules))


def gray_drill(
    *,
    base_dir: str,
    seed: int = 0,
    steps: int = 170,
    gray_from: int = 30,
    gray_until: int = 58,
    n_workers: int = 2,
    n_shards: int = 2,
    plan: Optional[ChaosPlan] = None,
    lease: float = 1.0,
    lr: float = 0.05,
    n_push: int = 2,
    n_pull: int = 2,
    batch: int = 16,
    wal_group_n: int = 4,
    fixture=None,
    step_sleep: float = 0.05,
    extra_steps: int = 400,
    gray_knobs: Optional[dict] = None,
    contain: bool = True,
) -> Dict:
    """One gray-failure containment drill (ISSUE 20).

    Mid-training, shard server 0 goes GRAY, not dead: a scheduled one-way
    partition eats the workers' pull requests toward it while its own
    lease renewals (separate star) keep flowing. The coordinator must
    tell "slow/cut-off" from "dead" and contain WITHOUT killing:

    1. both workers' renew tails carry per-link evidence (windowed pull
       requests-vs-replies) naming the victim — the asymmetric-partition
       witness its own clean tail can never be;
    2. :class:`GrayHealth` confirms suspicion over ``confirm_ticks`` and
       puts the victim on PROBATION (detection latency measured);
    3. still suspect after ``quarantine_after`` ticks, it checkpoint-parks
       the victim through the scheduler's park machinery — snapshot
       barrier, gray-granted ``PreemptRequest``, WAL'd park ticket, lease
       exempt (containment MTTR measured). The victim NEVER lease-expires
       and is NEVER revoked;
    4. the partition heals, the cooldown expires, the node agent restores
       the parked range from manifest + exact WAL replay (bit-identical
       proof, same as :func:`sched_drill`), and the resumed life re-enters
       the ladder at PROBATION, clearing to OK as clean windows accumulate.

    Workers run at least ``steps`` steps and then keep stepping (bounded
    by ``extra_steps``) until the ladder clears — chaos rules are all
    index-windowed, so the flexible tail cannot perturb the log.
    ``gray_knobs`` forwards extra :class:`GrayHealth` kwargs (the distmodel
    mutations' real-stack surface: ``hysteresis=False``,
    ``asymmetric=False``, ``evict_on_first_suspicion=True``).

    ``contain=False`` is the bench comparison leg: suspicion is disabled
    (``raise_threshold`` pinned unreachably high), the workers run the
    fixed script only, and the ladder contract is not asserted — the run
    measures what the SAME gray episode costs when nobody contains it.
    The gray rules are index-windowed, so the episode eventually drains
    through retransmits either way; only the goodput differs."""
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.coord.grayhealth import GrayHealth
    from distributed_ml_pytorch_tpu.parallel.sharded_ps import (
        ShardedAsynchronous,
    )
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params,
    )

    assert n_shards >= 2, "gray_drill needs a healthy shard (n_shards >= 2)"
    if fixture is not None:
        x, y, grad_fn, params0 = fixture
    else:
        x, y, grad_fn, params0 = _default_fixture(seed)
    flat0 = np.asarray(ravel_model_params(params0), np.float32)
    n_params = int(flat0.shape[0])
    # the victim is shard server 0 — the one star that carries the chaos
    # plan, so the windowed gray rules land on ITS pull channels
    victim_rank = 1

    log = ChaosLog()
    the_plan = plan if plan is not None else default_gray_plan(
        seed, n_workers=n_workers, gray_from=gray_from,
        gray_until=gray_until)
    agent_rank = 1 + n_shards + n_workers
    coord_world = InProcessTransport.create_world(2 + n_shards + n_workers)
    star_chaos: List[Dict[int, FaultyTransport]] = []
    for i in range(n_shards):
        world = InProcessTransport.create_world(1 + n_workers)
        hub = FaultyTransport(
            world[0], the_plan if i == 0 else ChaosPlan(seed=seed), log=log)
        star = {0: hub}
        for r in range(1, 1 + n_workers):
            star[r] = hub.sibling(world[r])
        star_chaos.append(star)

    def make_server_transport(i: int) -> ReliableTransport:
        return ReliableTransport(
            star_chaos[i][0], ack_timeout=0.05, max_backoff=0.25,
            max_retries=120, unreliable_codes=DRILL_UNRELIABLE,
            ack_on_delivery=False)

    rel_workers: List[Dict[int, ReliableTransport]] = []
    for i in range(n_shards):
        rel_workers.append({
            j: ReliableTransport(
                star_chaos[i][j], ack_timeout=0.05, max_backoff=0.25,
                max_retries=120, unreliable_codes=DRILL_UNRELIABLE)
            for j in range(1, 1 + n_workers)})

    manifest_path = os.path.join(base_dir, MANIFEST_NAME)
    coord = Coordinator(
        coord_world[0], n_params, lease=lease, speculation=False,
        manifest_dir=base_dir)
    knobs = dict(gray_knobs or {})
    if not contain:
        # the comparison leg: evidence still flows on the renew tails,
        # but the detector can never fire — the episode runs unmanaged
        knobs["raise_threshold"] = 1e9
    gray = GrayHealth(
        coord, actuator_rank=agent_rank,
        confirm_ticks=2, clear_ticks=2, quarantine_after=8,
        quarantine_cooldown=3.0, evict_after_quarantines=2,
        **knobs)
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 600}, daemon=True)
    coord_thread.start()

    def start_server(i: int) -> ElasticShardServer:
        client = CoordClient(coord_world[1 + i], "shard",
                             renew_interval=lease / 4)
        srv = ElasticShardServer(
            server_id=1 + i, n_params=n_params,
            transport=make_server_transport(i), coord=client,
            init_params=flat0, ckpt_dir=os.path.join(base_dir, f"shard{i}"),
            ckpt_every=0, wal=True, wal_group_n=wal_group_n)
        t = threading.Thread(target=srv.run, kwargs={"timeout": 600},
                             daemon=True)
        t.start()
        return srv

    servers: List[ElasticShardServer] = [start_server(i)
                                         for i in range(n_shards)]
    retired_servers: List[ElasticShardServer] = []
    _wait_for(lambda: len(coord.shard_map.entries) == n_shards, 60,
              "all shard servers to join the map")

    # --- the node agent: gray quarantine resumes land here --------------
    violations: List[str] = []
    resumed_info = {"replayed": 0, "bit_identical": None}
    resume_failed = threading.Event()
    resume_jobs: List[tuple] = []
    resume_ready = threading.Event()
    agent = CoordClient(coord_world[agent_rank], "agent",
                        renew_interval=lease / 4)

    def on_resume(grant_id, rank, snapshot_id):
        resume_jobs.append((grant_id, rank, snapshot_id))
        resume_ready.set()

    agent.on_resume = on_resume
    agent.join(timeout=30)

    def do_resume(grant_id: int, rank: int, snapshot_id: int) -> None:
        i = rank - 1
        old = servers[i]
        try:
            if snapshot_id <= 0 or not os.path.exists(manifest_path):
                raise FileNotFoundError(
                    f"no manifest for snapshot {snapshot_id}")
            manifest = FleetManifest.load(manifest_path)
            detach = getattr(old.transport, "detach", None)
            if detach is not None:
                detach()
            client = CoordClient(coord_world[1 + i], "shard",
                                 renew_interval=lease / 4)
            srv = ElasticShardServer(
                server_id=1 + i, n_params=n_params,
                transport=make_server_transport(i), coord=client,
                init_params=flat0,
                ckpt_dir=os.path.join(base_dir, f"shard{i}"),
                ckpt_every=0, wal=True, wal_group_n=wal_group_n)
            srv.restore_from_manifest(manifest)
            resumed_info["replayed"] += srv.ps.replayed_updates
            lo, hi = old.lo, old.hi
            identical = (
                np.array_equal(np.asarray(old.ps.central[lo:hi]),
                               np.asarray(srv.ps.central[lo:hi]))
                and srv.ps._apply_seq == old.ps._apply_seq
                and dict(srv.ps.applied_by_sender)
                == dict(old.ps.applied_by_sender))
            resumed_info["bit_identical"] = identical
            if not identical:
                violations.append(
                    f"gray resume of rank {rank} not bit-identical: parked "
                    f"apply_seq {old.ps._apply_seq} vs restored "
                    f"{srv.ps._apply_seq}")
            retired_servers.append(old)
            servers[i] = srv
            threading.Thread(target=srv.run, kwargs={"timeout": 600},
                             daemon=True).start()
        except Exception as e:  # noqa: BLE001 — the violation IS the result
            violations.append(
                f"gray resume lost acked state: rank {rank} parked without "
                f"a usable manifest ({e!r})")
            resume_failed.set()

    def agent_loop() -> None:
        while not agent_stop.is_set():
            if not resume_ready.wait(0.05):
                continue
            resume_ready.clear()
            while resume_jobs:
                do_resume(*resume_jobs.pop(0))

    agent_stop = threading.Event()
    agent_thread = threading.Thread(target=agent_loop, daemon=True)
    agent_thread.start()

    timings: Dict[str, float] = {}
    losses: Dict[int, list] = {}
    errors: list = []

    def recovered() -> bool:
        from distributed_ml_pytorch_tpu.coord.grayhealth import OK as G_OK

        return ((gray.recoveries >= 1
                 and gray.state_of(victim_rank) == G_OK)
                or gray.evictions >= 1 or resume_failed.is_set())

    def run_worker(j: int) -> None:
        try:
            _run_worker(j)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            errors.append((j, repr(e)))

    def _run_worker(j: int) -> None:
        client = CoordClient(coord_world[n_shards + j], "worker",
                             renew_interval=lease / 4)
        m = client.join(timeout=30)
        assert m is not None and m.entries, "worker never got a shard map"
        factory = lambda entry: rel_workers[entry.server_id - 1][j]
        params = jax.tree.map(jnp.asarray, params0)
        opt = ShardedAsynchronous(
            params, lr=lr, n_push=n_push, n_pull=n_pull,
            transports=[factory(e) for e in m.entries],
            coord=client, transport_factory=factory, shard_map=m)
        rng = jax.random.key(100 + j)
        my_losses = losses.setdefault(j, [])
        step = 0
        # fixed script, then a bounded flexible tail: keep the renew /
        # pull / evidence cadence alive until the ladder clears (every
        # chaos rule is index-windowed, so the tail cannot touch the log)
        while step < steps or (contain and step < steps + extra_steps
                               and not recovered()):
            sel = np.random.default_rng(j * 1000 + step).integers(
                0, len(x), batch)
            loss, grads = grad_fn(params, x[sel], y[sel],
                                  jax.random.fold_in(rng, step))
            params = opt.step(params, grads)
            my_losses.append(float(loss))
            time.sleep(step_sleep)
            step += 1
            if step == steps:
                # the fixed script is the same work on every leg; its
                # completion time is the goodput denominator the bench
                # compares containment-on vs -off with (the flexible
                # recovery tail would otherwise pad the ratio)
                timings[f"fixed_done_w{j}"] = time.monotonic()
        opt.finish()
        client.close()

    worker_threads = [threading.Thread(target=run_worker, args=(j,),
                                       daemon=True)
                      for j in range(1, n_workers + 1)]
    timings["day_start"] = time.monotonic()
    for t in worker_threads:
        t.start()
    for t in worker_threads:
        t.join(timeout=600)
    timings["day_end"] = time.monotonic()
    stuck = [t for t in worker_threads if t.is_alive()]
    agent_stop.set()
    agent_thread.join(timeout=10)
    for srv in servers:
        srv.stop()
    time.sleep(0.05)
    agent.close()
    coord.stop()
    coord_thread.join(timeout=30)

    # ---- the gray contract: contained, never killed --------------------
    if contain:
        if gray.probations < 1:
            violations.append(
                "gray victim was never detected (no probation)")
        if gray.quarantines < 1:
            violations.append(
                "gray victim was never contained (no quarantine)")
        if gray.evictions > 0:
            violations.append(
                f"gray plane EVICTED {gray.evictions} member(s) — "
                "containment must degrade, not kill")
        if gray.recoveries < 1 and not resume_failed.is_set():
            violations.append(
                "quarantined victim never earned its way back")
    expiry = [e for e in coord.events
              if "lease expired" in e and f" {victim_rank} " in e]
    if expiry:
        violations.append(
            f"renewing-but-gray victim lease-expired: {expiry[0]!r}")

    # ---- per-(worker, shard) accounting: every acked push applied ------
    acked: Dict[int, Dict[int, int]] = {}
    applied: Dict[int, Dict[int, int]] = {}
    for i in range(n_shards):
        acked[i] = {j: (rel_workers[i][j].acked_count(
            0, MessageCode.ShardPush) + rel_workers[i][j].acked_count(
            0, MessageCode.GradientUpdate) + rel_workers[i][j].acked_count(
            0, MessageCode.CompressedUpdate))
            for j in range(1, 1 + n_workers)}
        applied[i] = {j: servers[i].ps.applied_by_sender.get(j, 0)
                      for j in range(1, 1 + n_workers)}
        for j in range(1, 1 + n_workers):
            if acked[i][j] > applied[i][j]:
                violations.append(
                    f"acked delta lost: shard {i} worker {j}: acked "
                    f"{acked[i][j]} > applied {applied[i][j]}")

    for star in rel_workers:
        for t in star.values():
            t.close()
    for srv in servers:
        close = getattr(srv.transport, "close", None)
        if close is not None:
            close()
    for t in coord_world.values():
        t.close()

    gstats = gray.stats()
    return {
        "ok": not stuck and not errors and not violations,
        "violations": violations,
        "errors": errors,
        "stuck_workers": len(stuck),
        "losses": losses,
        "acked": acked,
        "applied": applied,
        "replayed_updates": resumed_info["replayed"],
        "bit_identical": resumed_info["bit_identical"],
        "gray": gstats,
        "detect_latency_s": (gstats["detection_latencies"][0]
                             if gstats["detection_latencies"] else None),
        "containment_mttr_s": (gstats["containment_mttrs"][0]
                               if gstats["containment_mttrs"] else None),
        "events": list(coord.events),
        "chaos_lines": log.lines(),
        "chaos_counts": log.counts(),
        "wall_s": timings["day_end"] - timings["day_start"],
        "fixed_wall_s": (max(timings[k] for k in timings
                             if k.startswith("fixed_done_w"))
                         - timings["day_start"]
                         if any(k.startswith("fixed_done_w")
                                for k in timings) else None),
        "servers": servers,
    }


def coordfail_drill(
    *,
    base_dir: str,
    seed: int = 0,
    steps: int = 20,
    snapshot_at: Optional[int] = 4,
    kill_at: Optional[int] = 8,
    outage_steps: int = 3,
    verify_at: Optional[int] = None,
    kill_during: str = "snapshot",
    n_workers: int = 2,
    n_shards: int = 2,
    plan: Optional[ChaosPlan] = None,
    lease: float = 2.0,
    grace: Optional[float] = None,
    lr: float = 0.05,
    n_push: int = 2,
    n_pull: int = 2,
    batch: int = 16,
    wal_group_n: int = 4,
    fixture=None,
    step_sleep: float = 0.05,
) -> Dict:
    """Kill the COORDINATOR mid-flight and prove the fleet survives it
    (ISSUE 17 tentpole acceptance).

    The control plane finally becomes a crashable rank: the coordinator's
    transport is chaos-wrapped (``FaultyTransport`` sharing the drill's
    ``ChaosLog``), and worker 1's step script crashes it silently — serve
    loop dead, members' control frames raising like dead sockets — while
    the data plane keeps training fail-open on the last shard map.

    ``kill_during="snapshot"`` crashes the hub right after it broadcasts a
    snapshot barrier (``SnapshotRequest`` in flight, ``SnapshotDone``
    frames landing on a dead socket); the restarted life must drive a NEW
    barrier to a published manifest. ``kill_during="preempt"`` spikes a
    serving tenant first and crashes the hub with one preemption in
    flight — the victim shard parked (WAL'd park table), its slot granted
    away — and the restarted life must neither strand the parked member
    nor double-grant its slot, then resume it when demand drops.

    Restart = a fresh ``Coordinator`` over the same ``durable_dir``:
    epoch bumped (every outbound frame of the old life is now
    stale-fenced), member table / map version / scheduler ledger / park
    table replayed from checkpoint + WAL, and a restart grace window that
    suspends lease expiry until join-retry traffic re-populates liveness
    — the drill asserts NO member is evicted across the outage.

    Determinism: chaos rides star 0's pull channel only (the
    ``sched_drill`` scoping argument) and the coordinator world carries
    no fault rules — its death is the step-scripted ``crash()``, and
    sends to a crashed rank raise BEFORE any channel draw or log record,
    so outage-window retry traffic cannot perturb the log. The
    acceptance test asserts byte-identical chaos lines 3x.

    Control-plane MTTR = crash → every live member re-attached to the
    new life (the grace window closed by traffic, not timeout).
    """
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.parallel.sharded_ps import (
        ShardedAsynchronous,
    )
    from distributed_ml_pytorch_tpu.utils.serialization import (
        ravel_model_params,
    )

    assert kill_during in ("snapshot", "preempt"), kill_during
    with_sched = kill_during == "preempt"
    if with_sched:
        assert n_shards >= 2, "preempt variant needs a survivor shard"
    if verify_at is None and kill_at is not None:
        verify_at = kill_at + outage_steps + 3
    if fixture is not None:
        x, y, grad_fn, params0 = fixture
    else:
        x, y, grad_fn, params0 = _default_fixture(seed)
    flat0 = np.asarray(ravel_model_params(params0), np.float32)
    n_params = int(flat0.shape[0])
    victim = n_shards - 1          # the scheduler's _pick_victim order
    victim_rank = 1 + victim

    TRAIN_ID, SERVE_ID = 1, 2

    log = ChaosLog()
    the_plan = plan if plan is not None else default_drill_plan(seed)
    agent_rank = 1 + n_shards + n_workers
    coord_world = InProcessTransport.create_world(
        (2 if with_sched else 1) + n_shards + n_workers)
    # the tentpole wiring: the COORDINATOR is a crashable chaos rank now,
    # sharing the drill's fault log; members reach it through siblings of
    # the same wrapper, so its scripted death is a dead socket fleet-wide
    coord_hub = FaultyTransport(coord_world[0], ChaosPlan(seed=seed),
                                log=log)
    coord_star: Dict[int, FaultyTransport] = {0: coord_hub}
    for r in coord_world:
        if r != 0:
            coord_star[r] = coord_hub.sibling(coord_world[r])

    # data-plane stars: chaos scoped to star 0 only (whose shard is never
    # parked) so the log stays a pure function of the step script
    star_chaos: List[Dict[int, FaultyTransport]] = []
    for i in range(n_shards):
        world = InProcessTransport.create_world(1 + n_workers)
        hub = FaultyTransport(
            world[0], the_plan if i == 0 else ChaosPlan(seed=seed), log=log)
        star = {0: hub}
        for r in range(1, 1 + n_workers):
            star[r] = hub.sibling(world[r])
        star_chaos.append(star)

    def make_server_transport(i: int) -> ReliableTransport:
        return ReliableTransport(
            star_chaos[i][0], ack_timeout=0.05, max_backoff=0.25,
            max_retries=120, unreliable_codes=DRILL_UNRELIABLE,
            ack_on_delivery=False)

    rel_workers: List[Dict[int, ReliableTransport]] = []
    for i in range(n_shards):
        rel_workers.append({
            j: ReliableTransport(
                star_chaos[i][j], ack_timeout=0.05, max_backoff=0.25,
                max_retries=120, unreliable_codes=DRILL_UNRELIABLE)
            for j in range(1, 1 + n_workers)})

    manifest_path = os.path.join(base_dir, MANIFEST_NAME)
    coord_dir = os.path.join(base_dir, "coord")

    def make_coordinator() -> Coordinator:
        return Coordinator(
            coord_hub, n_params, lease=lease, speculation=False,
            manifest_dir=base_dir, durable_dir=coord_dir, grace=grace)

    def make_scheduler(c: Coordinator):
        from distributed_ml_pytorch_tpu.coord.sched import FleetScheduler

        return FleetScheduler(
            c, registry=registry, require_manifest=True,
            actuator_rank=agent_rank, preempt_timeout=60.0,
            resume_timeout=60.0)

    registry = None
    coord = make_coordinator()
    life: Dict[str, object] = {"coord": coord}
    if with_sched:
        from distributed_ml_pytorch_tpu.coord.tenants import (
            TENANT_SERVING,
            TENANT_TRAINING,
            Tenant,
            TenantRegistry,
        )

        registry = TenantRegistry()
        registry.register(Tenant(TRAIN_ID, "train", kind=TENANT_TRAINING,
                                 priority=1, demand=n_shards,
                                 min_slots=n_shards - 1))
        registry.register(Tenant(SERVE_ID, "serve", kind=TENANT_SERVING,
                                 priority=5, demand=0))
        life["sched"] = make_scheduler(coord)
        for i in range(n_shards):
            life["sched"].register_member_slot(1 + i, TRAIN_ID)
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 600}, daemon=True)
    coord_thread.start()
    life["thread"] = coord_thread

    def start_server(i: int) -> ElasticShardServer:
        client = CoordClient(coord_star[1 + i], "shard",
                             renew_interval=lease / 4)
        srv = ElasticShardServer(
            server_id=1 + i, n_params=n_params,
            transport=make_server_transport(i), coord=client,
            init_params=flat0, ckpt_dir=os.path.join(base_dir, f"shard{i}"),
            ckpt_every=0, wal=True, wal_group_n=wal_group_n)
        t = threading.Thread(target=srv.run, kwargs={"timeout": 600},
                             daemon=True)
        t.start()
        return srv

    servers: List[ElasticShardServer] = [start_server(i)
                                         for i in range(n_shards)]
    retired_servers: List[ElasticShardServer] = []
    _wait_for(lambda: len(coord.shard_map.entries) == n_shards, 60,
              "all shard servers to join the map")

    # the live ranks that must RE-ATTACH to the restarted life (a parked
    # victim is durable-park-exempt, not re-attaching)
    expected_live = set(range(1, 1 + n_shards + n_workers))
    if with_sched:
        expected_live.add(agent_rank)
        expected_live.discard(victim_rank)

    timings: Dict[str, float] = {}
    losses: Dict[int, list] = {}
    opts: Dict[int, object] = {}
    errors: list = []
    violations: List[str] = []
    grants: List[tuple] = []
    member_epochs: Dict[int, int] = {}
    stale_drops: Dict[int, int] = {}
    resumed_info = {"replayed": 0, "bit_identical": None}
    resume_failed = threading.Event()
    restored_evt = threading.Event()
    verify_done = threading.Event()
    hold_evt = threading.Event()
    release_evt = threading.Event()
    held = {j: False for j in range(1, 1 + n_workers)}
    if kill_at is None:
        restored_evt.set()
        verify_done.set()

    # --- the agent (preempt variant): grants/resumes land here ----------
    agent = None
    agent_stop = threading.Event()
    if with_sched:
        resume_jobs: List[tuple] = []
        resume_ready = threading.Event()
        agent = CoordClient(coord_star[agent_rank], "agent",
                            renew_interval=lease / 4)

        def on_slot_grant(grant_id, tenant_id, action, slot_id):
            grants.append((grant_id, tenant_id, action, slot_id))

        def on_resume(grant_id, rank, snapshot_id):
            resume_jobs.append((grant_id, rank, snapshot_id))
            resume_ready.set()

        agent.on_slot_grant = on_slot_grant
        agent.on_resume = on_resume
        agent.join(timeout=30)

        def do_resume(grant_id: int, rank: int, snapshot_id: int) -> None:
            i = rank - 1
            old = servers[i]
            try:
                if snapshot_id <= 0 or not os.path.exists(manifest_path):
                    raise FileNotFoundError(
                        f"no manifest for snapshot {snapshot_id}")
                manifest = FleetManifest.load(manifest_path)
                detach = getattr(old.transport, "detach", None)
                if detach is not None:
                    detach()
                client = CoordClient(coord_star[1 + i], "shard",
                                     renew_interval=lease / 4)
                srv = ElasticShardServer(
                    server_id=1 + i, n_params=n_params,
                    transport=make_server_transport(i), coord=client,
                    init_params=flat0,
                    ckpt_dir=os.path.join(base_dir, f"shard{i}"),
                    ckpt_every=0, wal=True, wal_group_n=wal_group_n)
                srv.restore_from_manifest(manifest)
                resumed_info["replayed"] += srv.ps.replayed_updates
                lo, hi = old.lo, old.hi
                identical = (
                    np.array_equal(np.asarray(old.ps.central[lo:hi]),
                                   np.asarray(srv.ps.central[lo:hi]))
                    and srv.ps._apply_seq == old.ps._apply_seq
                    and dict(srv.ps.applied_by_sender)
                    == dict(old.ps.applied_by_sender))
                resumed_info["bit_identical"] = identical
                if not identical:
                    violations.append(
                        f"resume of rank {rank} not bit-identical across "
                        f"the coordinator restart")
                retired_servers.append(old)
                servers[i] = srv
                threading.Thread(target=srv.run, kwargs={"timeout": 600},
                                 daemon=True).start()
            except Exception as e:  # noqa: BLE001 — the violation IS the result
                violations.append(
                    f"resume lost the parked member: rank {rank} ({e!r})")
                resume_failed.set()

        def agent_loop() -> None:
            while not agent_stop.is_set():
                if not resume_ready.wait(0.05):
                    continue
                resume_ready.clear()
                while resume_jobs:
                    do_resume(*resume_jobs.pop(0))

        agent_thread = threading.Thread(target=agent_loop, daemon=True)
        agent_thread.start()

    # --- coordinator life management ------------------------------------
    def kill_coordinator() -> None:
        # reap the serve loop FIRST (stop() sends nothing — a silent
        # death), then crash the endpoint so every member's control
        # frames raise like a dead socket; the tiny stop->crash gap only
        # queues frames nobody will read
        life["coord"].stop()
        life["thread"].join(timeout=30)
        coord_hub.crash()
        timings["killed"] = time.monotonic()
        timings["map_version_at_kill"] = life["coord"].shard_map.version

    def restore_coordinator() -> None:
        t0 = time.monotonic()
        coord_hub.restart()
        c2 = make_coordinator()
        if with_sched:
            life["sched2"] = make_scheduler(c2)
        t = threading.Thread(target=c2.run, kwargs={"timeout": 600},
                             daemon=True)
        life["coord2"], life["thread2"] = c2, t
        t.start()
        timings["restored"] = time.monotonic()
        timings["restore_s"] = timings["restored"] - t0

    # MTTR watcher: re-attached = the restarted life's grace window was
    # closed by join-retry TRAFFIC (grace_pending drained) and every
    # expected live rank is in its member table
    def watch_reattach() -> None:
        while "killed" not in timings:
            if watch_stop.wait(0.02):
                return
        while not watch_stop.is_set():
            c2 = life.get("coord2")
            if (c2 is not None and not c2._grace_pending
                    and expected_live <= set(c2.members)):
                timings["reattached"] = time.monotonic()
                return
            watch_stop.wait(0.02)

    watch_stop = threading.Event()
    watcher = None
    if kill_at is not None:
        watcher = threading.Thread(target=watch_reattach, daemon=True)
        watcher.start()

    def _follow(j: int) -> None:
        if hold_evt.is_set() and not release_evt.is_set() and not held[j]:
            opts[j].hold_shard(1 + victim)
            held[j] = True
        if release_evt.is_set() and held[j] and not resume_failed.is_set():
            opts[j].release_shard(1 + victim)
            held[j] = False

    def step_hook(j: int, step: int) -> None:
        time.sleep(step_sleep)
        sched = life.get("sched")
        if j != 1:
            if kill_at is not None and step == verify_at:
                # the fleet must OUTLIVE the verify window (a finished
                # worker leaves, and "everyone re-attached" needs everyone)
                verify_done.wait(300)
                if with_sched:
                    release_evt.wait(300)
            if with_sched:
                _follow(j)
            return
        if not with_sched and snapshot_at is not None and step == snapshot_at:
            life["coord"].trigger_snapshot()
            _wait_for(lambda: os.path.exists(manifest_path)
                      and life["coord"].manifests_written > 0, 60,
                      "the pre-kill snapshot barrier to publish")
        if with_sched and kill_at is not None and step == snapshot_at:
            timings["peak"] = time.monotonic()
            registry.set_demand(SERVE_ID, 1)
        if with_sched and sched is not None and snapshot_at < step \
                and not hold_evt.is_set() and sched.preempts_done > 0:
            hold_evt.set()
        if kill_at is not None:
            if step == kill_at:
                if with_sched:
                    # mid-preemption: the victim is parked (its park WAL'd
                    # by the doomed life), the serving grant outstanding
                    _wait_for(lambda: sched.preempts_done > 0
                              or sched.preempts_aborted > 0, 120,
                              "the preempt to park the victim")
                    hold_evt.set()
                    _follow(1)
                else:
                    # mid-barrier: SnapshotRequest broadcast, then death —
                    # every SnapshotDone lands on a dead socket
                    life["coord"].trigger_snapshot()
                kill_coordinator()
            elif step == kill_at + outage_steps:
                try:
                    restore_coordinator()
                finally:
                    restored_evt.set()
            elif step == verify_at:
                try:
                    _wait_for(lambda: "reattached" in timings, 120,
                              "the fleet to re-attach to the new life")
                    if with_sched:
                        timings["offpeak"] = time.monotonic()
                        registry.set_demand(SERVE_ID, 0)
                        _wait_for(
                            lambda: life["sched2"].resumes_done > 0
                            or resume_failed.is_set(), 120,
                            "the restarted life to resume the parked rank")
                        release_evt.set()
                    else:
                        # the restarted life must drive a barrier of its
                        # OWN to a published manifest
                        life["coord2"].trigger_snapshot()
                        _wait_for(
                            lambda: life["coord2"].manifests_written > 0,
                            60, "a post-restart snapshot to publish")
                finally:
                    verify_done.set()
                    if with_sched:
                        release_evt.set()
        if with_sched:
            _follow(1)

    def run_worker(j: int) -> None:
        try:
            _run_worker(j)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            errors.append((j, repr(e)))
            verify_done.set()
            release_evt.set()

    def _run_worker(j: int) -> None:
        client = CoordClient(coord_star[n_shards + j], "worker",
                             renew_interval=lease / 4)
        m = client.join(timeout=30)
        assert m is not None and m.entries, "worker never got a shard map"
        factory = lambda entry: rel_workers[entry.server_id - 1][j]
        params = jax.tree.map(jnp.asarray, params0)
        opt = ShardedAsynchronous(
            params, lr=lr, n_push=n_push, n_pull=n_pull,
            transports=[factory(e) for e in m.entries],
            coord=client, transport_factory=factory, shard_map=m)
        opts[j] = opt
        rng = jax.random.key(100 + j)
        my_losses = losses.setdefault(j, [])
        for step in range(steps):
            sel = np.random.default_rng(j * 1000 + step).integers(
                0, len(x), batch)
            loss, grads = grad_fn(params, x[sel], y[sel],
                                  jax.random.fold_in(rng, step))
            params = opt.step(params, grads)
            my_losses.append(float(loss))
            step_hook(j, step)
        opt.finish()
        member_epochs[n_shards + j] = client.coord_epoch
        stale_drops[n_shards + j] = client.stale_epoch_dropped
        client.close()

    worker_threads = [threading.Thread(target=run_worker, args=(j,),
                                       daemon=True)
                      for j in range(1, n_workers + 1)]
    for t in worker_threads:
        t.start()
    for t in worker_threads:
        t.join(timeout=600)
    stuck = [t for t in worker_threads if t.is_alive()]
    watch_stop.set()
    if watcher is not None:
        watcher.join(timeout=10)
    if with_sched:
        agent_stop.set()
        member_epochs[agent_rank] = agent.coord_epoch
        stale_drops[agent_rank] = agent.stale_epoch_dropped
        agent.close()
    for srv in servers:
        c = getattr(srv, "coord", None)
        if isinstance(c, CoordClient):
            member_epochs[srv.server_id] = c.coord_epoch
            stale_drops[srv.server_id] = c.stale_epoch_dropped
        srv.stop()
    time.sleep(0.05)
    final = life.get("coord2") or life["coord"]
    final.stop()
    for key in ("thread", "thread2"):
        t = life.get(key)
        if t is not None:
            t.join(timeout=30)

    # ---- sequence accounting (unchanged contract: acked <= applied) ----
    acked: Dict[int, Dict[int, int]] = {}
    applied: Dict[int, Dict[int, int]] = {}
    for i in range(n_shards):
        acked[i] = {j: (rel_workers[i][j].acked_count(
            0, MessageCode.ShardPush) + rel_workers[i][j].acked_count(
            0, MessageCode.GradientUpdate) + rel_workers[i][j].acked_count(
            0, MessageCode.CompressedUpdate))
            for j in range(1, 1 + n_workers)}
        applied[i] = {j: servers[i].ps.applied_by_sender.get(j, 0)
                      for j in range(1, 1 + n_workers)}
        for j in range(1, 1 + n_workers):
            if acked[i][j] > applied[i][j]:
                violations.append(
                    f"acked delta lost: shard {i} worker {j}: acked "
                    f"{acked[i][j]} > applied {applied[i][j]}")
    accounting_ok = not any(v.startswith("acked delta") for v in violations)
    if with_sched and "sched2" in life:
        violations.extend(life["sched2"].ledger.audit())

    for star in rel_workers:
        for t in star.values():
            t.close()
    for srv in servers:
        close = getattr(srv.transport, "close", None)
        if close is not None:
            close()
    for t in coord_world.values():
        t.close()

    coord2 = life.get("coord2")
    events2 = list(coord2.events) if coord2 is not None else []
    evictions = [e for e in list(life["coord"].events) + events2
                 if "lease expired" in e]
    mttr = (timings["reattached"] - timings["killed"]
            if "reattached" in timings and "killed" in timings else None)
    ok = (not stuck and not errors and not violations and accounting_ok
          and not evictions)
    if kill_at is not None:
        ok = ok and coord2 is not None and mttr is not None \
            and coord2.epoch == life["coord"].epoch + 1 \
            and coord2.shard_map.version >= timings["map_version_at_kill"]
        if with_sched:
            ok = ok and life["sched2"].resumes_done > 0 \
                and bool(resumed_info["bit_identical"])
        else:
            ok = ok and coord2.manifests_written > 0
    return {
        "ok": ok,
        "errors": errors,
        "stuck_workers": len(stuck),
        "violations": violations,
        "losses": losses,
        "acked": acked,
        "applied": applied,
        "accounting_ok": accounting_ok,
        "evictions": evictions,
        "epochs": (life["coord"].epoch,
                   coord2.epoch if coord2 is not None else None),
        "map_versions": (timings.get("map_version_at_kill"),
                         (coord2 or life["coord"]).shard_map.version),
        "restored_members": (coord2.restored_members
                             if coord2 is not None else 0),
        "member_epochs": member_epochs,
        "stale_epoch_dropped": sum(stale_drops.values()),
        "manifests_written": (life["coord"].manifests_written,
                              coord2.manifests_written
                              if coord2 is not None else None),
        "grants": grants,
        "resumes_done": (life["sched2"].resumes_done
                         if with_sched and "sched2" in life else None),
        "bit_identical": resumed_info["bit_identical"],
        "replayed_updates": resumed_info["replayed"],
        "chaos_lines": log.lines(),
        "chaos_counts": log.counts(),
        "events": list(life["coord"].events),
        "events2": events2,
        "mttr_s": mttr,
        "outage_s": (timings["restored"] - timings["killed"]
                     if "restored" in timings and "killed" in timings
                     else None),
        "restore_s": timings.get("restore_s"),
        "servers": servers,
    }


def sched_demo(seed: int = 0, base_dir: Optional[str] = None) -> Dict:
    """One self-contained scheduler pass (``coord/cli.py --sched-demo``)."""
    import tempfile

    base = base_dir or tempfile.mkdtemp(prefix="sched_")
    out = sched_drill(base_dir=base, seed=seed,
                      plan=default_drill_plan(seed))
    return {
        "ok": out["ok"] and out["replayed_updates"] > 0,
        "violations": out["violations"],
        "preempt_mttr_s": out["sched"]["preempt_mttr_s"],
        "resume_mttr_s": out["sched"]["resume_mttr_s"],
        "replayed_updates": out["replayed_updates"],
        "bit_identical": out["bit_identical"],
        "acked": out["acked"],
        "applied": out["applied"],
        "held_pushes": out["held_pushes"],
        "grants": out["grants"],
        "events": out["events"],
        "chaos": out["chaos_counts"],
        "state_dir": base,
    }


def drill_demo(seed: int = 0, base_dir: Optional[str] = None) -> Dict:
    """One self-contained drill pass (``coord/cli.py --drill``)."""
    import tempfile

    base = base_dir or tempfile.mkdtemp(prefix="drill_")
    out = recovery_drill(base_dir=base, seed=seed,
                         plan=default_drill_plan(seed))
    return {
        # > 0: the drill must actually have exercised WAL replay (acked
        # updates that ONLY the logs held), or "ok" proves nothing
        "ok": out["ok"] and out["replayed_updates"] > 0,
        "mttr_s": out["mttr_s"],
        "restore_s": out["restore_s"],
        "replayed_updates": out["replayed_updates"],
        "acked": out["acked"],
        "applied": out["applied"],
        "chaos": out["chaos_counts"],
        "events": out["events"],
        "manifest": out["manifest"],
        "state_dir": base,
    }
