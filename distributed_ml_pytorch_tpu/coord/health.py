"""The numerical-health acceptance scenario as reusable machinery
(ISSUE 8 tentpole).

:func:`health_scenario` stands up the full training immune system in one
process — coordinator (auto-rollback watchdog + worker reputation) + N
elastic WAL'd shard servers behind the admission gate + M DownPour workers
over reliable transports — and runs the ISSUE 8 script:

1. train cleanly; at a scripted step, drive a snapshot barrier so a good
   :class:`FleetManifest` exists (the rollback target);
2. a **poisoned worker**'s push channel suffers seeded SDC: first a
   norm-preserving-enough *scale* corruption (``×factor``, re-stamped CRC —
   bit-perfect on the wire) that SLIPS the admission gate's z-score and
   silently drives the central params toward divergence, then *NaN*
   injection that the gate catches and quarantines, nacking every one;
3. the fleet's loss telemetry (EWMAs riding lease renewals) diverges; the
   coordinator's watchdog broadcasts a **RollbackRequest barrier**: shards
   restore the manifest snapshot in place (checkpoint + WAL capped at its
   apply seq, tail dropped), workers drop their in-flight accumulators and
   pull, training resumes — MTTR is measured;
4. the repeat offender's nack count (riding its renewals) crosses the
   reputation limit and its lease is **revoked** (rejoin only after a
   cooldown, with fresh params);
5. the run finishes in the fault-free corridor, every rejected update was
   explicitly nacked (never silently dropped) and none ever reached a WAL.

Determinism contract: SDC decisions for enveloped pushes are keyed by the
reliability envelope's sequence number — a pure function of the worker's
step script (pushes are the only enveloped worker→server traffic here;
pulls ride plain) — and retransmits re-derive the same corruption without
re-logging, so the chaos log renders byte-identically across runs
(``tests/test_health.py`` asserts it 3×). The scripted barriers (snapshot
BEFORE poison, worker 1 waiting out the rollback) order the wall-clock
events without touching any faulted channel.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from distributed_ml_pytorch_tpu.coord.coordinator import Coordinator
from distributed_ml_pytorch_tpu.coord.elastic import ElasticShardServer
from distributed_ml_pytorch_tpu.coord.manifest import MANIFEST_NAME
from distributed_ml_pytorch_tpu.coord.member import CoordClient
from distributed_ml_pytorch_tpu.utils.chaos import (
    ChaosLog,
    ChaosPlan,
    FaultyTransport,
    SDCRule,
)
from distributed_ml_pytorch_tpu.utils.health import GradientAdmission
from distributed_ml_pytorch_tpu.utils.messaging import (
    InProcessTransport,
    MessageCode,
    ReliableTransport,
)

#: codes that ride PLAIN in health worlds — same reasoning as the drill's
#: DRILL_UNRELIABLE: pulls/replies are periodic, idempotent and
#: cadence-driven, so keeping them out of the envelope keeps the enveloped
#: seq space (which keys the SDC decisions) a pure function of the push
#: script. UpdateNack stays ENVELOPED: a nack is the explicit-reject
#: contract and gets retransmit service.
HEALTH_UNRELIABLE = (
    MessageCode.Heartbeat,
    MessageCode.LeaseRenew,
    MessageCode.ParameterRequest,
    MessageCode.ParameterUpdate,
)


def poisoned_worker_sdc(worker: int, *, scale_after: int, scale_until: int,
                        nan_after: int, nan_until: Optional[int] = None,
                        factor: float = -8.0) -> tuple:
    """The scripted poisoned-worker fault mix for ``worker``'s push channel
    (ISSUE 8): a window of norm-preserving-enough *scale* SDC (slips the
    admission gate; ``factor < 0`` turns descent deltas into ascent — the
    corruption the gate CANNOT see and the rollback watchdog exists for),
    followed by *NaN* SDC (caught + nacked at the gate — the reputation
    driver). ``nan_until`` bounds the episode (a transient fault — the
    overheated part recovers): past it the worker's pushes are clean
    again and the gate readmits them, so the fleet re-converges at full
    throughput even while reputation still has the worker's lease
    revoked (the data plane judges updates, not history). ``skip=6``
    preserves the ShardPush version/range head: the model is a corrupted
    gradient buffer, not a corrupted protocol stamp. Windows are
    envelope-seq indices == push indices."""
    return (
        SDCRule(src=worker, dst=0, code=int(MessageCode.ShardPush), p=1.0,
                kind="scale", factor=factor, skip=6,
                after=scale_after, until=scale_until),
        SDCRule(src=worker, dst=0, code=int(MessageCode.ShardPush), p=1.0,
                kind="nan", skip=6, after=nan_after, until=nan_until),
    )


def _default_fixture(seed: int):
    from distributed_ml_pytorch_tpu.coord.demo import (
        _default_fixture as fixture,
    )

    return fixture(seed)


def _wait_for(predicate, timeout: float, what: str, poll: float = 0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        got = predicate()
        if got:
            return got
        time.sleep(poll)
    raise TimeoutError(
        f"health: timed out after {timeout:.0f}s waiting for {what}")


def health_scenario(
    *,
    base_dir: str,
    seed: int = 0,
    steps: int = 64,
    n_workers: int = 2,
    n_shards: int = 2,
    poison_worker: Optional[int] = 2,
    snapshot_at: int = 20,
    scale_after: int = 11,
    scale_until: int = 16,
    nan_after: int = 16,
    nan_until: Optional[int] = 22,
    poison_factor: float = -16.0,
    rollback_wait_at: int = 36,
    watchdog_at: Optional[int] = None,
    lease: float = 5.0,
    renew_interval: float = 0.1,
    lr: float = 0.05,
    n_push: int = 2,
    n_pull: int = 2,
    batch: int = 16,
    step_sleep: float = 0.03,
    z_max: float = 6.0,
    warmup: int = 2,
    reputation_nacks: int = 6,
    reputation_cooldown: float = 60.0,
    rollback_loss_factor: float = 1.2,
    rollback_timeout: float = 60.0,
    wal_group_n: int = 4,
    fixture=None,
) -> Dict:
    """Run one pass of the immune-system script (module docstring).

    ``poison_worker=None`` runs the fault-free corridor baseline (no SDC,
    no rollback expected — the snapshot barrier still fires). Step indices
    (``snapshot_at``, ``rollback_wait_at``) are on worker 1's loop;
    ``scale_after``/``scale_until``/``nan_after`` are PUSH indices on the
    poisoned worker's channel (envelope seqs).

    The rollback watchdog starts DISARMED and the poisoned worker arms it
    at step ``watchdog_at`` (default: the step after its last scale-window
    push), after draining its push flusher and waiting for every shard to
    have processed the whole window. That ordering is the scenario's one
    deliberate crutch: a watchdog that fires mid-window restores the
    manifest while gate-slipping scale pushes are still streaming — they
    re-poison the restored params, and the rollback cooldown (correctly)
    refuses an immediate second barrier, so the run ends diverged. Real
    deployments tune ``rollback_cooldown`` against their poison dwell
    time; the acceptance instead pins the deterministic case: window
    drained -> watchdog fires -> restore sticks (stale diverged-gradient
    pushes that arrive after it are z-rejected by the gate — the layers
    cover each other).
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
    poisoned = poison_worker is not None

    plan = ChaosPlan(
        seed=seed,
        sdc=(poisoned_worker_sdc(
            poison_worker, scale_after=scale_after, scale_until=scale_until,
            nan_after=nan_after, nan_until=nan_until,
            factor=poison_factor) if poisoned else ()))

    # --- worlds: plain coordination star + one chaos-wrapped reliable PS
    # star per shard, all sharing one log (drill topology) ----------------
    log = ChaosLog()
    coord_world = InProcessTransport.create_world(1 + n_shards + n_workers)
    star_chaos: List[Dict[int, FaultyTransport]] = []
    for i in range(n_shards):
        world = InProcessTransport.create_world(1 + n_workers)
        hub = FaultyTransport(world[0], plan, log=log)
        star = {0: hub}
        for r in range(1, 1 + n_workers):
            star[r] = hub.sibling(world[r])
        star_chaos.append(star)

    # breaker_grace: the health plan is SDC-ONLY — frames are corrupted in
    # place, never dropped or delayed — so an RTO blowup here can only be
    # scheduler starvation (jit'd grad threads hogging this 1-core host's
    # GIL), not a dead peer. Left at its default (= max_backoff, 0.25 s)
    # the breaker false-opens under load and its exponential cooldown
    # turns a transient stall into a stuck poison-window drain; a long
    # grace keeps retransmits flowing instead.
    def make_server_transport(i: int) -> ReliableTransport:
        return ReliableTransport(
            star_chaos[i][0], ack_timeout=0.05, max_backoff=0.25,
            max_retries=120, unreliable_codes=HEALTH_UNRELIABLE,
            ack_on_delivery=False, breaker_grace=60.0)

    rel_workers: List[Dict[int, ReliableTransport]] = []
    for i in range(n_shards):
        rel_workers.append({
            j: ReliableTransport(
                star_chaos[i][j], ack_timeout=0.05, max_backoff=0.25,
                max_retries=120, unreliable_codes=HEALTH_UNRELIABLE,
                breaker_grace=60.0)
            for j in range(1, 1 + n_workers)})

    manifest_path = os.path.join(base_dir, MANIFEST_NAME)
    if watchdog_at is None:
        watchdog_at = scale_until * n_push  # first step past the window
    coord = Coordinator(
        coord_world[0], n_params, lease=lease, speculation=False,
        manifest_dir=base_dir, auto_rollback=False,  # armed at watchdog_at
        rollback_loss_factor=rollback_loss_factor,
        rollback_cooldown=600.0,  # at most ONE rollback per run: the log's
        # determinism (and the assertion "exactly the scripted barrier")
        # must not depend on how fast post-restore telemetry recovers
        rollback_timeout=rollback_timeout,
        reputation_nacks=reputation_nacks,
        reputation_cooldown=reputation_cooldown)
    # flight recorder (ISSUE 12): the rollback barrier auto-dumps the
    # decision timeline into base_dir/obs — every rollback MTTR ships
    # with its window. Observational only: the 3x byte-identical
    # chaos-log acceptance runs WITH this attached (the recorder-
    # determinism guard for the health scenario).
    from distributed_ml_pytorch_tpu.utils import obs as _obs

    coord.recorder = _obs.SpanRecorder("coord", "coord")
    coord.obs_dir = os.path.join(base_dir, "obs")
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 600}, daemon=True)
    coord_thread.start()

    servers: List[ElasticShardServer] = []
    for i in range(n_shards):
        client = CoordClient(coord_world[1 + i], "shard",
                             renew_interval=renew_interval)
        srv = ElasticShardServer(
            server_id=1 + i, n_params=n_params,
            transport=make_server_transport(i), coord=client,
            init_params=flat0, ckpt_dir=os.path.join(base_dir, f"shard{i}"),
            ckpt_every=0, wal=True, wal_group_n=wal_group_n,
            admission=GradientAdmission(z_max=z_max, warmup=warmup),
            manifest_path=manifest_path)
        servers.append(srv)
        threading.Thread(target=srv.run, kwargs={"timeout": 600},
                         daemon=True).start()
    _wait_for(lambda: len(coord.shard_map.entries) == n_shards, 60,
              "all shard servers to join the map")

    losses: Dict[int, list] = {}
    opts: Dict[int, object] = {}
    errors: list = []
    snap_evt = threading.Event()
    timings: Dict[str, float] = {}

    def step_hook(j: int, step: int) -> None:
        if poisoned and j == poison_worker and step == watchdog_at:
            # arm the watchdog only once the scale window is fully THROUGH
            # the shards (docstring: a mid-window rollback gets re-poisoned
            # and the cooldown forbids a second). The flusher drain hands
            # every window push to the in-process wire (instant delivery);
            # the wait below covers the shards' serve loops consuming them.
            opts[j]._flusher.drain()
            _wait_for(lambda: all(
                (servers[i].ps.applied_by_sender.get(j, 0)
                 + servers[i].ps.quarantined_by_sender.get(j, 0))
                >= scale_until for i in range(n_shards)), 180,
                "the scale-poison window to drain through every shard")
            coord.auto_rollback = True
            # hold here until the barrier closes: the watchdog fires off
            # this worker's own diverged telemetry (its renew thread keeps
            # flowing while it waits), and waiting guarantees steps remain
            # to consume the phase-0 drop-and-pull after completion
            _wait_for(lambda: coord.rollbacks_done >= 1, 120,
                      "the watchdog-triggered rollback to complete")
        if j != 1:
            # the poison windows are push indices PAST the snapshot: every
            # other worker barriers just before its first poisonable push
            # so the manifest provably predates the poison (the rollback
            # target must be clean) — this couples only thread timing on
            # unfaulted channels, so the chaos log stays deterministic
            if step == snapshot_at:
                snap_evt.wait(300)
            return
        if step == snapshot_at:
            coord.trigger_snapshot()
            try:
                _wait_for(lambda: os.path.exists(manifest_path)
                          and coord.manifests_written > 0, 60,
                          "the snapshot barrier to publish a manifest")
            finally:
                snap_evt.set()
        if poisoned and step == rollback_wait_at:
            # the acceptance needs >= 1 COMPLETED rollback inside the run,
            # with post-rollback steps left to re-converge: hold the
            # scripting worker here until the watchdog has fired and the
            # barrier closed (its renew thread keeps the diverged telemetry
            # flowing while it waits)
            timings["wait_start"] = time.monotonic()
            _wait_for(lambda: coord.rollbacks_done >= 1, 120,
                      "the coordinator's auto-rollback to complete")
            timings["rollback_seen"] = time.monotonic()

    def run_worker(j: int) -> None:
        try:
            _run_worker(j)
        except Exception as e:  # noqa: BLE001 — surfaced to the caller
            errors.append((j, repr(e)))
            snap_evt.set()  # never leave the other workers barriered

    def _run_worker(j: int) -> None:
        client = CoordClient(coord_world[n_shards + j], "worker",
                             renew_interval=renew_interval)
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
            loss = float(loss)
            # loss rides into step(): it feeds the lease-renewal telemetry
            # AND gates the worker's own update application (a nonfinite
            # loss means these grads must not touch the params)
            params = opt.step(params, grads, loss=loss)
            my_losses.append(loss)
            if step_sleep > 0:
                time.sleep(step_sleep)
            step_hook(j, step)
        opt.finish()
        client.close()

    worker_threads = [threading.Thread(target=run_worker, args=(j,),
                                       daemon=True)
                      for j in range(1, n_workers + 1)]
    for t in worker_threads:
        t.start()
    for t in worker_threads:
        t.join(timeout=600)
    stuck = [t for t in worker_threads if t.is_alive()]
    for srv in servers:
        srv.stop()
    time.sleep(0.05)
    coord.stop()
    coord_thread.join(timeout=30)

    # ---- the explicit-reject ledger: every quarantined update must have
    # been nacked (never silently dropped), and the sequence accounting
    # must close — acked <= applied + quarantined + rolled-back ----------
    acked: Dict[int, Dict[int, int]] = {}
    applied: Dict[int, Dict[int, int]] = {}
    quarantined: Dict[int, Dict[int, int]] = {}
    for i in range(n_shards):
        acked[i] = {j: (rel_workers[i][j].acked_count(
            0, MessageCode.ShardPush) + rel_workers[i][j].acked_count(
            0, MessageCode.GradientUpdate))
            for j in range(1, 1 + n_workers)}
        applied[i] = {j: servers[i].ps.applied_by_sender.get(j, 0)
                      for j in range(1, 1 + n_workers)}
        quarantined[i] = {j: servers[i].ps.quarantined_by_sender.get(j, 0)
                          for j in range(1, 1 + n_workers)}
    accounting_ok = all(
        acked[i][j] <= (applied[i][j] + quarantined[i][j]
                        + servers[i].ps.rolled_back_updates)
        for i in range(n_shards) for j in range(1, 1 + n_workers))
    nacks_explicit = all(
        srv.ps.quarantined == srv.ps.nacks_sent for srv in servers)
    central_finite = all(
        bool(np.isfinite(srv.central).all()) for srv in servers)

    for star in rel_workers:
        for t in star.values():
            t.close()
    for srv in servers:
        close = getattr(srv.transport, "close", None)
        if close is not None:
            close()
    for t in coord_world.values():
        t.close()

    worker_nacks = {j: getattr(opts.get(j), "nacks", 0)
                    for j in range(1, 1 + n_workers)}
    return {
        "ok": (not stuck and not errors and accounting_ok
               and nacks_explicit and central_finite),
        "errors": errors,
        "stuck_workers": len(stuck),
        "losses": losses,
        "acked": acked,
        "applied": applied,
        "quarantined": quarantined,
        "accounting_ok": accounting_ok,
        "nacks_explicit": nacks_explicit,
        "central_finite": central_finite,
        "worker_nacks": worker_nacks,
        "worker_rollbacks": {j: getattr(opts.get(j), "rollbacks_seen", 0)
                             for j in range(1, 1 + n_workers)},
        "quarantined_total": sum(srv.ps.quarantined for srv in servers),
        "nacks_sent_total": sum(srv.ps.nacks_sent for srv in servers),
        "rollbacks": coord.rollbacks_done,
        "rollbacks_abandoned": coord.rollbacks_abandoned,
        "rollback_mttr_s": (coord.rollback_mttrs[0]
                            if coord.rollback_mttrs else None),
        "revoked_workers": coord.revoked_workers,
        "chaos_lines": log.lines(),
        "chaos_counts": log.counts(),
        "events": list(coord.events),
        "stats": {srv.server_id: dict(srv.stats) for srv in servers},
        "servers": servers,
    }


def health_demo(seed: int = 0, base_dir: Optional[str] = None) -> Dict:
    """One self-contained pass of the acceptance script
    (``coord/cli.py --health``)."""
    import tempfile

    base = base_dir or tempfile.mkdtemp(prefix="health_")
    out = health_scenario(base_dir=base, seed=seed)
    first = {j: round(float(np.mean(l[:4])), 3)
             for j, l in out["losses"].items()}
    last = {j: round(float(np.mean(l[-4:])), 3)
            for j, l in out["losses"].items()}
    return {
        "ok": (out["ok"] and out["rollbacks"] >= 1
               and out["quarantined_total"] > 0
               and out["revoked_workers"] >= 1),
        "rollbacks": out["rollbacks"],
        "rollback_mttr_s": out["rollback_mttr_s"],
        "quarantined": out["quarantined_total"],
        "nacks_sent": out["nacks_sent_total"],
        "worker_nacks": out["worker_nacks"],
        "revoked_workers": out["revoked_workers"],
        "first_losses": first,
        "last_losses": last,
        "chaos": out["chaos_counts"],
        "events": out["events"],
        "state_dir": base,
    }
