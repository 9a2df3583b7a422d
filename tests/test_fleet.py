"""Fleet serving (ISSUE 6): multi-engine routing, stream migration across
engine death (token-identical, byte-identical chaos logs), SLO-aware
overload control (shed/brownout/deadline), and the frontend fail-open /
hold-queue satellites.

THE acceptance scenario: 3 engines + router under ``FaultyTransport``
(seeded loss on stream frames, duplicated submits), one engine killed
mid-decode — every in-flight stream completes token-identical (CPU) to a
single-engine ``generate()``, three runs produce byte-identical chaos
logs, and lease expiry (a member whose renewals stop while its serve loop
keeps running) triggers the same migration as a scripted crash.
"""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_ml_pytorch_tpu.models.generate import generate
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
from distributed_ml_pytorch_tpu.serving.engine import ServingEngine
from distributed_ml_pytorch_tpu.serving.fleet import EngineMember, FleetRouter
from distributed_ml_pytorch_tpu.serving.frontend import (
    RequestRejected,
    ServingClient,
    ServingFrontend,
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
)

pytestmark = pytest.mark.fleet

VOCAB = 64


@pytest.fixture(scope="module")
def lm_and_params():
    model = TransformerLM(vocab_size=VOCAB, d_model=32, n_heads=4,
                          n_layers=2, d_ff=64, max_len=256)
    params = model.init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return model, params


def make_engine(lm_and_params, warm=True, **kw):
    model, params = lm_and_params
    kw.setdefault("slots", 2)
    kw.setdefault("cache_size", 200)
    kw.setdefault("decode_block", 4)
    kw.setdefault("prefill_bucket", 8)
    engine = ServingEngine(model, params, **kw)
    if warm:
        # compile the buckets/decode block OUTSIDE the probed window — a
        # cold engine stalls seconds in XLA and reads as dead to a router
        for bucket in (8, 16):
            if engine.pool.capacity_needed(bucket, bucket, 6) \
                    <= engine.pool.cache_size:
                w = engine.submit(np.zeros(bucket, np.int32), 6)
                engine.run_until_idle()
                assert w.done
        engine.reset_metrics()
    return engine


def expected(lm_and_params, prompt, n, **kw):
    model, params = lm_and_params
    if "seed" in kw:
        kw["rng"] = jax.random.key(kw.pop("seed"))
    return np.asarray(generate(
        model, params, jnp.asarray(prompt, jnp.int32)[None], n, **kw)
    )[0, len(prompt):].tolist()


def fleet_world(lm_and_params, n_engines=3, plan=None, router_kw=None,
                member_coords=None, member_kw=None):
    """N warmed engines behind a FleetRouter on a 2-rank world (rank 0 hub,
    rank 1 client), optionally chaos-wrapped with one shared log."""
    world = InProcessTransport.create_world(2)
    log = None
    if plan is not None:
        world, log = FaultyTransport.wrap_world(world, plan)
    members = []
    for i in range(n_engines):
        coord = member_coords[i] if member_coords else None
        members.append(EngineMember(
            i, make_engine(lm_and_params), coord=coord,
            **(member_kw or {})).start())
    kw = {"probe_timeout": 0.5}
    kw.update(router_kw or {})
    router = FleetRouter(world[0], members, **kw)
    thread = threading.Thread(target=router.serve_forever, daemon=True)
    thread.start()
    return world, members, router, thread, log


def teardown_fleet(world, router, thread):
    router.stop()
    thread.join(timeout=10)
    for t in world.values():
        t.close()


def wait_for(cond, timeout=30.0, interval=0.002):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return False


# ---------------------------------------------------------------------------
# engine-level: the gen_offset resume primitive migration rides on
# ---------------------------------------------------------------------------

def test_gen_offset_resume_is_token_identical(lm_and_params):
    """Resuming prompt + generated-so-far with the matching gen_offset
    continues the stream token-identically — greedy AND sampled (the
    sampling key schedule is position-in-stream, not position-on-engine)."""
    prompt = np.random.default_rng(1).integers(0, VOCAB, size=5)
    for kw in ({}, {"temperature": 0.8, "top_k": 8, "seed": 11}):
        want = expected(lm_and_params, prompt, 20, **dict(kw))
        engine_a = make_engine(lm_and_params)
        full = engine_a.submit(prompt, 20, **kw)
        engine_a.run_until_idle()
        assert full.tokens == want
        for cut in (1, 7, 19):
            engine_b = make_engine(lm_and_params, warm=False)
            resumed = engine_b.submit(
                np.concatenate([prompt, np.asarray(want[:cut], np.int32)]),
                20 - cut, gen_offset=cut, **kw)
            engine_b.run_until_idle()
            assert resumed.tokens == want[cut:], (kw, cut)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

def test_router_routes_by_occupancy_and_session_affinity(lm_and_params):
    world, members, router, thread, _ = fleet_world(lm_and_params, 2)
    try:
        client = ServingClient(world[1])
        # 4 concurrent long streams over 2 engines x 2 slots: occupancy
        # routing must spread them instead of stacking one engine
        rids = [client.submit(np.arange(4), 60) for _ in range(4)]
        assert wait_for(lambda: len(router._routes) == 4)
        with router._routes_lock:
            used = [r.engine_id for r in router._routes.values()]
        assert sorted(used).count(0) == 2 and sorted(used).count(1) == 2
        for rid in rids:
            assert len(list(client.stream(rid, timeout=120))) == 60
        # session affinity: consecutive submits of one session stick to one
        # engine while it has room (prefix locality)
        sids = [client.submit(np.arange(4), 4, session=9) for _ in range(2)]
        assert wait_for(
            lambda: len([r for r in router._routes.values()
                         if r.session == 9]) == 2)
        with router._routes_lock:
            pinned = {r.engine_id for r in router._routes.values()
                      if r.session == 9}
        assert len(pinned) == 1
        for rid in sids:
            list(client.stream(rid, timeout=60))
    finally:
        teardown_fleet(world, router, thread)


# ---------------------------------------------------------------------------
# THE acceptance: migration under chaos, byte-identical logs, 3x
# ---------------------------------------------------------------------------

def _acceptance_plan():
    # faults restricted to deterministic-index channels: the hub's first 8
    # StreamTokens frames (drops recovered by the client resume protocol —
    # retransmits are unfaulted, so their count never touches the log) and
    # the client's 3 SubmitRequest frames (dups replay, never double-submit)
    return ChaosPlan([
        FaultRule(code=int(MessageCode.StreamTokens), drop=0.4, until=8),
        FaultRule(code=int(MessageCode.SubmitRequest), dup=0.3),
    ], seed=29)


def _run_fleet_acceptance_once(lm_and_params):
    """3 engines + router under chaos, one engine killed mid-decode; returns
    (tokens per request, chaos log lines, router stats)."""
    world, members, router, thread, log = fleet_world(
        lm_and_params, 3, plan=_acceptance_plan())
    try:
        client = ServingClient(world[1], resume_after=0.25)
        prompt = np.random.default_rng(0).integers(0, VOCAB, size=5)
        specs = [dict(n=120), dict(n=100, temperature=0.8, top_k=8, seed=3),
                 dict(n=90)]
        rids = []
        for s in specs:
            s = dict(s)
            rids.append(client.submit(prompt, s.pop("n"), **s))
        # kill the first engine observed with an in-flight stream that has
        # streamed at least 2 tokens — a mid-decode death by construction
        victim = {}

        def find_victim():
            with router._routes_lock:
                for r in router._routes.values():
                    if not r.done and r.engine_id >= 0 and len(r.tokens) >= 2:
                        victim["id"] = r.engine_id
                        return True
            return False

        assert wait_for(find_victim), "no stream ever got mid-decode"
        members[victim["id"]].crash()
        streams = [list(client.stream(r, timeout=180)) for r in rids]
        assert wait_for(lambda: router.migrations >= 1, timeout=5)
        return prompt, specs, streams, log.lines(), {
            "migrations": router.migrations,
            "failures": router.migration_failures,
        }
    finally:
        teardown_fleet(world, router, thread)


def test_fleet_acceptance_migration_token_identical_3x(lm_and_params):
    """ISSUE 6 acceptance: one engine killed mid-decode under seeded chaos
    — every in-flight stream completes token-identical to a single-engine
    generate(), across THREE runs with byte-identical chaos logs."""
    logs = []
    for _run in range(3):
        prompt, specs, streams, lines, stats = \
            _run_fleet_acceptance_once(lm_and_params)
        for spec, got in zip(specs, streams):
            s = dict(spec)
            want = expected(lm_and_params, prompt, s.pop("n"), **s)
            assert got == want, f"stream diverged after migration: {spec}"
        assert stats["migrations"] >= 1 and stats["failures"] == 0
        logs.append(lines)
    assert logs[0] == logs[1] == logs[2], "chaos logs not byte-identical"
    assert logs[0], "no faults ever fired"


def test_lease_expiry_triggers_migration(lm_and_params):
    """The OTHER detection path: a member whose lease renewals stop while
    its serve loop keeps beating (control-plane death). The local probe
    sees a healthy engine; the coordinator's fleet view drops its rank —
    and that alone must trigger the same token-identical migration."""
    from distributed_ml_pytorch_tpu.coord.coordinator import Coordinator
    from distributed_ml_pytorch_tpu.coord.member import CoordClient

    coord_world = InProcessTransport.create_world(4)
    coord = Coordinator(coord_world[0], n_params=8, lease=0.6)
    coord_thread = threading.Thread(
        target=coord.run, kwargs={"timeout": 120}, daemon=True)
    coord_thread.start()
    clients = [CoordClient(coord_world[i], "engine", renew_interval=0.1)
               for i in (1, 2, 3)]
    world, members, router, thread, _ = fleet_world(
        lm_and_params, 3,
        router_kw={"probe_timeout": 60.0, "fleet": coord},  # probe blinded
        member_coords=clients,
        # throttled decode keeps the stream in flight across one lease
        member_kw={"throttle": 0.05})
    try:
        assert wait_for(lambda: len(coord.live_engine_ranks()) == 3,
                        timeout=10)
        client = ServingClient(world[1], resume_after=0.25)
        prompt = np.random.default_rng(2).integers(0, VOCAB, size=6)
        rid = client.submit(prompt, 110)
        victim = {}

        def started():
            with router._routes_lock:
                for r in router._routes.values():
                    if not r.done and r.engine_id >= 0 and len(r.tokens) >= 2:
                        victim["id"] = r.engine_id
                        return True
            return False

        assert wait_for(started)
        # kill ONLY the control-plane life: renewals stop, serving does not
        members[victim["id"]].coord.stop()
        assert wait_for(
            lambda: victim["id"] not in
            {m.engine_id for m in router._healthy_members()}, timeout=10), \
            "lease expiry never marked the member down"
        toks = list(client.stream(rid, timeout=180))
        assert toks == expected(lm_and_params, prompt, 110)
        assert router.migrations >= 1
    finally:
        teardown_fleet(world, router, thread)
        coord.stop()
        coord_thread.join(timeout=10)
        for c in clients:
            c.stop()
        for t in coord_world.values():
            t.close()


# ---------------------------------------------------------------------------
# overload plane: shed / brownout / deadline
# ---------------------------------------------------------------------------

def overloaded_frontend(lm_and_params, **kw):
    """A 1-slot engine with a long-running occupant, so pressure >= 1."""
    engine = make_engine(lm_and_params, slots=1, cache_size=200,
                         max_queue=16)
    world = InProcessTransport.create_world(2)
    frontend = ServingFrontend(engine, world[0], **kw)
    thread = threading.Thread(target=frontend.serve_forever, daemon=True)
    thread.start()
    return engine, world, frontend, thread


def test_shed_lowest_priority_with_explicit_reject(lm_and_params):
    # shed_occupancy=2.0 on a 1-slot engine: overload begins once one
    # request runs AND one waits — the waiting one is the displacement pool
    engine, world, frontend, thread = overloaded_frontend(
        lm_and_params, shed_occupancy=2.0)
    try:
        client = ServingClient(world[1])
        occupant = client.submit(np.arange(4), 120)  # fills the only slot
        assert wait_for(lambda: engine.pressure()[0] == 1)
        mid = client.submit(np.arange(4), 8, priority=2)  # queues: now 2.0
        assert wait_for(lambda: len(frontend._waiting_routes()) == 1)
        # overloaded: a LOWER-priority submit cannot displace mid — it is
        # shed outright with an explicit reject …
        low = client.submit(np.arange(4), 8, priority=1)
        with pytest.raises(RequestRejected):
            list(client.stream(low, timeout=30))
        assert frontend.shed == 1
        # … while a HIGHER-priority one displaces mid (mid gets the reject)
        high = client.submit(np.arange(4), 8, priority=5)
        with pytest.raises(RequestRejected):
            list(client.stream(mid, timeout=30))
        assert frontend.shed == 2
        assert len(list(client.stream(occupant, timeout=180))) == 120
        assert len(list(client.stream(high, timeout=60))) == 8
    finally:
        frontend.stop()
        thread.join(timeout=10)
        for t in world.values():
            t.close()


def test_brownout_caps_max_new_before_shedding(lm_and_params):
    engine, world, frontend, thread = overloaded_frontend(
        lm_and_params, brownout_occupancy=1.0, brownout_max_new=5)
    try:
        client = ServingClient(world[1])
        occupant = client.submit(np.arange(4), 60)
        assert wait_for(lambda: engine.pressure()[0] == 1)
        # browned out, NOT shed: served, but truncated to brownout_max_new
        dim = client.submit(np.arange(4), 40, priority=1)
        toks = list(client.stream(dim, timeout=120))
        assert len(toks) == 5
        assert frontend.brownouts == 1 and frontend.shed == 0
        assert len(list(client.stream(occupant, timeout=120))) == 60
    finally:
        frontend.stop()
        thread.join(timeout=10)
        for t in world.values():
            t.close()


def test_deadline_expired_waiting_work_is_shed(lm_and_params):
    """No serve loop: the scheduling timeline is driven by hand, so the
    deadline expiry is exact (the existing silent-client test's style)."""
    engine = make_engine(lm_and_params, slots=1, max_queue=16)
    world = InProcessTransport.create_world(2)
    frontend = ServingFrontend(engine, world[0])
    try:
        client = ServingClient(world[1])
        occupant = client.submit(np.arange(4), 30)
        doomed = client.submit(np.arange(4), 8, deadline_ms=100)
        assert wait_for(lambda: len(frontend._waiting_routes()) == 2)
        time.sleep(0.15)  # the doomed deadline passes while both wait
        frontend._sweep(time.monotonic())
        assert frontend.shed == 1
        engine.run_until_idle()  # the survivor is served to completion
        with pytest.raises(RequestRejected):
            list(client.stream(doomed, timeout=30))
        assert len(list(client.stream(occupant, timeout=60))) == 30
    finally:
        frontend.stop()
        for t in world.values():
            t.close()


# ---------------------------------------------------------------------------
# satellites: fail-open without a control plane; hold-queue overflow
# ---------------------------------------------------------------------------

def test_frontend_fails_open_without_fleet(lm_and_params):
    """fleet=None (no control plane) must keep admitting — the documented
    fail-open path, previously untested."""
    engine = make_engine(lm_and_params)
    world = InProcessTransport.create_world(2)
    frontend = ServingFrontend(engine, world[0], fleet=None)
    thread = threading.Thread(target=frontend.serve_forever, daemon=True)
    thread.start()
    try:
        client = ServingClient(world[1])
        toks = client.generate(np.arange(5), 10, timeout=60)
        assert len(toks) == 10
        assert frontend.held_peak == 0  # nothing was ever held
    finally:
        frontend.stop()
        thread.join(timeout=10)
        for t in world.values():
            t.close()


class _DownFleet:
    def __init__(self):
        self.up = False

    def engine_up(self):
        return self.up


def test_hold_queue_overflow_under_down_fleet(lm_and_params):
    """With the fleet DOWN: the first hold_queue submits are held (arrival
    order), the overflow gets explicit rejects, and recovery re-admits
    every held request to completion."""
    engine = make_engine(lm_and_params)
    fleet = _DownFleet()
    world = InProcessTransport.create_world(2)
    frontend = ServingFrontend(engine, world[0], fleet=fleet, hold_queue=3)
    thread = threading.Thread(target=frontend.serve_forever, daemon=True)
    thread.start()
    try:
        client = ServingClient(world[1])
        rids = [client.submit(np.arange(4), 6) for _ in range(5)]
        assert wait_for(lambda: frontend.held_peak == 3)
        with frontend._held_lock:
            assert len(frontend._held) == 3
        rejected = 0
        for rid in rids[3:]:
            with pytest.raises(RequestRejected):
                list(client.stream(rid, timeout=30))
            rejected += 1
        assert rejected == 2
        fleet.up = True  # recovery: the sweep re-admits in arrival order
        for rid in rids[:3]:
            assert len(list(client.stream(rid, timeout=120))) == 6
        with frontend._held_lock:
            assert not frontend._held
    finally:
        frontend.stop()
        thread.join(timeout=10)
        for t in world.values():
            t.close()


# ---------------------------------------------------------------------------
# overload soak
# ---------------------------------------------------------------------------

def _open_loop(lm_and_params, rate, n=36, deadline_ms=8000, **router_kw):
    """``n`` Poisson arrivals at ``rate``/s into a 2-engine fleet, submitted
    on the clock whatever the fleet has finished. Returns goodput (tokens/s
    of requests done within their deadline), the rejects the client saw
    and the router's own shed count."""
    rng = np.random.default_rng(5)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    plan = [(rng.integers(0, VOCAB, size=int(rng.integers(4, 9))),
             int(rng.integers(6, 15)), int(rng.integers(0, 3)))
            for _ in range(n)]
    # the raw frame collector below never sends StreamAck: keep the
    # silent-client reaper out of the way
    world, _members, router, thread, _ = fleet_world(
        lm_and_params, n_engines=2,
        router_kw={"client_deadline": 3600.0, **router_kw})
    try:
        client = ServingClient(world[1])
        state = {}  # rid -> [arrival, tokens seen, done_at, rejected]
        t0, sent = time.perf_counter(), 0
        while sent < n or any(s[2] is None for s in state.values()):
            now = time.perf_counter() - t0
            assert now < 300, "open loop: stragglers never finished"
            while sent < n and arrivals[sent] <= now:
                prompt, max_new, priority = plan[sent]
                rid = client.submit(prompt, max_new, priority=priority,
                                    deadline_ms=deadline_ms)
                state[rid] = [arrivals[sent], 0, None, False]
                sent += 1
            msg = world[1].recv(timeout=0.002)
            if msg is None or msg[2].size < 1:
                continue
            _src, code, payload = msg
            entry = state.get(int(payload[0]))
            if entry is None:
                continue
            if code == MessageCode.ServeReject:
                entry[2], entry[3] = time.perf_counter() - t0, True
            elif code == MessageCode.StreamTokens and payload.size >= 3:
                entry[1] = max(entry[1], int(payload[2]) + payload.size - 3)
                if payload[1] and entry[2] is None:
                    entry[2] = time.perf_counter() - t0
        wall = time.perf_counter() - t0
    finally:
        teardown_fleet(world, router, thread)
    good = sum(toks for arrived, toks, done_at, rejected in state.values()
               if not rejected and done_at <= arrived + deadline_ms / 1e3)
    rejects = sum(1 for s in state.values() if s[3])
    return good / wall, rejects, router.shed + router.migration_failures


@pytest.mark.slow
@pytest.mark.soak
def test_overload_soak_2x_rate_degrades_not_dies(lm_and_params):
    """Acceptance (overload half): at 2x the baseline arrival rate the
    fleet sheds/brownouts instead of collapsing — goodput-under-SLO stays
    >= 80% of the 1x value, and every shed request got an explicit reject
    (client-side rejects == router-side shed count)."""
    base_rate = 4.0
    goodput_1x, _, _ = _open_loop(lm_and_params, base_rate)
    goodput_2x, rejects, shed = _open_loop(
        lm_and_params, 2 * base_rate, shed_occupancy=3.0,
        brownout_occupancy=2.0, brownout_max_new=6)
    assert goodput_1x > 0
    assert goodput_2x >= 0.8 * goodput_1x, (
        f"fleet collapsed under 2x load: {goodput_2x:.1f} vs "
        f"{goodput_1x:.1f} tok/s goodput")
    # every shed request was told so explicitly — no silent drops
    assert rejects == shed
