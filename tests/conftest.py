"""Test bootstrap: run everything on an 8-device virtual CPU mesh.

The framework's distributed paths (psum allreduce, ppermute p2p, sharded
train steps) are unit-tested on virtual CPU devices — the single-host
cluster simulation recommended in SURVEY.md §4, replacing the reference's
localhost multi-process smoke topology (``Makefile:13-20``).

The suite never touches a chip: ``JAX_PLATFORMS=cpu`` and eight devices from
``jax_num_cpu_devices``, set here before JAX initializes a backend. The chip
is reached through ``python chip_smoke.py`` on a machine that has one.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TPU_DISTBELIEF_TEST_ENV"] = "1"
# entry points turn the persistent compile cache on (runtime/startup.py);
# the suite and the subprocess worlds it spawns neither need nor fill it
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

from distributed_ml_pytorch_tpu.runtime.mesh import force_cpu_devices  # noqa: E402

N_DEVICES = 8

force_cpu_devices(N_DEVICES)

assert len(jax.devices()) == N_DEVICES and jax.devices()[0].platform == "cpu", (
    f"expected {N_DEVICES} virtual CPU devices, got {jax.devices()}"
)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from distributed_ml_pytorch_tpu.runtime import data_mesh

    return data_mesh(8)


@pytest.fixture
def lock_witness():
    """Runtime lock-order witness (analysis/witness.py, ISSUE 4): under
    DISTCHECK_WITNESS=1 the chaos/coord acceptance scenarios double as
    concurrency validators — every lock acquisition order observed during
    the run must be acyclic. Without the env flag this is a no-op, so the
    default suite pays nothing."""
    from distributed_ml_pytorch_tpu.analysis.witness import maybe_install

    w = maybe_install()
    yield w
    if w is not None:
        w.uninstall()
        assert not w.cycles(), w.report()
        # bounded-state witness (ISSUE 19): at teardown, every container
        # the static DC503 pass cleared via a fallible exemption must
        # actually be within budget — read-only len() sampling, so the
        # chaos suites' byte-identical log guarantees are untouched
        from distributed_ml_pytorch_tpu.analysis.witness import (
            check_exempt_budget,
        )

        over = check_exempt_budget()
        assert not over, (
            "DC503-exempt containers over budget at scenario teardown "
            f"(cls, attr, len): {over}")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "network: needs internet egress (real CIFAR-10 download); "
        "deselect with -m 'not network' — these skip themselves when "
        "the download fails",
    )
    config.addinivalue_line(
        "markers",
        "slow: spawns real processes or trains end-to-end (minutes on a "
        "1-core host); `make test` deselects these for a fast core signal, "
        "`make test-all` runs everything",
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tests (utils/chaos.py + the "
        "reliability layer); `make chaos` selects exactly these — fast "
        "seeded cases run in tier-1, soak variants are additionally slow",
    )
    config.addinivalue_line(
        "markers",
        "coord: elastic control-plane tests (coord/ — membership, leases, "
        "shard rebalancing, speculation); `make coord` selects exactly "
        "these — fast cases run in tier-1, the wall-clock scenario tests "
        "are additionally listed in slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "drill: disaster-recovery drill tests (coord/drill.py + utils/"
        "wal.py — snapshot barrier, kill-and-restore, sequence "
        "accounting); `make drill` selects exactly these — fast cases run "
        "in tier-1, the full kill-all scenarios are additionally measured "
        "into slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "fleet: fleet-serving tests (serving/fleet.py — occupancy routing, "
        "stream migration across engine death, overload shed/brownout); "
        "`make fleet` selects exactly these — fast cases run in tier-1, "
        "the acceptance scenarios are additionally in slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "soak: sustained-load scenarios (the 2x-overload goodput soak); "
        "`make soak` selects exactly these — all also slow, so tier-1 "
        "never pays for them",
    )
    config.addinivalue_line(
        "markers",
        "health: numerical-health tests (utils/health.py admission gate, "
        "SDC chaos, UpdateNack quarantine, worker reputation, coordinator "
        "auto-rollback — ISSUE 8); `make health` selects exactly these — "
        "fast units run in tier-1, the 3x acceptance scenario is "
        "additionally measured into slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "mpmd: MPMD pipeline-plane tests (parallel/mpmd.py + "
        "coord/stages.py — per-stage compiled programs, StagePlacement, "
        "stage death/restart with watermark replay, stage speculation — "
        "ISSUE 10); `make mpmd` selects exactly these — fast units run in "
        "tier-1, the fleet scenarios are additionally measured into "
        "slow_tests.txt; the manifest drill variant also carries the "
        "drill marker",
    )
    config.addinivalue_line(
        "markers",
        "codec: codec-plane tests (utils/codecs.py — the WIRE_PLANES "
        "registry's totality over codec-id-bearing WIRE_SCHEMAS, loss "
        "contracts, the int8 bound, delta-reply identity, tok16 "
        "exactness — ISSUE 18); `make codec` selects exactly these — "
        "all fast, all in tier-1",
    )
    config.addinivalue_line(
        "markers",
        "distmodel: bounded protocol model checking (analysis/"
        "distmodel.py — exactly-once / lease / watermark-replay "
        "invariants, the seeded-mutation soundness corpus, and the "
        "counterexample-to-chaos replays against the real transport "
        "stack — ISSUE 13); `make distmodel` runs the checker itself, "
        "these tests run in tier-1",
    )
    config.addinivalue_line(
        "markers",
        "sched: multi-tenant scheduler tests (coord/sched.py + "
        "coord/tenants.py — capacity ledger, admit/pack/preempt/resume "
        "decisions, the park-and-restore drill, autoscale actuation — "
        "ISSUE 16); `make sched` selects exactly these — fast units run "
        "in tier-1, the full drill scenarios are additionally measured "
        "into slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "coordfail: control-plane durability tests (coord/coordinator.py "
        "WAL+checkpoint restart, epoch fencing, the restart grace window, "
        "the coordfail distmodel plane and the kill-the-coordinator drill "
        "— ISSUE 17); `make coordfail` selects exactly these — fast units "
        "run in tier-1, the 3x drill acceptance is additionally in "
        "slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "distflow: interprocedural dataflow lint tests (analysis/"
        "distflow.py — DC501 receive ordering, DC502 fenced-mutation "
        "gating, DC503 bounded state + the runtime bounded-state "
        "witness, DC504 blocking-under-lock — ISSUE 19); `make "
        "distflow` selects exactly these — all fast, all in tier-1",
    )
    config.addinivalue_line(
        "markers",
        "netweather: adaptive-wire tests under network weather "
        "(utils/chaos.WeatherRule + the RTO/window/breaker machinery in "
        "utils/messaging.ReliableTransport); `make netweather` selects "
        "exactly these — fast units run in tier-1, the training "
        "acceptance is additionally measured into slow_tests.txt",
    )
    config.addinivalue_line(
        "markers",
        "gray: gray-failure plane tests (coord/grayhealth.py adaptive "
        "suspicion + containment ladder, utils/chaos.GrayRule scheduled "
        "one-way partitions / lossy links / stalls, the renew-tail wire "
        "compatibility, the gray distmodel plane — ISSUE 20); `make "
        "gray` selects exactly these — fast units run in tier-1, the "
        "mid-training gray drill acceptance is additionally measured "
        "into slow_tests.txt",
    )


# Modules whose tests launch real subprocess worlds (interpreter start + jit
# compile per process) or run whole example trainings — the wall-clock tail
# of the suite. Marked wholesale here so a new test in these files cannot be
# forgotten; in-process tests that also take minutes opt in with an explicit
# @pytest.mark.slow at the test site.
SLOW_MODULES = {
    "test_examples",
    "test_launchers",
    "test_multihost_bootstrap",
    "test_multihost_branches",
    "test_ps_fault_injection",
    "test_ps_multiprocess",
    "test_real_data",
    "test_sharded_ps",
}


def _listed_slow_tests():
    """Node IDs marked slow by measurement (>= 4 s call time on this host —
    see tests/slow_tests.txt for the regeneration command). Kept as a
    generated file so the cut is data, not opinion; a renamed test drops
    out of the list and simply runs fast-set until the next regeneration."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "slow_tests.txt")
    if not os.path.exists(path):
        return frozenset()
    with open(path) as fh:
        return frozenset(
            line.strip() for line in fh
            if line.strip() and not line.startswith("#")
        )


SLOW_TESTS = _listed_slow_tests()


def pytest_collection_modifyitems(config, items):
    for item in items:
        if (
            item.module.__name__.rsplit(".", 1)[-1] in SLOW_MODULES
            or item.nodeid in SLOW_TESTS
        ):
            item.add_marker(pytest.mark.slow)
