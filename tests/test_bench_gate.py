"""The MFU regression gate (bench.py --gate, ISSUE 9) — logic on canned
records, no device run (this is the tier-1 twin of `make bench-gate`).

The gate's contract: a leg below its recorded floor minus tolerance fails;
a floored leg MISSING from the record fails (a silently dropped leg must
not pass); a leg without measured MFU fails too (the benchmark itself
refuses to run without a TPU, so such a record did not come from it).
"""

import copy
import json
import os
import subprocess
import sys

import pytest

import bench

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
SMOKE = os.path.join(HERE, "data", "bench_gate_smoke.json")


def _smoke_record():
    with open(SMOKE) as fh:
        return json.load(fh)


def test_canned_record_passes_floors():
    floors = bench.load_floors()
    assert not bench.check_mfu_floors(_smoke_record(), floors)
    assert bench.gate(_smoke_record(), floors) == 0


def test_simulated_mfu_drop_breaches_exactly_that_leg():
    floors = bench.load_floors()
    rec = _smoke_record()
    floor = floors["legs"]["large_batch_b1024"]
    rec["legs"]["large_batch_b1024"]["mfu"] = floor - floors["tolerance"] - 0.001
    breaches = bench.check_mfu_floors(rec, floors)
    assert len(breaches) == 1 and "large_batch_b1024" in breaches[0]
    assert bench.gate(rec, floors) == 1
    # within tolerance of the floor: still passing (hysteresis band)
    rec["legs"]["large_batch_b1024"]["mfu"] = floor - floors["tolerance"] / 2
    assert not bench.check_mfu_floors(rec, floors)


def test_missing_leg_is_a_breach_not_a_pass():
    floors = bench.load_floors()
    rec = _smoke_record()
    del rec["legs"]["parity_b64"]
    breaches = bench.check_mfu_floors(rec, floors)
    assert any("parity_b64" in b and "missing" in b for b in breaches)
    assert bench.gate(rec, floors) == 1


def test_unmeasured_mfu_is_a_breach_not_a_skip():
    """bench.py only runs on a TPU whose peak is in the table, so a record
    with no MFU did not come from it: the gate fails, it does not skip."""
    floors = bench.load_floors()
    rec = _smoke_record()
    for leg in rec["legs"].values():
        leg.pop("mfu", None)
    breaches = bench.check_mfu_floors(rec, floors)
    assert len(breaches) == 3 and all("no measured MFU" in b for b in breaches)
    assert bench.gate(rec, floors) == 1


def test_bench_without_a_tpu_fails_and_prints_no_record():
    out = subprocess.run(
        [sys.executable, "bench.py"], cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert out.stdout.strip() == ""


def test_build_record_carries_floors_and_headline():
    floors = bench.load_floors()
    legs = {name: bench.Rate(v) for name, v in [
        ("parity_b64", 1.18e6), ("large_batch_b1024", 1.64e6),
        ("grad_accum_b1024", 1.52e6)]}
    rec = bench.build_record(legs, torch_base=845.0, floors=floors)
    assert rec["headline_leg"] == bench.HEADLINE_LEG
    assert rec["value"] == round(float(legs[bench.HEADLINE_LEG]), 1)
    assert rec["vs_baseline"] == round(1.64e6 / 845.0, 2)
    for name, leg in rec["legs"].items():
        assert leg["mfu_floor"] == floors["legs"][name]
    # the parity leg keeps the reference batch; the throughput legs report
    # theirs — side-by-side legs, one record
    assert rec["legs"]["parity_b64"]["batch"] == 64
    assert rec["legs"]["large_batch_b1024"]["batch"] == bench.LARGE_BATCH


def test_cli_gate_exit_codes(tmp_path):
    """`python bench.py --gate --json FILE` is the make bench-gate smoke:
    exit 0 on the canned record, non-zero on a seeded regression — with
    no jax import (the gate must stay cheap enough for `make test`)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    ok = subprocess.run(
        [sys.executable, "bench.py", "--gate", "--json", SMOKE],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert ok.returncode == 0, ok.stderr
    bad = copy.deepcopy(_smoke_record())
    bad["legs"]["grad_accum_b1024"]["mfu"] = 0.01
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(bad))
    fail = subprocess.run(
        [sys.executable, "bench.py", "--gate", "--json", str(bad_path)],
        cwd=REPO, env=env, capture_output=True, text=True)
    assert fail.returncode == 1
    assert "grad_accum_b1024" in fail.stderr


def test_bench_all_only_ps_tpu_must_be_named_first():
    """ps_tpu's children need the chip; a phase run before it may have left
    the parent holding it, so the order is refused, not tried."""
    out = subprocess.run(
        [sys.executable, "bench_all.py", "--only", "tpu", "--only", "ps_tpu"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    assert "ps_tpu must be the first phase" in out.stderr
    assert out.stdout.strip() == ""


@pytest.mark.slow
def test_bench_all_cpu_mesh_phase_runs_after_the_parent_took_a_backend():
    """The eight-device measurements run in a child: the parent's backend
    (one CPU device here, the TPU on a chip machine) is already up when the
    full table gets to them, and a live backend's device count is fixed."""
    code = ("import jax, bench_all\n"
            "assert len(jax.devices()) == 1\n"
            "bench_all.cpu_mesh_phase()\n"
            "print('RECORDS', sorted(r['metric'] for r in bench_all.RESULTS))\n")
    env = {k: v for k, v in os.environ.items() if k != "JAX_NUM_CPU_DEVICES"}
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO,
        env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert ("RECORDS ['allreduce_2way_gradient_exchange_rate', "
            "'resnet18_8way_dp_step_throughput']") in out.stdout
    assert '"hardware": "8 virtual cpu devices"' in out.stdout
