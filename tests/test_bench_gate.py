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
