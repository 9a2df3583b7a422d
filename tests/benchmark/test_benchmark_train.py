"""The ``lm_train`` driver at a tiny size on the CPU: the result line, the
int8 control, and the faults a training cell can have. Each fault test skips
the harness's look for a chip and drives the rest of a run with the timed path
broken underneath; ``correct`` has to come out false."""

import json

import pytest
from benchmark_testlib import cpu_device, tiny_root

from benchmarks import harness

SEED = 2**31 + 11
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def run(tmp_path, trace=False):
    return harness.run_cell("tiny-train", SEED, 0.3, trace, root=tiny_root(tmp_path),
                            devices=cpu_device())


def test_sound_run_prints_the_contracts_line_and_names_its_device(tmp_path, capsys):
    result = run(tmp_path)
    harness.emit(result)
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS and line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert all(set(v) == {"value", "unit"} and v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 1
    assert set(line["compared"]) == {"grad_norm_gap", "change_norm_gap"}
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert err.strip().splitlines()[-1].startswith("compared change_norm_gap = ")


def test_a_traced_run_off_the_chip_reports_no_share_of_a_peak(tmp_path):
    result = run(tmp_path, trace=True)
    assert result["correct"] and result["metrics"] == {} and "breakdown" not in result
    assert "busy_s" not in result["device"]


def test_the_int8_control_comes_out_not_correct(tmp_path):
    """The reference in the program's place, every matmul's operands rounded
    to int8: at least one compared number has to pass its limit."""
    m = harness.load_manifest(tiny_root(tmp_path))
    cell = harness.Cell(str(tmp_path), m, "tiny-train")
    driver = harness.load_module(str(tmp_path), m, "drivers", "lm_train")
    from benchmarks import traffic

    t = cell.workload["traffic"]
    batches = [traffic.token_batch(SEED, i, t["batch"], t["seq"], cell.config["vocab_size"])
               for i in range(3)]
    ref, key = cell.reference(), harness.seed_key(SEED)
    lr = cell.workload["trainer"]["lr"]
    want = ref.adam_steps(key, batches, cell.config, lr)
    control = driver.compare_steps(ref.adam_steps(key, batches, cell.config, lr, quant=True), want)
    limits = cell.workload["limits"]
    assert any(control[name] > limits[name] for name in limits), (control, limits)


def break_step(monkeypatch, breaker):
    from distributed_ml_pytorch_tpu.parallel import fsdp

    real = fsdp.make_fsdp_lm_train_step
    monkeypatch.setattr(fsdp, "make_fsdp_lm_train_step",
                        lambda *a, **kw: breaker(real(*a, **kw)))


def test_a_step_that_returns_its_state_unchanged_is_not_correct(tmp_path, monkeypatch):
    import jax

    break_step(monkeypatch, lambda step: jax.jit(lambda s, t, g: (s, step(s, t, g)[1])))
    result = run(tmp_path)
    assert result["correct"] is False
    assert result["compared"]["grad_norm_gap"]["value"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    break_step(monkeypatch, lambda step: lambda s, t, g: step(s, t[: t.shape[0] // 2],
                                                              g[: g.shape[0] // 2]))
    result = run(tmp_path)
    assert result["correct"] is False
