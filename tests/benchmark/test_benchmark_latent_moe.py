"""The ``kanana-2-30b-a3b`` configuration's benchmark files at a tiny size on
the CPU: the ``latent_moe_serve`` driver's result line with and without a
trace, each planted fault out of ``correct``, and the readers of the new
scopes on a recorded trace, every per-layer metric of the cell through the
harness.

``recorded_latent_moe_trace.json`` is in the form ``benchmarks/program_trace.py``
loads, with scope paths as a v5e trace of the cell showed them (PR 35, read by
hand). In nanoseconds, window 0..100000. One admission: a mixer's q projection
2000 and its expanded attention 1500; an expert layer's router 500, under
``moe/experts`` a gather of 500, and the grouped product of 3000 under NO scope
(the TPU compiler's grouped-matmul kernel comes back as ``ragged-dot-none:``,
its scope path dropped: the metric picks it by that name too), the shared
expert 1000. One decode block of two steps inside the scan's ``while`` (70000), a
step: q 2000, under ``mla/absorb`` a product of 1000 and the bounded loop's
``while`` of 3000 that holds a product of 2000 (so 4000 of own time under the
scope, 6000 under ``/mla/``); router 500, ``moe/experts`` 6000, shared 1000
(7500 under ``/moe/``); the argmax 1000.
"""

import json
import os
import types

import pytest
from benchmark_testlib import HERE, REPO, cpu_device, real_manifest
from latent_moe_testlib import CELL, CONFIG, STANDS_FOR, latent_moe_root, real_workload
from test_benchmark_program_trace import as_trace

from benchmarks import harness, latent_moe_counts, latent_moe_faults

SEED = 2**31 + 29
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
COMPARED = {"token_logit_gap_mean", "wrong_length_requests", "expert_set_mismatch_share"}
COUNTERS_OFF_CHIP = {"router_load_max_over_mean.history", "experts_touched.history",
                     "slot_occupancy.history", "prefill_pad_share.history", "ttft_p95_ms.history"}


def cell_metrics(group: str) -> list:
    return [e["name"] for e in real_manifest()[group] if STANDS_FOR in e.get("workloads", [])]


# ------------------------------------------------------------- the driver
def test_a_sound_run_prints_the_contracts_line(tmp_path, capsys):
    harness.emit(harness.run_cell(CELL, SEED, 0.6, False, root=latent_moe_root(tmp_path),
                                  devices=cpu_device()))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS and line["correct"] is True
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == set(cell_metrics("end_to_end")) | {"setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["compared"]) == COMPARED
    assert {k: v["limit"] for k, v in line["compared"].items()} == real_workload()["limits"]
    assert line["compared"]["expert_set_mismatch_share"]["value"] < 0.02


def test_a_traced_run_off_the_chip_reports_the_programs_counters_alone(tmp_path):
    """No share of a peak without a chip: the engine's counters, the model's
    own counts of choices, and the engine's spans out of the host's profile."""
    result = harness.run_cell(CELL, SEED, 1.2, True, root=latent_moe_root(tmp_path),
                              devices=cpu_device())
    assert result["correct"] and "breakdown" not in result and result["failed"] == 0
    assert COUNTERS_OFF_CHIP <= set(result["metrics"]) <= COUNTERS_OFF_CHIP | {
        "prefill_host_share.history"}
    value = lambda name: result["metrics"][name]["value"]
    assert 1.0 <= value("router_load_max_over_mean.history") <= 8.0
    assert 2.0 <= value("experts_touched.history") <= 8.0  # of 8, at least one slot's 2
    assert 0 < value("prefill_pad_share.history") < 100
    # first tokens given before the traced sub-window closed, not the profiler's stop
    assert 0 < value("ttft_p95_ms.history") < 1000
    assert 0 < result["attempted"] < 24


@pytest.fixture(scope="module")
def sound(tmp_path_factory) -> dict:
    result = harness.run_cell(CELL, SEED, 0.6, False, devices=cpu_device(),
                              root=latent_moe_root(tmp_path_factory.mktemp("sound")))
    assert result["correct"] is True
    return {name: c["value"] for name, c in result["compared"].items()}


@pytest.mark.parametrize("fault, number, least", [
    ("drop_sixth", "expert_set_mismatch_share", 0.01),     # the next layer routes another input
    ("no_renorm", "expert_set_mismatch_share", 0.01),
    ("pad_rows_read", "token_logit_gap_mean", 2e-4),
])
def test_a_planted_fault_moves_the_number_that_reads_it(tmp_path, sound, fault, number, least):
    """In float32 at this size the sound program reads 0 and 2e-5; each of
    these faults reads ten times that or more. (Whether a fault passes the
    real cell's limits, set for bfloat16 at the published widths, is read on
    the chip: ``benchmarks/latent_moe_faults.py``, ``PERF.md``.)"""
    with latent_moe_faults.planted(fault):
        result = harness.run_cell(CELL, SEED, 0.6, False, root=latent_moe_root(tmp_path),
                                  devices=cpu_device())
    assert result["failed"] == 0 and result["compared"]["wrong_length_requests"]["value"] == 0
    assert result["compared"][number]["value"] > max(least, 10 * sound[number])


def test_the_bias_in_the_weights_can_be_planted_and_is_gone_afterwards():
    """``bias_in_weights`` moves a weight by half a percent, under what
    bfloat16 rounding moves it: a reading, not a fault with a verdict. The test
    holds that the patch is what it says and that ``planted`` takes it out."""
    import jax
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import moe

    x, w = jax.random.normal(jax.random.key(0), (16, 64)), jax.random.normal(jax.random.key(1), (64, 8))
    bias = jnp.full(8, 0.25).at[3].set(-0.25)
    idx, sound_w, scores = moe.route_topk_sigmoid(x, w / 8, bias, 2, 2.448)
    with latent_moe_faults.planted("bias_in_weights"):
        idx_f, planted_w, _ = moe.route_topk_sigmoid(x, w / 8, bias, 2, 2.448)
    assert bool((idx == idx_f).all()) and not bool(jnp.allclose(sound_w, planted_w, atol=1e-3))
    chosen = jnp.take_along_axis(scores + bias, idx, axis=-1)
    assert bool(jnp.allclose(planted_w, chosen / chosen.sum(-1, keepdims=True) * 2.448, atol=1e-6))
    assert bool(jnp.allclose(moe.route_topk_sigmoid(x, w / 8, bias, 2, 2.448)[1], sound_w))


# -------------------------------------------------------------- the counts
def test_decode_bytes_and_flops_against_numbers_worked_by_hand():
    with open(os.path.join(REPO, "benchmarks", "configs", "kanana-2-30b-a3b.json")) as fh:
        cfg = json.load(fh)
    c = latent_moe_counts
    expert, attn = 3 * 2048 * 768, 26_345_984
    assert c.expert_params(cfg) == expert == 4_718_592 and c.attention_params(cfg) == attn
    head = 2048 * 128256
    active = 7 * (attn - 512) + 3 * 2048 * 6144 + 6 * (8 * expert + 2048 * 128) + head
    assert c.active_matmul_params(cfg) == active
    # what a token meets outside embedding and head is the "A3B" of the name, less norms and bias
    assert c.decode_flops(cfg, 1500) == 2 * active + 7 * 2 * 32 * (2 * 512 + 64) * 1500
    assert c.prefill_flops(cfg, 1000) == \
        2 * (active - head) * 1000 + 7 * 2 * 32 * (192 + 128) * 1000 * 1000 / 2 + 2 * head
    fixed = 2 * (c.total_params(cfg) - 6 * 128 * expert - head)
    assert c.fixed_stream_bytes(cfg) == fixed and fixed == pytest.approx(1.09e9, rel=0.01)
    # 35 live slots holding 60,000 rows between them, 6 layers x 104 experts touched
    assert c.decode_step_bytes(cfg, 60_000, 624) == fixed + 624 * 2 * expert + 60_000 * 7 * 1152
    assert c.absorb_step_bytes(cfg, 60_000) == 7 * (60_000 * 1152 + 2 * 512 * 32 * 256)
    assert c.expert_choice_flops(cfg, 1280 * 6 * 6) == 2 * expert * 1280 * 36


# ------------------------------------------------------ the recorded trace
COUNTERS = {"traced_steps": 2, "decode_steps": 2, "moe_expert_bytes": 6000.0,
            "moe_group_flops": 17500.0, "mla_absorb_bytes": 2000.0, "decode_bytes": 35000.0,
            "prefill_flops": 3e4, "decode_flops": 2e4, "slot_occupancy": 0.5,
            "prefill_pad_share": 25.0, "router_load_max_over_mean": 1.4, "experts_touched": 104.0,
            "ttft_p95_ms": 300.0}
PEAKS = {"flops_bf16": 1e10, "hbm_bytes_per_s": 1e9}
WORKED = {
    "serve_mfu.history": 100 * 5e4 / (1e-4 * 1e10),
    "decode_hbm_roofline.history": 100 * (35000 / 1e9) / 70000e-9,
    "moe_expert_roofline.history": 100 * (6000 / 1e9) / 12000e-9,   # 6 us of bytes over 12 us
    "moe_group_roofline.history": 100 * (17500 / 1e10) / 3500e-9,
    "mla_absorb_roofline.history": 100 * (2000 / 1e9) / 8000e-9,
    "moe_ms_per_step.history": 15000e-6 / 2,
    "attn_ms_per_step.history": 12000e-6 / 2,
    "router_load_max_over_mean.history": 1.4,
    "experts_touched.history": 104.0,
    "slot_occupancy.history": 50.0,
    "prefill_pad_share.history": 25.0,
    "prefill_host_share.history": 12.0,
    "ttft_p95_ms.history": 300.0,
    "device_idle.history": 100 * (1 - 78500 / 100000),  # the admission's 8500 and the block's 70000
}


def view_of(devices=None, **counters) -> dict:
    with open(os.path.join(HERE, "recorded_latent_moe_trace.json")) as fh:
        rec = json.load(fh)["serve"]
    if devices is not None:
        rec["devices"] = devices(rec["devices"])
    tr = as_trace(rec)
    tr["planes"][0]["lines"].append({"name": "XLA Modules", "events": [
        ["jit__admit_jit(1)", 2000, 8500], ["jit__decode_block_jit(2)", 20000, 70000]]})
    return {"cell": types.SimpleNamespace(config=CONFIG, root=REPO, chips=1),
            "counters": dict(COUNTERS, **counters), "peaks": PEAKS, "trace": tr, "window_s": 1e-4,
            "program_spans": rec["spans"], "program_devices": rec["devices"]}


def read(name: str, view: dict):
    m = real_manifest()
    spec = harness.load_json(REPO, m, "metrics", name)
    return harness.load_module(REPO, m, "readers", spec["reader"]).read(view, spec["params"])


@pytest.mark.parametrize("name", sorted(WORKED))
def test_the_scopes_give_the_hand_worked_numbers(name):
    assert read(name, view_of()) == pytest.approx(WORKED[name])


def test_taking_the_new_scopes_away_silences_the_metrics_that_read_them():
    def moved(devices):
        for d in devices:
            d["ops"] = [[op[0].replace("moe/experts", "moe/kernel").replace("ragged-dot", "gmm")
                         .replace("mla/absorb", "mla/step")] + op[1:] for op in d["ops"]]
        return devices

    view = view_of(moved)
    for name in ("moe_expert_roofline.history", "moe_group_roofline.history",
                 "mla_absorb_roofline.history"):
        assert read(name, view) is None
    assert read("moe_ms_per_step.history", view) == pytest.approx(WORKED["moe_ms_per_step.history"])
    assert read("moe_expert_roofline.history", view_of(moe_expert_bytes=0.0)) is None
    # the grouped product alone, the glue under the scope taken away: 3000 of the 3500
    only_kernel = view_of(lambda ds: [dict(d, ops=[op for op in d["ops"] if "experts/gather" not in op[0]])
                                      for d in ds])
    assert read("moe_group_roofline.history", only_kernel) == pytest.approx(
        100 * (17500 / 1e10) / 3000e-9)
    # a share of a roofline is never clipped
    assert read("mla_absorb_roofline.history", view_of(mla_absorb_bytes=2e5)) == pytest.approx(2500.0)


def test_every_metric_of_the_cell_comes_through_the_harness():
    m = real_manifest()
    cell = harness.Cell(REPO, m, STANDS_FOR)
    names = cell_metrics("per_layer")
    assert cell.metric_names("per_layer") == names and set(names) == set(WORKED)
    assert cell.metric_names("end_to_end") == cell_metrics("end_to_end") + ["setup_s"]
    view = view_of()
    view["cell"] = cell
    got = harness.per_layer_metrics(cell, view)
    assert list(got) == names and all(v["value"] > 0 for v in got.values())
    units = {e["name"]: e["unit"] for e in m["per_layer"]}
    assert all(got[n]["unit"] == units[n] for n in names)


def test_the_driver_reads_load_and_touched_experts_from_the_programs_counts():
    driver = harness.load_module(REPO, real_manifest(), "drivers", "latent_moe_serve")
    step = {"sum": [30, 10, 0, 0], "events": 10, "nonzero_mean": 1.5}
    got = driver.routing_counters({"prefill": {"layer_1/moe/expert_choices": {"sum": [10, 10, 10, 10]}},
                                   "decode": {"layer_1/moe/expert_choices": step}})
    assert got == {"router_load_max_over_mean": 40 / 20, "experts_touched": 1.5}
    assert driver.routing_counters({"prefill": {}, "decode": {}}) == {
        "router_load_max_over_mean": None, "experts_touched": None}
