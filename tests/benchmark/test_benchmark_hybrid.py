"""The ``olmo-hybrid-7b`` configuration's benchmark files at a tiny size on
the CPU: the ``hybrid_serve`` driver's result line with and without a trace,
``hybrid_counts`` against numbers worked by hand, and the readers of the
recurrence's scopes on a recorded trace. (The faults and the int8 control
have a file of their own: each is another session.)

``recorded_hybrid_trace.json`` is in the form ``benchmarks/program_trace.py``
loads, with scope paths as a v5e trace of the cell showed them (PR 30, read by
hand). In nanoseconds, window 0..100000. One admission: a linear layer's q
projection 2000, convolution 500, under ``gdn/chunk`` the inverse's products 4000
and a ``while`` of 2000 that holds a product of 1000 (so 6000 of own time
under the scope), gated norm 500; a full layer's q 1500. One decode block of
two steps inside the scan's ``while`` (70000, own 6000), a step: linear q 3000,
convolution 500, ``gdn/recur`` 5000, gated norm 500, o 4000 (13000 under
``/gdn/``), MLP 6000, cached attention 8000, the sort 5000.
"""

import json
import os
import types

import pytest
from benchmark_testlib import HERE, REPO, cpu_device, real_manifest
from hybrid_testlib import CELL, CONFIG, STANDS_FOR, hybrid_root, real_workload
from test_benchmark_program_trace import as_trace

from benchmarks import harness, hybrid_counts

SEED = 2**31 + 13
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]
GEN = ["serve_mfu.gen", "decode_hbm_roofline.gen", "device_idle.gen", "gdn_recur_roofline.gen",
       "gdn_chunk_roofline.gen", "gdn_ms_per_step.gen", "attn_ms_per_step.gen",
       "prefill_pad_share.gen", "prefill_host_share.gen", "slot_occupancy.gen"]


def real_config() -> dict:
    with open(os.path.join(REPO, "benchmarks", "configs", "olmo-hybrid-7b.json")) as fh:
        return json.load(fh)


# ------------------------------------------------------------- the driver
def test_a_sound_run_prints_the_contracts_line(tmp_path, capsys):
    harness.emit(harness.run_cell(CELL, SEED, 0.6, False, root=hybrid_root(tmp_path),
                                  devices=cpu_device()))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS and line["correct"] is True
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["compared"]) == {"token_logit_gap_mean", "wrong_length_requests"}
    assert line["compared"]["token_logit_gap_mean"]["limit"] == \
        real_workload()["limits"]["token_logit_gap_mean"]


def test_a_traced_run_off_the_chip_reports_the_engines_counters_alone(tmp_path):
    """No share of a peak without a chip: the engine's counters, and its spans
    out of the host's profile. The traced run stops offering when its traced
    sub-window closes, so it attempts fewer than the plan holds."""
    # 24 requests over 1.2 s; the traced 0.3 s close at 0.6 s, half of them not yet due
    result = harness.run_cell(CELL, SEED, 1.2, True, root=hybrid_root(tmp_path),
                              devices=cpu_device())
    assert result["correct"] and "breakdown" not in result and result["failed"] == 0
    # the spans' metric is there when an admission fell into the traced 0.3 s
    assert {"prefill_pad_share.gen", "slot_occupancy.gen"} <= set(result["metrics"]) <= {
        "prefill_pad_share.gen", "slot_occupancy.gen", "prefill_host_share.gen"}
    assert 0 < result["metrics"]["prefill_pad_share.gen"]["value"] < 100
    assert 0 < result["metrics"]["slot_occupancy.gen"]["value"] <= 100
    assert 0 < result["attempted"] < 24


# -------------------------------------------------------------- the counts
def test_parameters_of_the_published_model_and_of_the_cut():
    cut = real_config()
    whole = dict(cut, layer_types=cut["layer_types"] * 2)
    assert hybrid_counts.total_params(whole) == pytest.approx(7.43e9, rel=0.005)
    assert hybrid_counts.total_params(cut) == pytest.approx(4.10e9, rel=0.005)
    # by hand: MLP 3 x 3840 x 11008, full mixer 4 x 3840^2, linear mixer 6 x 3840^2 + 2 x 3840 x 30
    assert hybrid_counts.mlp_params(cut) == 126_812_160
    assert hybrid_counts.layer_matmul_params(cut, "full_attention") == 126_812_160 + 58_982_400
    assert hybrid_counts.layer_matmul_params(cut, "linear_attention") == 126_812_160 + 88_473_600 + 230_400
    assert hybrid_counts.linear_mixer_small_params(cut) == 4 * 11520 + 60 + 192
    # the reference's tree holds exactly what is counted
    import jax

    from benchmarks.reference import olmo_hybrid

    tree = jax.eval_shape(lambda k: olmo_hybrid.make_params(k, CONFIG), jax.random.key(0))
    assert sum(a.size for a in jax.tree.leaves(tree)) == hybrid_counts.total_params(CONFIG)


def test_decode_bytes_and_flops_against_numbers_worked_by_hand():
    cfg = real_config()
    state, row, tail = 30 * 192 * 96 * 4, 2 * 3840 * 2, 4 * 11520 * 2
    assert (state, row, tail) == (2_211_840, 15_360, 92_160)
    assert hybrid_counts.state_bytes(cfg) == state and hybrid_counts.kv_row_bytes(cfg) == 4 * row
    weights = 2 * (hybrid_counts.matmul_params(cfg) + hybrid_counts.small_params(cfg))
    assert weights == pytest.approx(7.43e9, rel=0.005)
    # 20 live slots holding 9000 rows between them
    assert hybrid_counts.decode_step_bytes(cfg, 9000, 20) == \
        weights + 9000 * row * 4 + 20 * 12 * (2 * state + tail)
    assert hybrid_counts.state_step_bytes(cfg, 20) == 20 * 12 * 2 * state
    rule, conv = 6 * 96 * 192 * 30, 2 * 4 * 11520
    assert hybrid_counts.rule_flops_per_token(cfg) == rule
    matmuls = hybrid_counts.matmul_params(cfg)
    assert matmuls == 12 * 215_516_160 + 4 * 185_794_560 + 3840 * 100352
    assert hybrid_counts.decode_flops(cfg, 500) == \
        2 * matmuls + 4 * (2 * 2 * 500 * 3840) + 12 * (rule + conv)
    head = 3840 * 100352
    assert hybrid_counts.prefill_flops(cfg, 300) == \
        2 * (matmuls - head) * 300 + 4 * (2 * 300 * 300 * 3840) + 12 * (rule + conv) * 300 + 2 * head
    assert hybrid_counts.rule_prefill_flops(cfg, 300) == 12 * rule * 300


# ------------------------------------------------------ the recorded trace
COUNTERS = {"traced_steps": 2, "decode_steps": 2, "gdn_state_bytes": 2000.0,
            "gdn_chunk_flops": 12000.0, "decode_bytes": 35000.0,
            "prefill_flops": 3e4, "decode_flops": 2e4}
PEAKS = {"flops_bf16": 1e10, "hbm_bytes_per_s": 1e9}


def view_of(devices=None, **counters) -> dict:
    with open(os.path.join(HERE, "recorded_hybrid_trace.json")) as fh:
        rec = json.load(fh)["serve"]
    if devices is not None:
        rec["devices"] = devices(rec["devices"])
    tr = as_trace(rec)
    tr["planes"][0]["lines"].append({"name": "XLA Modules", "events": [
        ["jit__admit_jit(1)", 2000, 10500], ["jit__decode_block_jit(2)", 20000, 70000]]})
    return {"cell": types.SimpleNamespace(config=CONFIG, root=REPO, chips=1),
            "counters": dict(COUNTERS, **counters), "peaks": PEAKS, "trace": tr, "window_s": 1e-4,
            "program_spans": rec["spans"], "program_devices": rec["devices"]}


def read(name: str, view: dict):
    m = real_manifest()
    spec = harness.load_json(REPO, m, "metrics", name)
    return harness.load_module(REPO, m, "readers", spec["reader"]).read(view, spec["params"])


def without(pattern: str, replacement=None):
    """The recorded operations with every path holding ``pattern`` taken away
    (or, with ``replacement``, moved under another scope)."""
    def change(devices):
        for d in devices:
            if replacement is None:
                d["ops"] = [op for op in d["ops"] if pattern not in op[0]]
            else:
                d["ops"] = [[op[0].replace(pattern, replacement)] + op[1:] for op in d["ops"]]
        return devices
    return change


@pytest.mark.parametrize("name, value", [
    ("gdn_ms_per_step.gen", 26000e-6 / 2),                      # 13000 a step under /gdn/
    ("attn_ms_per_step.gen", 16000e-6 / 2),
    ("gdn_recur_roofline.gen", 100 * (2000 / 1e9) / 10000e-9),  # 2 us of bytes over 10 us
    ("gdn_chunk_roofline.gen", 100 * (12000 / 1e10) / 6000e-9),
    ("decode_hbm_roofline.gen", 100 * (35000 / 1e9) / 70000e-9),
    ("serve_mfu.gen", 100 * 5e4 / (1e-4 * 1e10)),
    ("prefill_host_share.gen", 12.0),
])
def test_the_scopes_give_the_hand_worked_numbers(name, value):
    assert read(name, view_of()) == pytest.approx(value)


def test_taking_the_gdn_paths_away_moves_the_metrics_that_read_them():
    """A rule that leaves the ``gdn/recur`` scope (a kernel under another
    name) silences its roofline and shortens the mixer's time; a mixer under
    another module name silences both; attention's time stays."""
    no_rule = view_of(without("/gdn/gdn/recur/"))
    assert read("gdn_recur_roofline.gen", no_rule) is None
    assert read("gdn_ms_per_step.gen", no_rule) == pytest.approx(16000e-6 / 2)
    renamed = view_of(without("gdn", "mixer"))
    assert read("gdn_ms_per_step.gen", renamed) is None
    assert read("gdn_recur_roofline.gen", renamed) is None
    assert read("gdn_chunk_roofline.gen", renamed) is None
    assert read("attn_ms_per_step.gen", renamed) == pytest.approx(16000e-6 / 2)


def test_the_new_reader_gives_nothing_where_there_is_nothing_to_read():
    assert read("gdn_recur_roofline.gen", view_of(gdn_state_bytes=0.0)) is None
    view = view_of()
    view["program_devices"] = None  # a program that wrote no scope paths, or no trace
    assert read("gdn_recur_roofline.gen", view) is None and read("gdn_ms_per_step.gen", view) is None
    # a share of a roofline is never clipped
    assert read("gdn_recur_roofline.gen", view_of(gdn_state_bytes=2e5)) == pytest.approx(2000.0)


def test_every_metric_of_the_cell_comes_through_the_harness():
    m = real_manifest()
    cell = harness.Cell(REPO, m, STANDS_FOR)
    assert cell.metric_names("per_layer") == GEN
    assert cell.metric_names("end_to_end") == ["ttft_p95_ms", "tpot_p95_ms", "setup_s"]
    view = view_of(slot_occupancy=0.5, prefill_pad_share=25.0)
    view["cell"] = cell
    got = harness.per_layer_metrics(cell, view)
    assert list(got) == GEN and all(v["value"] > 0 for v in got.values())
    units = {e["name"]: e["unit"] for e in m["per_layer"]}
    assert all(got[n]["unit"] == units[n] for n in GEN)
