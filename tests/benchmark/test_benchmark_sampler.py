"""``sample_ms_per_step.chat``: the sampler's own device time a decode step,
read by ``decode_scope_time`` (``scope_time`` over the window's decode steps:
``lm_serve`` counts ``decode_steps`` and no ``traced_steps``) from the scope ``sample`` that ``sample_tokens_dynamic``
writes (PR 31), and from where a program of before that scope put the same
operations.

The view is built by hand, in nanoseconds, window 0..100000, two decode steps
inside the scan's ``while``. A step: the head 6000; the sampler's conditional
1500, which holds its branch's one reduction of 1000 (so 500 of own time at
the conditional and 1000 under the branch: 1500 under ``/sample/``); the
per-row key folding 200 under the step's anonymous ``vmap()``. One admission
samples too (400 under ``jit(_admit_jit)``) and is no part of a decode step.
"""

import json
import os

import pytest
from benchmark_testlib import HERE, REPO, real_manifest
from test_benchmark_program_trace import as_trace, read as read_metric

from benchmarks import harness

NAME = "sample_ms_per_step.chat"
STEP = "jit(_decode_block_jit)/while/body/closed_call/"
SCOPED = [
    ["jit(_admit_jit)/sample/cond:", 1000, 400],
    ["jit(_decode_block_jit)/while:", 20000, 70000],
    [STEP + "vmap(TransformerLM)/lm_head/dot_general:", 22000, 6000],
    [STEP + "vmap()/vmap(jit(_threefry_fold_in))/xor:", 28000, 200],
    [STEP + "sample/cond:", 29000, 1500],
    [STEP + "sample/cond/branch_0_fun/reduce:", 29200, 1000],
    [STEP + "vmap(TransformerLM)/lm_head/dot_general:", 52000, 6000],
    [STEP + "vmap()/vmap(jit(_threefry_fold_in))/xor:", 58000, 200],
    [STEP + "sample/cond:", 59000, 1500],
    [STEP + "sample/cond/branch_0_fun/reduce:", 59200, 1000],
]


def view_of(ops) -> dict:
    rec = {"devices": [{"name": "/device:TPU:0", "ops": ops}],
           "spans": [["bench:window", 0, 100000, "python", {}]]}
    return {"cell": None, "counters": {"decode_steps": 2}, "peaks": None,
            "trace": as_trace(rec), "window_s": 1e-4,
            "program_spans": rec["spans"], "program_devices": rec["devices"]}


def read(view: dict):
    return read_metric(NAME, view)


def test_the_entry_and_its_file():
    m = real_manifest()
    (entry,) = [e for e in m["per_layer"] if e["name"] == NAME]
    assert entry == {"name": NAME, "unit": "ms", "better": "lower", "source": "device_trace",
                     "layer": "model step", "moves": "tpot_p95_ms",
                     "workloads": ["gpt2l-serve-chat"]}
    spec = harness.load_json(REPO, m, "metrics", NAME)
    assert spec["reader"] == "decode_scope_time" and spec["layer"] == entry["layer"]
    assert NAME in harness.Cell(REPO, m, "gpt2l-serve-chat").metric_names("per_layer")
    reports = {e["name"]: e.get("workloads") for e in m["end_to_end"]}
    assert "gpt2l-serve-chat" in reports[entry["moves"]]


def test_every_per_layer_entry_still_has_its_file():
    m = real_manifest()
    for entry in m["per_layer"]:
        spec = harness.load_json(REPO, m, "metrics", entry["name"])
        assert hasattr(harness.load_module(REPO, m, "readers", spec["reader"]), "read")


def test_the_scope_gives_the_hand_worked_number():
    # (500 + 1000 + 200) ns a step, the admission's 400 left out
    assert read(view_of(SCOPED)) == pytest.approx(1700e-6)
    m = real_manifest()
    cell = harness.Cell(REPO, m, "gpt2l-serve-chat")
    line = harness.per_layer_metrics(cell, dict(view_of(SCOPED), cell=cell))
    assert line[NAME] == {"value": pytest.approx(1700e-6), "unit": "ms"}


def test_a_program_without_the_scope_is_read_where_its_sampler_lay():
    """PR 30's program: the sort, the reductions and the draw lie under the
    step's ``vmap()`` and ``vmap(jit(...))``, never under the model's."""
    before = [op for op in SCOPED if "/sample/" not in op[0]] + [
        [STEP + "vmap(jit(sort))/sort:", 30000, 5000],
        [STEP + "vmap()/reduce:", 35000, 300],
        [STEP + "vmap(jit(_gumbel))/jit(_uniform)/vmap()/xor:", 35300, 100],
        [STEP + "vmap(jit(sort))/sort:", 60000, 5000],
    ]
    assert read(view_of(before)) == pytest.approx((2 * 200 + 2 * 5000 + 300 + 100) / 2 * 1e-6)
    with open(os.path.join(HERE, "recorded_hybrid_trace.json")) as fh:
        recorded = json.load(fh)["serve"]["devices"][0]["ops"]
    assert read(view_of(recorded)) == pytest.approx(5000e-6)  # its sort, a step


def test_no_sampler_in_the_trace_gives_nothing_never_zero():
    model_only = [op for op in SCOPED if "TransformerLM" in op[0] or op[0].endswith("while:")]
    assert read(view_of(model_only)) is None
    admission_only = model_only + [SCOPED[0]]
    assert read(view_of(admission_only)) is None
    no_trace = view_of(SCOPED)
    no_trace["trace"] = no_trace["program_devices"] = None
    assert read(no_trace) is None
    no_step = view_of(SCOPED)
    no_step["counters"] = {"decode_steps": 0, "traced_steps": 5}  # the decode steps count, nothing else
    assert read(no_step) is None
