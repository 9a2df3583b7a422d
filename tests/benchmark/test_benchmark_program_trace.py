"""The readers of what the PROGRAM wrote into a trace (``program_span``,
``scope_time``, ``benchmarks/program_trace.py``) give the hand-worked numbers
on the small recorded trace beside this file, and nothing where there is
nothing to read.

``recorded_program_trace.json`` is in the form ``program_trace`` loads: host
spans with their attributes, device operations with the scope paths a v5e
trace showed. In nanoseconds, window 0..100000 in both halves.

``serve``: the engine runs two rounds. Round one (1000-48000) admits two
requests (prefills 3500-11500 and 12000-20500, waits 400 and 1000 us),
decodes (22000-45000, fetch ends 44500) and emits (45500-47500); round two
(50000-90000) decodes (dispatch at 51500) and emits (86200-89000). The device
runs 3000-3200, 6500-10500, 14000-19500, 24500-30000, 30500-44000,
53500-85000 and 88000-89000: busy 61200. Its idle gaps by the innermost span
over their middle: 0-3000 ``serve.step`` alone and 44000-53500 and
89000-100000 under nothing (23500, unattributed), 3200-6500 and 10500-14000
``serve.prefill`` (6800), 19500-24500 ``serve.decode`` and 85000-88000
``serve.emit`` (8000), and a pause of 500 between two operations.

``train``: two steps' operations, forward 29000 (q 5000, reshape 1000, flash
kernel 8000 nested in a pathless ``while`` of 14000, MLP 8000, head 5000, loss
2000), backward 49000 (head 10000, MLP 15000, flash 16000, a cast under
``attn`` 4000, o 4000), neither 13000 (the while's own 6000, Adam 6000, a copy
1000): busy 91000.
"""

import json
import os
import re
import types

import pytest
from benchmark_testlib import HERE, REPO, real_manifest

from benchmarks import counts, harness, program_trace, trace

NEW = ["queue_wait_mean_ms.chat", "prefill_host_share.chat", "between_blocks_max_ms.chat",
       "idle_prefill.chat", "idle_decode.chat", "idle_unattributed.chat",
       "fwd_ms_per_step.train", "bwd_ms_per_step.train", "update_ms_per_step.train",
       "head_loss_ms_per_step.train", "attn_core_fwd_roofline.train",
       "attn_core_bwd_roofline.train"]
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TINY = {"n_embd": 8, "n_layer": 1}           # attention: 6 x 1 x 16^2 x 8 = 12288 FLOPs a step
COUNTERS = {"batch": 1, "seq": 16, "traced_steps": 2}
PEAKS = {"flops_bf16": 1e10}


def recorded(half: str) -> dict:
    with open(os.path.join(HERE, "recorded_program_trace.json")) as fh:
        return json.load(fh)[half]


def as_trace(rec: dict) -> dict:
    """The recorded half in ``trace.load_xplane``'s form: what the harness
    hands the readers as ``view["trace"]``."""
    planes = [{"name": d["name"], "lines": [{"name": trace.OPS_LINE, "events": d["ops"]}]}
              for d in rec["devices"]]
    window = [s[:3] for s in rec["spans"] if s[0] == trace.WINDOW_SPAN]
    return {"planes": planes + [{"name": "/host:CPU",
                                 "lines": [{"name": "python", "events": window}]}]}


def view_of(half: str, *, on_chip: bool = True, cell=None, **changes) -> dict:
    rec = recorded(half)
    rec.update(changes)
    cell = cell or types.SimpleNamespace(config=TINY, root=REPO)
    return {"cell": cell, "counters": dict(COUNTERS), "peaks": PEAKS if on_chip else None,
            "trace": as_trace(rec) if on_chip else None, "window_s": 1e-4,
            "program_spans": rec["spans"], "program_devices": rec["devices"] if on_chip else None}


def read(name: str, view: dict):
    m = real_manifest()
    spec = harness.load_json(REPO, m, "metrics", name)
    return harness.load_module(REPO, m, "readers", spec["reader"]).read(view, spec["params"])


# ------------------------------------------------------------ hand-worked
@pytest.mark.parametrize("name, value", [
    ("queue_wait_mean_ms.chat", 0.7),          # (400 + 1000) us / 2
    ("prefill_host_share.chat", 16.5),         # 8000 + 8500 of 100000
    ("between_blocks_max_ms.chat", 0.007),     # fetch ends 44500, next dispatch 51500
    ("idle_prefill.chat", 6.8),
    ("idle_decode.chat", 8.0),
    ("idle_unattributed.chat", 23.5),
])
def test_serving_spans_give_the_hand_worked_numbers(name, value):
    assert read(name, view_of("serve")) == pytest.approx(value)


@pytest.mark.parametrize("name, value", [
    ("fwd_ms_per_step.train", 29000e-6 / 2),
    ("bwd_ms_per_step.train", 49000e-6 / 2),
    ("update_ms_per_step.train", 13000e-6 / 2),
    ("head_loss_ms_per_step.train", 17000e-6 / 2),            # 5000 + 2000 + 10000
    # least time for a third of 2 x 12288 FLOPs at 1e10/s over reshape + kernel
    ("attn_core_fwd_roofline.train", 100 * (24576 / 3 / 1e10) / 9000e-9),
    ("attn_core_bwd_roofline.train", 100 * (24576 * 2 / 3 / 1e10) / 20000e-9),  # kernel + cast
])
def test_scope_paths_give_the_hand_worked_numbers(name, value):
    assert counts.attention_train_flops(TINY, 1, 16) == 12288
    assert read(name, view_of("train")) == pytest.approx(value)


def test_the_parts_sum_to_what_the_outside_readers_give():
    serve = view_of("serve")
    busy_s, window_s = trace.busy_and_window_s(serve["trace"])
    idle = 100.0 * (1.0 - busy_s / window_s)
    parts = [read(n, serve) for n in NEW[3:6]]
    pauses = 100.0 * 500 / 100000
    assert idle == pytest.approx(38.8) and sum(parts) + pauses == pytest.approx(idle)
    named = dict(trace.breakdown(serve["trace"])["idle_gaps"])
    assert named["device:between operations"] == pytest.approx(500e-9)
    train = view_of("train")
    busy_s, _ = trace.busy_and_window_s(train["trace"])
    steps = COUNTERS["traced_steps"]
    assert sum(read(n, train) for n in NEW[6:9]) * steps / 1e3 == pytest.approx(busy_s)


def test_every_metric_of_a_cell_comes_through_the_harness():
    m = real_manifest()
    for half, cell_name, mine in (("serve", "gpt2l-serve-chat", NEW[:6]),
                                  ("train", "gpt2m-train-1k", NEW[6:])):
        cell = harness.Cell(REPO, m, cell_name)
        view = view_of(half, cell=cell)
        view["counters"].update(batch=8, seq=1024)
        got = harness.per_layer_metrics(cell, view)
        assert set(mine) <= set(got) and all(got[n]["value"] > 0 for n in mine)
        units = {e["name"]: e["unit"] for e in m["per_layer"]}
        assert all(got[n]["unit"] == units[n] for n in mine)


# -------------------------------------------------------- nothing to read
@pytest.mark.parametrize("name", NEW)
def test_nothing_to_read_gives_nothing_never_zero(name):
    half = "serve" if name.endswith(".chat") else "train"
    # a program that writes no span and no path (as before PR 26)
    bare = view_of(half, spans=[s for s in recorded(half)["spans"] if s[0] == trace.WINDOW_SPAN],
                   devices=[{"name": "/device:TPU:0",
                             "ops": [["", *op[1:]] for op in recorded(half)["devices"][0]["ops"]]}])
    assert read(name, bare) is None
    # no profile on disk at all
    empty = view_of(half)
    empty.update(program_spans=None, program_devices=None)
    assert read(name, empty) is None
    # off a chip: no device trace, so nothing from the device side
    off = read(name, view_of(half, on_chip=False))
    host_side = NEW[:3]
    assert (off is not None) == (name in host_side)


def test_a_round_that_dispatched_nothing_is_no_stretch_between_blocks():
    spans = recorded("serve")["spans"] + [["serve.step", 48200, 1000, "python", {}]]
    view = view_of("serve", spans=sorted(spans, key=lambda s: s[1]))
    assert read("between_blocks_max_ms.chat", view) is None


def test_no_operation_under_the_scope_gives_nothing():
    ops = [op for op in recorded("train")["devices"][0]["ops"] if "/attn/" not in op[0]]
    view = view_of("train", devices=[{"name": "/device:TPU:0", "ops": ops}])
    assert read("attn_core_fwd_roofline.train", view) is None
    assert read("fwd_ms_per_step.train", view) is not None


# --------------------------------------------------------------- the file
def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def test_each_operation_takes_the_scope_path_of_its_own_metadata_in_an_xplane_file():
    stat_name = lambda key, name: _field(5, _field(1, key) + _field(2, _field(1, key) + _field(2, name)))
    event_meta = lambda key, name, stats: _field(4, _field(1, key) + _field(
        2, _field(1, key) + _field(2, name) + b"".join(_field(5, s) for s in stats)))
    event = lambda key, offset_ps, duration_ps: _field(
        4, _field(1, key) + _field(2, offset_ps) + _field(3, duration_ps)
        + _field(4, _field(1, 4) + _field(3, offset_ps)))          # an event stat: skipped
    line = lambda name, *events: _field(3, _field(1, 7) + _field(2, name) + _field(3, 1000)
                                        + b"".join(events))
    path = "jit(step)/transpose(jvp(TransformerLM))/block_7/attn/pallas_call:"
    device = (_field(1, 3) + _field(2, "/device:TPU:0")
              + line("XLA Modules", event(10, 0, 9_000_000))     # another line: skipped whole
              + line("XLA Ops", event(10, 2_000_999, 3_000_000), event(11, 5_500_000, 1_000_000),
                     event(13, 7_000_000, 1_000_000), event(12, 8_000_000, 500_000))
              + stat_name(1, "hlo_category") + stat_name(2, "tf_op") + stat_name(3, "jit(step)/add:")
              + event_meta(10, "%attn.7 = custom-call()", [_field(1, 1) + _field(5, "custom-call"),
                                                           _field(1, 2) + _field(5, path)])
              + event_meta(11, "%add.1 = add()", [_field(1, 2) + _field(7, 3)])  # a reference to a name
              # a second program's operation of the same HLO text, under another path
              + event_meta(13, "%add.1 = add()", [_field(1, 2) + _field(5, "jit(other)/add:")])
              + event_meta(12, "%copy-done.2 = copy-done()", [_field(1, 1) + _field(5, "copy-done")])
              + _field(6, _field(1, 1) + b"\x11" + b"\0" * 8))  # a plane stat with a double
    host = (_field(2, "/host:CPU") + stat_name(1, "tf_op")
            + event_meta(1, "serve.prefill", [_field(1, 1) + _field(5, "not a device")])
            + line("XLA Ops", event(1, 0, 1000)))
    pathless = _field(2, "/device:TPU:1") + line("XLA Ops", event(10, 0, 1000))
    got = program_trace.devices_of(_field(1, device) + _field(1, host) + _field(1, pathless))
    assert got == [{"name": "/device:TPU:0", "ops": [
        [path, 1000 + 2000, 3000], ["jit(step)/add:", 1000 + 5500, 1000],
        ["jit(other)/add:", 1000 + 7000, 1000], ["", 1000 + 8000, 500]]}]


# ------------------------------------------------- what would go unnoticed
SPANS = ["serve.step", "serve.prefill", "serve.decode", "serve.decode.dispatch",
         "serve.decode.fetch", "serve.emit"]


@pytest.mark.parametrize("name", SPANS)
def test_every_span_the_engine_writes_moves_a_metric_when_it_is_taken_away(name):
    spans = recorded("serve")["spans"]
    if name == "serve.step":
        # what tells a stretch in which the engine went idle from a stall: a
        # round that only evicts lies between the two blocks
        spans = sorted(spans + [["serve.step", 48200, 1000, "python", {}]], key=lambda s: s[1])
    before = {n: read(n, view_of("serve", spans=spans)) for n in NEW[:6]}
    without = [s for s in spans if s[0] != name]
    after = {n: read(n, view_of("serve", spans=without)) for n in NEW[:6]}
    assert before != after, f"no metric reads {name}"


def test_the_engine_writes_these_spans_and_no_other():
    written = set()
    for module in ("engine", "cache"):
        with open(os.path.join(REPO, "distributed_ml_pytorch_tpu", "serving", module + ".py")) as fh:
            written |= set(re.findall(r'\bspan\("([^"]+)"', fh.read()))
    assert written == set(SPANS)
    assert {s[0] for s in recorded("serve")["spans"]} == set(SPANS) | {trace.WINDOW_SPAN}


@pytest.mark.parametrize("rename", [
    lambda p: p.replace("jvp(TransformerLM)", "jvp(GPT)"),                       # another class
    lambda p: p.replace("jvp(TransformerLM)/", "jvp(jit(apply))/TransformerLM/"),  # wrapped in a jit
    lambda p: p.replace("jvp()/reduce_sum:", "jvp(jit(take_along_axis))/gather:"),
    lambda p: p.replace("jvp()/reduce_sum:", "jvp()/broadcast_in_dim;jit(step)/jvp()/reshape:"),
], ids=["renamed", "jitted", "library_function", "two_paths"])
def test_head_and_loss_are_picked_by_where_they_lie_not_by_the_models_name(rename):
    ops = [[rename(op[0]), *op[1:]] for op in recorded("train")["devices"][0]["ops"]]
    view = view_of("train", devices=[{"name": "/device:TPU:0", "ops": ops}])
    assert read("head_loss_ms_per_step.train", view) == pytest.approx(17000e-6 / 2)


# ------------------------------------------------------------ the entries
@pytest.mark.parametrize("name", NEW)
def test_each_added_entry_names_files_that_exist_and_a_metric_its_cell_reports(name):
    m = real_manifest()
    (entry,) = [e for e in m["per_layer"] if e["name"] == name]
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["source"] in SOURCES
    spec = harness.load_json(REPO, m, "metrics", name)
    assert spec["layer"] == entry["layer"]
    reader = harness.load_module(REPO, m, "readers", spec["reader"])
    assert hasattr(reader, "read")
    assert entry["source"] == {"program_span": "program_span", "scope_time": "device_trace"}[spec["reader"]]
    reports = {e["name"]: e.get("workloads") for e in m["end_to_end"]}
    for cell in entry["workloads"]:
        assert cell in reports[entry["moves"]]
        assert name in harness.Cell(REPO, m, cell).metric_names("per_layer")


def test_the_twelve_follow_what_the_parent_had_and_none_is_named_twice():
    names = [e["name"] for e in real_manifest()["per_layer"]]
    assert len(set(names)) == len(names)
    mine = [n for n in names if n in NEW]
    assert mine == NEW  # all twelve, in the order of the issue
    had = ["train_mfu", "flash_attn_roofline", "device_idle.train", "slot_occupancy.chat",
           "generator_late_p95_ms", "serve_mfu.chat", "decode_hbm_roofline.chat",
           "device_idle.chat"]
    assert names[:names.index(NEW[0])] == had  # unchanged, and before them
