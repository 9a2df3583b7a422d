"""Timeline analyzer over canned multi-member flight dumps (ISSUE 12).

Fixtures live under ``tests/data/timeline/`` — four dumps covering the
contract surface: a stage with a proper attribution summary, a death dump
with spans only (fallback summation), a TORN dump (truncated line mid-
crash), and an unknown-plane dump that must be surfaced, not dropped.
Also hosts the ``timeline.check_bubble_attribution`` schema gate tests (the
check ``timeline.main`` runs on the attribution it is about to print).
"""

import json
import os

import pytest

from distributed_ml_pytorch_tpu.analysis import timeline

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURES = os.path.join(HERE, "data", "timeline")


@pytest.fixture(scope="module")
def report():
    return timeline.analyze(FIXTURES)


def test_loads_all_dumps_and_counts_torn_lines(report):
    assert report["n_dumps"] == 4
    # the torn fixture has exactly 2 unparseable lines (truncated json +
    # garbage); they are tolerated AND counted, never fatal
    assert report["torn_lines"] == 2
    # the valid spans AROUND the tear still load
    (d,) = [d for d in timeline.load_dir(FIXTURES)
            if d["member"] == "driver"]
    assert len(d["events"]) == 1 and len(d["spans"]) == 1
    # ring-drop accounting propagates from the meta headers
    assert report["ring_dropped_spans"] == 1


def test_unknown_plane_surfaced_not_dropped(report):
    assert report["unknown_planes"] == ["quantum"]
    mystery = [m for m in report["members"] if m["member"] == "mystery"]
    assert mystery, "unknown-plane member must still be attributed"
    # its states are attributed generically (4s of spans over 4s wall)
    assert mystery[0]["accounted"] == pytest.approx(1.0, abs=1e-6)


def test_attribution_prefers_summary_and_sums_to_one(report):
    s0 = next(m for m in report["members"] if m["member"] == "stage0")
    # the attribution event's exact numbers win over span summation
    assert s0["wall_s"] == 10.0
    assert s0["fractions"]["compute"] == pytest.approx(0.4)
    assert s0["fractions"]["wait-grad"] == pytest.approx(0.3)
    assert s0["accounted"] == pytest.approx(1.0, abs=1e-6)
    assert s0["unknown_states"] == []


def test_attribution_fallback_sums_spans_for_death_dump(report):
    s1 = next(m for m in report["members"] if m["member"] == "stage1")
    assert s1["reason"] == "death"
    # spans cover 1.5e9..11.5e9 ns -> 10 s wall, fully accounted
    assert s1["wall_s"] == pytest.approx(10.0)
    assert s1["seconds"]["compute"] == pytest.approx(4.5)
    assert s1["seconds"]["wire-blocked"] == pytest.approx(2.0)
    assert s1["accounted"] == pytest.approx(1.0, abs=1e-6)


def test_bubble_aggregates_stage_members(report):
    b = report["bubble_attribution"]
    assert b["stages"] == 2
    assert b["stage_seconds"] == pytest.approx(20.0)
    # compute = (4.0 + 4.5) / 20
    assert b["fractions"]["compute"] == pytest.approx(0.425)
    assert b["bubble_fraction"] == pytest.approx(0.575)
    assert b["wait_fraction"] + b["fractions"]["compute"] == pytest.approx(
        1.0, abs=1e-3)


def test_wire_attribution_from_wire_stats_events(report):
    w = report["wire_attribution"]
    assert w["members_reporting"] == 1
    assert w["sent"] == 100 and w["retries"] == 5
    assert w["retransmit_share"] == pytest.approx(0.05)
    assert w["ack_frames"] == 25
    assert w["acks_per_data_frame"] == pytest.approx(25 / 95)
    assert w["credit_block_s"] == pytest.approx(0.25)


def test_correlation_journeys_cross_members(report):
    j = report["journeys"]
    # corr 7 and 8 each appear on multiple members (driver + stages)
    assert j["cross_member_units"] >= 2
    longest = j["longest"][0]
    assert len(longest["members"]) >= 2


def test_render_is_human_readable(report):
    text = timeline.render(report)
    assert "bubble" in text and "stage0" in text
    assert "unknown plane" in text  # the WARNING line for 'quantum'
    assert "torn" in text


def test_cli_timeline_subcommand(capsys):
    from distributed_ml_pytorch_tpu.analysis import cli

    assert cli.main(["timeline", FIXTURES]) == 0
    out = capsys.readouterr().out
    assert "bubble" in out
    assert cli.main(["timeline", FIXTURES, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["n_dumps"] == 4


def test_missing_dir_raises_and_empty_dir_exits_nonzero(tmp_path):
    with pytest.raises(FileNotFoundError):
        timeline.analyze(str(tmp_path / "nope"))
    from distributed_ml_pytorch_tpu.analysis import cli

    empty = tmp_path / "empty"
    empty.mkdir()
    assert cli.main(["timeline", str(empty)]) == 1


# ------------------------- bubble_attribution schema gate ----------------

def _good_attr():
    return {
        "stages": 4,
        "stage_seconds": 40.0,
        "fractions": {"compute": 0.12, "wait-act": 0.40, "wait-grad": 0.30,
                      "wire-blocked": 0.08, "ckpt": 0.05, "idle": 0.05},
        "bubble_fraction": 0.88,
        "wait_fraction": 0.88,
    }


def test_bubble_attribution_schema_accepts_good_record():
    assert timeline.check_bubble_attribution(_good_attr()) == _good_attr()


@pytest.mark.parametrize("mutate, msg", [
    (lambda a: a.pop("fractions"), "fractions"),
    (lambda a: a["fractions"].update({"napping": 0.5}), "unknown state"),
    (lambda a: a["fractions"].update({"idle": 0.5}), "sum"),
    (lambda a: a.update(bubble_fraction=1.5), "not in [0, 1]"),
    (lambda a: a.update(bubble_fraction=0.5), "1 - compute"),
    (lambda a: a.update(stages=0), "stages"),
])
def test_bubble_attribution_schema_rejects_breaches(mutate, msg):
    attr = _good_attr()
    mutate(attr)
    with pytest.raises(ValueError, match=None) as exc:
        timeline.check_bubble_attribution(attr)
    assert msg.split()[0] in str(exc.value)


def test_bubble_attribution_accepts_real_analyzer_output():
    """The analyzer's own fixture-derived record passes the gate (the two
    halves of the pipeline agree on the schema)."""
    rep = timeline.analyze(FIXTURES)
    timeline.check_bubble_attribution(rep["bubble_attribution"])


def test_timeline_command_exits_nonzero_on_a_broken_state_clock(
        tmp_path, capsys):
    """A stage dump whose exclusive states overlap (fractions sum to 1.3)
    is refused where the report is made: ``analyze`` still returns it,
    ``main`` prints the checker's message and no decomposition."""
    meta = {"kind": "meta", "member": "stage0", "plane": "mpmd",
            "reason": "exit", "dropped": 0}
    flush = {"name": "attribution", "state": "event", "t0_ns": 1, "t1_ns": 1,
             "meta": {"wall_s": 10.0, "compute": 5.0, "wait-act": 8.0}}
    (tmp_path / "flight_stage0.jsonl").write_text(
        json.dumps(meta) + "\n" + json.dumps(flush) + "\n")
    rep = timeline.analyze(str(tmp_path))
    assert rep["bubble_attribution"]["fractions"]["wait-act"] == 0.8
    assert timeline.main([str(tmp_path)]) == 1
    out = capsys.readouterr()
    assert "sum to 1.3000" in out.err and "bubble" not in out.out
