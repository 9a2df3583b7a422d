"""The ``lm_serve`` driver at a tiny size on the CPU: the result line with
and without a trace, the int8 control, and a token altered where it is
produced. (The int8 control has a file of its own: it needs the real cell's
width and takes most of a minute.)"""

import json

import numpy as np
from benchmark_testlib import cpu_device, tiny_root

from benchmarks import harness

SEED = 2**31 + 13
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device", "compared"]


def run(tmp_path, trace=False):
    return harness.run_cell("tiny-serve", SEED, 0.6, trace, root=tiny_root(tmp_path),
                            devices=cpu_device())


def test_sound_run_prints_the_contracts_line_and_names_its_device(tmp_path, capsys):
    harness.emit(run(tmp_path))
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert list(line) == CONTRACT_KEYS and line["correct"] is True
    assert line["attempted"] == 12 and line["failed"] == 0
    assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == {"token_logit_gap_mean", "wrong_length_requests"}


def test_a_traced_run_off_the_chip_reports_counters_and_no_share_of_a_peak(tmp_path):
    result = run(tmp_path, trace=True)
    assert result["correct"] and "breakdown" not in result and "busy_s" not in result["device"]
    assert set(result["metrics"]) == {"slot_occupancy.chat", "generator_late_p95_ms"}
    assert 0 < result["metrics"]["slot_occupancy.chat"]["value"] <= 100


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path, monkeypatch):
    from distributed_ml_pytorch_tpu.serving.cache import SlotKVPool

    real = SlotKVPool.decode_block_step

    def altered(self, *args):
        toks = np.array(real(self, *args))
        toks[:, 1] = (toks[:, 1] + 17) % 120
        return toks

    monkeypatch.setattr(SlotKVPool, "decode_block_step", altered)
    result = run(tmp_path)
    assert result["correct"] is False
    c = result["compared"]["token_logit_gap_mean"]
    assert c["value"] > c["limit"]
