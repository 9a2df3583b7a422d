"""Shared by the hybrid cell's tests: the tiny manifest's temporary root with a
tiny hybrid configuration, a cell of it and the real cell's per-layer metrics
added as files and entries (``tests/benchmark/tiny`` itself is not edited)."""

import json
import os

from benchmark_testlib import REPO, tiny_root

CELL, STANDS_FOR = "tiny-hybrid-serve", "olmoh-serve-gen"

#: the tests' tiny published-style configuration (hidden 64, two periods of
#: 3 linear + 1 full, 2 heads, key 8 / value 16) with the benchmark's own keys
with open(os.path.join(REPO, "tests", "tiny_olmo_hybrid_config.json")) as _fh:
    CONFIG = dict(json.load(_fh), name="tiny-hybrid", reference="olmo_hybrid", reduced=[],
                  source="none: a CPU test preset, never a cell")


def real_workload() -> dict:
    with open(os.path.join(REPO, "benchmarks", "workloads", STANDS_FOR + ".json")) as fh:
        return json.load(fh)


def workload() -> dict:
    """The real cell's file at a size the CPU serves in a second: the same
    driver, limits, traffic kind and bucket-to-prompt proportions."""
    w = real_workload()
    w["engine"] = {"slots": 4, "cache_size": 96, "decode_block": 4, "prefill_bucket": 32,
                   "max_queue": 256}
    w["traffic"].update(arrivals={"process": "poisson", "rate": 20.0},
                        prompt_tokens={"median": 16, "sigma": 0.8, "lo": 4, "hi": 48},
                        output_tokens={"median": 12, "sigma": 0.5, "lo": 6, "hi": 24},
                        max_total_tokens=96)
    w.update(drain_limit_s=30.0, trace={"seconds": 0.3}, reference={"requests": 4})
    return w


#: one period at a width where the int8 control reads over the real cell's
#: limit (at hidden 64 it reads inside bfloat16's own noise): 8 heads of 64,
#: linear heads of key 48 / value 96, the published 1 : 2
WIDE = {"hidden_size": 512, "intermediate_size": 1024, "vocab_size": 2048,
        "num_attention_heads": 8, "num_key_value_heads": 8, "linear_num_key_heads": 8,
        "linear_num_value_heads": 8, "linear_key_head_dim": 48, "linear_value_head_dim": 96,
        "num_hidden_layers": 4, "layer_types": ["linear_attention"] * 3 + ["full_attention"]}


def hybrid_root(tmp_path, **config_changes) -> str:
    root = tiny_root(tmp_path)
    with open(os.path.join(root, "tiny", "configs", "tiny-hybrid.json"), "w") as fh:
        json.dump(dict(CONFIG, **config_changes), fh)
    with open(os.path.join(root, "tiny", "workloads", CELL + ".json"), "w") as fh:
        json.dump(workload(), fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        m = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    m["configs"].append({"name": "tiny-hybrid", "source": "none", "reduced": [], "why": "test",
                         "file": "tiny/configs/tiny-hybrid.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-hybrid", "traffic": "gen", "chips": 1,
                           "why": "test"})
    for e in m["end_to_end"]:
        if e["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            e["workloads"].append(CELL)
    m["per_layer"] += [dict(e, workloads=[CELL]) for e in real["per_layer"]
                       if e.get("workloads") == [STANDS_FOR]]
    with open(path, "w") as fh:
        json.dump(m, fh)
    return root
