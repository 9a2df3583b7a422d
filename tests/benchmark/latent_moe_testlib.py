"""Shared by the latent-attention, routed-expert cell's tests: the tiny
manifest's temporary root with a tiny configuration of the same keys, a cell of
it and the real cell's per-layer metrics added as files and entries
(``tests/benchmark/tiny`` itself is not edited)."""

import json
import os

from benchmark_testlib import REPO, tiny_root

CELL, STANDS_FOR = "tiny-latent-moe-serve", "kanana2-serve-history"

#: the tests' tiny published-style configuration (hidden 64, a dense layer and
#: two expert layers, 8 experts top-2, 4 heads, latent 32 + rope 8)
with open(os.path.join(REPO, "tests", "tiny_kanana2_config.json")) as _fh:
    CONFIG = dict(json.load(_fh), name="tiny-latent-moe", reference="deepseek_v3_mla_moe",
                  reduced=[], source="none: a CPU test preset, never a cell")


def real_workload() -> dict:
    with open(os.path.join(REPO, "benchmarks", "workloads", STANDS_FOR + ".json")) as fh:
        return json.load(fh)


def workload() -> dict:
    """The real cell's file at a size the CPU serves in a second: the same
    driver, limits, traffic kind and bucket-to-prompt proportions."""
    w = real_workload()
    w["engine"] = {"slots": 4, "cache_size": 96, "decode_block": 4, "prefill_bucket": 16,
                   "max_queue": 256}
    w["traffic"].update(arrivals={"process": "poisson", "rate": 20.0},
                        prompt_tokens={"median": 32, "sigma": 0.35, "lo": 20, "hi": 64},
                        output_tokens={"median": 12, "sigma": 0.4, "lo": 6, "hi": 24},
                        max_total_tokens=96)
    w.update(drain_limit_s=30.0, trace={"seconds": 0.3}, reference={"requests": 4})
    return w


def latent_moe_root(tmp_path, **config_changes) -> str:
    root = tiny_root(tmp_path)
    with open(os.path.join(root, "tiny", "configs", "tiny-latent-moe.json"), "w") as fh:
        json.dump(dict(CONFIG, **config_changes), fh)
    with open(os.path.join(root, "tiny", "workloads", CELL + ".json"), "w") as fh:
        json.dump(workload(), fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        m = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        real = json.load(fh)
    m["configs"].append({"name": "tiny-latent-moe", "source": "none", "reduced": [], "why": "test",
                         "file": "tiny/configs/tiny-latent-moe.json"})
    m["workloads"].append({"name": CELL, "config": "tiny-latent-moe", "traffic": "history",
                           "chips": 1, "why": "test"})
    reported = {e["name"] for e in real["end_to_end"] if STANDS_FOR in e.get("workloads", [])}
    for e in m["end_to_end"]:
        if e["name"] in reported:
            e["workloads"].append(CELL)
    m["per_layer"] += [dict(e, workloads=[CELL]) for e in real["per_layer"]
                       if e.get("workloads") == [STANDS_FOR]]
    with open(path, "w") as fh:
        json.dump(m, fh)
    return root
