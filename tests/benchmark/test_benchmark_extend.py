"""A cell, a per-layer metric with its reader, and a driver added as files
(and entries) are found and run without editing a file that was there."""

import json
import os

from benchmark_testlib import cpu_device, tiny_root

from benchmarks import harness

DRIVER = '''
import jax.numpy as jnp

class Session:
    def __init__(self, ctx):
        self.ctx = ctx
    def run_window(self):
        total = float(jnp.arange(self.ctx.workload["n"]).sum())
        return {"attempted": 1, "failed": 0, "metrics": {"echo_rate": total},
                "counters": {"echoed": total}}
    def release(self):
        pass
    def compare(self):
        return [("echo_gap", 0.0, 0.0)]

def setup(ctx):
    return Session(ctx)
'''


def test_added_files_are_found(tmp_path):
    root = tiny_root(tmp_path)
    new = os.path.join(root, "tiny")
    for sub in ("drivers", "metrics", "readers"):
        os.makedirs(os.path.join(new, sub), exist_ok=True)
    with open(os.path.join(new, "drivers", "echo.py"), "w") as fh:
        fh.write(DRIVER)
    with open(os.path.join(new, "readers", "halve.py"), "w") as fh:
        fh.write("def read(view, params):\n    return view['counters'][params['counter']] / 2\n")
    with open(os.path.join(new, "workloads", "echo-cell.json"), "w") as fh:
        json.dump({"driver": "echo", "n": 5}, fh)
    with open(os.path.join(new, "metrics", "echo_half.json"), "w") as fh:
        json.dump({"layer": "echo", "reader": "halve", "params": {"counter": "echoed"}}, fh)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as fh:
        m = json.load(fh)
    m["workloads"].append({"name": "echo-cell", "config": "tiny-gpt2", "traffic": "echo",
                           "chips": 1, "why": "test"})
    m["end_to_end"].append({"name": "echo_rate", "unit": "x", "better": "higher", "bound": 0.01,
                            "source": "host_clock", "workloads": ["echo-cell"]})
    m["per_layer"].append({"name": "echo_half", "unit": "x", "better": "higher",
                           "source": "program_counter", "layer": "echo", "moves": "echo_rate",
                           "workloads": ["echo-cell"]})
    with open(path, "w") as fh:
        json.dump(m, fh)
    plain = harness.run_cell("echo-cell", 1, 0.1, False, root=root, devices=cpu_device())
    assert plain["correct"] and set(plain["metrics"]) == {"echo_rate", "setup_s"}
    assert plain["metrics"]["echo_rate"] == {"value": 10.0, "unit": "x"}
    traced = harness.run_cell("echo-cell", 1, 0.1, True, root=root, devices=cpu_device())
    assert traced["metrics"] == {"echo_half": {"value": 5.0, "unit": "x"}}
