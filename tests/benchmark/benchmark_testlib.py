"""Shared by the benchmark's tests: a temporary root that holds the tiny
manifest beside the real ``benchmarks/`` directory."""

import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: the cell each tiny cell stands for: its limits are the ones held
STANDS_FOR = {"tiny-train": "gpt2m-train-1k", "tiny-serve": "gpt2l-serve-chat"}


def tiny_root(tmp_path) -> str:
    """``tmp_path`` as a benchmark root: the tiny manifest, ``benchmarks``
    (the real one, linked) and ``tiny`` (a copy, so a test may add files)."""
    root = str(tmp_path)
    os.symlink(os.path.join(REPO, "benchmarks"), os.path.join(root, "benchmarks"))
    shutil.copytree(os.path.join(HERE, "tiny"), os.path.join(root, "tiny"))
    shutil.move(os.path.join(root, "tiny", "BENCHMARK.json"), os.path.join(root, "BENCHMARK.json"))
    return root


def real_manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cpu_device():
    import jax

    return jax.devices("cpu")[:1]
