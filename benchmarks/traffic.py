"""The one traffic generator: a workload file's ``traffic`` block and a seed
give the requests (or batches) of a run.

Open-loop request traffic (``kind: "requests"``): arrivals are a Poisson
process, or a two-state burst process, at a rate fixed in the file; prompt
and output lengths are clipped log-normals. Every seed gets the SAME set of
inter-arrival gaps and the SAME set of lengths, taken at evenly spaced
quantiles of their distributions, each in another order: the amount of work in
a run does not depend on the seed, only its order does, and runs on different
seeds differ by what order does to a queue, not by how much was drawn. The
order is any permutation: short gaps and long answers clump as independent
draws would, and nothing smooths them. (The
arithmetic of ``bench_serving.py::make_arrivals`` / ``make_plan``, which draw
independently per seed, is the origin; latencies here count from the due time.)

Training traffic (``kind: "token_batches"``): batch ``i`` of a run is drawn
from ``(seed, i)``, so every step sees rows that all differ.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class RequestPlan:
    due: np.ndarray          # seconds from the window's start, ascending
    prompt_len: np.ndarray   # tokens
    new_tokens: np.ndarray   # tokens to emit
    prompts: list            # int32 arrays


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a log-normal with the given
    median and sigma, clipped to ``[lo, hi]``."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["lo"], spec["hi"]).astype(np.int64)


def _due_times(arrivals: dict, n: int, rng) -> np.ndarray:
    """Due times of ``n`` requests. Unit-rate exponential gaps at evenly
    spaced quantiles (the same set for every seed, summing to ``n``), in the
    seed's order, mapped through the inverse of the cumulative rate: a
    constant ``rate`` for ``process: "poisson"``; for ``"bursts"`` windows of
    ``on_s`` seconds at ``factor`` times the rate of the ``off_s`` seconds
    between them, the mean staying ``rate``."""
    rate = float(arrivals["rate"])
    gaps = -np.log1p(-_quantiles(n))
    gaps = gaps * n / gaps.sum()
    gaps = gaps[rng.permutation(n)]
    u = np.cumsum(gaps) - 0.5 * gaps[0]  # the first is due half a gap in
    kind = arrivals.get("process", "poisson")
    if kind == "poisson":
        return u / rate
    if kind != "bursts":
        raise ValueError(f"unknown arrival process {kind!r}")
    factor, on_s, off_s = (float(arrivals[k]) for k in ("factor", "on_s", "off_s"))
    period = on_s + off_s
    r_off = rate * period / (factor * on_s + off_s)
    r_on = factor * r_off
    k, rem = np.divmod(u, rate * period)
    in_on = rem <= r_on * on_s
    return k * period + np.where(in_on, rem / r_on, on_s + (rem - r_on * on_s) / r_off)


def request_plan(traffic: dict, seed: int, seconds: float, vocab: int) -> RequestPlan:
    """The requests offered in a window of ``seconds``: ``round(rate x
    seconds)`` of them, all due inside the window."""
    n = max(1, int(round(float(traffic["arrivals"]["rate"]) * seconds)))
    rng = np.random.default_rng([int(seed), 0x7AF1C])
    due = _due_times(traffic["arrivals"], n, rng)
    prompt = _lognormal_lengths(traffic["prompt_tokens"], n)[rng.permutation(n)]
    new = _lognormal_lengths(traffic["output_tokens"], n)[rng.permutation(n)]
    limit = int(traffic.get("max_total_tokens", 0))
    if limit:
        new = np.maximum(1, np.minimum(new, limit - prompt))
    prompts = [rng.integers(0, vocab, size=int(p)).astype(np.int32) for p in prompt]
    return RequestPlan(due=due, prompt_len=prompt, new_tokens=new, prompts=prompts)


def token_batch(seed: int, index: int, batch: int, seq: int, vocab: int):
    """Batch ``index`` of a training run: ``(tokens, targets)``, int32, the
    targets shifted by one with the last position padded (and masked by the
    trainer)."""
    rng = np.random.default_rng([int(seed), 0x7BA7C, int(index)])
    tokens = rng.integers(0, vocab, size=(batch, seq)).astype(np.int32)
    targets = np.concatenate(
        [tokens[:, 1:], np.zeros((batch, 1), np.int32)], axis=1)
    return tokens, targets
