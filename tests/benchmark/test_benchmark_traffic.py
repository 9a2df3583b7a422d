"""The generator: the same seed gives the same traffic, another seed the same
work in another order."""

import numpy as np
import pytest
from benchmark_testlib import REPO  # noqa: F401  (puts the repo on sys.path)

from benchmarks import traffic

CHAT = {"arrivals": {"process": "poisson", "rate": 12.0},
        "prompt_tokens": {"median": 160, "sigma": 0.7, "lo": 32, "hi": 640},
        "output_tokens": {"median": 96, "sigma": 0.5, "lo": 32, "hi": 256},
        "max_total_tokens": 1024}
BURST = dict(CHAT, arrivals={"process": "bursts", "rate": 12.0, "factor": 6.0, "on_s": 0.5, "off_s": 1.5})


@pytest.mark.parametrize("mix", [CHAT, BURST], ids=["poisson", "bursts"])
def test_same_seed_same_requests_other_seed_same_work_in_another_order(mix):
    a = traffic.request_plan(mix, 2**31 + 5, 25, 50257)
    b = traffic.request_plan(mix, 2**31 + 5, 25, 50257)
    c = traffic.request_plan(mix, 7, 25, 50257)
    assert np.array_equal(a.due, b.due) and all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert len(a.due) == len(c.due) == 300 and not np.array_equal(a.prompt_len, c.prompt_len)
    for field in ("prompt_len", "new_tokens"):
        assert np.array_equal(np.sort(getattr(a, field)), np.sort(getattr(c, field)))
    assert np.all(np.diff(a.due) > 0) and 0 < a.due[0] and a.due[-1] < 25
    assert abs(a.due[-1] - c.due[-1]) < 0.5  # both end with the window
    assert a.prompt_len.min() >= 32 and a.prompt_len.max() <= 640
    assert 140 <= np.median(a.prompt_len) <= 180 and 85 <= np.median(a.new_tokens) <= 107
    assert np.all(a.prompt_len + a.new_tokens <= 1024)


def test_poisson_arrivals_keep_their_clumps():
    """Nothing smooths the order: counts a second scatter as a Poisson
    process's do (variance about the mean), and some second is crowded."""
    dispersion, busiest = [], []
    for seed in range(40):
        plan = traffic.request_plan(CHAT, 2**31 + seed, 30, 50257)
        per_s = np.histogram(plan.due, bins=np.arange(31))[0]
        dispersion.append(per_s.var() / per_s.mean())
        busiest.append(per_s.max())
    assert 0.8 < np.mean(dispersion) < 1.1  # 1 for Poisson; a little under: the total is fixed
    assert min(busiest) >= 16  # a third over the rate of 12 a second, on every seed


def test_bursts_keep_the_mean_rate_and_crowd_the_on_windows():
    plan = traffic.request_plan(BURST, 3, 40, 50257)
    phase = np.mod(plan.due, 2.0)
    on = (phase < 0.5).mean()  # a quarter of the time, factor 6: 6 x 0.5 / (6 x 0.5 + 1.5) of requests
    assert abs(on - 2 / 3) < 0.05 and len(plan.due) == 480


def test_token_batches_differ_by_step_and_repeat_by_seed():
    t0, g0 = traffic.token_batch(2**31 + 9, 0, 4, 16, 100)
    t1, _ = traffic.token_batch(2**31 + 9, 1, 4, 16, 100)
    again, _ = traffic.token_batch(2**31 + 9, 0, 4, 16, 100)
    assert np.array_equal(t0, again) and not np.array_equal(t0, t1)
    assert np.array_equal(g0[:, :-1], t0[:, 1:]) and len({tuple(r) for r in t0}) == 4
