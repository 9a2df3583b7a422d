"""The serving engine's own spans and counters (``serve.*`` through
``utils/tracing.span``; ``queue_wait_ms`` and ``between_blocks_ms`` in
``slo_summary()``, the second on an injected clock).

A tiny engine serves one set of requests under ONE profile, taken once for
the module with the tracer levels the benchmark sets; the spans are read back
through the benchmark's own loader (``benchmarks/program_trace.py``), so the
file the chip runs are reduced with is the file read here. Host spans and
their stats are written on the CPU too; the device side is not.
"""

import glob
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import program_trace
from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
from distributed_ml_pytorch_tpu.serving import engine as engine_module
from distributed_ml_pytorch_tpu.serving.engine import ServingEngine

VOCAB = 64
#: (prompt length, new tokens): more requests than slots, so some wait
REQUESTS = [(5, 9), (6, 10), (7, 11), (8, 12), (3, 5)]
PARENT_OF = {"serve.prefill": "serve.step", "serve.decode": "serve.step",
             "serve.emit": "serve.step", "serve.decode.dispatch": "serve.decode",
             "serve.decode.fetch": "serve.decode"}


def make_engine(**kw):
    model = TransformerLM(vocab_size=VOCAB, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                          max_len=128)
    params = model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))["params"]
    return ServingEngine(model, params, slots=2, cache_size=64, decode_block=4,
                         prefill_bucket=8, **kw)


def serve(engine):
    rng = np.random.default_rng(7)
    requests = [engine.submit(rng.integers(0, VOCAB, size=p), n) for p, n in REQUESTS]
    engine.run_until_idle()
    return requests


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """``(requests, spans)`` of one served set under one profile."""
    engine = make_engine()
    serve(engine)  # every shape compiled before the profile
    engine.reset_metrics()
    out = str(tmp_path_factory.mktemp("profile"))
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out, profiler_options=opts)
    try:
        requests = serve(engine)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))
    return requests, program_trace.load_spans(path), engine


def inside(child, parent) -> bool:
    return (child[3] == parent[3] and parent[1] <= child[1]
            and child[1] + child[2] <= parent[1] + parent[2])


@pytest.mark.parametrize("name", sorted(PARENT_OF))
def test_every_span_lies_inside_its_parent_on_its_thread(traced, name):
    _requests, spans, _engine = traced
    children = [s for s in spans if s[0] == name]
    parents = [s for s in spans if s[0] == PARENT_OF[name]]
    assert children, f"no {name} span was written"
    for child in children:
        assert sum(inside(child, p) for p in parents) == 1, child


def test_a_requests_prefill_carries_its_id_and_its_wait_in_the_queue(traced):
    requests, spans, _engine = traced
    prefills = {s[4]["request_id"]: s for s in spans if s[0] == "serve.prefill"}
    assert set(prefills) == {r.request_id for r in requests}
    for r in requests:
        attrs = prefills[r.request_id][4]
        assert set(attrs) == {"request_id", "queue_wait_us", "prompt_tokens", "bucket_tokens"}
        assert attrs["queue_wait_us"] == int((r.t_admit - r.t_submit) * 1e6)
        # what the admission padded: the prompt's length and its bucket's
        assert attrs["prompt_tokens"] == r.prompt.size <= attrs["bucket_tokens"]
        assert attrs["bucket_tokens"] == _engine._bucket_len(r.prompt.size)
        assert r.t_submit <= r.t_admit <= r.t_first_token <= r.t_done
    # five requests into two slots: the later ones waited for a slot, through
    # at least one decode block
    waits = sorted(s[4]["queue_wait_us"] for s in prefills.values())
    blocks = [s[2] for s in spans if s[0] == "serve.decode"]
    assert waits[-1] > min(blocks) / 1e3


def test_the_engine_writes_the_documented_spans_and_one_dispatch_and_fetch_a_block(traced):
    _requests, spans, _engine = traced
    by = lambda name: [s for s in spans if s[0] == name]
    assert {s[0] for s in spans} == set(PARENT_OF) | {"serve.step"}
    assert len(by("serve.decode.dispatch")) == len(by("serve.decode.fetch")) == len(by("serve.decode"))
    assert len(by("serve.emit")) == len(by("serve.decode")) <= len(by("serve.step"))
    # a fetch ends before the next dispatch starts: what ``between_blocks`` runs between
    ends = [s[1] + s[2] for s in by("serve.decode.fetch")]
    starts = [s[1] for s in by("serve.decode.dispatch")]
    assert all(e <= t for e, t in zip(ends, starts[1:]))


def test_an_idle_engine_writes_no_span_and_costs_no_round(traced):
    _requests, spans, engine = traced
    assert engine.step() is False
    # every round that was written did something: it holds a prefill or a
    # block, but for the last, which evicts what the last block finished
    inner = [s for s in spans if s[0] in ("serve.prefill", "serve.decode")]
    steps = [s for s in spans if s[0] == "serve.step"]
    bare = [step for step in steps if not any(inside(s, step) for s in inner)]
    assert bare == steps[-1:]


def test_without_a_profile_the_tokens_are_the_same_and_the_summary_holds_both(traced):
    requests, _spans, _engine = traced
    engine = make_engine()
    plain = serve(engine)
    assert [r.tokens for r in plain] == [r.tokens for r in requests]
    summary = engine.slo_summary()
    waits = [(r.t_admit - r.t_submit) * 1e3 for r in plain]
    assert summary["queue_wait_ms"]["count"] == len(REQUESTS)
    assert summary["queue_wait_ms"]["max"] == pytest.approx(max(waits))
    between = summary["between_blocks_ms"]
    assert between["count"] >= 3 and between["max"] >= between["p50"] > 0.0
    assert between["max_phase"] in ("evict", "admit", "emit", "outside_step")
    engine.reset_metrics()
    summary = engine.slo_summary()
    assert summary["queue_wait_ms"] is None and summary["between_blocks_ms"] is None


class Clock:
    """``perf_counter`` for the engine alone: a tick a reading, and whatever
    a test adds for the host's time somewhere."""

    def __init__(self, tick: float = 1e-5):
        self.now, self.tick = 100.0, tick

    def perf_counter(self) -> float:
        self.now += self.tick
        return self.now


def slow(clock: Clock, seconds: float, fn):
    def wrapped(*args, **kwargs):
        clock.now += seconds
        return fn(*args, **kwargs)
    return wrapped


@pytest.mark.parametrize("phase", ["outside_step", "evict", "admit", "emit"])
def test_the_longest_stretch_between_two_blocks_names_the_phase_that_held_it(monkeypatch, phase):
    clock = Clock()
    monkeypatch.setattr(engine_module, "time", types.SimpleNamespace(perf_counter=clock.perf_counter))
    engine = make_engine()
    # two requests in flight; the first is done after two blocks and is evicted
    # while the second still decodes, and a third waits for its slot
    for prompt_len, new_tokens in ((5, 8), (6, 40), (7, 12)):
        engine.submit(np.arange(prompt_len) % VOCAB, new_tokens)
    for _ in range(2):
        assert engine.step()
    usual = engine.slo_summary()["between_blocks_ms"]
    assert usual["count"] == 1 and usual["max"] < 1.0  # a few ticks of 0.01 ms
    if phase == "outside_step":
        clock.now += 0.5  # the caller does something else between two rounds
    elif phase == "evict":
        monkeypatch.setattr(engine.pool, "reset_slots", slow(clock, 0.5, engine.pool.reset_slots))
    elif phase == "admit":
        monkeypatch.setattr(engine.pool, "admit", slow(clock, 0.5, engine.pool.admit))
    else:
        engine.on_tokens = slow(clock, 0.5, lambda *args: None)
        assert engine.step()  # the callbacks run after this round's block: the NEXT stretch holds them
        engine.on_tokens = None
    assert engine.step()
    between = engine.slo_summary()["between_blocks_ms"]
    assert between["max_phase"] == phase
    held = 1000.0 if phase == "emit" else 500.0  # a callback for each of the two slots
    assert held <= between["max"] < held + 2.0    # and a few ticks
    engine.run_until_idle()
    assert engine.slo_summary()["between_blocks_ms"]["max_phase"] == phase
