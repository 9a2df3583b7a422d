"""Tracing/profiling subsystem: step timing stats + bounded trace windows."""

import os
import time

import jax
import jax.numpy as jnp

from distributed_ml_pytorch_tpu.utils.tracing import StepTimer, TraceWindow


def test_step_timer_excludes_warmup_and_reports_stats():
    t = StepTimer(skip=2, items_per_step=64)
    for i in range(6):
        t.start()
        time.sleep(0.01)
        t.tick()
    s = t.summary()
    # 6 intervals seen, first 2 skipped as warmup
    assert s["steps"] == 4
    assert 5.0 <= s["mean_ms"] <= 100.0
    assert s["p50_ms"] <= s["p99_ms"] * 1.0001
    assert s["items_per_sec"] > 0
    assert "items/s" in t.report()


def test_step_timer_empty_reports_none():
    assert StepTimer().summary() is None
    assert StepTimer().report() is None


def test_step_timer_tick_without_start_records_nothing():
    t = StepTimer(skip=0)
    t.tick()  # no start(): must not record an interval
    assert t.summary() is None


def test_step_timer_excludes_between_step_work():
    t = StepTimer(skip=0)
    t.start()
    time.sleep(0.005)
    t.tick()
    time.sleep(0.05)  # between-steps host work: must not be timed
    t.start()
    time.sleep(0.005)
    t.tick()
    s = t.summary()
    assert s["steps"] == 2
    assert s["p99_ms"] < 40.0, "between-step gap leaked into step timing"


def test_trace_window_captures_bounded_steps(tmp_path):
    profile_dir = str(tmp_path / "trace")
    tw = TraceWindow(profile_dir, start=2, n_steps=2)
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    for step in range(6):
        tw.on_step(step)
        f(x).block_until_ready()
    tw.close()
    # xprof writes under <dir>/plugins/profile/<run>/
    found = []
    for root, _dirs, files in os.walk(profile_dir):
        found.extend(files)
    assert found, f"no trace files written under {profile_dir}"


def test_trace_window_closes_when_run_ends_inside_window(tmp_path):
    profile_dir = str(tmp_path / "trace2")
    tw = TraceWindow(profile_dir, start=1, n_steps=10)
    x = jnp.ones((8, 8))
    f = jax.jit(lambda a: a @ a)
    for step in range(3):  # run ends well before start+n_steps
        tw.on_step(step)
        f(x).block_until_ready()
        tw.after_step(step + 1)
    # after_step must NOT have closed early (window still open at step 3)...
    assert tw._active
    tw.close()  # ...but close() bounds it at end of run
    assert not tw._active and tw._done


def test_trace_window_after_step_bounds_exactly(tmp_path):
    tw = TraceWindow(str(tmp_path / "trace3"), start=0, n_steps=2)
    x = jnp.ones((8, 8))
    f = jax.jit(lambda a: a @ a)
    tw.on_step(0)
    f(x).block_until_ready()
    tw.after_step(1)
    assert tw._active  # window covers steps [0, 2)
    tw.on_step(1)
    f(x).block_until_ready()
    tw.after_step(2)
    assert not tw._active and tw._done  # closed the moment step 1 completed


def test_trace_window_disabled_is_noop(tmp_path):
    tw = TraceWindow(None)
    for step in range(5):
        tw.on_step(step)
    tw.close()  # must not raise or write
    tw.warn_if_never_opened()  # disabled: stays silent


def test_trace_window_warns_when_never_reached(capsys):
    tw = TraceWindow("/tmp/unused-trace-dir", start=100, n_steps=10)
    for step in range(3):
        tw.on_step(step)
    tw.close()
    tw.warn_if_never_opened()
    err = capsys.readouterr().err
    assert "never reached" in err


def test_trace_window_opens_when_chunk_strides_over_it(tmp_path):
    profile_dir = str(tmp_path / "trace4")
    tw = TraceWindow(profile_dir, start=10, n_steps=10)
    x = jnp.ones((8, 8))
    f = jax.jit(lambda a: a @ a)
    tw.on_step(0, n_steps=32)  # chunk [0, 32) strides over [10, 20)
    assert tw._active
    f(x).block_until_ready()
    tw.after_step(32)
    assert tw._done


def test_step_timer_tick_n_drops_warmup_chunks():
    t = StepTimer(skip=2)
    t.start()
    time.sleep(0.05)  # "compile" chunk: includes warmup steps → dropped whole
    t.tick_n(8)
    assert t.summary() is None
    t0 = time.perf_counter()
    t.start()
    time.sleep(0.008)
    t.tick_n(4)  # steady chunk: all 4 recorded at dt/4 each
    steady_ms = (time.perf_counter() - t0) * 1e3  # on a loaded host the sleep overruns
    s = t.summary()
    assert s["steps"] == 4
    assert s["mean_ms"] <= steady_ms / 4, "compile time leaked into steady-state stats"
