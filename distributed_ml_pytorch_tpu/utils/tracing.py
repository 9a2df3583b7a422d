"""Tracing / profiling — a subsystem the reference lacks entirely.

The reference's only timing artifact is a ``datetime.now()`` per logged
iteration (``example/main.py:77``; SURVEY.md §5.1 records tracing as ABSENT).
On TPU, profiling is how every real perf decision gets made, so the framework
ships it as a first-class utility:

- :class:`StepTimer` — cheap wall-clock stats over training steps (mean /
  p50 / p99 / throughput), printed per epoch. Measures *dispatch-to-ready*
  time by blocking on the step output, so it reflects device time, not just
  Python overhead.
- :class:`TraceWindow` — captures an XLA/TPU profiler trace (viewable in
  TensorBoard / xprof) for a bounded window of steps, via
  ``jax.profiler.start_trace``/``stop_trace``. Bounded because a whole-run
  trace of a training job is gigabytes; a 10-step window shows the steady
  state.
- :func:`annotate_step` — ``jax.profiler.StepTraceAnnotation`` passthrough so
  per-step markers line up in the trace viewer.
- :func:`span` — ``jax.profiler.TraceAnnotation`` passthrough: the package's
  one span call. A span lands in whatever profile is being taken (a
  ``TraceWindow``, ``jax.profiler.start_trace``, the profiler server), on the
  clock the device's events are on, and nowhere when none is.
"""

from __future__ import annotations

import time
from typing import Optional

import numpy as np


class StepTimer:
    """Wall-clock per-step statistics with warmup exclusion.

    Bracket each step with :meth:`start` (just before dispatch) and
    :meth:`tick` (after blocking on the step's output), so the recorded
    interval is dispatch-to-ready device time — host-side logging, batch
    slicing, and checkpoint dispatch between steps are excluded. ``skip``
    initial intervals are discarded (compile + cache warmup). A :meth:`tick`
    without a preceding :meth:`start` records nothing.
    """

    def __init__(self, skip: int = 2, items_per_step: Optional[int] = None):
        self.skip = skip
        self.items_per_step = items_per_step
        self._seen = 0
        self._times: list = []
        self._last: Optional[float] = None

    def start(self) -> None:
        self._last = time.perf_counter()

    def tick(self) -> None:
        if self._last is None:
            return
        dt = time.perf_counter() - self._last
        self._last = None
        self._seen += 1
        if self._seen > self.skip:
            self._times.append(dt)

    def tick_n(self, n: int) -> None:
        """Record the elapsed interval as ``n`` equal steps (chunked dispatch:
        one start/tick pair covers a whole scanned chunk of n steps).

        A chunk containing any warmup step is dropped whole — its interval
        includes XLA compile, and averaging compile over n "steps" would
        pollute every recorded sample (per-step mode excludes it via skip).
        """
        if self._last is None or n < 1:
            return
        dt = (time.perf_counter() - self._last) / n
        self._last = None
        if self._seen < self.skip:
            self._seen += n  # warmup chunk: count it, record nothing
            return
        self._seen += n
        self._times.extend([dt] * n)

    def reset_stats(self) -> None:
        """Clear collected intervals but keep warmup state.

        Lets one timer span a whole run (warmup = compile, which happens only
        on the very first steps) while reporting per epoch.
        """
        self._times = []

    def summary(self) -> Optional[dict]:
        if not self._times:
            return None
        t = np.asarray(self._times)
        out = {
            "steps": int(t.size),
            "mean_ms": float(t.mean() * 1e3),
            "p50_ms": float(np.percentile(t, 50) * 1e3),
            "p99_ms": float(np.percentile(t, 99) * 1e3),
        }
        if self.items_per_step:
            out["items_per_sec"] = float(self.items_per_step / t.mean())
        return out

    def report(self, prefix: str = "steps") -> Optional[str]:
        s = self.summary()
        if s is None:
            return None
        line = "{}: {} timed, mean {:.2f} ms, p50 {:.2f} ms, p99 {:.2f} ms".format(
            prefix, s["steps"], s["mean_ms"], s["p50_ms"], s["p99_ms"]
        )
        if "items_per_sec" in s:
            line += ", {:.0f} items/s".format(s["items_per_sec"])
        return line


class TraceWindow:
    """Capture an xprof trace for global steps ``[start, stop)``.

    Call :meth:`on_step` with the global step index before dispatching that
    step; the trace starts when ``step == start`` and stops at ``stop`` (or at
    :meth:`close`, whichever comes first). No-op when ``profile_dir`` is
    falsy, so callers can wire it unconditionally.
    """

    def __init__(self, profile_dir: Optional[str], start: int = 10, n_steps: int = 10):
        self.profile_dir = profile_dir
        self.start = start
        self.stop = start + n_steps
        self._active = False
        self._done = False
        self._first_step: Optional[int] = None

    def on_step(self, step: int, n_steps: int = 1) -> None:
        """Open the trace when the dispatch ``[step, step + n_steps)`` overlaps
        the window; call before dispatch. ``n_steps > 1`` (chunked dispatch)
        rounds the capture out to chunk granularity — a chunk that strides
        over the window still gets traced."""
        if not self.profile_dir or self._done:
            return
        if self._first_step is None:
            self._first_step = step
        if not self._active and step < self.stop and step + n_steps > self.start:
            import jax

            jax.profiler.start_trace(self.profile_dir)
            self._active = True
        elif self._active and step >= self.stop:
            self.close()

    def after_step(self, next_step: int) -> None:
        """Close the trace as soon as the window's last step has completed.

        Call with the *next* global step after blocking on the current one —
        this bounds the capture to exactly the window even when the run (or an
        epoch) ends before another ``on_step`` would fire, keeping evals and
        final checkpoint saves out of the trace.
        """
        if self._active and next_step >= self.stop:
            self.close()

    def close(self) -> None:
        if self._active:
            import jax

            jax.profiler.stop_trace()
            self._active = False
            self._done = True
            print(f"wrote profiler trace to {self.profile_dir}")

    def warn_if_never_opened(self) -> None:
        """Loud diagnostic for a window the run never reached.

        Call at end of run: if profiling was requested but the window
        ``[start, stop)`` never opened (run too short, or empty window),
        say so instead of exiting 0 with an empty trace dir.
        """
        if self.profile_dir and not self._done and not self._active:
            import sys

            if self._first_step is not None and self._first_step >= self.stop:
                # resumed run started past the window — lowering start can
                # never help; it must move above the resume step
                hint = (
                    "the run started at step {} — raise --profile-start past "
                    "the resume point".format(self._first_step)
                )
            else:
                hint = "lower --profile-start or raise --profile-steps"
            print(
                "warning: --profile-dir was set but the trace window "
                f"[{self.start}, {self.stop}) was never reached; no trace "
                f"written ({hint})",
                file=sys.stderr,
            )


def annotate_step(name: str, step: int):
    """Step annotation context for the trace viewer."""
    import jax

    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def span(name: str, **attrs):
    """A host span in the profiler's own trace, as a context manager.

    Names are lower case with dots for nesting (``serve.prefill.dispatch``);
    ``attrs`` are whole numbers (microseconds end in ``_us``) and come back
    as the event's stats. A span's cause is the span that encloses it on its
    thread; spans of one request share ``request_id``. There is no switch:
    with no profile being taken this is one object construction.
    """
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)
