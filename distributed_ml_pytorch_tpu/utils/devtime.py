"""Device-true timing from bounded profiler traces.

Why this exists: a host clock around a dispatch measures the host too — the
enqueue, the scheduler, a device→host fetch — and for programs of a few
milliseconds that is most of what it sees. The XLA profiler records
per-program start/stop on the device clock, so its durations are what the
chip spent and nothing else.

``device_time`` runs a callable a few times inside a bounded
``jax.profiler.trace`` window (the same machinery ``utils/tracing.py``
exposes for training jobs, SURVEY.md §5.1) and parses the emitted
Chrome-trace JSON for the device-side program spans. The result reports
per-call device time plus a per-program breakdown (useful for roofline
attribution: e.g. decode's weight-read program vs its sampling program).

Off-TPU (the CPU test mesh) the XLA CPU backend does not emit comparable
device spans, so the utility falls back to wall-clock differencing and says
so in the result; tests cover the parser on a canned trace instead.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile
import time
from dataclasses import dataclass, field


@dataclass
class DeviceTiming:
    """Per-call device time for one traced callable."""

    per_call_s: float
    calls: int
    #: program name -> (count, total_seconds) on the device timeline
    programs: dict = field(default_factory=dict)
    #: "trace" (device-true) or "wallclock" (off-TPU fallback)
    source: str = "trace"

    @property
    def per_call_ms(self) -> float:
        return self.per_call_s * 1e3


def parse_device_spans(trace_json: dict) -> dict:
    """Device-pid complete spans from a Chrome-trace dict.

    Returns ``{event_name: (count, total_seconds)}`` for 'X' (complete)
    events on processes whose ``process_name`` metadata mentions a device
    (``/device:``). Nested fusion spans are included under their own names;
    the top-level XLA program spans are the ``jit_*``-named ones.
    """
    events = trace_json.get("traceEvents", [])
    device_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            if "/device:" in str(e.get("args", {}).get("name", "")):
                device_pids.add(e["pid"])
    out: dict = {}
    for e in events:
        if e.get("ph") == "X" and e.get("pid") in device_pids and "dur" in e:
            name = e.get("name", "?")
            n, tot = out.get(name, (0, 0.0))
            out[name] = (n + 1, tot + e["dur"] / 1e6)
    return out


def _top_level_total(programs: dict) -> tuple[int, float]:
    """(dominant span count, total_seconds) of top-level XLA program spans.

    XLA names a jitted program's device span ``jit_<fn>(<fingerprint>)``;
    everything else (``fusion.N``, ``copy.N``, …) is nested inside one.
    All jit spans are summed — the caller traced only the calls it wants
    attributed — and the count returned is that of the program carrying
    the most device time (auxiliary micro-programs like a cache init can
    run more OFTEN than the main program, so a max-count heuristic would
    misattribute; ``device_time`` divides by its own known call count
    anyway).
    """
    n_calls, total, biggest = 0, 0.0, -1.0
    for name, (n, tot) in programs.items():
        if name.startswith("jit"):
            total += tot
            if tot > biggest:
                biggest, n_calls = tot, n
    return n_calls, total


def device_time(fn, *args, calls: int = 10, warmup: int = 2,
                trace_dir: str | None = None) -> DeviceTiming:
    """Per-call device time of ``fn(*args)`` from a profiler trace.

    ``fn`` should be jitted (or jit-compatible: it will be dispatched as-is);
    each call's result is forced via a scalar fetch, so every dispatch has
    finished before the next starts and before the trace window closes. On
    non-TPU backends falls back to wall-clock around the forced calls
    (source="wallclock").
    """
    import jax

    def force(r):
        leaf = jax.tree.leaves(r)[0]
        float(leaf.reshape(-1)[0])

    for _ in range(warmup):
        force(fn(*args))

    if jax.devices()[0].platform != "tpu":
        t0 = time.perf_counter()
        r = None
        for _ in range(calls):
            r = fn(*args)
        force(r)
        dt = time.perf_counter() - t0
        return DeviceTiming(per_call_s=dt / calls, calls=calls,
                            source="wallclock")

    own_dir = trace_dir is None
    tdir = trace_dir or tempfile.mkdtemp(prefix="devtime_")
    # host/python tracers OFF: only device spans matter here, and host
    # events count against the trace's event cap, which truncates the
    # device timeline when it is reached
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 0
    opts.python_tracer_level = 0
    try:
        with jax.profiler.trace(tdir, profiler_options=opts):
            # every call is forced individually: an unforced intermediate
            # dispatch can land outside the trace window (observed with
            # large-footprint programs), silently dropping its span. The
            # extra per-call fetch is host time — device spans are clean.
            for _ in range(calls):
                force(fn(*args))
        paths = sorted(glob.glob(os.path.join(
            tdir, "plugins", "profile", "*", "*.trace.json.gz")))
        if not paths:
            raise RuntimeError(f"profiler produced no trace under {tdir}")
        with gzip.open(paths[-1]) as fh:
            programs = parse_device_spans(json.load(fh))
    finally:
        if own_dir:
            import shutil
            shutil.rmtree(tdir, ignore_errors=True)
    n, total = _top_level_total(programs)
    if n == 0:
        raise RuntimeError(
            "no jit program spans on the device timeline; was fn jitted?")
    # divide by the number of spans the DOMINANT program actually has, not
    # the requested call count: a span dropped by profiler-buffer overflow
    # leaves n < calls, and `total` then covers exactly n real executions —
    # dividing by `calls` would deflate per-call time and inflate MFU
    # silently.
    # Auxiliary micro-programs fold into the per-call figure (negligible).
    return DeviceTiming(per_call_s=total / n, calls=n, programs=programs)
