"""From a profiler trace to numbers: the benchmark's one reducer.

``load_xplane`` turns the profiler's ``.xplane.pb`` into a small plain
dictionary (planes, lines, events as ``[name, start_ns, duration_ns]``),
and everything else works on that dictionary, so the reduction can be checked
on a recorded trace kept with the tests. There is no fallback to a host clock:
a trace without a device plane raises :class:`NoDevicePlane`.

What a TPU trace holds (read by hand from this machine's v5e, PR 25): one
plane ``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA Modules``
(one event a program execution, named ``jit_<fn>(<fingerprint>)``), ``XLA
Ops`` (one event an HLO operation, named by its whole HLO text, ``%attn.75 =
(...) custom-call(...), custom_call_target="tpu_custom_call", ...``; a
``while`` spans its body's operations, so events nest) and ``Async XLA Ops``
(copies in flight, not counted as busy); host threads are lines of the plane
``/host:CPU``, where the benchmark's own ``jax.profiler.TraceAnnotation``
spans (all named ``bench:*``) sit on the same clock. A serving window of one
second holds about a million operations, so names are interned and a pattern
is tried once per distinct name.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:window"
BETWEEN_OPS_NS = 2000  # a shorter gap is not attributed to the host


class NoDevicePlane(RuntimeError):
    """The trace has no accelerator plane: nothing ran on a chip, or the
    profile was taken on a machine without one."""


def load_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` into the plain form. Device planes are kept
    whole; of the host planes only the benchmark's own spans are kept."""
    from jax.profiler import ProfileData

    planes, names = [], {}
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                name = e.name
                if device or name.startswith(SPAN_PREFIX):
                    name = names.setdefault(name, name)  # one string per distinct name
                    events.append([name, int(e.start_ns), int(e.duration_ns)])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------- pieces
def device_planes(trace: dict) -> list:
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not planes:
        raise NoDevicePlane(
            "no /device:TPU:<n> plane in the trace (planes: "
            f"{[p['name'] for p in trace['planes']]})")
    return planes


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def host_spans(trace: dict) -> list:
    """The benchmark's own spans, ``[name, start_ns, duration_ns]``, sorted."""
    spans = [e for p in trace["planes"] if not DEVICE_PLANE.match(p["name"])
             for line in p["lines"] for e in line["events"]
             if e[0].startswith(SPAN_PREFIX)]
    return sorted(spans, key=lambda e: e[1])


def window_ns(trace: dict) -> tuple:
    """``(start, end)`` of the traced window: the ``bench:window`` span, or,
    where the host recorded none, the extent of the device's operations."""
    for name, start, dur in host_spans(trace):
        if name == WINDOW_SPAN:
            return start, start + dur
    starts, ends = [], []
    for plane in device_planes(trace):
        for e in _line(plane, OPS_LINE):
            starts.append(e[1])
            ends.append(e[1] + e[2])
    if not starts:
        raise NoDevicePlane("the device planes hold no operation")
    return min(starts), max(ends)


def _clipped(events, w0: int, w1: int):
    for e in events:
        a, b = max(e[1], w0), min(e[1] + e[2], w1)
        if b > a:
            yield a, b, e


def busy_intervals(events, w0: int, w1: int) -> list:
    """Union of the events' intervals inside the window, merged, sorted."""
    out = []
    for a, b, _ in sorted(_clipped(events, w0, w1), key=lambda t: t[0]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_and_window_s(trace: dict) -> tuple:
    """Seconds in which an operation ran on the device, averaged over the
    chips in the trace, and the length of the traced window."""
    w0, w1 = window_ns(trace)
    busy = [sum(b - a for a, b in busy_intervals(_line(p, OPS_LINE), w0, w1))
            for p in device_planes(trace)]
    return sum(busy) / len(busy) / 1e9, (w1 - w0) / 1e9


def matched_seconds(trace: dict, patterns, line: str = OPS_LINE) -> float:
    """Device seconds inside the window of the events on ``line`` whose name
    matches one of ``patterns``, averaged over the chips; matching events
    nested in one another are counted once (their union)."""
    rx = [re.compile(p) for p in patterns]
    w0, w1 = window_ns(trace)
    totals, verdict = [], {}
    for plane in device_planes(trace):
        hits = []
        for e in _line(plane, line):
            if e[0] not in verdict:
                verdict[e[0]] = any(r.search(e[0]) for r in rx)
            if verdict[e[0]]:
                hits.append(e)
        totals.append(sum(b - a for a, b in busy_intervals(hits, w0, w1)))
    return sum(totals) / len(totals) / 1e9


def self_times(events, w0: int, w1: int) -> dict:
    """``{name: seconds}`` of each event's own time: its duration less what
    the events nested inside it cover."""
    out: dict = {}
    stack: list = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            _end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + own / 1e9

    for a, b, e in sorted(_clipped(events, w0, w1), key=lambda t: (t[0], -t[1])):
        close(a)
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, e[0], b - a])
    close(float("inf"))
    return out


def _family(name: str) -> str:
    """``%fusion.123 = f32[...] fusion(...)`` and ``fusion.7`` are one family
    of operations, ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"[.\d]+$", "", head) or head


def breakdown(trace: dict, top: int = 10) -> dict:
    """The device operations that took most time (own time, by family of
    operation, first chip) and the longest idle gaps by what the host was
    doing (the innermost ``bench:*`` span over the gap's middle)."""
    w0, w1 = window_ns(trace)
    plane = device_planes(trace)[0]
    ops: dict = {}
    for name, s in self_times(_line(plane, OPS_LINE), w0, w1).items():
        ops[_family(name)] = ops.get(_family(name), 0.0) + s
    spans = [s for s in host_spans(trace) if s[0] != WINDOW_SPAN]
    gaps: dict = {}
    edges = [[w0, w0]] + busy_intervals(_line(plane, OPS_LINE), w0, w1) + [[w1, w1]]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start <= end:
            continue
        if start - end < BETWEEN_OPS_NS:  # the device's own pauses, not the host's
            gaps["device:between operations"] = (
                gaps.get("device:between operations", 0.0) + (start - end) / 1e9)
            continue
        mid = (start + end) // 2
        inner = [s for s in spans if s[1] <= mid < s[1] + s[2]]
        name = min(inner, key=lambda s: s[2])[0] if inner else "host:unattributed"
        gaps[name] = gaps.get(name, 0.0) + (start - end) / 1e9
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}


def structure(trace: dict, top: int = 25) -> str:
    """A hand-readable account of a trace: planes, lines, and the events that
    took most time on each line. For reading a trace before
    writing a pattern against it."""
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            ev = line["events"]
            out.append(f"  LINE {line['name']}: {len(ev)} events")
            agg: dict = {}
            for name, _s, dur in ev:
                t = agg.setdefault(name, [0, 0])
                t[0] += 1
                t[1] += dur
            for name, (n, dur) in sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]:
                out.append(f"    {dur / 1e6:10.3f} ms x{n:<6d} {name[:200]}")
    return "\n".join(out)
