"""Reader ``program_span``: the program's own host spans (``serve.*``, written
by the serving engine into the profiler's trace, ``benchmarks/program_trace.py``)
to one number over the traced window. ``measure`` says which:

- ``attr_mean``: mean of attribute ``attr`` over the spans named ``span`` that
  start in the window, times ``scale``.
- ``union_share``: the share (%) of the window that spans named ``span``
  cover, overlaps counted once.
- ``gap_max``: the longest stretch, times ``scale``, from the end of a span
  named ``from`` to the start of the next named ``to``, both in the window; a
  stretch that holds a whole ``serve.step`` is left out (a round that
  dispatched nothing: no slot was waiting for the host).
- ``idle_under``: the share (%) of the window in which the device ran nothing
  (gaps of ``trace.BETWEEN_OPS_NS`` or more) and the innermost ``serve.*`` span
  over the gap's middle is one of ``under`` or a child of one; with
  ``not_under`` instead, every other gap, those under no span among them.
  Needs the device trace, so off a chip it reads nothing.

No span of the name in the window (a program that writes none, as before
PR 26): nothing, never 0.
"""

from benchmarks import program_trace, trace


def read(view: dict, params: dict):
    spans = program_trace.host_spans(view)
    window = program_trace.window_ns(view, spans)
    if not spans or window is None:
        return None
    w0, w1 = window
    serve = [s for s in spans if s[0].startswith(program_trace.SPAN_PREFIX)
             and s[1] < w1 and s[1] + s[2] > w0]
    if not serve:
        return None
    return MEASURES[params["measure"]](view, serve, w0, w1, params)


def attr_mean(view, spans, w0, w1, params):
    values = [s[4][params["attr"]] for s in spans
              if s[0] == params["span"] and w0 <= s[1] < w1 and params["attr"] in s[4]]
    if not values:
        return None
    return float(params.get("scale", 1.0)) * sum(values) / len(values)


def union_share(view, spans, w0, w1, params):
    mine = [s for s in spans if s[0] == params["span"]]
    if not mine:
        return None
    covered = sum(b - a for a, b in trace.busy_intervals(mine, w0, w1))
    return 100.0 * covered / (w1 - w0)


def gap_max(view, spans, w0, w1, params):
    inside = [s for s in spans if s[1] >= w0 and s[1] + s[2] <= w1]
    steps = [s for s in inside if s[0] == "serve.step"]
    longest, end = None, None
    for s in inside:  # sorted by start
        if s[0] == params["to"] and end is not None:
            idle_round = any(end <= r[1] and r[1] + r[2] <= s[1] for r in steps)
            if not idle_round and (longest is None or s[1] - end > longest):
                longest = s[1] - end
            end = None
        if s[0] == params["from"]:
            end = s[1] + s[2]
    return None if longest is None else float(params.get("scale", 1.0)) * longest


def idle_under(view, spans, w0, w1, params):
    if view.get("trace") is None:
        return None
    group = params.get("under") or params["not_under"]
    total = 0
    for a, b in program_trace.idle_gaps(view):
        mid = (a + b) // 2
        over = [s for s in spans if s[1] <= mid < s[1] + s[2]]
        inner = min(over, key=lambda s: s[2])[0] if over else ""
        if program_trace.under(inner, group) == ("under" in params):
            total += b - a
    return 100.0 * total / (w1 - w0)


MEASURES = {"attr_mean": attr_mean, "union_share": union_share, "gap_max": gap_max,
            "idle_under": idle_under}
