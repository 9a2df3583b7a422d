"""Reader ``decode_scope_time``: ``scope_time``'s milliseconds a step for a
serving cell, where a step is one DECODE step of the traced window.

``scope_time`` divides by the counter ``traced_steps``, which the training
driver and ``hybrid_serve`` give and ``lm_serve`` does not; every serving
driver counts ``decode_steps`` in the traced window, so this reader divides by
that and is ``scope_time`` otherwise (``any_of``, ``none_of``, own device time
by scope path). No trace, no decode step traced, no scope paths or no matching
operation: nothing, never 0.
"""

from benchmarks import program_trace
from benchmarks.readers import scope_time


def read(view: dict, params: dict):
    program_trace.own_seconds_by_scope(view)  # once a run, kept on the run's own view
    counters = dict(view["counters"], traced_steps=view["counters"].get("decode_steps", 0))
    return scope_time.read(dict(view, counters=counters), params)
