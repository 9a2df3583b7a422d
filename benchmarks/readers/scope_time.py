"""Reader ``scope_time``: device time of the operations the PROGRAM put under
a scope, whatever implements them.

An operation's scope path is the one its trace metadata carries
(``benchmarks/program_trace.py``: ``jit(step)/transpose(jvp(TransformerLM))/
block_7/attn/pallas_call:``); an operation without one has the path ``""``.
``any_of`` and ``none_of`` (regular expressions, data in the metric's file) pick
the paths: one of ``any_of`` must match where the list is given, and none of
``none_of``. What is summed is each operation's OWN time in the traced window
(a ``while`` does not count its body twice), averaged over the chips.

Without ``work`` the number is milliseconds a traced step. With ``work`` (a
function of ``benchmarks/counts.py`` taking the configuration, batch and
sequence length) and ``work_share`` it is the share of the roofline ``bound``:
the least time for that work over the time the scope took. No trace, no scope
paths in it, or no matching operation: nothing, never 0.
"""

import re

from benchmarks import counts, program_trace


def selected(path: str, any_of, none_of) -> bool:
    if any(r.search(path) for r in none_of):
        return False
    return not any_of or any(r.search(path) for r in any_of)


def read(view: dict, params: dict):
    own = program_trace.own_seconds_by_scope(view)
    steps = view["counters"].get("traced_steps", 0)
    if not own or not any(own) or not steps:  # no trace, or no operation with a path
        return None
    any_of = [re.compile(p) for p in params.get("any_of", [])]
    none_of = [re.compile(p) for p in params.get("none_of", [])]
    seconds = sum(s for path, s in own.items() if selected(path, any_of, none_of))
    if seconds <= 0.0:
        return None
    if "work" not in params:
        return 1e3 * seconds / steps
    c = view["counters"]
    work = (getattr(counts, params["work"])(view["cell"].config, c["batch"], c["seq"])
            * steps * float(params.get("work_share", 1.0)))
    return 100.0 * (work / view["peaks"][params["bound"]]) / seconds
