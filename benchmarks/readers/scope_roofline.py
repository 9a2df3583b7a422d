"""Reader ``scope_roofline``: the share of a roofline that the operations the
PROGRAM put under a scope reach, for work the driver counted.

The operations are picked as ``scope_time`` picks them: by scope path
(``benchmarks/program_trace.py``), ``any_of`` and ``none_of`` regular
expressions, each operation's OWN device time in the traced window. ``work``
names a counter of the window (bytes or operations the mathematics needed for
what ran under the scope, counted by the driver from shapes) and ``bound`` the
peak it is held against: the least time for that work over the time the scope
took. No trace, no scope paths in it, no matching operation or nothing
counted: nothing, never 0. No share is clipped.
"""

import re

from benchmarks import program_trace
from benchmarks.readers.scope_time import selected


def read(view: dict, params: dict):
    own = program_trace.own_seconds_by_scope(view)
    work = float(view["counters"].get(params["work"]) or 0.0)
    if not own or not any(own) or work <= 0.0:
        return None
    any_of = [re.compile(p) for p in params.get("any_of", [])]
    none_of = [re.compile(p) for p in params.get("none_of", [])]
    seconds = sum(s for path, s in own.items() if selected(path, any_of, none_of))
    if seconds <= 0.0:
        return None
    return 100.0 * (work / view["peaks"][params["bound"]]) / seconds
