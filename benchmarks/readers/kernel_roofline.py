"""Reader ``kernel_roofline``: a kernel's (or a program's) share of its
roofline: the least time the chip could take for the work, over the device
time of the events that did it.

``patterns`` (regular expressions, data in the metric's file) pick the events
on ``line`` of the device plane; ``work`` names the count of operations or
bytes the traced window needed and ``bound`` the peak it is held against.
Where no event matches (the kernel is off the path, or there is no trace) the
reader returns nothing, never 0.
"""

from benchmarks import counts, trace


def needed(view: dict, work: str) -> float:
    c, cfg = view["counters"], view["cell"].config
    if work == "train_attention_flops":
        return counts.attention_train_flops(cfg, c["batch"], c["seq"]) * c.get("traced_steps", 0)
    return float(c.get(work) or 0.0)


def read(view: dict, params: dict):
    if view["trace"] is None:
        return None
    seconds = trace.matched_seconds(view["trace"], params["patterns"], params["line"])
    work = needed(view, params["work"])
    if seconds <= 0.0 or work <= 0.0:
        return None
    return 100.0 * (work / view["peaks"][params["bound"]]) / seconds
