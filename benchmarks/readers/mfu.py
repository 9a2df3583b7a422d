"""Reader ``mfu``: the whole step's share of the chip's peak.

The operations the mathematics needs (``benchmarks/counts.py``: no
recomputation, no padding) for the work done in the traced window, over the
window's length times the peak of the chips the cell holds. ``kind: "train"``
counts the traced steps; ``kind: "serve"`` counts every prompt token prefilled
and every token decoded in the window. Off a chip there is no trace and no
peak, and the reader returns nothing.
"""

from benchmarks import counts


def read(view: dict, params: dict):
    if view["trace"] is None:
        return None
    c, cell = view["counters"], view["cell"]
    if params["kind"] == "train":
        flops = counts.train_step_flops(cell.config, c["batch"], c["seq"]) * c.get("traced_steps", 0)
    else:
        flops = c.get("prefill_flops", 0.0) + c.get("decode_flops", 0.0)
    if not flops:
        return None
    return 100.0 * flops / (view["window_s"] * view["peaks"]["flops_bf16"] * cell.chips)
