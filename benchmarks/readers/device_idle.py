"""Reader ``device_idle``: the share of the traced window in which no
operation ran on the device (1 - union of the device's operations over the
window, averaged over the chips)."""

from benchmarks import trace


def read(view: dict, params: dict):
    if view["trace"] is None:
        return None
    busy_s, window_s = trace.busy_and_window_s(view["trace"])
    return 100.0 * (1.0 - busy_s / window_s)
