"""Reader ``counter``: a number the driver counted in the window (the
engine's own ``slo_summary``, the generator's lateness), scaled."""


def read(view: dict, params: dict):
    value = view["counters"].get(params["counter"])
    if value is None:
        return None
    return float(value) * float(params.get("scale", 1.0))
