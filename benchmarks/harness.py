"""The benchmark's harness: everything a run does that is not one kind of
workload's own.

It is driven by data. ``BENCHMARK.json`` names cells, configurations and
metrics; whatever belongs to one of them is a file found by that name in one
of the directories ``paths`` lists:

- ``workloads/<cell>.json``: driver kind, traffic parameters, reckoned bytes
- ``configs/<config>.json``: the model's sizes as run, and its reference
- ``metrics/<metric>.json``: a per-layer metric's reader and its parameters
- ``drivers/<kind>.py``: build, warm, run the window, compare
- ``readers/<reader>.py``: trace, spans or counters to one number
- ``reference/<name>.py``: a configuration's plain reference

so a later PR adds a cell, a configuration, a metric, a reader or a driver by
adding files and entries, and edits none that is there.
"""

from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import time

from benchmarks import trace as trace_mod

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_DIR = ".bench_trace"  # inside the checkout, git-ignored, one run's trace at most


# ------------------------------------------------------------- finding files
def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def find_file(root: str, manifest: dict, kind: str, name: str, ext: str) -> str:
    """``<path>/<kind>/<name><ext>`` in the first of ``paths`` that has it."""
    tried = []
    for base in manifest["paths"]:
        path = os.path.join(root, base, kind, name + ext)
        if os.path.isfile(path):
            return path
        tried.append(path)
    raise FileNotFoundError(f"no {kind} file for {name!r}: tried {tried}")


def load_json(root, manifest, kind, name) -> dict:
    with open(find_file(root, manifest, kind, name, ".json")) as fh:
        return json.load(fh)


def load_module(root, manifest, kind, name):
    """Import ``<path>/<kind>/<name>.py`` by its file, under a name of its own."""
    path = find_file(root, manifest, kind, name, ".py")
    mod_name = f"benchmark_{kind}_{name}".replace("-", "_").replace(".", "_")
    if mod_name in sys.modules and getattr(sys.modules[mod_name], "__file__", None) == path:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with the files it names."""

    def __init__(self, root: str, manifest: dict, name: str):
        entries = {w["name"]: w for w in manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has {sorted(entries)}")
        self.root, self.manifest, self.name = root, manifest, name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.workload = load_json(root, manifest, "workloads", name)
        config_entry = {c["name"]: c for c in manifest["configs"]}[self.entry["config"]]
        with open(os.path.join(root, config_entry["file"])) as fh:
            self.config = json.load(fh)
        self.peaks_table = load_peaks(root, manifest)

    def reference(self):
        return load_module(self.root, self.manifest, "reference", self.config["reference"])

    def metric_names(self, group: str) -> list:
        """The metrics of ``end_to_end`` or ``per_layer`` that this cell reports."""
        return [m["name"] for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def load_peaks(root, manifest) -> dict:
    for base in manifest["paths"]:
        path = os.path.join(root, base, "peaks.json")
        if os.path.isfile(path):
            with open(path) as fh:
                return json.load(fh)["devices"]
    raise FileNotFoundError("no peaks.json under any of `paths`")


def peaks_for(table: dict, kind: str) -> dict:
    """The published peaks of ``kind``; a device not in the table is an error."""
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in peaks.json (has {sorted(table)})")
    return table[kind]


# ------------------------------------------------------------------- device
def require_chips(n: int) -> list:
    """The ``n`` accelerator chips the cell asks for, or exit without a result."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend could be initialised
        raise SystemExit(f"benchmark: JAX found no device: {e}")
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"benchmark: the cell needs {n} TPU chip(s); JAX reports "
              f"{len(devices)} x {devices[0].platform} ({devices[0].device_kind})",
              file=sys.stderr)
        raise SystemExit(3)
    return devices[:n]


def device_block(devices) -> dict:
    """The ``device`` key of the result line, as JAX reports the device.
    ``memory_peak_bytes`` is the peak on the fullest chip."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def seed_key(seed: int):
    """A PRNG key from any whole number (seeds run past 32 signed bits)."""
    import jax

    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache, where ``JAX_COMPILATION_CACHE_DIR``
    says or at the fixed path ``<checkout>/.jax_cache``; every program kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no cap: the chip machines cap the cache at 192 MiB, a training step's
    # program is larger than that, and a program that is not kept is compiled
    # again, for minutes, by every run
    jax.config.update("jax_compilation_cache_max_size", -1)
    return jax.config.jax_compilation_cache_dir


# ------------------------------------------------------------------ tracing
class Tracer:
    """The profiler around a sub-window of the measured window. The driver
    calls :meth:`start` and :meth:`stop` between two pieces of work, with the
    device idle; :meth:`span` names what the host does, on the trace's clock."""

    def __init__(self, root: str, enabled: bool):
        self.enabled = enabled
        self.dir = os.path.join(root, TRACE_DIR)
        self.active = False
        self._window = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1   # the benchmark's own spans, little else
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._window = self.span("bench:window")
        self._window.__enter__()
        self.active = True

    def stop(self) -> None:
        import jax

        self._window.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.active = False

    @staticmethod
    def span(name: str, **kw):
        import jax

        return jax.profiler.TraceAnnotation(name, **kw)

    def load(self) -> dict:
        paths = sorted(glob.glob(os.path.join(self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError(f"the profiler left no trace under {self.dir}")
        return trace_mod.load_xplane(paths[-1])


# --------------------------------------------------------------------- a run
class Context:
    """What a driver is given."""

    def __init__(self, cell: Cell, seed: int, seconds: float, tracer: Tracer, devices):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.tracer, self.devices = tracer, devices
        self.config, self.workload = cell.config, cell.workload


def held(numbers: dict, limits: dict) -> list:
    """``(name, value, limit)`` for each number that has a limit; a number
    that is not finite is written as 1e30 (JSON has no NaN) and so fails."""
    finite = lambda v: float(v) if abs(float(v)) < 1e30 else 1e30
    return [(name, finite(numbers[name]), limit) for name, limit in limits.items()]


def per_layer_metrics(cell: Cell, view: dict) -> dict:
    """Each per-layer metric of the cell through its reader. A reader that
    finds nothing to read returns ``None`` and the metric is left out."""
    out = {}
    for entry in cell.manifest["per_layer"]:
        if "workloads" in entry and cell.name not in entry["workloads"]:
            continue
        spec = load_json(cell.root, cell.manifest, "metrics", entry["name"])
        reader = load_module(cell.root, cell.manifest, "readers", spec["reader"])
        value = reader.read(view, spec.get("params", {}))
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool, *, root: str = ROOT,
             t_start: float | None = None, devices=None) -> dict:
    """One run of one cell; returns the result line as a dictionary.

    ``devices`` given skips the look for a chip (the tests' way in: they hand
    over CPU devices and get a line whose device says so and that holds no
    share of a peak)."""
    t_start = time.perf_counter() if t_start is None else t_start
    manifest = load_manifest(root)
    cell = Cell(root, manifest, name)
    if devices is None:
        devices = require_chips(cell.chips)
        enable_compile_cache(root)
    on_chip = devices[0].platform == "tpu"
    tracer = Tracer(root, enabled=bool(trace))
    ctx = Context(cell, seed, seconds, tracer, devices)
    driver = load_module(root, manifest, "drivers", cell.workload["driver"])

    session = driver.setup(ctx)
    setup_s = time.perf_counter() - t_start
    window = session.run_window()          # {"attempted", "failed", "metrics", "counters"}
    device = device_block(devices)         # the peak is read before the reference runs
    session.release()                      # the program's state is freed
    checks = session.compare()             # [(name, value, limit), ...]

    if trace:
        view = {"cell": cell, "counters": window["counters"], "trace": None,
                "peaks": None, "window_s": None}
        if on_chip:
            view["trace"] = tracer.load()
            view["peaks"] = peaks_for(cell.peaks_table, device["kind"])
            busy_s, window_s = trace_mod.busy_and_window_s(view["trace"])
            view["window_s"] = window_s
            device.update(busy_s=busy_s, window_s=window_s)
        metrics = per_layer_metrics(cell, view)
        shutil.rmtree(tracer.dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        values = dict(window["metrics"], setup_s=setup_s)
        metrics = {n: {"value": float(values[n]), "unit": units[n]}
                   for n in cell.metric_names("end_to_end")}

    result = {
        "correct": all(value <= limit for _n, value, limit in checks) and bool(checks),
        "attempted": int(window["attempted"]),
        "failed": int(window["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if trace and on_chip:
        result["breakdown"] = trace_mod.breakdown(view["trace"])
    result["compared"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result


def emit(result: dict) -> None:
    """The numbers compared, each beside its limit, as the last lines on
    standard error; the result as the last line on standard output."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} = {c['value']!r} limit {c['limit']!r} {verdict}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
