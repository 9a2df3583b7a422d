"""What the PROGRAM wrote into a profiler trace: its own host spans with their
attributes, and each device operation's scope path.

``benchmarks/trace.py`` reads a trace from outside: event names, starts and
durations, and of the host only the benchmark's ``bench:*`` spans. This file
reads the same ``.xplane.pb`` a second time (it is still under
``<checkout>/.bench_trace`` while the readers run) for what ``trace.py``
drops, into a plain form the tests keep a recorded copy of::

    {"spans": [[name, start_ns, duration_ns, thread, {attribute: value}], ...],
     "devices": [{"name": "/device:TPU:0",
                  "ops": [[scope_path, start_ns, duration_ns], ...]}]}

**Host spans.** The serving engine writes these through
``utils/tracing.span`` (``jax.profiler.TraceAnnotation``), so they lie on the
clock of the device's events; keyword arguments come back as the event's
stats. A span's cause is the span that encloses it on its thread (the thread
is the ``/host:CPU`` line's name). The engine writes a span only where a
metric reads it; the names, where each is written (``serving/engine.py``,
``serving/cache.py``) and what reads it:

- ``serve.step``: one scheduling round, written only when a request waits or
  a slot is active. ``between_blocks_max_ms.chat`` drops a stretch that holds
  a whole round (the engine went idle in it), and an idle gap under it alone
  is ``idle_unattributed.chat``'s.
- ``serve.prefill``: one request's admission, with ``request_id`` (the
  ``Request``'s, to find the request a wait belongs to) and
  ``queue_wait_us``. ``queue_wait_mean_ms.chat``, ``prefill_host_share.chat``,
  ``idle_prefill.chat``.
- ``serve.decode`` > ``serve.decode.dispatch``, ``serve.decode.fetch``: one
  decode block; the call that enqueues it; the wait for its tokens.
  ``idle_decode.chat``; ``between_blocks_max_ms.chat`` runs from a fetch's end
  to the next dispatch's start.
- ``serve.emit``: the per-slot loop after the fetch, ``on_tokens`` callbacks
  included. ``idle_decode.chat``.

Kept besides: ``bench:window``, the traced window, so that the host-side
reductions work where there is no device plane.

**Scope paths** (read by hand from this machine's v5e, jax 0.9.0, PR 26). An
``XLA Ops`` event carries three stats only (``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier``), and no derived line holds
scopes. The path is in the event's METADATA, which ``jax.profiler.ProfileData``
does not show: each ``XEventMetadata`` of the device plane (its ``name`` is
the event's name, the operation's whole HLO text) has the stats
``hlo_category``, ``program_id``, ``flops``, ``bytes_accessed``, ``source``,
``source_stack`` and ``tf_op``, and ``tf_op`` is the path, for example
``jit(step)/transpose(jvp(TransformerLM))/block_7/attn/pallas_call:``. So
the protobuf's metadata maps are walked here with a few lines of wire-format
reading (no protobuf package is needed), and each ``XLA Ops`` event takes the
path of its own metadata id: names do not do, because two programs in one
plane can hold an operation of the same HLO text under different paths. The
device planes are read this way alone; ``ProfileData`` is parsed once, for the
host spans. What the paths look like: a
forward operation is under ``jvp(...)``, a backward one under
``transpose(jvp(...))``; flax module names follow (``block_3/attn/q``,
``Dense_0``, ``tok_embed``); a fusion carries ONE path, its root's, so an
optimizer update the compiler fused into a weight-gradient matmul counts as
backward; copies and slices in flight (``copy-done``, ``slice-done``) have no
path at all and read as ``""``. ``fsdp.py``'s dense loss multiplies by the
head's kernel itself, so head and loss lie under the anonymous ``jvp()`` /
``transpose(jvp())`` and ``jvp(jit(take_along_axis))``, not under ``lm_head``:
a ``jvp`` of no flax module, followed by the primitive and no module's name,
which is how ``head_loss_ms_per_step.train`` picks them, whatever the model's
class is called. These paths are debug
information, which JAX's persistent compilation cache strips before it hashes
a program: an executable out of the cache carries the names of whichever
build compiled it first. The metrics here read only scopes that the program
has written since before PR 26, and none that a PR adds to a jitted program
can be trusted across a shared cache.

**Adding a metric** needs no code: a ``metrics/<name>.json`` that names the
reader ``program_span`` (host spans: ``measure`` is ``attr_mean``,
``union_share``, ``gap_max`` or ``idle_under``, see ``readers/program_span.py``)
or ``scope_time`` (device own time of the operations whose path matches
``any_of`` and none of ``none_of``, as milliseconds a traced step or, with
``work`` and ``work_share``, as a share of a roofline, see
``readers/scope_time.py``), and an entry in ``BENCHMARK.json``. A reader that
finds no span or no matching operation returns nothing, never 0.
"""

from __future__ import annotations

import glob
import os

from benchmarks import harness, trace

SPAN_PREFIX = "serve."
SCOPE_STAT = "tf_op"


def xplane_path(view: dict):
    """The run's ``.xplane.pb``, or nothing where no profile was left."""
    paths = sorted(glob.glob(os.path.join(
        view["cell"].root, harness.TRACE_DIR, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def _cached(view: dict, key: str, load):
    """``load(path)`` once a run: the result rides the run's own ``view``."""
    if key not in view:
        path = xplane_path(view)
        view[key] = load(path) if path else None
    return view[key]


def host_spans(view: dict):
    """``[[name, start_ns, duration_ns, thread, attributes], ...]`` sorted by
    start, or nothing."""
    return _cached(view, "program_spans", load_spans)


def device_ops(view: dict):
    """``[{"name": plane, "ops": [[scope, start_ns, duration_ns], ...]}]``
    of the accelerator planes, or nothing."""
    devices = _cached(view, "program_devices", load_devices)
    return devices or None


# ------------------------------------------------------------ the two loads
def load_spans(path: str) -> list:
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue  # a million events a second, none of them a host span
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX) or e.name == trace.WINDOW_SPAN:
                    spans.append([e.name, int(e.start_ns), int(e.duration_ns), line.name,
                                  {k: v for k, v in e.stats if not k.startswith("_")}])
    return sorted(spans, key=lambda s: s[1])


def load_devices(path: str) -> list:
    with open(path, "rb") as fh:
        return devices_of(fh.read())


# ----------------------------------------------- protobuf, the device planes
# XSpace.planes = 1; XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5
# (maps: key 1, value 2); XLine: name 2, timestamp_ns 3, events 4; XEvent:
# metadata_id 1, offset_ps 2, duration_ps 3; XEventMetadata: name 2, stats 5;
# XStatMetadata: name 2; XStat: metadata_id 1, str_value 5, ref_value 7 (a
# stat_metadata id whose name is the string).
def _varint(buf, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key = buf[i]  # one byte for every field number under 16
        if key < 0x80:
            i += 1
        else:
            key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_entry(buf):
    key = value = None
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _text(fields, number: int) -> str:
    return next((bytes(v).decode() for f, v in fields if f == number), "")


def _scope_by_metadata_id(plane_fields) -> dict:
    """``{event metadata id: scope path}`` of one plane. The id, not the
    event's name, is the key: two programs in one plane (the prefill buckets
    and the decode block) can hold an operation of the same HLO text under
    different paths."""
    stat_names = {}
    for f, v in plane_fields:
        if f == 5:
            key, meta = _map_entry(v)
            stat_names[key] = _text(_fields(meta), 2)
    scope_ids = {k for k, n in stat_names.items() if n == SCOPE_STAT}
    paths = {}
    for f, v in plane_fields:
        if f != 4 or not scope_ids:
            continue
        key, meta = _map_entry(v)
        for g, x in _fields(meta):
            if g == 5:
                stat = dict(_fields(x))
                if stat.get(1) in scope_ids:
                    paths[key] = (bytes(stat[5]).decode() if 5 in stat
                                  else stat_names.get(stat.get(7), ""))
    return paths


def devices_of(data: bytes) -> list:
    """The accelerator planes of a serialized ``XSpace`` whose event metadata
    carries scope paths, each ``XLA Ops`` event with its own metadata's path
    (``""`` where it has none); start and duration in whole nanoseconds on
    the line's clock, as ``ProfileData`` gives them."""
    devices = []
    for field, plane in _fields(memoryview(data)):
        if field != 1:
            continue
        plane_fields = list(_fields(plane))
        name = _text(plane_fields, 2)
        if not trace.DEVICE_PLANE.match(name):
            continue
        paths = _scope_by_metadata_id(plane_fields)
        if not paths:
            continue
        for f, line in plane_fields:
            if f != 3:
                continue
            if _text(_fields(line), 2) != trace.OPS_LINE:  # the name comes first
                continue
            line_fields = list(_fields(line))
            t0 = next((v for g, v in line_fields if g == 3), 0)
            ops = []
            for g, event in line_fields:
                if g != 4:
                    continue
                key = offset_ps = duration_ps = 0
                for h, x in _fields(event):
                    if h == 1:
                        key = x
                    elif h == 2:
                        offset_ps = x
                    elif h == 3:
                        duration_ps = x
                ops.append([paths.get(key, ""), t0 + offset_ps // 1000, duration_ps // 1000])
            devices.append({"name": name, "ops": ops})
    return devices


# -------------------------------------------------------------- reductions
def window_ns(view: dict, spans) -> tuple:
    """The traced window: the device trace's where there is one, else the
    ``bench:window`` span among the host spans, else nothing."""
    if view.get("trace") is not None:
        return trace.window_ns(view["trace"])
    for name, start, dur, _thread, _attrs in spans or ():
        if name == trace.WINDOW_SPAN:
            return start, start + dur
    return None


def under(name: str, prefixes) -> bool:
    """Whether a span called ``name`` is one of ``prefixes`` or a child of one
    (``a.b`` is under ``a``)."""
    return any(name == p or name.startswith(p + ".") for p in prefixes)


def idle_gaps(view: dict) -> list:
    """``[(start_ns, end_ns), ...]`` inside the traced window in which no
    operation ran on the first chip for ``trace.BETWEEN_OPS_NS`` or longer
    (a shorter pause is the device's own, not the host's); once a run."""
    if "program_idle_gaps" not in view:
        tr = view["trace"]
        w0, w1 = trace.window_ns(tr)
        ops = next(line["events"] for line in trace.device_planes(tr)[0]["lines"]
                   if line["name"] == trace.OPS_LINE)
        edges = [[w0, w0]] + trace.busy_intervals(ops, w0, w1) + [[w1, w1]]
        view["program_idle_gaps"] = [
            (end, start) for (_, end), (start, _) in zip(edges, edges[1:])
            if start - end >= trace.BETWEEN_OPS_NS]
    return view["program_idle_gaps"]


def own_seconds_by_scope(view: dict):
    """``{scope path: seconds}`` of each operation's own device time inside
    the traced window (nested operations taken out, as ``trace.self_times``
    does), averaged over the chips; once a run."""
    if "program_scope_seconds" not in view:
        devices = device_ops(view)
        if not devices or view.get("trace") is None:
            view["program_scope_seconds"] = None
        else:
            w0, w1 = trace.window_ns(view["trace"])
            total: dict = {}
            for device in devices:
                for scope, s in trace.self_times(device["ops"], w0, w1).items():
                    total[scope] = total.get(scope, 0.0) + s / len(devices)
            view["program_scope_seconds"] = total
    return view["program_scope_seconds"]
