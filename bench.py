"""Benchmark harness (BASELINE.md config #1, the reference's headline workload).

Measures steady-state training throughput (images/sec/chip) of the flagship
AlexNet on CIFAR-10-shaped data on the TPU chip (with no chip the benchmark
fails: a rate from another device is not this metric), as THREE first-class
legs reported side by side in one JSON record (round 9 — the ceiling the round-5 audit
measured is now the shipped number):

- ``parity_b64`` — the reference training recipe exactly (batch 64, SGD
  lr 0.008, reference ``example/main.py:142,144-145``): the parity leg
  every trajectory/steps-to-accuracy comparison anchors to.
- ``large_batch_b1024`` — the identical architecture at batch 1024 with
  Pallas-fused conv epilogues (``ops/fused_conv.py``): the throughput
  leg, and the record's headline ``value``.
- ``grad_accum_b1024`` — batch 1024 as a microbatch-256 accumulation
  scan whose applied update is scaled to the SUM of the sixteen
  batch-64 mean-gradient updates at frozen params
  (``make_accum_train_step(effective_update_batch=64)``): large-batch
  geometry, batch-64-recipe effective update (first-order).

Every leg records its ``mfu_floor`` from ``bench_floors.json``; ``--gate``
re-checks measured MFU against the floors and exits non-zero on a breach
(``--json FILE`` gates a canned/previous record with no device run — the
``make bench-gate`` tier-1 smoke), so the headline can never silently
regress below its recorded floor again.

``vs_baseline`` is measured, not assumed: the reference's own workload
(``make single`` configuration, batch 64) is timed in torch on CPU (the
reference publishes no numbers, BASELINE.md). The printed ratio is the
headline leg's images/sec over torch-CPU-images/sec; the baseline keeps
the reference's fixed recipe because that IS the baseline.

Prints exactly ONE JSON line on stdout; all narration goes to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

BATCH = 64
LR = 0.008
SCAN_K = 100       # steps fused into one compiled program (lax.scan)
LARGE_BATCH = 1024       # the throughput legs' batch (audited plateau zone)
LARGE_SCAN_K = 20        # updates per compiled program for the large legs
ACCUM_MICROBATCH = 256   # grad-accum leg: 4 microbatches per update
EFFECTIVE_UPDATE = 64    # ...whose update preserves the batch-64 recipe
TRIALS = 5         # scan dispatches inside the traced window
BASELINE_STEPS = 12
FLOORS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bench_floors.json")
HEADLINE_LEG = "large_batch_b1024"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Rate(float):
    """images/sec (or tokens/sec) that also carries the leg's FLOPs story:
    ``.flops_per_step`` (XLA's count for one step), ``.tflops`` (achieved),
    ``.mfu`` (fraction of the chip's bf16 peak) — any may be None when the
    backend doesn't report flops or the device kind has no peak entry."""

    flops_per_step: float | None = None
    tflops: float | None = None
    mfu: float | None = None

    @staticmethod
    def make(value: float, flops_per_step, step_seconds) -> "Rate":
        from distributed_ml_pytorch_tpu.utils.flops import utilization

        r = Rate(value)
        r.flops_per_step = flops_per_step
        r.tflops, r.mfu = utilization(flops_per_step, step_seconds)
        return r

    def mfu_note(self) -> str:
        """Human fragment for BASELINE notes: '12.3 TFLOP/s, 6.2% MFU'."""
        if self.tflops is None:
            return "flops not reported by backend"
        if self.mfu is None:
            return f"{self.tflops:.1f} TFLOP/s (no peak table for device)"
        return f"{self.tflops:.1f} TFLOP/s, {self.mfu:.1%} MFU"

    def record_fields(self) -> dict:
        """The FLOPs story as JSON record fields of bench.py's headline."""
        rec = {}
        if self.tflops is not None:
            rec["flops_per_step"] = self.flops_per_step
            rec["tflops"] = round(self.tflops, 2)
            if self.mfu is not None:
                rec["mfu"] = round(self.mfu, 4)
        return rec


def make_batch(batch: int, seed: int = 0, k: int = 0,
               shape: tuple = (32, 32, 3), n_classes: int = 10):
    """Synthetic image batch (CIFAR-shaped by default); ``k > 0`` stacks k
    distinct microbatches on a leading axis (for the scanned trainer)."""
    rng = np.random.default_rng(seed)
    n = (k or 1) * batch
    images = rng.normal(size=(n, *shape)).astype(np.float32)
    labels = (np.arange(n) % n_classes).astype(np.int32)
    if k:
        return images.reshape(k, batch, *shape), labels.reshape(k, batch)
    return images, labels


def bench_jax(batch: int = BATCH, k: int | None = None, model=None,
              input_shape: tuple = (32, 32, 3), n_classes: int = 10,
              trials: int | None = None,
              step_builder=None, flops_override=None) -> float:
    """Steady-state images/sec of the scanned AlexNet trainer on the chip.

    Measurement boundary: K distinct microbatches train inside ONE compiled
    program (``make_scan_train_step``'s ``lax.scan``), so host dispatch is
    amortized — the framework's idiomatic execution for small models — and
    the time is the program's span on the device's own timeline
    (``utils/devtime.device_time``), ``trials`` dispatches of it, divided by
    K. Raises without a TPU: a CPU has no such timeline and no peak to set
    the rate against.

    ``step_builder(model, tx)`` overrides the compiled program (default
    ``make_scan_train_step``; the grad-accum leg passes
    ``make_scan_accum_train_step``) — it must keep the
    ``(state, images [k,B,...], labels [k,B], rng) → (state, losses [k])``
    contract so the timing/flops machinery applies unchanged.
    ``flops_override`` replaces XLA's per-dispatch flop count for legs
    whose program nests a scan (cost_analysis counts each scan body ONCE,
    so a microbatch scan inside the update body under-reports by the
    microbatch count; the caller passes the equivalent plain-step count).
    """
    import jax

    from distributed_ml_pytorch_tpu.models import AlexNet
    from distributed_ml_pytorch_tpu.runtime.startup import require_tpu
    from distributed_ml_pytorch_tpu.training.trainer import (
        create_train_state,
        make_scan_train_step,
    )
    from distributed_ml_pytorch_tpu.utils.devtime import device_time
    from distributed_ml_pytorch_tpu.utils.flops import compiled_flops

    require_tpu("bench_jax")
    k = SCAN_K if k is None else k
    trials = trials or TRIALS

    model = model if model is not None else AlexNet(num_classes=10)
    state, tx = create_train_state(
        model, jax.random.key(0), lr=LR, sample_shape=(1, *input_shape)
    )
    train_scan = (step_builder or make_scan_train_step)(model, tx)
    images, labels = make_batch(batch, k=k, shape=input_shape, n_classes=n_classes)
    images = jax.device_put(images)
    labels = jax.device_put(labels)
    rng = jax.random.key(1)

    losses = None
    for _ in range(2):  # compile + cache warmup
        state, losses = train_scan(state, images, labels, rng)
    float(losses[-1])

    holder = {"s": state, "l": losses}

    def one_call():
        holder["s"], holder["l"] = train_scan(
            holder["s"], images, labels, rng)
        return holder["l"]

    t = device_time(one_call, calls=max(2, trials), warmup=1)
    per_step = t.per_call_s / k
    state, losses = holder["s"], holder["l"]
    log(f"  device-true: {t.per_call_ms:.2f} ms per {k}-step scan "
        f"({t.calls} traced calls)")

    # XLA's cost_analysis counts a lax.scan body ONCE (not x trip count —
    # verified against a bare scanned matmul), so the k-step scan program's
    # reported flops ARE the per-step flops (+ negligible outside-body ops)
    if flops_override is not None:
        scan_flops = flops_override
    else:
        scan_flops = compiled_flops(train_scan, state, images, labels, rng)
    rate = Rate.make(batch / per_step, scan_flops, per_step)
    dev = jax.devices()[0]
    log(f"jax [{dev.platform} {dev.device_kind}]: device-true trace, batch "
        f"{batch}, {k}-step scans → {per_step * 1e6:.1f} us/step, "
        f"{rate:.1f} img/s ({rate.mfu_note()}), final loss {float(losses[-1]):.4f}")
    return rate


def make_torch_alexnet():
    """The reference's CIFAR AlexNet as one torch Sequential (SURVEY.md C7) —
    the single spec shared by the throughput baseline here and the parity
    tests (``tests/test_parity.py``)."""
    import torch.nn as tnn

    return tnn.Sequential(
        tnn.Conv2d(3, 64, 11, stride=4, padding=5), tnn.ReLU(),
        tnn.MaxPool2d(2, 2),
        tnn.Conv2d(64, 192, 5, padding=2), tnn.ReLU(),
        tnn.MaxPool2d(2, 2),
        tnn.Conv2d(192, 384, 3, padding=1), tnn.ReLU(),
        tnn.Conv2d(384, 256, 3, padding=1), tnn.ReLU(),
        tnn.Conv2d(256, 256, 3, padding=1), tnn.ReLU(),
        tnn.MaxPool2d(2, 2),
        tnn.Flatten(),
        tnn.Linear(256, 10),
    )


def bench_torch_cpu(batch: int = BATCH, steps: int = BASELINE_STEPS) -> float | None:
    """images/sec of the reference workload (torch CPU, same recipe).

    The model is the reference's CIFAR AlexNet re-stated from its architecture
    spec (SURVEY.md C7: five convs 3→64 k11 s4 p5 / 64→192 k5 p2 / 192→384 k3
    p1 / 384→256 k3 p1 / 256→256 k3 p1, three 2×2 maxpools, Linear(256, 10)).
    """
    try:
        import torch
        import torch.nn.functional as F
    except Exception as e:  # torch unavailable: no measured baseline
        log(f"torch baseline unavailable: {e}")
        return None

    torch.manual_seed(0)
    model = make_torch_alexnet()
    opt = torch.optim.SGD(model.parameters(), lr=LR, momentum=0.0)
    images_np, labels_np = make_batch(batch)
    images = torch.from_numpy(images_np.transpose(0, 3, 1, 2).copy())  # NCHW
    labels = torch.from_numpy(labels_np.astype(np.int64))

    def step():
        opt.zero_grad()
        loss = F.cross_entropy(model(images), labels)
        loss.backward()
        opt.step()
        return loss.detach()

    for _ in range(2):
        step()
    rates = []
    for _ in range(3):  # the CPU is shared; median out scheduler noise
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step()
        dt = time.perf_counter() - t0
        rates.append(steps * batch / dt)
    med = float(np.median(rates))
    log(f"torch [cpu]: median of 3x{steps}-step windows, batch {batch} "
        f"→ {med:.1f} img/s, final loss {float(loss):.4f}")
    return med


def run_headline_legs() -> dict:
    """Measure the three config-1 legs; ``{leg_name: Rate}``.

    The grad-accum leg's MFU numerator reuses the large-batch leg's XLA
    flop count: its program nests the microbatch scan inside the update
    body and ``cost_analysis`` counts scan bodies once (under-reporting by
    the microbatch count), while the real work per update — conv
    forward/backward over the same 1024 images plus one full-size
    optimizer apply — matches the plain batch-1024 step's count.

    The large legs run the Pallas-fused epilogues; a kernel the compiler or
    the runtime rejects fails the benchmark (there is no unfused rerun to
    hide it behind).
    """
    from distributed_ml_pytorch_tpu.models import AlexNet
    from distributed_ml_pytorch_tpu.training.trainer import (
        make_scan_accum_train_step,
    )

    legs: dict = {}
    log("--- leg parity_b64 (reference recipe)")
    legs["parity_b64"] = bench_jax()
    fused = AlexNet(num_classes=10, fused_epilogue=True)
    log("--- leg large_batch_b1024 (fused epilogues)")
    large = bench_jax(batch=LARGE_BATCH, model=fused, k=LARGE_SCAN_K)
    large.fused_epilogue = True
    legs["large_batch_b1024"] = large
    log("--- leg grad_accum_b1024 (microbatch scan, batch-64 effective update)")
    accum = bench_jax(
        batch=LARGE_BATCH, model=fused, k=LARGE_SCAN_K,
        step_builder=lambda m, tx: make_scan_accum_train_step(
            m, tx, ACCUM_MICROBATCH, effective_update_batch=EFFECTIVE_UPDATE),
        flops_override=large.flops_per_step,
    )
    accum.fused_epilogue = True
    legs["grad_accum_b1024"] = accum
    return legs


#: per-leg honesty notes for the headline record
LEG_NOTES = {
    "parity_b64": (
        "reference recipe (batch 64, SGD lr 0.008) — the trajectory-parity "
        "leg; conv-geometry-bound (per-fusion audit, BASELINE.md #1)"),
    "large_batch_b1024": (
        "identical architecture, batch 1024, Pallas-fused conv epilogues "
        "(ops/fused_conv.py) — the audited ~35%-MFU plateau as the shipped "
        "headline"),
    "grad_accum_b1024": (
        "batch 1024 as a microbatch-256 accumulation scan; applied update "
        "= sum of the 16 batch-64 mean-grad updates at frozen params "
        "(first-order equal to 16 recipe steps); flops numerator = the "
        "plain batch-1024 program's XLA count (nested-scan bodies are "
        "counted once by cost_analysis)"),
}


def load_floors(path: str | None = None) -> dict:
    """The checked-in MFU floors: ``{"tolerance": f, "legs": {name: floor}}``."""
    with open(path or FLOORS_PATH) as fh:
        floors = json.load(fh)
    return floors


def build_record(legs: dict, torch_base: float | None,
                 floors: dict | None = None) -> dict:
    """The one-line headline JSON: headline value = the large-batch leg,
    every leg reported side by side with its recorded ``mfu_floor``."""
    headline = legs[HEADLINE_LEG]
    rec = {
        "metric": "alexnet_cifar10_train_throughput_per_chip",
        "value": round(float(headline), 1),
        "unit": "images/sec/chip",
        "vs_baseline": round(float(headline) / torch_base, 2) if torch_base else None,
        "headline_leg": HEADLINE_LEG,
    }
    if isinstance(headline, Rate):
        rec.update(headline.record_fields())
    floor_legs = (floors or {}).get("legs", {})
    batches = {"parity_b64": BATCH, "large_batch_b1024": LARGE_BATCH,
               "grad_accum_b1024": LARGE_BATCH}
    rec["legs"] = {}
    for name, rate in legs.items():
        leg = {"img_per_s": round(float(rate), 1), "batch": batches.get(name)}
        if isinstance(rate, Rate):
            leg.update(rate.record_fields())
        if getattr(rate, "fused_epilogue", None) is not None:
            leg["fused_epilogue"] = rate.fused_epilogue
        if name in floor_legs:
            leg["mfu_floor"] = floor_legs[name]
        if name in LEG_NOTES:
            leg["note"] = LEG_NOTES[name]
        rec["legs"][name] = leg
    rec["recipe_note"] = (
        "round 9: the round-5 audit's measured batch-256-1024 plateau "
        "(~35% MFU / 1.64M img/s) is now the shipped headline leg; the "
        "batch-64 reference recipe stays first-class as the parity leg, "
        "and the grad-accum leg carries the batch-64 effective update at "
        "large-batch geometry. --gate enforces the recorded mfu_floor "
        "per leg (bench_floors.json)")
    return rec


def check_mfu_floors(record: dict, floors: dict) -> list:
    """Gate logic, pure on (record, floors): the list of breaches.

    A leg listed in the floors but missing from the record is a breach (a
    silently dropped leg must fail the gate, not pass it), and so is a leg
    without a measured MFU: the benchmark only runs on a TPU whose peak is
    in the table, so a record without one did not come from it.
    """
    tol = float(floors.get("tolerance", 0.0))
    legs = record.get("legs", {})
    breaches = []
    for name, floor in sorted(floors.get("legs", {}).items()):
        leg = legs.get(name)
        if leg is None:
            breaches.append(f"{name}: leg missing from the bench record "
                            f"(floor {floor:.3f})")
            continue
        mfu = leg.get("mfu")
        if mfu is None:
            breaches.append(f"{name}: no measured MFU in the record "
                            f"(floor {floor:.3f} not checkable)")
        elif mfu < floor - tol:
            breaches.append(
                f"{name}: MFU {mfu:.4f} < floor {floor:.3f} - tol {tol:.3f}")
    return breaches


def gate(record: dict, floors: dict) -> int:
    breaches = check_mfu_floors(record, floors)
    for line in breaches:
        log(f"gate: FAIL {line}")
    if breaches:
        log(f"gate: {len(breaches)} MFU floor breach(es)")
        return 1
    log(f"gate: ok ({len(floors.get('legs', {}))} leg(s) at or above floor)")
    return 0


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--gate", action="store_true",
                    help="check measured MFU per leg against the recorded "
                         "floors (bench_floors.json); exit non-zero on a "
                         "breach")
    ap.add_argument("--json", metavar="FILE", default=None,
                    help="with --gate: gate this previously-emitted record "
                         "(no device run) — the `make bench-gate` smoke; "
                         "accepts the raw record or a driver wrapper with "
                         "a 'parsed' field")
    ap.add_argument("--floors", metavar="FILE", default=None,
                    help="floors file (default: bench_floors.json beside "
                         "this script)")
    args = ap.parse_args(argv)

    floors = load_floors(args.floors)
    if args.json:
        if not args.gate:
            ap.error("--json only makes sense with --gate")
        with open(args.json) as fh:
            record = json.load(fh)
        if "parsed" in record and "legs" not in record:
            record = record["parsed"]
        return gate(record, floors)

    from distributed_ml_pytorch_tpu.runtime import startup

    startup.enable_compile_cache()
    startup.require_tpu("bench.py")
    device = startup.device_summary()
    log(f"bench: {device}")
    legs = run_headline_legs()
    base = bench_torch_cpu()
    rec = build_record(legs, base, floors)
    rec["device"] = device
    print(json.dumps(rec), flush=True)
    if args.gate:
        return gate(rec, floors)
    return 0


if __name__ == "__main__":
    sys.exit(main())
