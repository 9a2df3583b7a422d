#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the repo's main paths once, through the entry points a user would
call, at the full width of models the repo supports (random weights from a
seed), and checks what comes out by the repo's own means:

- ``trainer``  ``training.cli --no-distributed --backend tpu`` (AlexNet, the
  reference recipe: batch 64, SGD lr 0.008, synthetic CIFAR), then the b1024
  ``fused_epilogue=True`` scan step (``bench.py``'s headline leg) against the
  unfused model on the same batches, and the DownPour chunk step's compiled
  program (the Pallas flat-axpy accumulate);
- ``downpour`` ``launch --world-size 2 --tpu-worker 1``: a CPU parameter
  server and one worker process that owns the chip, real processes, four
  push/pull cadences;
- ``lm``       ``examples.train_lm --mode single`` at GPT-2-small width
  (768d/12h/12L, vocab 50304, bf16, RoPE), batch 8 x seq 2048, plus compiled
  ``flash_attention`` against ``blockwise_attention`` on the chip;
- ``serve``    ``serving.cli --demo`` at the same width, then the engine's
  greedy streams against ``models.generate`` on the chip;
- ``multichip`` / ``downpour4`` only when the host has four chips or more;
  otherwise one line says how many there were.

One process for each chip: this parent never imports jax. Each stage is a
child (``chip_smoke.py --stage NAME``, which is also how one stage is run by
hand) run in turn, so the ``downpour`` stage's worker can own the chip its
launcher does not touch. A stage that fails, fails the run; nothing is
retried on another device, and there is no mode that runs without one.

Exits 0 only if every stage ran on a TPU, and then prints as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
With no TPU (or ``JAX_PLATFORMS=cpu``), or outside the repo, it exits
non-zero, says why, and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")

ONE_CHIP_STAGES = ("trainer", "downpour", "lm", "serve")
FOUR_CHIP_STAGES = ("multichip", "downpour4")
#: seconds a stage may take before it is killed (the whole run has 1200)
STAGE_TIMEOUT_S = {"probe": 120, "trainer": 420, "downpour": 360, "lm": 420,
                   "serve": 480, "multichip": 900, "downpour4": 420}
TOTAL_BUDGET_S = 1150

CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'

# ----------------------------------------------------------------- parent


def _kill_group(proc: subprocess.Popen) -> None:
    """Stop a stage and everything it started (launcher ranks included)."""
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def run_stage(name: str, deadline: float) -> dict:
    """Run one stage as a child process; returns its result record.

    The child's output is passed through line by line. The record comes back
    through a file, so that nothing a stage prints can be mistaken for it.
    """
    os.makedirs(OUT_DIR, exist_ok=True)
    result_path = os.path.join(OUT_DIR, f"{name}.json")
    if os.path.exists(result_path):
        os.remove(result_path)
    cmd = [sys.executable, os.path.abspath(__file__), "--stage", name,
           "--result", result_path]
    timeout = min(STAGE_TIMEOUT_S[name], max(1.0, deadline - time.monotonic()))
    t0 = time.monotonic()
    print(f"=== stage {name} (limit {timeout:.0f}s)", flush=True)
    proc = subprocess.Popen(cmd, cwd=HERE, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True,
                            start_new_session=True)
    timed_out = False

    def on_alarm(_sig, _frm):
        nonlocal timed_out
        timed_out = True
        _kill_group(proc)

    signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, timeout)
    try:
        for line in proc.stdout:
            print(f"[{name}] {line}", end="", flush=True)
        rc = proc.wait()
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        _kill_group(proc)  # ranks a dead launcher left behind
    wall = time.monotonic() - t0
    record = {"ok": False, "stage": name, "wall_s": round(wall, 1)}
    if timed_out:
        record["error"] = f"killed at its {timeout:.0f}s limit"
    elif rc != 0:
        record["error"] = f"exit code {rc}"
    elif not os.path.exists(result_path):
        record["error"] = "exited 0 but wrote no result"
    else:
        with open(result_path) as fh:
            record.update(json.load(fh))
        record["wall_s"] = round(wall, 1)
    state = "ok" if record["ok"] else f"FAILED ({record.get('error')})"
    print(f"=== stage {name}: {state} in {wall:.1f}s", flush=True)
    return record


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--stage", help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.stage:
        os.makedirs(OUT_DIR, exist_ok=True)
        return run_child(args.stage, args.result
                         or os.path.join(OUT_DIR, f"{args.stage}.json"))

    t_start = time.monotonic()
    deadline = t_start + TOTAL_BUDGET_S
    records = [run_stage("probe", deadline)]
    if not records[0]["ok"]:
        print("chip_smoke: no TPU for this run "
              f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '')!r}); "
              "see the probe's error above", file=sys.stderr)
        return 1
    device = records[0]["device"]
    for name in ONE_CHIP_STAGES + FOUR_CHIP_STAGES:
        if name in FOUR_CHIP_STAGES and device["count"] < 4:
            print(f"=== stage {name}: not run, this host has "
                  f"{device['count']} chip(s) and the stage needs 4",
                  flush=True)
            continue
        records.append(run_stage(name, deadline))
        if not records[-1]["ok"]:
            break

    ok = all(r["ok"] for r in records)
    print("--- summary (stage, wall, compile requests/cache hits/compiled)")
    for r in records:
        c = r.get("cache", {})
        print(f"  {r['stage']:<10} {'ok' if r['ok'] else 'FAILED':<7}"
              f"{r['wall_s']:>7.1f}s  requests={c.get('requests', '-')} "
              f"hits={c.get('hits', '-')} compiled={c.get('compiled', '-')}"
              + (f"  set-up {r['setup_s']:.1f}s" if "setup_s" in r else ""))
    total = {k: sum(r.get("cache", {}).get(k, 0) for r in records)
             for k in ("requests", "hits", "compiled")}
    setup = sum(r.get("setup_s", 0.0) for r in records)
    print(f"  total      {time.monotonic() - t_start:>14.1f}s  "
          f"requests={total['requests']} hits={total['hits']} "
          f"compiled={total['compiled']}  set-up {setup:.1f}s", flush=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as fh:
        json.dump({"ok": ok, "device": device, "stages": records,
                   "cache": total, "setup_s": round(setup, 1),
                   "wall_s": round(time.monotonic() - t_start, 1)}, fh,
                  indent=1)
    if not ok:
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


# ------------------------------------------------------------------ child
# Everything below runs in a stage's own process and may import jax.


class Checks:
    """A stage's assertions: each prints one line; the first that does not
    hold raises, which fails the stage."""

    def __init__(self):
        self.passed: list = []

    def ok(self, name: str, cond: bool, detail: str = "") -> None:
        print(f"  {'ok  ' if cond else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not cond:
            raise RuntimeError(f"check failed: {name}: {detail}")
        self.passed.append(name)

    def on_chip(self, name: str, array) -> None:
        """``array`` lives on a TPU."""
        platforms = sorted({d.platform for d in array.devices()})
        self.ok(f"{name} on the chip", platforms == ["tpu"],
                f"devices {sorted(str(d) for d in array.devices())}")

    def custom_calls(self, name: str, compiled_text: str) -> None:
        """The compiled program holds Mosaic kernels."""
        n = compiled_text.count(CUSTOM_CALL)
        self.ok(f"{name} compiled with Pallas kernels", n > 0,
                f"{n} tpu_custom_call(s) in the compiled text")


def run_child(stage: str, result_path: str) -> int:
    from distributed_ml_pytorch_tpu.runtime import startup

    t0 = time.monotonic()
    if stage not in ("downpour", "downpour4"):
        # the launcher stages leave the chip to their worker ranks
        startup.enable_compile_cache()
        startup.require_tpu("chip_smoke")
    checks = Checks()
    extra = STAGE_FNS[stage](checks) or {}
    record = {"ok": True, "stage": stage, "checks": checks.passed,
              "child_wall_s": round(time.monotonic() - t0, 1), **extra}
    record.setdefault("cache", startup.compile_cache_stats())
    with open(result_path, "w") as fh:
        json.dump(record, fh)
    return 0


def stage_probe(checks: Checks) -> dict:
    from importlib import metadata

    import jax
    import jaxlib

    from distributed_ml_pytorch_tpu.runtime import startup
    from distributed_ml_pytorch_tpu.utils.flops import device_peak_flops

    device = startup.device_summary()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"platform={device['platform']} device_kind={device['kind']!r} "
          f"devices={device['count']} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu} "
          f"python={sys.version.split()[0]}", flush=True)
    checks.ok("default backend is a TPU", device["platform"] == "tpu",
              str(jax.devices()))
    # (raises on a TPU the table does not know)
    checks.ok("device_kind is in the peak-FLOP/s table", True,
              f"{device_peak_flops() / 1e12:.0f} TFLOP/s bf16")
    return {"device": device,
            "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu}}


def _read_losses(csv_path: str) -> list:
    import csv

    with open(csv_path) as fh:
        return [float(r["training_loss"]) for r in csv.DictReader(fh)]


def _loss_checks(checks: Checks, name: str, losses) -> None:
    import numpy as np

    losses = np.asarray(losses, np.float64)
    q = max(1, len(losses) // 4)
    checks.ok(f"{name} losses finite", bool(np.isfinite(losses).all()),
              f"{len(losses)} steps")
    checks.ok(f"{name} loss lower at the end",
              losses[-q:].mean() < losses[:q].mean(),
              f"first {q}: {losses[:q].mean():.4f}, last {q}: "
              f"{losses[-q:].mean():.4f}")


def stage_trainer(checks: Checks) -> dict:
    import jax
    import numpy as np

    import bench
    from distributed_ml_pytorch_tpu.models import AlexNet
    from distributed_ml_pytorch_tpu.training import cli
    from distributed_ml_pytorch_tpu.training.trainer import (
        create_train_state,
        make_scan_train_step,
    )

    # -- the CLI trainer, reference recipe
    log_dir = os.path.join(OUT_DIR, "trainer_logs")
    steps = 64
    rc = cli.main([
        "--no-distributed", "--backend", "tpu",
        "--model", "alexnet", "--batch-size", "64", "--lr", "0.008",
        "--epochs", "1", "--synthetic-data",
        "--synthetic-train-size", str(64 * steps),
        "--synthetic-test-size", "512", "--test-batch-size", "512",
        "--log-interval", "32", "--log-dir", log_dir])
    checks.ok("training.cli exit code", rc == 0, str(rc))
    csv_path = os.path.join(log_dir, "tpu.csv")
    checks.ok("CSV written", os.path.exists(csv_path), csv_path)
    losses = _read_losses(csv_path)
    checks.ok("one CSV row per step", len(losses) == steps, str(len(losses)))
    _loss_checks(checks, "training.cli", losses)

    # -- bench.py's headline leg: the fused-epilogue scan step against the
    #    unfused model, same init, same batches
    batch, k, dispatches = bench.LARGE_BATCH, 4, 3
    images, labels = bench.make_batch(batch, k=k)
    images, labels = jax.device_put(images), jax.device_put(labels)
    rng = jax.random.key(1)
    setup_s = 0.0

    def run(model, name):
        nonlocal setup_s
        state, tx = create_train_state(
            model, jax.random.key(0), lr=bench.LR, sample_shape=(1, 32, 32, 3))
        t0 = time.monotonic()
        step = make_scan_train_step(model, tx).lower(
            state, images, labels, rng).compile()
        setup_s += time.monotonic() - t0
        out = []
        for _ in range(dispatches):
            state, loss = step(state, images, labels, rng)
            out.append(loss)
        checks.on_chip(f"{name} b{batch} scan step losses", out[-1])
        return step.as_text(), np.concatenate([np.asarray(l) for l in out])

    fused_text, fused = run(AlexNet(num_classes=10, fused_epilogue=True),
                            "fused")
    plain_text, plain = run(AlexNet(num_classes=10), "unfused")
    checks.custom_calls(f"fused-epilogue b{batch} scan step", fused_text)
    checks.ok("unfused step has no Pallas kernel",
              plain_text.count(CUSTOM_CALL) == 0)
    checks.ok("fused losses finite", bool(np.isfinite(fused).all()),
              f"{fused.size} steps, {fused[0]:.5f} -> {fused[-1]:.5f}")
    checks.ok("first loss bit-identical (same params: the forward's promise)",
              fused[0] == plain[0], f"{fused[0]!r} vs {plain[0]!r}")
    worst = float(np.max(np.abs(fused - plain) / np.abs(plain)))
    checks.ok("fused == unfused losses over the trajectory (rtol 1e-5)",
              worst <= 1e-5,
              f"max relative difference {worst:.2e} over {fused.size} steps; "
              f"bitwise equal: {bool(np.array_equal(fused, plain))}")

    # -- the DownPour worker's compiled program and its Pallas accumulate
    from distributed_ml_pytorch_tpu.ops import flat_axpy
    from distributed_ml_pytorch_tpu.parallel.async_ps import (
        default_downpour_tx,
        init_downpour_accumulator,
        make_downpour_chunk_step,
    )

    model = AlexNet(num_classes=10)
    params = model.init(jax.random.key(0),
                        np.zeros((1, 32, 32, 3), np.float32))["params"]
    tx = default_downpour_tx(bench.LR)
    _flat, n, pad, accum = init_downpour_accumulator(params)
    bxs, bys = bench.make_batch(64, k=3)
    t0 = time.monotonic()
    chunk = make_downpour_chunk_step(model, tx, pad).lower(
        params, tx.init(params), accum, bxs, bys, rng, 0).compile()
    setup_s += time.monotonic() - t0
    checks.custom_calls("DownPour chunk step (flat-axpy accumulate)",
                        chunk.as_text())
    y = jax.random.normal(jax.random.key(2), (n + pad,))
    x = jax.random.normal(jax.random.key(3), (n + pad,))
    axpy = jax.jit(lambda a, b: flat_axpy(a, b, -bench.LR)).lower(y, x).compile()
    checks.custom_calls("flat_axpy", axpy.as_text())
    got, want = np.asarray(axpy(y, x)), np.asarray(y) - bench.LR * np.asarray(x)
    checks.ok("flat_axpy == y + alpha*x", bool(np.allclose(got, want, rtol=1e-6,
                                                           atol=1e-6)),
              f"{n + pad} elements, max abs diff "
              f"{float(np.max(np.abs(got - want))):.2e}")
    return {"setup_s": round(setup_s, 1)}


def _launch_world(checks: Checks, chips: str, n_workers: int) -> dict:
    """Run the launcher CLI as a user would, ``chips`` being its flag that
    hands them out; check every rank's own lines."""
    import numpy as np

    log_dir = os.path.join(OUT_DIR, f"downpour{n_workers}_logs")
    steps = 40
    cmd = [sys.executable, "-m", "distributed_ml_pytorch_tpu.launch",
           "--world-size", str(1 + n_workers)] + chips.split() + [
        "--", "--model", "alexnet", "--batch-size", "64", "--lr", "0.008",
        "--epochs", "1", "--synthetic-data",
        "--synthetic-train-size", str(64 * steps),
        "--synthetic-test-size", "512", "--test-batch-size", "512",
        "--num-push", "10", "--num-pull", "10",
        "--log-interval", "20", "--log-dir", log_dir]
    print("  $ " + " ".join(cmd[1:]), flush=True)
    proc = subprocess.run(cmd, cwd=HERE, stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    sys.stdout.write(proc.stdout)
    checks.ok("launcher exit code", proc.returncode == 0, str(proc.returncode))
    out = proc.stdout

    def find(pattern: str) -> "re.Match":
        """The one place ``pattern`` occurs in the world's output (searched
        in the whole text: ranks share a pipe, so lines can interleave)."""
        hits = list(re.finditer(pattern, out))
        if len(hits) != 1:
            checks.ok(f"/{pattern}/ printed once", False,
                      str([h.group(0) for h in hits]))
        return hits[0]

    checks.ok("server stayed on the CPU",
              find(r"ps server rank 0: platform=(\w+)").group(1) == "cpu")
    cache = {"requests": 0, "hits": 0, "compiled": 0}
    seen, losses = [], []
    for rank in range(1, 1 + n_workers):
        where = find(rf"ps worker rank {rank}: platform=(\w+) "
                     rf"device_kind='[^']*' devices=(\d+)"
                     r"(?: TPU_VISIBLE_CHIPS=(\d+))?")
        # (a lone --tpu-worker owns every chip of its host; under --tpu
        # each worker must see exactly one)
        checks.ok(f"worker {rank} says it ran on the tpu",
                  where.group(1) == "tpu"
                  and (n_workers == 1 or where.group(2) == "1"),
                  where.group(0))
        seen.append(where.group(3))
        kind = find(rf"transport: rank {rank} kind=(\w+)")
        checks.ok(f"worker {rank} transport named", True, kind.group(0))
        losses.append(_read_losses(os.path.join(log_dir, f"node{rank}.csv")))
        checks.ok(f"worker {rank} wrote one CSV row per step",
                  len(losses[-1]) == steps, str(len(losses[-1])))
        checks.ok(f"worker {rank} losses finite",
                  bool(np.isfinite(losses[-1]).all()),
                  f"first 10: {np.mean(losses[-1][:10]):.4f}, "
                  f"last 10: {np.mean(losses[-1][-10:]):.4f}")
        stats = find(rf"ps worker rank {rank}: compile cache \S+ "
                     r"requests=(\d+) hits=(\d+) compiled=(\d+)")
        for key, value in zip(cache, stats.groups()):
            cache[key] += int(value)
    # One worker's loss is the model's and must fall. Several are
    # asynchronous on purpose: a pull may install central parameters that do
    # not hold this worker's last pushes yet, so within 40 steps one of four
    # need not end lower (on four real chips one did not); the world learns
    # if the loss averaged over its workers does.
    _loss_checks(checks, f"DownPour, mean over {n_workers} worker(s):",
                 np.mean(losses, axis=0))
    if n_workers > 1:
        # each saw ONE device and they ran side by side, which a chip shared
        # by two processes does not allow; the launcher named a chip for each
        checks.ok("the workers were handed different chips",
                  sorted(map(str, seen)) == [str(i) for i in range(n_workers)],
                  f"TPU_VISIBLE_CHIPS per worker: {seen}")
    pushes = int(find(r"parameter server: gradient staleness over (\d+) "
                      r"pushes").group(1))
    checks.ok("server applied at least two pushes from each worker",
              pushes >= 2 * n_workers,
              f"{pushes} pushes from {n_workers} worker(s): cadence 10 over "
              f"{steps} steps, plus the final flush")
    checks.ok("server saw every worker finish",
              "parameter server: all workers done" in out)
    return {"cache": cache,
            "transport": find(r"transport: rank 0 kind=(\w+)").group(1)}


def stage_downpour(checks: Checks) -> dict:
    return _launch_world(checks, "--tpu-worker 1", n_workers=1)


def stage_downpour4(checks: Checks) -> dict:
    """One CPU server and four workers, each process seeing one chip."""
    return _launch_world(checks, "--tpu", n_workers=4)


def _rel_err(got, want) -> float:
    """Max abs error over the reference's max abs value."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


#: GPT-2-small, the one width every LM stage runs at (depth included)
GPT2_SMALL = ["--d-model", "768", "--n-heads", "12", "--n-layers", "12",
              "--d-ff", "3072", "--vocab", "50304", "--dtype", "bfloat16",
              "--pos-encoding", "rope"]
LM_BATCH = ["--batch", "8", "--seq", "2048"]

#: greedy decode near-ties: two logits this many bf16 steps apart (or fewer)
#: are one value to a bf16 program, and either is a correct argmax (partings
#: measured on the v5e sat under 1 step; a wrong cache row costs ~100)
GREEDY_TIE_ULPS = 4

#: flash vs blockwise attention, both bf16 in / f32 accumulate: outputs and
#: gradients agree to a few bf16 roundings (2^-8 each) of the largest value
BF16_ATTN_TOL = 2e-2


def _attention_check(checks: Checks, flash, reference, arrays, label: str):
    """Compiled ``flash`` (Pallas) against ``reference`` (plain JAX) on the
    same ``(q, k, v, cotangent)``: output and all three gradients. Returns
    the flash side's ``(out, dq, dk, dv)``."""
    import jax

    q, k, v, g = arrays

    def vjp_of(fn):
        def f(q, k, v):
            out, pull = jax.vjp(fn, q, k, v)
            return (out,) + pull(g)
        return jax.jit(f)

    flash_c = vjp_of(flash).lower(q, k, v).compile()
    checks.custom_calls(f"{label} forward+backward", flash_c.as_text())
    got = flash_c(q, k, v)
    want = vjp_of(reference)(q, k, v)
    checks.on_chip(f"{label} output", got[0])
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        err = _rel_err(a, b)
        checks.ok(f"{label} {name} matches the reference "
                  f"(normalised max error <= {BF16_ATTN_TOL})",
                  err <= BF16_ATTN_TOL, f"{err:.2e} at {q.shape} {q.dtype}")
    return got


def stage_lm(checks: Checks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ml_pytorch_tpu.ops.attention import (
        blockwise_attention,
        finalize_attention,
        flash_attention,
    )
    from examples import train_lm

    res = train_lm.run(["--mode", "single", "--steps", "4"] + GPT2_SMALL
                       + LM_BATCH)
    losses = np.asarray(res["losses"])
    checks.ok("train_lm losses finite", bool(np.isfinite(losses).all()),
              str(losses.tolist()))
    # (a bf16 loss near 11 moves in steps of 1/16: equal neighbours happen)
    checks.ok("train_lm loss never rises and ends lower",
              bool(np.all(np.diff(losses) <= 0) and losses[-1] < losses[0]),
              str(losses.tolist()))
    checks.on_chip("train_lm params",
                   res["state"].params["lm_head"]["kernel"])
    # the same jitted step, looked at: this lowering is answered by the
    # compile cache the run above filled
    text = res["step"].lower(res["state"], *res["batch"]).compile().as_text()
    checks.custom_calls("train_lm step (flash forward and backward)", text)

    def reference(q, k, v):
        acc, _m, l = blockwise_attention(q, k, v, causal=True)
        return finalize_attention(acc, l).astype(q.dtype)

    arrays = [jax.random.normal(jax.random.key(i), (8, 12, 2048, 64),
                                jnp.bfloat16) for i in range(4)]
    _attention_check(checks,
                     lambda q, k, v: flash_attention(q, k, v, causal=True),
                     reference, arrays, "flash_attention")
    return {"setup_s": round(res["setup_s"], 1)}


def stage_serve(checks: Checks) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ml_pytorch_tpu.models.generate import generate
    from distributed_ml_pytorch_tpu.serving import cli

    argv = GPT2_SMALL + ["--slots", "8", "--cache-size", "1024"]
    t0 = time.monotonic()
    rc = cli.main(argv + ["--demo", "6"])
    setup_s = time.monotonic() - t0
    checks.ok("serving.cli --demo 6 (3 greedy, 3 sampled) exit code",
              rc == 0, str(rc))

    # the same engine the CLI builds, asked directly so the streams can be
    # held against generate() on the same device
    parser = cli.build_parser()
    args = parser.parse_args(argv)
    lm, params = cli._build_model(args, parser)
    engine = cli._make_engine(lm, params, args)
    checks.on_chip("engine params", params["lm_head"]["kernel"])
    rng = np.random.default_rng(0)
    new = 24
    prompts = [rng.integers(0, args.vocab, size=12).astype(np.int32)
               for _ in range(3)]
    greedy = [engine.submit(p, new) for p in prompts]
    sampled = [engine.submit(p, new, temperature=0.8, top_k=8, seed=i)
               for i, p in enumerate(prompts)]
    engine.run_until_idle()
    for req in greedy + sampled:
        checks.ok(f"request {req.request_id} answered",
                  req.done and len(req.tokens) == new
                  and all(0 <= t < args.vocab for t in req.tokens),
                  f"{len(req.tokens)} tokens")

    def reference(model, prompt):
        return np.asarray(generate(model, params, jnp.asarray(prompt)[None],
                                   new))[0, len(prompt):].tolist()

    # Greedy decoding is an argmax, and the engine (8 slots x 1024 cache
    # rows) and generate() (one sequence, a 48-row cache) are different
    # programs whose bf16 logits differ in the last place; where the two best
    # logits are closer than that, the streams part and both are right. Two
    # checks keep that from hiding a wrong cache row. In bf16 every engine
    # token, and generate()'s token wherever the streams part, must be the
    # argmax of a full-precision forward (float32, six-pass matmuls) to
    # within GREEDY_TIE_ULPS bf16 steps. Then the same engine and generate()
    # are run in that full precision, where no such ties are left, and there
    # the streams must be identical.
    exact_lm = lm.clone(dtype=jnp.float32)

    def ulps_below_best(prompt, tokens):
        """For each generated token: how far its full-precision logit sits
        under the best one, in bf16 steps at that magnitude."""
        seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(
                exact_lm.apply({"params": params}, jnp.asarray(seq)[None])[
                    0, len(prompt) - 1:], np.float32)
        best = logits.max(axis=-1)
        chosen = logits[np.arange(len(tokens)), np.asarray(tokens)]
        return (best - chosen) / 2.0 ** (np.floor(np.log2(np.abs(best))) - 7)

    identical = 0
    for req, prompt in zip(greedy, prompts):
        gaps = ulps_below_best(prompt, req.tokens)
        checks.ok(f"bf16 greedy request {req.request_id}: every token is the "
                  f"full-precision argmax (within {GREEDY_TIE_ULPS} bf16 "
                  "steps)", bool(gaps.max() <= GREEDY_TIE_ULPS),
                  f"largest gap {gaps.max():.2f} steps, {int((gaps > 0).sum())}"
                  f" of {new} tokens not the strict argmax")
        ref = reference(lm, prompt)
        if req.tokens == ref:
            identical += 1
            continue
        t = next(i for i, (a, b) in enumerate(zip(req.tokens, ref)) if a != b)
        gap = ulps_below_best(prompt, ref[:t + 1])[t]
        checks.ok(f"bf16 greedy request {req.request_id} parts from "
                  f"generate() only at a tie (token {t})",
                  bool(gap <= GREEDY_TIE_ULPS),
                  f"engine {req.tokens[t]} vs generate {ref[t]}: generate's "
                  f"token is {gap:.2f} bf16 steps under the best logit")
    print(f"  bf16 greedy streams token-identical to generate(): {identical} "
          f"of {len(greedy)}", flush=True)

    with jax.default_matmul_precision("highest"):
        engine = cli._make_engine(exact_lm, params, args)
        exact = [engine.submit(p, new) for p in prompts]
        engine.run_until_idle()
        for req, prompt in zip(exact, prompts):
            ref = reference(exact_lm, prompt)
            checks.ok(f"float32 greedy request {req.request_id} "
                      "token-identical to generate()", req.tokens == ref,
                      f"engine {req.tokens[:6]}.. generate {ref[:6]}..")
    return {"setup_s": round(setup_s, 1)}


def stage_multichip(checks: Checks) -> dict:
    """Four chips in one process: sync data parallel through the CLI, the
    multichip dry run on real devices, tensor parallel through the example,
    and ring-flash attention against the blockwise ring."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import __graft_entry__ as graft
    from distributed_ml_pytorch_tpu.parallel.ring import make_ring_attention
    from distributed_ml_pytorch_tpu.runtime.mesh import make_mesh
    from distributed_ml_pytorch_tpu.training import cli
    from examples import train_lm

    n = 4
    devs = jax.devices()[:n]
    checks.ok("four distinct devices", len({d.id for d in devs}) == n,
              str(devs))

    log_dir = os.path.join(OUT_DIR, "sync_logs")
    steps = 48
    rc = cli.main([
        "--mode", "sync", "--backend", "tpu",
        "--model", "alexnet", "--batch-size", "64", "--lr", "0.008",
        "--epochs", "1", "--synthetic-data",
        "--synthetic-train-size", str(64 * n * steps),
        "--synthetic-test-size", "512", "--test-batch-size", "512",
        "--log-interval", "16", "--log-dir", log_dir])
    checks.ok("training.cli --mode sync exit code", rc == 0, str(rc))
    losses = _read_losses(os.path.join(log_dir, "node0.csv"))
    checks.ok("sync-DP took one step per global batch of 256",
              len(losses) == steps, str(len(losses)))
    _loss_checks(checks, "sync-DP", losses)

    graft.dryrun_multichip(n)
    checks.ok(f"dryrun_multichip({n}) ran on these devices", True)

    res = train_lm.run(["--mode", "tp", "--steps", "4"] + GPT2_SMALL
                       + LM_BATCH)
    tp_losses = np.asarray(res["losses"])
    checks.ok("train_lm --mode tp losses finite, never rising, lower at "
              "the end",
              bool(np.isfinite(tp_losses).all()
                   and np.all(np.diff(tp_losses) <= 0)
                   and tp_losses[-1] < tp_losses[0]),
              str(tp_losses.tolist()))
    kernel = res["state"].params["lm_head"]["kernel"]
    checks.ok("tp params sharded over four devices",
              len(kernel.sharding.device_set) == n
              and not kernel.sharding.is_fully_replicated,
              f"lm_head {kernel.sharding.spec} on "
              f"{sorted(d.id for d in kernel.sharding.device_set)}")
    text = res["step"].lower(res["state"], *res["batch"]).compile().as_text()
    checks.custom_calls("tp step (flash inside make_sharded_attn_fn)", text)

    # ring attention over the four chips: the flash ring against the
    # blockwise ring, forward and gradients
    mesh = make_mesh({"seq": n}, devices=devs)
    spec = P(None, None, "seq", None)
    sharding = NamedSharding(mesh, spec)
    arrays = [jax.device_put(
        jax.random.normal(jax.random.key(i), (2, 12, n * 1024, 64),
                          jnp.bfloat16), sharding) for i in range(4)]
    out = _attention_check(
        checks,
        make_ring_attention(mesh, "seq", causal=True, impl="flash"),
        make_ring_attention(mesh, "seq", causal=True, impl="blockwise"),
        arrays, "ring-flash attention")[0]
    checks.ok("ring output sharded over four devices",
              len(out.sharding.device_set) == n,
              str(sorted(d.id for d in out.sharding.device_set)))
    return {"setup_s": round(res["setup_s"], 1)}


STAGE_FNS = {"probe": stage_probe, "trainer": stage_trainer,
             "downpour": stage_downpour, "lm": stage_lm, "serve": stage_serve,
             "multichip": stage_multichip, "downpour4": stage_downpour4}


if __name__ == "__main__":
    sys.exit(main())
