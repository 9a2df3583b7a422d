"""One-command real-data verification (VERDICT r2 #6) — self-closing.

Every accuracy claim in BASELINE.md was measured on the deterministic
synthetic CIFAR-10 stand-in because this sandbox has no network egress; the
download path itself is implemented and tested against a fabricated archive
(``data/cifar10.py``, ``tests/test_data.py``). This script is the one
command that closes the gap the moment egress exists:

    make verify-real-data        (or: python verify_real_data.py)

It downloads the genuine dataset via the framework's own
``download_cifar10`` (md5-verified, atomic install), runs ONE
steps-to-target pass of both frameworks on the identical real batch stream
(``bench_all.bench_steps_to_accuracy``), derives every reported crossing
from the recorded accuracy curves, and appends the outcome to
``BASELINE.md`` under a "Real-data verification" heading plus a JSON line
on stdout. Without egress it prints SKIP and exits 0, so CI can run it
unconditionally.

Reported, all honestly:
- steps to 99% (the synthetic north-star bar — real CIFAR-10 will cap-hit
  at this recipe; the cap-hit is recorded as the measured outcome),
- steps to 60% (reachable at the reference recipe's horizon, so the
  cross-framework step comparison is informative on real data), and
- the FINAL accuracies of both frameworks after the full 2000-step stream
  — the parity delta the north-star acceptance bar asks about.
"""

from __future__ import annotations

import datetime
import json
import os
import sys


def _first_crossing(curve, eval_every, target):
    for i, acc in enumerate(curve):
        if acc >= target:
            return (i + 1) * eval_every
    return None


def main() -> int:
    from distributed_ml_pytorch_tpu.data import load_cifar10

    from distributed_ml_pytorch_tpu.data.cifar10 import (
        CIFAR10_MD5, CIFAR10_URL, _TARBALL)

    drop_path = os.path.abspath(os.path.join("./data", _TARBALL))
    try:
        x, _y, _xt, _yt, is_synth = load_cifar10(
            root="./data", synthetic=False, download=True)
    except Exception as e:
        print(
            f"SKIP: real CIFAR-10 unavailable ({type(e).__name__}: {e}) — "
            "no network egress here.\n"
            "To close the bar WITHOUT egress, drop the canonical tarball "
            "where the loader already looks (it is picked up, md5-verified, "
            "and used on the next run — no code change needed):\n"
            f"  file : {_TARBALL}\n"
            f"  from : {CIFAR10_URL}\n"
            f"  md5  : {CIFAR10_MD5}\n"
            f"  to   : {drop_path}\n"
            "then re-run:  make verify-real-data",
            file=sys.stderr)
        print(json.dumps({"metric": "real_data_verification",
                          "status": "skipped_no_egress",
                          "drop_file_to_close": drop_path,
                          "expected_md5": CIFAR10_MD5}))
        return 0
    assert not is_synth and len(x) == 50000

    from bench_all import bench_steps_to_accuracy, log
    from distributed_ml_pytorch_tpu.runtime import startup

    startup.enable_compile_cache()
    log(f"verify_real_data: {startup.device_summary()}")

    # one pass, both frameworks, full 2000-step stream; every target's
    # crossing derives from the recorded curves
    (_js, _ts, torch_status, jax_acc, torch_acc, curves) = (
        bench_steps_to_accuracy(target=0.60, synthetic=False))
    ee = curves["eval_every"]
    results = {
        "jax_steps_to_99": _first_crossing(curves["jax"], ee, 0.99),
        "jax_steps_to_60": _first_crossing(curves["jax"], ee, 0.60),
        "torch_steps_to_99": _first_crossing(curves["torch"], ee, 0.99),
        "torch_steps_to_60": _first_crossing(curves["torch"], ee, 0.60),
        "torch_status": torch_status,
        "jax_final_acc": jax_acc,
        "torch_final_acc": torch_acc,
    }
    delta = (abs(jax_acc - torch_acc) if torch_acc is not None else None)
    results["final_acc_delta"] = delta
    rec = {"metric": "real_data_verification", "status": "measured", **results}
    print(json.dumps(rec))

    stamp = datetime.datetime.now().strftime("%Y-%m-%d")
    t_final = (f"{torch_acc:.4f}" if torch_acc is not None
               else f"unavailable ({torch_status})")
    d_final = f"{delta:.4f}" if delta is not None else "n/a"
    row = (f"| real CIFAR-10 ({stamp}) | jax→99%: "
           f"{results['jax_steps_to_99'] or 'cap'} steps, jax→60%: "
           f"{results['jax_steps_to_60'] or 'cap'}, torch→60%: "
           f"{results['torch_steps_to_60'] or 'cap'} | final acc "
           f"jax {jax_acc:.4f} vs torch {t_final} (Δ {d_final}) | "
           "identical 2000-step batch stream, reference recipe |\n")
    header = "## Real-data verification (appended by verify_real_data.py)\n"
    existing = ""
    if os.path.exists("BASELINE.md"):
        with open("BASELINE.md", encoding="utf-8") as fh:
            existing = fh.read()
    with open("BASELINE.md", "a", encoding="utf-8") as fh:
        if header not in existing:
            fh.write("\n" + header + "\n| run | steps-to-target | parity | "
                     "boundary |\n|---|---|---|---|\n")
        fh.write(row)
    log("appended real-data verification row to BASELINE.md")
    return 0


if __name__ == "__main__":
    sys.exit(main())
