"""distcheck CLI — ``python -m distributed_ml_pytorch_tpu.analysis``.

Runs the four checker families over a package tree, applies inline
suppressions and the checked-in baseline, and exits non-zero when any
unsuppressed, non-baselined finding remains — the ``make lint`` contract.

    python -m distributed_ml_pytorch_tpu.analysis                 # the package
    python -m distributed_ml_pytorch_tpu.analysis --baseline tests/distcheck_baseline.txt
    python -m distributed_ml_pytorch_tpu.analysis --keys          # baseline keys (regen script)
    python -m distributed_ml_pytorch_tpu.analysis --json          # machine-readable findings
    python -m distributed_ml_pytorch_tpu.analysis path/to/pkg     # any tree (fixtures)

The ``timeline`` subcommand (ISSUE 12) is the package's first RUNTIME
analyzer: it merges flight-recorder dumps and attributes the bubble and
the wire (``analysis/timeline.py``; ``make timeline``):

    python -m distributed_ml_pytorch_tpu.analysis timeline <dump-dir> [--json]

The ``distmodel`` subcommand (ISSUE 13) model-checks the extracted
protocol: bounded exhaustive exploration of the exactly-once / lease /
watermark-replay invariants, with every counterexample emitted as a
replayable chaos schedule (``analysis/distmodel.py``; ``make distmodel``):

    python -m distributed_ml_pytorch_tpu.analysis distmodel [--json] [--mutate NAME] [--out DIR]
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional, Tuple

from distributed_ml_pytorch_tpu.analysis import (
    concurrency,
    distflow,
    protomodel,
    tracing_hygiene,
    wire,
)
from distributed_ml_pytorch_tpu.analysis.core import (
    Finding,
    Package,
    apply_suppressions,
    baseline_keys,
    load_package,
    read_baseline,
)

CHECKERS = (wire.check, protomodel.check, concurrency.check,
            tracing_hygiene.check, distflow.check)


def analyze(pkg: Package) -> Tuple[List[Finding], List[Finding]]:
    """(active, suppressed) findings for one loaded package."""
    findings: List[Finding] = []
    for checker in CHECKERS:
        findings.extend(checker(pkg))
    return apply_suppressions(pkg, findings)


def analyze_path(root: str, rel_base: Optional[str] = None):
    return analyze(load_package(root, rel_base=rel_base))


def default_root() -> str:
    import distributed_ml_pytorch_tpu

    return os.path.dirname(os.path.abspath(distributed_ml_pytorch_tpu.__file__))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv and argv[0] == "timeline":
        # runtime analyzer (ISSUE 12): its own arg surface, no package scan
        from distributed_ml_pytorch_tpu.analysis import timeline

        return timeline.main(argv[1:])
    if argv and argv[0] == "distmodel":
        # bounded model checker (ISSUE 13): its own arg surface
        from distributed_ml_pytorch_tpu.analysis import distmodel

        return distmodel.main(argv[1:])
    parser = argparse.ArgumentParser(
        prog="distcheck",
        description="protocol / concurrency / tracing-hygiene static "
                    "analysis for the distributed_ml_pytorch_tpu stack")
    parser.add_argument(
        "root", nargs="?", default=None,
        help="package directory to analyze (default: the installed "
             "distributed_ml_pytorch_tpu package)")
    parser.add_argument(
        "--baseline", default=None, metavar="FILE",
        help="known-findings file; only NEW findings fail the run "
             "(tests/distcheck_baseline.txt in CI)")
    parser.add_argument(
        "--keys", action="store_true",
        help="print baseline keys instead of rendered findings "
             "(consumed by tests/regen_distcheck_baseline.py)")
    parser.add_argument(
        "--show-suppressed", action="store_true",
        help="also list findings silenced by inline suppressions")
    parser.add_argument(
        "--json", action="store_true",
        help="machine-readable findings on stdout (CI consumes lint "
             "results without scraping text)")
    args = parser.parse_args(argv)

    root = args.root or default_root()
    active, suppressed = analyze_path(root)
    baseline = read_baseline(args.baseline) if args.baseline else frozenset()
    keys = baseline_keys(active)
    new = [f for f, k in zip(active, keys) if k not in baseline]
    known = [f for f, k in zip(active, keys) if k in baseline]

    if args.keys:
        for key in keys:
            print(key)
        return 0
    if args.json:
        import json as _json

        def row(f, key, baselined):
            return {"path": f.path, "line": f.line, "code": f.code,
                    "message": f.message, "baseline_key": key,
                    "baselined": baselined}

        payload = {
            "clean": not new,
            "counts": {"new": len(new), "baselined": len(known),
                       "suppressed": len(suppressed)},
            "findings": [row(f, k, k in baseline)
                         for f, k in zip(active, keys)],
        }
        if args.show_suppressed:
            payload["suppressed"] = [
                {"path": f.path, "line": f.line, "code": f.code,
                 "message": f.message} for f in suppressed]
        _json.dump(payload, sys.stdout, indent=2, sort_keys=True)
        sys.stdout.write("\n")
        return 0 if not new else 1

    for f in new:
        print(f.render())
    if known:
        print(f"# {len(known)} known finding(s) carried by the baseline "
              f"({args.baseline})", file=sys.stderr)
    if args.show_suppressed and suppressed:
        print(f"# {len(suppressed)} suppressed finding(s):", file=sys.stderr)
        for f in suppressed:
            print("#   " + f.render(), file=sys.stderr)
    if new:
        print(f"distcheck: {len(new)} finding(s)", file=sys.stderr)
        return 1
    print(f"distcheck: clean ({len(suppressed)} suppressed"
          + (f", {len(known)} baselined" if known else "") + ")",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
