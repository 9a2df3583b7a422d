"""C8: CLI + process bootstrap (parity with reference ``example/main.py:140-168``).

Reproduces the reference's 15-flag surface (``example/main.py:142-155``) and
adds the TPU-era flags (``--backend``, ``--model``, ``--mode``, data options).
Flag-mapping notes:

- ``--cuda`` (reference: move model to GPU) → alias for ``--backend=tpu``:
  "put compute on the accelerator". Asking for the chip and not getting it
  (no TPU, or ``JAX_PLATFORMS=cpu`` inherited from the shell) is an error,
  never a CPU run under a TPU name.
- ``--rank``/``--world-size``/``--master``/``--port`` configure either the
  async-PS control plane (TCP star, ``utils/messaging.py``) or multi-host
  JAX (``runtime/mesh.py``), replacing MASTER_ADDR/MASTER_PORT + gloo
  (``example/main.py:163-165``).
- ``--server`` turns this process into the parameter server
  (``example/main.py:166-167`` → ``init_server`` parity). Unlike the
  reference — where ``main(args)`` still runs after ``server.run()`` returns,
  a structural quirk (SURVEY.md §3.2) — the server process exits cleanly.
- ``--mode`` selects the parallelism strategy for distributed runs:
  ``ps`` (async parameter server, the reference's core), ``sync``
  (per-step psum allreduce over the device mesh — BASELINE.json's
  ``--backend=tpu`` north-star path), ``local-sgd`` (compiled periodic
  averaging, the idiomatic reformulation of push/pull cadence).
"""

from __future__ import annotations

import argparse
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description="Distbelief training example (TPU-native)")
    # --- reference 15-flag surface (example/main.py:142-155) ---
    p.add_argument("--batch-size", type=int, default=64, metavar="N",
                   help="input batch size for training (default: 64)")
    p.add_argument("--test-batch-size", type=int, default=10000, metavar="N",
                   help="input batch size for testing (default: 10000)")
    p.add_argument("--epochs", type=int, default=20, metavar="N",
                   help="number of epochs to train (default: 20)")
    p.add_argument("--lr", type=float, default=0.008, metavar="LR",
                   help="learning rate (default: 0.008)")
    p.add_argument("--num-pull", type=int, default=10, metavar="N",
                   help="how often to pull params (default: 10)")
    p.add_argument("--num-push", type=int, default=10, metavar="N",
                   help="how often to push grads (default: 10)")
    p.add_argument("--cuda", action="store_true", default=False,
                   help="use the accelerator (alias for --backend=tpu on this hardware)")
    p.add_argument("--log-interval", type=int, default=100, metavar="N",
                   help="how often to evaluate and print out")
    p.add_argument("--no-distributed", action="store_true", default=False,
                   help="run the single-process baseline instead of distributed training")
    p.add_argument("--rank", type=int, metavar="N",
                   help="rank of current process (0 is server, 1+ is training node)")
    p.add_argument("--world-size", type=int, default=3, metavar="N",
                   help="size of the world")
    p.add_argument("--server", action="store_true", default=False,
                   help="server node?")
    p.add_argument("--n-servers", type=int, default=1, metavar="K",
                   help="(--mode ps) shard the parameter server across K "
                        "ranks (0..K-1), each owning a contiguous range of "
                        "the central vector on its own port (port+shard) — "
                        "the DistBelief layout (parallel/sharded_ps.py)")
    p.add_argument("--master", type=str, default="localhost",
                   help="ip address of the master (server) node")
    p.add_argument("--port", type=str, default="29500",
                   help="port on master node to communicate with")
    # --- TPU-era extensions ---
    p.add_argument("--backend", type=str, default="auto", choices=["auto", "tpu", "cpu"],
                   help="compute backend: auto = jax default platform; tpu = "
                        "fail unless the run is on a TPU; cpu = force the "
                        "CPU platform")
    p.add_argument("--mode", type=str, default="ps",
                   choices=["ps", "sync", "local-sgd", "fsdp"],
                   help="distributed strategy: async parameter server (reference core), "
                        "sync psum allreduce, compiled local-SGD averaging, or "
                        "fully-sharded data parallel (ZeRO-3: 1/N params per device)")
    p.add_argument("--model", type=str, default="alexnet",
                   choices=["alexnet", "lenet", "resnet18", "resnet50"],
                   help="model architecture (reference hardcodes AlexNet, example/main.py:41)")
    p.add_argument("--dtype", type=str, default="float32", choices=["float32", "bfloat16"],
                   help="compute dtype (bfloat16 feeds the MXU natively)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--data-root", type=str, default="./data",
                   help="CIFAR-10 location (reference downloads here, example/main.py:24)")
    p.add_argument("--synthetic-data", action="store_true", default=False,
                   help="force the deterministic synthetic dataset")
    p.add_argument("--download", action="store_true", default=False,
                   help="fetch real CIFAR-10 (checksum-verified) into "
                        "--data-root when missing; failures fall back to the "
                        "synthetic stand-in (the reference always downloads, "
                        "example/main.py:24 — default-off here so offline "
                        "runs never stall on a dead network)")
    p.add_argument("--synthetic-train-size", type=int, default=50000)
    p.add_argument("--synthetic-test-size", type=int, default=10000)
    p.add_argument("--log-dir", type=str, default="runs",
                   help="worker CSV telemetry directory (default an "
                        "UNTRACKED run directory — the old tracked "
                        "log/node*.csv churn is gone; both log/ and runs/ "
                        "are .gitignored)")
    p.add_argument("--transport", type=str, default="auto",
                   choices=["auto", "native", "python"],
                   help="PS control-plane transport: C++ library "
                        "(native/transport.cpp), pure Python, or auto-detect")
    p.add_argument("--reliable", action="store_true", default=False,
                   help="wrap the PS control plane in the reliability layer "
                        "(per-peer sequence numbers, frame CRC, ack+retry "
                        "with capped backoff, receiver dedup — gradient "
                        "pushes apply exactly once under duplicates/loss); "
                        "set it on EVERY rank of the world")
    p.add_argument("--sync-every", type=int, default=0, metavar="K",
                   help="local-sgd mode: average params every K steps "
                        "(default 0 = use --num-push)")
    p.add_argument("--ckpt-dir", type=str, default="",
                   help="checkpoint directory (empty = checkpointing off; "
                        "reference has no checkpointing at all, SURVEY.md §5.4)")
    p.add_argument("--ckpt-every", type=int, default=500, metavar="N",
                   help="save a checkpoint every N global steps (--mode ps: "
                        "every N gradient pushes, summed across workers)")
    p.add_argument("--ckpt-keep", type=int, default=3, metavar="N",
                   help="retain the newest N checkpoints (ignored by --mode "
                        "ps, which keeps one atomically-replaced file)")
    p.add_argument("--resume", action="store_true", default=False,
                   help="resume from the latest checkpoint in --ckpt-dir")
    p.add_argument("--wal", action="store_true", default=False,
                   help="PS server: write-ahead-log every applied update "
                        "BEFORE its delivery ack (requires --ckpt-dir; "
                        "pair with --reliable — the deferred ack rides the "
                        "reliability envelope); recovery = restore "
                        "checkpoint + replay the log, so no acked "
                        "GradientUpdate can be lost to a crash")
    p.add_argument("--admission", action="store_true", default=False,
                   help="PS server: numerical admission gate (ISSUE 8) — "
                        "every GradientUpdate/ShardPush passes finiteness "
                        "+ per-worker EWMA norm-outlier checks BEFORE "
                        "accounting/WAL; rejects are quarantined and "
                        "explicitly nacked (UpdateNack), the worker "
                        "resyncs by pulling fresh params")
    p.add_argument("--admission-z", type=float, default=6.0, metavar="Z",
                   help="admission gate: reject a push whose log-norm "
                        "z-score vs the sender's own history exceeds Z")
    p.add_argument("--admission-warmup", type=int, default=8, metavar="N",
                   help="admission gate: per-sender pushes admitted before "
                        "the z-score check activates (finiteness is "
                        "checked from the first push)")
    p.add_argument("--manifest-path", type=str, default="",
                   help="elastic shard servers (--coord): path of the "
                        "coordinator's FleetManifest — required to honor "
                        "auto-rollback barriers (RollbackRequest restores "
                        "the last good snapshot in place)")
    p.add_argument("--profile-dir", type=str, default="",
                   help="capture an xprof/TensorBoard trace of a training-step "
                        "window into this directory (reference has no tracing "
                        "at all, SURVEY.md §5.1)")
    p.add_argument("--profile-start", type=int, default=10, metavar="N",
                   help="global step at which the trace window opens")
    p.add_argument("--profile-steps", type=int, default=10, metavar="N",
                   help="number of steps the trace window covers")
    p.add_argument("--metrics-dump", type=str, default="", metavar="PATH",
                   help="write the metrics-registry snapshot JSON "
                        "(utils/metrics.get_registry, ISSUE 12) at exit — "
                        "reliable-transport counters, component stats; "
                        "'-' prints to stdout")
    p.add_argument("--rejoin", action="store_true", default=False,
                   help="PS-mode worker restart: reconnect to a running "
                        "server and ADOPT its central params instead of "
                        "installing this process's fresh init (elastic "
                        "recovery; the reference has none, SURVEY.md §5.3)")
    p.add_argument("--prefetch", type=int, default=2, metavar="N",
                   help="keep N batches' host→device copies in flight ahead "
                        "of compute (per-step path; 0 disables)")
    p.add_argument("--optimizer", type=str, default="sgd",
                   choices=("sgd", "adam", "adamw"),
                   help="optimizer; sgd is the reference recipe "
                        "(example/main.py:44). In --mode ps this is the "
                        "WORKER-local optimizer: pushes carry the local "
                        "param deltas and the server still just adds them "
                        "(the DownPour generalization)")
    p.add_argument("--momentum", type=float, default=0.0, metavar="M",
                   help="sgd momentum (the reference hardcodes 0.0)")
    p.add_argument("--weight-decay", type=float, default=None, metavar="WD",
                   help="weight decay: decoupled (AdamW-style) for adamw, "
                        "classic L2 for sgd/adam; unset keeps each "
                        "optimizer's default (adamw: optax's 1e-4), 0 disables")
    p.add_argument("--grad-clip", type=float, default=0.0, metavar="NORM",
                   help="clip gradients to this global norm before the "
                        "optimizer update; 0 disables")
    p.add_argument("--lr-schedule", type=str, default="constant",
                   choices=("constant", "inverse-epoch", "cosine"),
                   help="learning-rate schedule; the reference configures "
                        "1/(epoch+1) decay but never steps it (SURVEY.md "
                        "§5.6) — 'inverse-epoch' is that intent done right")
    p.add_argument("--grad-accum", type=int, default=1, metavar="K",
                   help="average gradients over K micro-batches before each "
                        "optimizer update (optax.MultiSteps) — effective "
                        "batch K×batch-size without K× activation HBM")
    p.add_argument("--steps-per-dispatch", type=int, default=1, metavar="K",
                   help="fuse up to K consecutive SGD steps into one "
                        "compiled program (lax.scan) — amortizes host "
                        "dispatch; per-step CSV logging and eval cadence "
                        "are preserved. In --mode ps, K caps the fused "
                        "between-comm runs (default auto = 64) and K > 1 "
                        "forces chunked dispatch on; in --mode local-sgd, "
                        "K steps round up to whole sync rounds per dispatch")
    p.add_argument("--chunked-dispatch", choices=("auto", "on", "off"),
                   default="auto",
                   help="(--mode ps workers) compile each between-comm run "
                        "of local SGD into one lax.scan dispatch with exact "
                        "push/pull cadence semantics; 'auto' enables it on "
                        "TPU, where per-batch dispatch — not the DownPour "
                        "protocol — bounds worker throughput")
    p.add_argument("--heartbeat-interval", type=float, default=1.0, metavar="SEC",
                   help="PS-mode worker liveness heartbeat cadence; 0 disables "
                        "(the reference has no failure detection, SURVEY.md §5.3)")
    p.add_argument("--worker-timeout", type=float, default=30.0, metavar="SEC",
                   help="PS-mode server declares a worker failed after this "
                        "long without a frame, instead of waiting forever; "
                        "0 disables")
    p.add_argument("--coord", type=str, default="", metavar="HOST:PORT",
                   help="attach this PS-mode rank to an elastic control "
                        "plane (coord/cli.py): membership + lease liveness, "
                        "coordinator-pushed shard maps (workers cut over at "
                        "step boundaries; shard servers resize), straggler "
                        "speculation. Empty = static fleet (the classic "
                        "launch-time topology)")
    p.add_argument("--staleness-damping", type=float, default=0.0, metavar="D",
                   help="PS-mode server scales each gradient push by "
                        "1/(1 + D*staleness), where staleness counts central "
                        "versions since that worker's last pull (straggler "
                        "mitigation, arxiv 2006.02924); 0 = reference "
                        "behavior (apply raw)")
    # --- scalable optimizer plane (ISSUE 14) ----------------------------
    p.add_argument("--compress", type=str, default="none",
                   choices=("none", "int8", "topk"),
                   help="PS-mode gradient wire compression "
                        "(utils/compress.py): pushes ride CompressedUpdate "
                        "frames with per-worker error-feedback residuals — "
                        "int8 = per-block symmetric quantization (~4x fewer "
                        "bytes), topk = sparsified (idx, value) pairs; the "
                        "server decodes BEFORE the admission gate and WAL")
    p.add_argument("--compress-block", type=int, default=1024, metavar="B",
                   help="int8 quantization block size (one absmax scale per "
                        "block; multiple of 4)")
    p.add_argument("--compress-topk", type=float, default=0.01, metavar="F",
                   help="top-k fraction of elements kept per push "
                        "(--compress topk)")
    p.add_argument("--combine", type=str, default="add",
                   choices=("add", "adasum"),
                   help="how the PS combines concurrent pushes: add = the "
                        "reference behavior; adasum = angle-aware merge "
                        "against the overlap applied since the pusher's "
                        "last pull (arXiv:2006.02924) — the alternative to "
                        "--staleness-damping (mutually exclusive)")
    p.add_argument("--server-opt", type=str, default="none",
                   choices=("none", "sgdm", "adam"),
                   help="ZeRO-style sharded server-side optimizer "
                        "(parallel/optplane.py): each server/shard owns "
                        "momentum (sgdm) or Adam moments for EXACTLY its "
                        "range — state cost scales 1/shards; state rides "
                        "checkpoints + WAL replay (arXiv:2004.13336)")
    p.add_argument("--server-lr", type=float, default=1.0, metavar="LR",
                   help="server-side optimizer step scale (1.0 with sgdm "
                        "momentum 0 reproduces the plain add)")
    p.add_argument("--server-momentum", type=float, default=0.9, metavar="M",
                   help="server-side sgdm momentum over incoming deltas")
    return p


def _apply_backend(args) -> None:
    """Act on ``--backend`` before the first computation, turn on the compile
    cache, and say where the run is."""
    from distributed_ml_pytorch_tpu.runtime import startup

    if args.cuda and args.backend == "auto":
        args.backend = "tpu"
    if args.backend == "cpu":
        from distributed_ml_pytorch_tpu.runtime.mesh import force_cpu_devices

        force_cpu_devices(int(os.environ.get("DMT_CPU_DEVICES", "1")))
    startup.enable_compile_cache()
    if args.backend == "tpu":
        startup.require_tpu("--backend tpu")
    if args.mode == "ps" and not args.no_distributed:
        role = "ps server" if args.server or args.rank == 0 else "ps worker"
        args.role = f"{role} rank {args.rank}"
    else:
        args.role = "trainer"
    startup.announce_devices(args.role)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    try:
        return _main(args)
    finally:
        if hasattr(args, "role"):  # start-up got as far as naming the run
            from distributed_ml_pytorch_tpu.runtime import startup

            startup.report_compile_cache(args.role)
        # observability plane (ISSUE 12): whatever the run registered or
        # attached (reliable-transport counters via make_transport, any
        # component providers) is dumped in one JSON snapshot
        if getattr(args, "metrics_dump", ""):
            from distributed_ml_pytorch_tpu.coord.cli import dump_metrics

            dump_metrics(args.metrics_dump)


def _main(args) -> int:
    print(args)
    try:
        _apply_backend(args)
    except RuntimeError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    import jax

    if args.resume and not args.ckpt_dir:
        print("error: --resume requires --ckpt-dir", file=sys.stderr)
        return 2

    if args.no_distributed:
        # reference `make single` / `make gpu` path (SURVEY.md §3.5)
        from distributed_ml_pytorch_tpu.training.trainer import train_single

        _announce_dataset(args)
        _state, logger = train_single(args)
        name = "single.csv" if jax.devices()[0].platform == "cpu" else "tpu.csv"
        path = logger.to_csv(name)
        print("wrote", path)
        print("Finished Training")
        return 0

    # Every advertised knob works in every mode (VERDICT r3 #1):
    # - ps workers build their local optax transform from the full surface
    #   (optimizer/momentum/weight-decay/grad-clip/lr-schedule/grad-accum,
    #   parallel/async_ps.py train_worker; --steps-per-dispatch caps the
    #   fused chunk length), and --profile-dir traces a worker-step window;
    # - local-sgd wires the same transform plus checkpoint/resume at round
    #   boundaries, profiling, and --steps-per-dispatch round fusion.

    if args.mode == "ps" and args.worker_timeout > 0:
        hb = args.heartbeat_interval
        if hb <= 0 or hb * 3 > args.worker_timeout:
            # without fast heartbeats, "silent" and "dead" are
            # indistinguishable: sparse push/pull cadence or a long jit
            # compile would falsely fail a healthy worker
            print(
                "warning: --worker-timeout {:.0f}s needs heartbeats well "
                "under it (got --heartbeat-interval {}); healthy-but-quiet "
                "workers may be declared failed".format(args.worker_timeout, hb),
                file=sys.stderr,
            )

    if args.mode == "ps":
        # only the module imports sit in the try: a run-time ImportError
        # from inside training must surface, not masquerade as a build issue
        try:
            if getattr(args, "n_servers", 1) > 1 or getattr(args, "coord", ""):
                # the sharded entry also hosts the elastic (--coord) path:
                # k=1 is just a one-entry shard map there
                from distributed_ml_pytorch_tpu.parallel.sharded_ps import (
                    run_sharded_ps_process as ps_entry,
                )
            else:
                from distributed_ml_pytorch_tpu.parallel.async_ps import (
                    run_ps_process as ps_entry,
                )
        except ImportError as e:
            print(f"error: --mode ps is unavailable in this build: {e}", file=sys.stderr)
            return 2
        return ps_entry(args)
    else:
        # mesh-based modes share one epilogue; each trainer returns
        # (state, MetricsLogger)
        if args.mode == "sync":
            from distributed_ml_pytorch_tpu.parallel.sync import train_sync as train_fn
        elif args.mode == "fsdp":
            from distributed_ml_pytorch_tpu.parallel.fsdp import train_fsdp as train_fn
        else:
            from distributed_ml_pytorch_tpu.parallel.local_sgd import (
                train_local_sgd as train_fn,
            )

        _announce_dataset(args)
        _state, logger = train_fn(args)
        path = logger.to_csv("node{}.csv".format(jax.process_index()))
        print("wrote", path)
        print("Finished Training")
        return 0


def _announce_dataset(args) -> None:
    from distributed_ml_pytorch_tpu.data.cifar10 import _load_pickle_batches

    real = (not args.synthetic_data) and _load_pickle_batches(args.data_root) is not None
    print("dataset: {} CIFAR-10".format("real" if real else "synthetic"))


if __name__ == "__main__":
    sys.exit(main())
