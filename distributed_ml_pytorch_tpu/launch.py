"""C11 parity: localhost multi-process launcher.

The reference launches its 3-process PS topology by hand from three shells
(``Makefile:13-20``) and its p2p demo with ``torch.multiprocessing`` spawn
(``pytorch_p2p_ex.py:26-36``). This module does both in one command::

    python -m distributed_ml_pytorch_tpu.launch --world-size 3 -- \
        --model lenet --epochs 1 --synthetic-data

spawning rank 0 as the parameter server and ranks 1..N-1 as workers, all
against a TCP rendezvous on localhost. Everything after ``--`` is forwarded to
the trainer CLI verbatim. On a real TPU pod this launcher is unnecessary —
the pod runtime starts one controller per host and ``runtime.mesh`` handles
rendezvous — so this exists for the single-host smoke topology the reference
relies on (SURVEY.md §4).
"""

from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
import time
from typing import List


def _free_port() -> str:
    with socket.socket() as s:
        s.bind(("", 0))
        return str(s.getsockname()[1])


def _free_port_block(n: int, attempts: int = 50) -> str:
    """A base port with ``n`` CONSECUTIVE free ports (sharded PS binds
    base..base+n-1, one star per shard) — verified by binding them all."""
    for _ in range(attempts):
        base = int(_free_port())
        socks = []
        try:
            for i in range(n):
                s = socket.socket()
                s.bind(("", base + i))
                socks.append(s)
            return str(base)
        except (OSError, OverflowError):  # taken, or base+i ran past 65535
            continue
        finally:
            for s in socks:
                s.close()
    raise RuntimeError(f"no block of {n} consecutive free ports found")


def cpu_platform_env(base: dict | None = None, n_devices: int = 1) -> dict:
    """Env for running a process on the CPU platform with ``n_devices`` virtual
    devices (shared by the launcher and the integration tests): a chip
    belongs to one process, so the server and every rank that was not handed
    a chip stay off the accelerator."""
    env = dict(base if base is not None else os.environ)
    env.update(JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES=str(n_devices))
    return env


def tpu_platform_env(chip: int | None = None, base: dict | None = None) -> dict:
    """Env for a process that OWNS a TPU. ``JAX_PLATFORMS=tpu`` replaces
    whatever the parent shell exported (a ``JAX_PLATFORMS=cpu`` there would
    otherwise turn the "TPU worker" into a CPU run under that name), so a
    process that cannot get its chip fails at start-up. ``chip=None`` leaves
    every chip of the host visible; ``chip=i`` narrows libtpu's view to chip
    ``i`` as a one-chip, one-process slice, so N such processes hold N
    different chips side by side."""
    env = dict(base if base is not None else os.environ)
    env.pop("JAX_NUM_CPU_DEVICES", None)
    env["JAX_PLATFORMS"] = "tpu"
    if chip is not None:
        env.update(
            TPU_VISIBLE_CHIPS=str(chip),
            TPU_CHIPS_PER_PROCESS_BOUNDS="1,1,1",
            TPU_PROCESS_BOUNDS="1,1,1",
        )
    return env


def rank_env(rank: int, *, cpu: bool = True,
             tpu_worker_rank: int | None = None, n_servers: int = 1) -> dict:
    """Per-rank environment for the PS topology.

    The control plane is host-side, so by default every rank runs on the CPU
    platform. A chip belongs to one process at a time, so the two TPU
    layouts hand chips out explicitly and never leave two ranks on the
    default platform:

    - ``tpu_worker_rank=r`` gives exactly rank ``r`` the host's chip(s) —
      the DownPour layout the reference was built for: a central server plus
      a worker that trains on the accelerator
      (``asgd/optim/Asynchronous.py:42-70``), with push/pull crossing the
      device↔host boundary at the step cadence. Everyone else is on the CPU.
    - ``cpu=False`` (the launcher's ``--tpu``) gives each WORKER rank its own
      chip — worker ``n_servers + i`` sees chip ``i`` and nothing else —
      and keeps the server ranks, which never train, on the CPU.
    """
    if tpu_worker_rank is not None:
        return tpu_platform_env() if rank == tpu_worker_rank else cpu_platform_env()
    if cpu or rank < n_servers:
        return cpu_platform_env()
    return tpu_platform_env(chip=rank - n_servers)


def launch_world(
    world_size: int,
    extra_args: List[str],
    *,
    port: str | None = None,
    cpu: bool = True,
    tpu_worker_rank: int | None = None,
    n_servers: int = 1,
    poll_interval: float = 0.2,
) -> int:
    """Spawn ``n_servers`` server rank(s) + workers; returns the worst exit
    code. ``n_servers > 1`` launches the sharded-PS layout (ranks
    0..n_servers-1 each hold a contiguous slice of the central vector).

    Children are monitored: if any process exits nonzero while others are
    still running, the rest are killed — a crashed worker must not leave the
    server blocked in accept()/run() forever.
    """
    if not 1 <= n_servers < world_size:
        raise ValueError(
            f"n_servers={n_servers} must leave at least one worker in a "
            f"world of {world_size}"
        )
    if tpu_worker_rank is not None and not n_servers <= tpu_worker_rank < world_size:
        # server ranks never train — pinning one wastes the chip and
        # mislabels CPU numbers as TPU numbers; out-of-range ranks would
        # silently pin nothing
        raise ValueError(
            f"tpu_worker_rank={tpu_worker_rank} must be a worker rank "
            f"({n_servers}..{world_size - 1})"
        )
    port = port or (_free_port_block(n_servers) if n_servers > 1 else _free_port())
    common = [
        sys.executable, "-m", "distributed_ml_pytorch_tpu.training.cli",
        "--mode", "ps", "--world-size", str(world_size), "--port", port,
    ] + (["--n-servers", str(n_servers)] if n_servers > 1 else []) + list(extra_args)
    envs = [
        rank_env(r, cpu=cpu, tpu_worker_rank=tpu_worker_rank,
                 n_servers=n_servers)
        for r in range(world_size)
    ]
    procs = [
        subprocess.Popen(common + ["--rank", str(r), "--server"], env=envs[r])
        for r in range(n_servers)
    ]
    for rank in range(n_servers, world_size):
        procs.append(
            subprocess.Popen(common + ["--rank", str(rank)], env=envs[rank])
        )
    try:
        while True:
            codes = [p.poll() for p in procs]
            if all(c is not None for c in codes):
                # any nonzero (including negative signal codes) is a failure
                return next((c for c in codes if c != 0), 0)
            if any(c not in (None, 0) for c in codes):
                bad = next(c for c in codes if c not in (None, 0))
                print(
                    f"launch: a process exited with code {bad}; terminating the rest",
                    file=sys.stderr,
                )
                for p in procs:
                    if p.poll() is None:
                        p.kill()
                for p in procs:
                    p.wait()
                return bad
            time.sleep(poll_interval)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Launch the PS topology on localhost (server + workers)"
    )
    parser.add_argument("--world-size", type=int, default=3)
    parser.add_argument("--port", type=str, default=None)
    parser.add_argument("--tpu", action="store_true",
                        help="give every worker rank its own TPU chip (worker "
                             "i sees chip i only); server ranks stay on CPU")
    parser.add_argument("--tpu-worker", type=int, default=None, metavar="RANK",
                        help="give this one worker rank the host's TPU while "
                             "the server and other ranks stay on CPU — the "
                             "DownPour accelerator-worker layout")
    parser.add_argument("--n-servers", type=int, default=1, metavar="K",
                        help="shard the parameter server across K ranks "
                             "(the DistBelief layout)")
    args, extra = parser.parse_known_args(argv)
    if extra and extra[0] == "--":
        extra = extra[1:]
    return launch_world(args.world_size, extra, port=args.port,
                        cpu=not args.tpu, tpu_worker_rank=args.tpu_worker,
                        n_servers=args.n_servers)


if __name__ == "__main__":
    sys.exit(main())
