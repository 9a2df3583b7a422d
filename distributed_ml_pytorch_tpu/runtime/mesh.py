"""L0 runtime: device mesh bootstrap (replaces reference ``example/main.py:163-165``).

The reference bootstraps distribution with env-var TCP rendezvous into a gloo
process group::

    os.environ['MASTER_ADDR'] = args.master
    os.environ['MASTER_PORT'] = args.port
    dist.init_process_group('gloo', rank=args.rank, world_size=args.world_size)

The TPU-native analog is multi-controller JAX: ``jax.distributed.initialize``
replaces the rendezvous (coordinator address in place of MASTER_ADDR:PORT),
and the transport underneath is XLA's compiled collectives over ICI within a
slice / DCN across slices — not a Python socket layer. All parallelism in this
framework is expressed over a named ``jax.sharding.Mesh`` built here.

For single-host testing, ``force_cpu_devices(n)`` provisions the virtual CPU
mesh that stands in for a cluster, mirroring how the reference smoke-tests its
3-rank topology on localhost (``Makefile:13-20``, SURVEY.md §4).
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

import jax
from jax.experimental import mesh_utils
from jax.sharding import Mesh


def initialize_distributed(
    coordinator_address: str | None = None,
    num_processes: int | None = None,
    process_id: int | None = None,
) -> None:
    """Join the multi-host runtime.

    Maps the reference CLI surface onto JAX's coordinator: ``--master``/
    ``--port`` → ``coordinator_address``, ``--world-size`` → ``num_processes``,
    ``--rank`` → ``process_id`` (reference ``example/main.py:151-155,163-165``).
    On Cloud TPU pods all three arguments are discovered automatically and may
    be ``None``. Safe to call once per process, before any jax computation.
    """
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def force_cpu_devices(n: int = 8) -> None:
    """Run this process on ``n`` virtual CPU devices — the single-host stand-in
    for a multi-chip mesh (the framework's analog of the reference's
    localhost multi-process smoke topology, SURVEY.md §4).

    Always an explicit request (the test suite's conftest, ``--backend cpu``,
    the localhost demos): nothing in the framework falls back to virtual
    devices on its own. Must run before JAX initializes a backend; JAX
    itself raises if the device count is changed later.
    """
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n)
    devs = jax.devices()
    if len(devs) != n or devs[0].platform != "cpu":
        raise RuntimeError(
            f"wanted {n} virtual CPU devices, got {devs}: force_cpu_devices "
            "must run before the first JAX computation")


def require_devices(n: int, what: str) -> None:
    """Raise unless ``n`` devices exist. Virtual CPU devices are never
    provisioned implicitly: a run that asked for ``n`` chips and silently got
    a CPU mesh would report a multi-chip result no chip produced."""
    have = len(jax.devices())
    if have < n:
        raise RuntimeError(
            f"{what} needs {n} devices, found {have} "
            f"({jax.devices()[0].platform}). For the CPU simulation ask for "
            f"it explicitly: JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES={n}")


def make_mesh(
    axis_sizes: Mapping[str, int] | None = None,
    *,
    devices: Sequence[jax.Device] | None = None,
) -> Mesh:
    """Build a named device mesh.

    ``axis_sizes`` maps axis names to sizes, e.g. ``{"data": 8}`` or
    ``{"data": 4, "model": 2}``. Defaults to a 1-D ``data`` mesh over every
    addressable device — the shape of the reference's world (rank list) with
    the parameter-server specialization removed: in sync SPMD every device is
    a worker.
    """
    devs = list(devices) if devices is not None else jax.devices()
    if axis_sizes is None:
        axis_sizes = {"data": len(devs)}
    names = tuple(axis_sizes.keys())
    shape = tuple(axis_sizes.values())
    n = int(np.prod(shape))
    if n != len(devs):
        raise ValueError(
            f"mesh shape {dict(axis_sizes)} needs {n} devices, have {len(devs)}"
        )
    if devices is None:
        mesh_devs = mesh_utils.create_device_mesh(shape)
    else:
        mesh_devs = np.array(devs).reshape(shape)
    return Mesh(mesh_devs, names)


def sharded_init(init_fn, rng, shardings):
    """Jit ``init_fn(rng)`` so its output lands with ``shardings`` — with
    values INDEPENDENT of the mesh shape.

    When ``jax_threefry_partitionable`` is switched off,
    ``jit(init_fn, out_shardings=...)`` generates DIFFERENT random values for
    a leaf that is sharded over one mesh axis while replicated over another
    (measured: identical keys gave divergent block kernels on a
    ``{"data": 2, "stage": 2}`` mesh vs a ``{"stage": 2}`` mesh — the root
    cause of the dp×pp×tp composite-loss "divergence" in dryrun_multichip;
    1-D meshes agree with the unsharded init exactly). There the init runs
    unsharded and is resharded with ``device_put`` — every device briefly
    holds the full tree, the compat price of value-determinism. With a
    partitionable threefry the sharded lowering is already value-invariant,
    so the memory-frugal ``out_shardings`` path is kept.
    """
    if jax.config.jax_threefry_partitionable:
        return jax.jit(init_fn, out_shardings=shardings)(rng)
    return jax.device_put(jax.jit(init_fn)(rng), shardings)


def data_mesh(n_devices: int | None = None) -> Mesh:
    """1-D ``data`` mesh over the first ``n_devices`` devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return make_mesh({"data": len(devs)}, devices=devs)


def local_device_count() -> int:
    return jax.local_device_count()


def process_rank() -> int:
    """This controller's rank (reference ``dist.get_rank()``, ``example/main.py:105``)."""
    return jax.process_index()


def world_size() -> int:
    return jax.process_count()
