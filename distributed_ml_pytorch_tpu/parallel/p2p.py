"""Point-to-point tensor exchange over the mesh (C10 parity).

The reference demo (``pytorch_p2p_ex.py:7-23``) spawns two processes and moves
a 1-element tensor from rank 0 to rank 1 with blocking ``dist.send``/
``dist.recv`` over gloo TCP. The TPU-native primitive for device-to-device
point-to-point movement is ``lax.ppermute`` — a compiled permutation
collective that rides ICI links directly, no host round-trip.

``python -m distributed_ml_pytorch_tpu.parallel.p2p`` reproduces the demo's
observable behavior (rank 1 ends up holding rank 0's value; every rank prints
what it has), on a 2-device mesh — virtual CPU devices when the host exposes
only one chip.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def p2p_send_recv(
    x: jax.Array,
    mesh: Mesh,
    pairs: Sequence[Tuple[int, int]],
    axis: str = "data",
    fill: str = "zeros",
) -> jax.Array:
    """Move per-device shards between devices: ``pairs`` is ``[(src, dst), ...]``.

    ``fill`` controls devices that are not a destination in ``pairs``:
    ``"zeros"`` (raw ``lax.ppermute`` semantics) or ``"keep"`` — retain the
    local shard, which is torch's semantics where ``dist.send`` leaves the
    source buffer intact and only ``dist.recv`` overwrites
    (``pytorch_p2p_ex.py:12-16``).
    """
    dsts = [d for _, d in pairs]

    def shard_fn(v):
        shifted = jax.lax.ppermute(v, axis, list(pairs))
        if fill == "keep":
            idx = jax.lax.axis_index(axis)
            is_dst = jnp.isin(idx, jnp.asarray(dsts))
            return jnp.where(is_dst, shifted, v)
        return shifted

    return jax.jit(
        jax.shard_map(shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis))
    )(x)


def p2p_shift(x: jax.Array, mesh: Mesh, shift: int = 1, axis: str = "data") -> jax.Array:
    """Ring shift: device i's shard moves to device (i+shift) % n. The building
    block of ring allreduce/ring attention schedules."""
    n = mesh.shape[axis]
    pairs = [(i, (i + shift) % n) for i in range(n)]
    return p2p_send_recv(x, mesh, pairs, axis)


def run_demo(n_devices: int = 2) -> np.ndarray:
    """Behavioral parity with ``pytorch_p2p_ex.py``: rank 0 holds 1.0, sends to
    rank 1; every rank prints its value."""
    from distributed_ml_pytorch_tpu.runtime import data_mesh

    devs = jax.devices()
    if len(devs) < n_devices:
        raise RuntimeError(
            f"p2p demo needs {n_devices} devices, found {len(devs)} — "
            "run via __main__ which provisions virtual CPU devices"
        )
    mesh = data_mesh(n_devices)
    # per-device 1-element shards: rank 0 → 1.0, others → 0.0 (reference :8-11)
    x = jnp.zeros((n_devices,), jnp.float32).at[0].set(1.0)
    from distributed_ml_pytorch_tpu.parallel.sync import shard_batch

    x = shard_batch(mesh, x)
    # fill="keep": torch's dist.send leaves the source tensor intact, so
    # rank 0 also prints 1.0 (pytorch_p2p_ex.py:16)
    out = p2p_send_recv(x, mesh, [(0, 1)], fill="keep")
    vals = np.asarray(out)
    for rank in range(n_devices):
        print("Rank ", rank, " has data ", vals[rank])
    return vals


if __name__ == "__main__":
    from distributed_ml_pytorch_tpu.runtime.mesh import require_devices
    from distributed_ml_pytorch_tpu.runtime.startup import announce_devices

    announce_devices("p2p demo")
    require_devices(2, "the p2p demo")
    run_demo(2)
