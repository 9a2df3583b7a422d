"""Synchronous data parallelism over the device mesh (BASELINE.json north star).

The reference has **no** synchronous allreduce path (SURVEY.md §2.4) — its only
collective usage is PS messaging plus a p2p demo — but the driver's north star
requires the TPU backend to train with per-step gradient allreduce over ICI,
replacing what a NCCL/gloo DDP run does on GPU clusters.

Design: one jitted step under ``jax.shard_map``. Each device computes the
loss/grads of its batch shard; an explicit ``lax.pmean`` over the ``data``
mesh axis is the gradient allreduce — compiled by XLA into ICI collectives on
a TPU slice (DCN across slices on multi-host meshes), overlapping with
backprop where the scheduler allows. Parameters and optimizer state are
replicated; the update is computed identically on every device, so no
broadcast is needed (the DDP invariant).

The same code runs single-host (one controller, all local devices) or
multi-host SPMD (every controller runs this same program after
``runtime.initialize_distributed``) — mesh construction is the only
difference, which keeps the trainer backend-agnostic per SURVEY.md §7.
"""

from __future__ import annotations

import copy
import time
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ml_pytorch_tpu.training.trainer import (
    TrainState,
    cross_entropy_loss,
    make_eval_fn,
    run_training_loop,
)
from distributed_ml_pytorch_tpu.utils.metrics import MetricsLogger

Pytree = Any


def shard_batch(mesh: Mesh, *arrays: np.ndarray, axis: str = "data"):
    """Place host arrays on the mesh, sharded along the leading (batch) dim.

    Single-controller: a plain ``device_put``. Multi-host: each controller
    passes its *process-local* slice of the global batch and the global array
    is assembled across hosts via ``make_array_from_process_local_data`` —
    each host only ever touches the data its own devices consume (per-host
    sharded loading, SURVEY.md §7 input-pipeline note).
    """
    out = tuple(
        put_sharded(mesh, a, P(axis, *([None] * (a.ndim - 1)))) for a in arrays
    )
    return out if len(out) > 1 else out[0]


def put_sharded(mesh: Mesh, array: np.ndarray, spec: P):
    """Place one host array on the mesh under ``spec`` — ``device_put`` on a
    single controller, cross-host assembly from per-process slices otherwise."""
    sharding = NamedSharding(mesh, spec)
    if jax.process_count() > 1:
        return jax.make_array_from_process_local_data(sharding, array)
    return jax.device_put(array, sharding)


def replicate(mesh: Mesh, tree: Pytree) -> Pytree:
    """Replicate a pytree across the mesh (params/opt state).

    A jitted identity rather than ``device_put``: ``device_put`` returns the
    *same* buffer when the array already has the target sharding, and the
    train steps donate their state — two states replicated from one source
    must not alias or donating one deletes the other.
    """
    sharding = NamedSharding(mesh, P())
    return jax.jit(lambda t: t, out_shardings=sharding)(tree)


def _sync_step_body(model, tx, axis: str, state: TrainState, images, labels, rng):
    """Per-device DDP step body (inside ``shard_map``), shared by the
    per-step and scanned dispatchers. The dropout rng folds in ``state.step``
    and the device index, so both dispatchers produce the same stream."""
    step_rng = jax.random.fold_in(
        jax.random.fold_in(rng, state.step), jax.lax.axis_index(axis)
    )

    def loss_fn(params):
        logits = model.apply(
            {"params": params}, images, train=True, rngs={"dropout": step_rng}
        )
        return cross_entropy_loss(logits, labels)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    # THE allreduce. Params enter replicated (invariant over the mesh) and
    # data enters sharded, so differentiation itself inserts the cross-
    # device psum of gradients — the transpose of the implicit pvary under
    # shard_map's varying-axes tracking. That psum IS the DDP allreduce,
    # compiled to an ICI collective (the reference's out-of-tree gloo C++
    # transport re-expressed as an XLA collective — SURVEY.md §2.2).
    # Normalize the sum of per-shard means into the global-batch mean:
    n = jax.lax.psum(1, axis)
    grads = jax.tree.map(lambda g: g / n, grads)
    loss = jax.lax.pmean(loss, axis)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return state.replace(params=params, opt_state=opt_state, step=state.step + 1), loss


def make_sync_train_step(
    model, tx: optax.GradientTransformation, mesh: Mesh, axis: str = "data"
) -> Callable:
    """Build the jitted DDP step: local grads + ``pmean`` allreduce + SGD."""

    def shard_fn(state: TrainState, images, labels, rng):
        return _sync_step_body(model, tx, axis, state, images, labels, rng)

    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P()),
        out_specs=(P(), P()),
    )
    # Donate the state so params/opt-state update in place in HBM.
    return jax.jit(sharded, donate_argnums=(0,))


def make_sync_scan_step(
    model, tx: optax.GradientTransformation, mesh: Mesh, axis: str = "data"
) -> Callable:
    """K DDP steps in ONE compiled program: ``lax.scan`` over a stacked
    ``[K, batch, ...]`` input *inside* the ``shard_map`` region, so each scan
    iteration runs the identical body (psum allreduce included) as
    :func:`make_sync_train_step` — host dispatch amortizes over K without
    changing the math (``--steps-per-dispatch`` for ``--mode sync``).
    Returns ``(state, losses[K])``."""

    def shard_fn(state: TrainState, images, labels, rng):
        def body(st, batch):
            return _sync_step_body(model, tx, axis, st, batch[0], batch[1], rng)

        return jax.lax.scan(body, state, (images, labels))

    sharded = jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(P(), P(None, axis), P(None, axis), P()),
        out_specs=(P(), P()),
    )
    return jax.jit(sharded, donate_argnums=(0,))


def train_data_parallel(
    args,
    mesh: Mesh | None,
    strategy: Callable,
    label: str,
) -> Tuple[TrainState, MetricsLogger]:
    """Shared data-parallel training driver (sync-DP and FSDP).

    ``--batch-size`` is the **per-device** batch (matching the reference's
    per-worker batch of 64, ``example/main.py:142``); the global batch is
    ``batch_size × mesh size``. Each epoch reshuffles; on multi-host meshes
    every controller loads only its strided shard of the training set and
    feeds its per-process slice of each global batch.

    ``strategy(model, tx, mesh, state) -> (state, sharded_step, scan_fn,
    suffix)`` owns everything layout-specific: placing the (possibly
    ckpt-restored) state on the mesh, and wrapping the jitted step so it
    shards each host batch itself; ``scan_fn`` is the chunked
    (``--steps-per-dispatch``) dispatcher or ``None`` when the strategy has
    none. Everything else — data, model, LR schedule, grad accum,
    checkpoint/resume, the epoch loop, telemetry — is one copy here.
    """
    from distributed_ml_pytorch_tpu.data import get_dataset, shard_for_process
    from distributed_ml_pytorch_tpu.models import get_model
    from distributed_ml_pytorch_tpu.runtime import data_mesh

    mesh = mesh or data_mesh()
    n_dev = mesh.devices.size
    global_batch = args.batch_size * n_dev

    x_train, y_train, x_test, y_test = get_dataset(args)
    n_proc = jax.process_count()
    if n_proc > 1:
        x_train, y_train = shard_for_process(x_train, y_train, jax.process_index(), n_proc)
    model = get_model(
        getattr(args, "model", "alexnet"),
        dtype=jnp.bfloat16 if getattr(args, "dtype", "float32") == "bfloat16" else jnp.float32,
    )
    from distributed_ml_pytorch_tpu.training.trainer import (
        setup_checkpoint,
        state_from_args,
    )

    per_proc_batch = global_batch // n_proc
    state, tx = state_from_args(args, model, len(x_train) // per_proc_batch)
    # restore (if resuming) BEFORE mesh placement: orbax hands back host
    # arrays and the strategy then lays them out like a fresh init
    ckpt, state, start_epoch, start_iter = setup_checkpoint(
        args, state, len(x_train) // per_proc_batch
    )
    state, sharded_step, scan_fn, suffix = strategy(model, tx, mesh, state)
    # say where the arrays live: a multi-chip run is only that if the batch
    # really is split over distinct devices (the batch's is the sharding
    # shard_batch gives every step's input)
    leaf = jax.tree.leaves(state.params)[0]
    batch = NamedSharding(mesh, P("data"))
    shape = (global_batch,) + x_train.shape[1:]
    ids = lambda sharding: sorted(d.id for d in sharding.device_set)
    print("{}: {} devices {}; params {} on {}; batch {} {} split as {} on {}"
          .format(label, mesh.devices.flat[0].platform,
                  [d.id for d in mesh.devices.flat], leaf.sharding.spec,
                  ids(leaf.sharding), shape, batch.spec,
                  batch.shard_shape(shape), ids(batch)))
    eval_step = make_eval_fn(model)
    logger = MetricsLogger(getattr(args, "log_dir", "log"))

    loop_args = copy.copy(args)
    loop_args.batch_size = per_proc_batch
    # the step wrapper shards each host batch itself (put_sharded needs the
    # numpy array, and on multi-host the per-process slice); default-device
    # prefetch would force an extra device→device reshard copy
    loop_args.prefetch = 0

    t0 = time.time()
    try:
        state = run_training_loop(
            model=model,
            state=state,
            train_step=sharded_step,
            eval_step=eval_step,
            data=(x_train, y_train, x_test, y_test),
            args=loop_args,
            logger=logger,
            ckpt=ckpt,
            start_epoch=start_epoch,
            start_iter=start_iter,
            scan_step=scan_fn,
        )
    finally:
        if ckpt is not None:
            ckpt.close()
    print(
        "Finished {} training ({:.1f}s, {} devices{})".format(
            label, time.time() - t0, n_dev, suffix
        )
    )
    return state, logger


def train_sync(args, mesh: Mesh | None = None) -> Tuple[TrainState, MetricsLogger]:
    """Synchronous data-parallel training loop (replicated params, in-graph
    gradient psum) — see :func:`train_data_parallel` for the shared driver."""

    def strategy(model, tx, mesh, state):
        state = replicate(mesh, state)
        train_step = make_sync_train_step(model, tx, mesh)
        scan_step = make_sync_scan_step(model, tx, mesh)
        rng = replicate(mesh, jax.random.key(getattr(args, "seed", 0) + 1))

        def sharded_step(state, bx, by, _rng):
            bx, by = shard_batch(mesh, bx, by)
            return train_step(state, bx, by, rng)

        def sharded_scan(state, bxs, bys, _rng):
            # stacked [K, batch, ...]: shard the batch (second) axis
            bxs = put_sharded(mesh, bxs, P(None, "data", *([None] * (bxs.ndim - 2))))
            bys = put_sharded(mesh, bys, P(None, "data", *([None] * (bys.ndim - 2))))
            return scan_step(state, bxs, bys, rng)

        return state, sharded_step, sharded_scan, ""

    return train_data_parallel(args, mesh, strategy, "sync-DP")
