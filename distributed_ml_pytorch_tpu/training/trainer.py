"""C4/C5: the trainer and evaluator (parity with reference ``example/main.py:31-133``).

The reference's hot loop (``example/main.py:57-91``) is zero_grad → forward →
cross_entropy → backward → step with periodic eval. Here the whole step —
forward, loss, backward, SGD update — is one jitted function: XLA fuses the
elementwise chain into the conv/matmul kernels on the MXU, and the only
host↔device traffic per step is the input batch in and a scalar loss out.

Parity decisions (SURVEY.md §7 "reproduce the intent, not the defect"):

- plain SGD, ``momentum=0.0`` (reference ``example/main.py:44``);
- eval every ``log_interval`` batches with ``i > 0`` (``:83-84``) and a
  verbose eval each epoch end (``:93``);
- ``test_loss`` is the *sum* of per-batch mean losses (``:125`` semantics —
  identical to a single number when ``test_batch_size`` covers the whole
  set, the reference default of 10000);
- accuracy over the **full** test set (the reference scores only its final
  batch with swapped args — a defect, not copied);
- no eval-mode leak: dropout is controlled per-call by ``train=``, unlike the
  reference whose ``net.eval()`` at ``:113`` permanently disables dropout
  after the first mid-epoch eval;
- the never-stepped LambdaLR scheduler (``:47-48``): the default
  (``--lr-schedule constant``) matches the reference's *effective* behavior,
  and ``make_lr_schedule`` offers its *configured* 1/(epoch+1) decay done
  right (``inverse-epoch``), plus cosine.
"""

from __future__ import annotations

import sys
import time
from functools import partial
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct

from distributed_ml_pytorch_tpu.data import CIFAR10_CLASSES, iterate_batches
from distributed_ml_pytorch_tpu.utils.metrics import (
    MetricsLogger,
    print_classification_report,
    print_eval_line,
)
from distributed_ml_pytorch_tpu.utils.tracing import (
    StepTimer,
    TraceWindow,
    annotate_step,
)

Pytree = Any


class TrainState(struct.PyTreeNode):
    """Minimal functional train state: params + optimizer state + step count."""

    params: Pytree
    opt_state: optax.OptState
    step: jnp.ndarray

    @classmethod
    def create(cls, params: Pytree, tx: optax.GradientTransformation) -> "TrainState":
        return cls(params=params, opt_state=tx.init(params), step=jnp.zeros((), jnp.int32))


def make_lr_schedule(
    kind: str, lr: float, steps_per_epoch: int = 1, total_epochs: int = 1
):
    """Working LR schedules — the reference *configures* a ``LambdaLR`` with
    ``1/(epoch+1)`` decay but never calls ``scheduler.step()``, so its lr
    stays constant (``example/main.py:47-48``; SURVEY.md §5.6 flags the dead
    scheduler). This implements the intent:

    - ``constant`` — the reference's *effective* behavior (default);
    - ``inverse-epoch`` — the reference's *configured* behavior, done right;
    - ``cosine`` — cosine decay to 0 over the whole run.

    Returns an optax schedule (step → lr) or a float for ``constant``.
    """
    if kind == "constant":
        return lr
    if kind == "inverse-epoch":
        spe = max(1, int(steps_per_epoch))
        return lambda step: lr / (step // spe + 1)
    if kind == "cosine":
        total = max(1, int(steps_per_epoch) * int(total_epochs))
        return optax.cosine_decay_schedule(lr, decay_steps=total)
    raise ValueError(f"unknown lr schedule {kind!r} (constant|inverse-epoch|cosine)")


def make_optimizer(
    name: str,
    lr,
    momentum: float = 0.0,
    weight_decay: float | None = None,
    grad_clip: float = 0.0,
) -> optax.GradientTransformation:
    """Optimizer registry for the ``--optimizer`` flag.

    ``sgd`` is the reference's recipe (``optim.SGD(lr, momentum=0.0)``,
    ``example/main.py:44``); ``adam`` and ``adamw`` are extensions. ``lr``
    may be a float or an optax schedule.

    ``grad_clip > 0`` prepends global-norm clipping. ``weight_decay`` is
    decoupled (AdamW-style) for ``adamw``; for ``sgd``/``adam`` it is
    classic L2 regularization (``optax.add_decayed_weights`` folded into the
    gradient before the update rule). ``None`` (the default) keeps each
    optimizer's own default — in particular adamw retains optax's 1e-4 —
    while an explicit ``0.0`` disables decay.
    """
    name = name.lower()
    if name == "sgd":
        base = optax.sgd(lr, momentum=momentum if momentum else None)
    elif name == "adam":
        base = optax.adam(lr)
    elif name == "adamw":
        base = optax.adamw(lr) if weight_decay is None else optax.adamw(
            lr, weight_decay=weight_decay
        )
    else:
        raise ValueError(f"unknown optimizer {name!r} (sgd|adam|adamw)")
    chain = []
    if grad_clip and grad_clip > 0:
        chain.append(optax.clip_by_global_norm(grad_clip))
    if weight_decay and name in ("sgd", "adam"):
        chain.append(optax.add_decayed_weights(weight_decay))
    if not chain:
        return base
    return optax.chain(*chain, base)


def build_tx(
    optimizer: str,
    lr,
    momentum: float = 0.0,
    weight_decay: float | None = None,
    grad_clip: float = 0.0,
    grad_accum: int = 1,
) -> optax.GradientTransformation:
    """``make_optimizer`` + the grad-accumulation wrap — the single assembly
    point shared by :func:`create_train_state` and :func:`tx_from_args` so
    a new chain element cannot diverge between the kwarg and CLI paths."""
    tx = make_optimizer(optimizer, lr, momentum, weight_decay, grad_clip)
    if int(grad_accum) > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=int(grad_accum))
    return tx


def create_train_state(
    model,
    rng: jax.Array,
    lr,
    momentum: float = 0.0,
    sample_shape=(1, 32, 32, 3),
    grad_accum: int = 1,
    optimizer: str = "sgd",
    weight_decay: float | None = None,
    grad_clip: float = 0.0,
) -> Tuple[TrainState, optax.GradientTransformation]:
    """Initialize params + optimizer (reference ``optim.SGD(lr, momentum=0.0)``,
    ``example/main.py:44``). ``lr`` may be a float or an optax schedule
    (see :func:`make_lr_schedule`).

    ``grad_accum > 1`` wraps the optimizer in ``optax.MultiSteps``: gradients
    average over that many consecutive micro-batches before one SGD update
    is applied — the effective batch grows without growing per-step HBM.
    """
    params = model.init(rng, jnp.zeros(sample_shape))["params"]
    tx = build_tx(optimizer, lr, momentum, weight_decay, grad_clip, grad_accum)
    return TrainState.create(params, tx), tx


def tx_from_args(args, steps_per_epoch: int) -> optax.GradientTransformation:
    """Build the optax transform from the CLI argument surface — the ONE
    place the optimizer/schedule/accumulation knobs are read, shared by the
    single-process, sync/fsdp, local-sgd, AND async-PS trainers so a new
    knob cannot be silently dropped by one mode.

    ``steps_per_epoch`` is in raw batches; with ``--grad-accum K`` the LR
    schedule advances once per K micro-batches (``optax.MultiSteps`` emits
    one optimizer update per K), so the schedule's epoch is measured in
    optimizer updates.
    """
    grad_accum = int(getattr(args, "grad_accum", 1) or 1)
    lr = make_lr_schedule(
        getattr(args, "lr_schedule", "constant"),
        args.lr,
        steps_per_epoch=max(1, int(steps_per_epoch) // grad_accum),
        total_epochs=args.epochs,
    )
    return build_tx(
        getattr(args, "optimizer", "sgd"),
        lr,
        getattr(args, "momentum", 0.0),
        getattr(args, "weight_decay", None),
        getattr(args, "grad_clip", 0.0),
        grad_accum,
    )


def state_from_args(args, model, steps_per_epoch: int, sample_shape=(1, 32, 32, 3)):
    """``(state, tx)`` from the CLI surface (see :func:`tx_from_args`)."""
    tx = tx_from_args(args, steps_per_epoch)
    params = model.init(
        jax.random.key(getattr(args, "seed", 0)), jnp.zeros(sample_shape)
    )["params"]
    return TrainState.create(params, tx), tx


def cross_entropy_loss(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Mean softmax cross-entropy (reference ``F.cross_entropy``, ``example/main.py:71``)."""
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()


def chunked_lm_loss(model, params, tokens, targets, chunk: int = 2048,
                    ce_dtype=None):
    """Masked-mean next-token CE WITHOUT materializing (batch, seq, vocab)
    logits — the long-context LM loss.

    At S=32k the GPT-2-small logits tensor alone is 6.6 GB (f32), which is
    what stops the full model training at that length, not the attention
    (the flash kernel handles S=32k fine — ops/attention.py). This runs
    the Transformer body once (``model.clone(head=False)`` → post-LayerNorm
    hiddens, O(S·d)), then a ``lax.scan`` over sequence chunks applies the
    lm_head matmul + CE per chunk under ``jax.checkpoint`` — the backward
    recomputes each chunk's logits instead of saving them, so peak logits
    memory is O(chunk·vocab) in both passes.

    Same loss definition as ``fsdp.lm_loss_builder`` (final sequence
    position masked); exact equality is tested. ``seq`` must divide by
    ``chunk``.

    ``ce_dtype`` (default ``None``): dtype the per-chunk logits are cast
    to before the softmax CE. ``None`` keeps the activation dtype — the
    dense-loss convention, +3.7% on the 32k leg vs an f32 upcast. Under
    bf16 activations the CE gradient (softmax − one-hot) is then computed
    from 8-bit-mantissa logits; a measured 60-step bf16 training
    comparison at vocab 16k tracks the per-chunk-f32 trajectory within
    noise (``tests/test_transformer.py::
    test_chunked_lm_loss_bf16_ce_tracks_f32_ce_training``), but callers
    training larger vocabularies who want f32 CE can pass
    ``ce_dtype=jnp.float32`` — the upcast buffer is per-chunk
    (``chunk × vocab``), not the full sequence.
    """
    b, s = tokens.shape
    if s % chunk:
        raise ValueError(f"seq {s} must divide by chunk {chunk}")
    h = model.clone(head=False).apply({"params": params}, tokens)
    w = params["lm_head"]["kernel"]
    n = s // chunk
    hc = h.reshape(b, n, chunk, h.shape[-1]).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, n, chunk).transpose(1, 0, 2)
    mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    mc = mask.reshape(b, n, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk_ce(w_, h_c, t_c, m_c):
        # 2-D logits in the activation dtype — the same convention as the
        # dense loss (fsdp.lm_loss_builder): the old per-chunk f32 upcast
        # materialized a 412 MB f32 logits buffer per 2048-token chunk at
        # GPT-2-small shapes (2x the bf16 bytes through HBM, twice per
        # step under the checkpoint's recompute)
        b_, c_, d_ = h_c.shape
        logits = h_c.reshape(b_ * c_, d_) @ w_.astype(h_c.dtype)
        if ce_dtype is not None:
            logits = logits.astype(ce_dtype)
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits, t_c.reshape(-1))
        return jnp.sum(ce * m_c.reshape(-1))

    def body(carry, xs):
        h_c, t_c, m_c = xs
        return carry + chunk_ce(w, h_c, t_c, m_c), None

    loss_sum, _ = jax.lax.scan(body, jnp.zeros(()), (hc, tc, mc))
    return loss_sum / jnp.sum(mask)


def _sgd_step_body(model, tx, state: TrainState, images, labels, dropout_rng):
    """Unjitted single-step update shared by the per-step and scanned trainers.

    The dropout rng folds in ``state.step``, so the same body produces the
    same stream whether steps are dispatched one at a time or scanned.
    """
    rng = jax.random.fold_in(dropout_rng, state.step)

    def loss_fn(params):
        logits = model.apply(
            {"params": params}, images, train=True, rngs={"dropout": rng}
        )
        return cross_entropy_loss(logits, labels)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return state.replace(params=params, opt_state=opt_state, step=state.step + 1), loss


def make_train_step(model, tx: optax.GradientTransformation) -> Callable:
    """One fully-jitted SGD step: forward + loss + backward + update."""

    # Donating the state lets XLA update params/opt-state in place instead of
    # allocating a second copy in HBM each step (ignored, with no harm, on
    # backends that can't donate).
    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state: TrainState, images, labels, dropout_rng) -> Tuple[TrainState, jnp.ndarray]:
        return _sgd_step_body(model, tx, state, images, labels, dropout_rng)

    return train_step


def make_scan_train_step(model, tx: optax.GradientTransformation) -> Callable:
    """K SGD steps in ONE compiled program via ``lax.scan`` — the TPU-idiomatic
    trainer for small models, where per-step host dispatch dominates.

    ``(state, images [K,B,...], labels [K,B], dropout_rng) → (state, losses [K])``
    processes K *distinct* microbatches with exactly the same per-step update
    (and dropout stream) as :func:`make_train_step` dispatched K times — the
    equivalence is tested — but pays the host→device round-trip once per K
    steps instead of per step. There is no reference counterpart
    (its hot loop is Python per step, ``example/main.py:59-91``).
    """

    @partial(jax.jit, donate_argnums=(0,))
    def scan_train_step(state: TrainState, images, labels, dropout_rng):
        def body(st, batch):
            bx, by = batch
            return _sgd_step_body(model, tx, st, bx, by, dropout_rng)

        return jax.lax.scan(body, state, (images, labels))

    return scan_train_step


def _accum_update_body(model, tx, microbatch: int, state: TrainState,
                       images, labels, dropout_rng,
                       effective_update_batch: Optional[int],
                       remat: bool):
    """Unjitted large-batch update via a microbatch accumulation scan.

    ``images`` is one large batch ``(B, ...)`` with ``B = k·microbatch``;
    the scan runs the forward+backward on each microbatch and accumulates
    the SUM of per-microbatch mean gradients into a zeros-initialized
    accumulator (a scan carry — XLA updates it in place, so peak HBM is
    one microbatch's activations + one gradient-sized buffer, never the
    full batch's activations).

    Update semantics (the large-batch recipe knob):

    - ``effective_update_batch=None`` — the accumulated grad is divided
      by ``k``: exactly the mean over the full ``B`` (one large-batch
      step; equal to the unaccumulated step up to float summation order).
    - ``effective_update_batch=e`` (e.g. 64) — the accumulated grad is
      scaled by ``microbatch/e``, making it ``Σ`` of the ``B/e``
      batch-``e`` mean gradients at the current params. For SGD the
      applied update is then the SUM of the ``B/e`` reference-recipe
      batch-``e`` updates evaluated at frozen params — first-order
      equivalent to ``B/e`` sequential recipe steps (linear-scaling, per
      the weight-update engineering of arXiv:2004.13336) — so the
      throughput leg preserves the batch-64 *effective update* while the
      compute runs at large-batch geometry.

    ``remat`` wraps the microbatch loss in ``jax.checkpoint`` (recompute
    activations in the backward) — measured OFF as the default: AlexNet
    microbatch activations are far below HBM, so remat only adds FLOPs.
    """
    b = images.shape[0]
    if b % microbatch:
        raise ValueError(f"batch {b} must divide by microbatch {microbatch}")
    k = b // microbatch
    if effective_update_batch is not None:
        if effective_update_batch <= 0:
            raise ValueError(
                f"effective_update_batch must be positive, got "
                f"{effective_update_batch} (use None for the large-batch "
                f"mean update)")
        scale = microbatch / float(effective_update_batch)
    else:
        scale = 1.0 / k
    mi = images.reshape(k, microbatch, *images.shape[1:])
    ml = labels.reshape(k, microbatch)

    def micro_loss(params, bx, by, rng):
        logits = model.apply(
            {"params": params}, bx, train=True, rngs={"dropout": rng})
        return cross_entropy_loss(logits, by)

    if remat:
        micro_loss = jax.checkpoint(micro_loss)
    step_key = jax.random.fold_in(dropout_rng, state.step)

    def body(carry, batch):
        acc, loss_sum, j = carry
        bx, by = batch
        rng = jax.random.fold_in(step_key, j)  # unique per (update, micro)
        loss, grads = jax.value_and_grad(micro_loss)(state.params, bx, by, rng)
        acc = jax.tree.map(jnp.add, acc, grads)
        return (acc, loss_sum + loss, j + 1), None

    zeros = jax.tree.map(jnp.zeros_like, state.params)
    carry0 = (zeros, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (acc, loss_sum, _), _ = jax.lax.scan(body, carry0, (mi, ml))
    grads = jax.tree.map(lambda gsum: gsum * scale, acc)
    updates, opt_state = tx.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    new_state = state.replace(
        params=params, opt_state=opt_state, step=state.step + 1)
    return new_state, loss_sum / k


def make_accum_train_step(model, tx: optax.GradientTransformation,
                          microbatch: int,
                          effective_update_batch: Optional[int] = None,
                          remat: bool = False) -> Callable:
    """ONE optimizer update from a large batch via a microbatch scan.

    ``(state, images [B, ...], labels [B], dropout_rng) → (state, loss)``
    with ``B`` a multiple of ``microbatch``. See :func:`_accum_update_body`
    for the accumulator and update-scaling semantics; the state is donated
    so params/opt-state update in place.
    """

    @partial(jax.jit, donate_argnums=(0,))
    def accum_step(state: TrainState, images, labels, dropout_rng):
        return _accum_update_body(
            model, tx, microbatch, state, images, labels, dropout_rng,
            effective_update_batch, remat)

    return accum_step


def make_scan_accum_train_step(model, tx: optax.GradientTransformation,
                               microbatch: int,
                               effective_update_batch: Optional[int] = None,
                               remat: bool = False) -> Callable:
    """U accumulated large-batch updates in ONE compiled program.

    ``(state, images [U, B, ...], labels [U, B], dropout_rng) →
    (state, losses [U])`` — the :func:`make_scan_train_step` analog for
    the gradient-accumulation recipe, so the large-batch bench legs pay
    host dispatch once per U updates like the parity leg does.
    """

    @partial(jax.jit, donate_argnums=(0,))
    def scan_accum_step(state: TrainState, images, labels, dropout_rng):
        def outer(st, batch):
            bx, by = batch
            return _accum_update_body(
                model, tx, microbatch, st, bx, by, dropout_rng,
                effective_update_batch, remat)

        return jax.lax.scan(outer, state, (images, labels))

    return scan_accum_step


def make_eval_fn(model) -> Callable:
    """Jitted per-batch eval: (summed-mean loss contribution, predictions)."""

    @jax.jit
    def eval_step(params, images, labels):
        logits = model.apply({"params": params}, images, train=False)
        loss = cross_entropy_loss(logits, labels)
        preds = jnp.argmax(logits, axis=-1)
        return loss, preds

    return eval_step


def evaluate(
    eval_step: Callable,
    params: Pytree,
    x_test: np.ndarray,
    y_test: np.ndarray,
    test_batch_size: int,
    verbose: bool = False,
) -> Tuple[float, float]:
    """Full test-set pass (reference ``evaluate``, ``example/main.py:110-133``).

    Returns ``(test_loss, test_accuracy)`` where ``test_loss`` accumulates
    per-batch mean losses (reference ``:125`` summed semantics) and accuracy
    covers the whole test set.
    """
    total_loss = 0.0
    preds_all = []
    labels_all = []
    for bx, by in iterate_batches(
        x_test, y_test, min(test_batch_size, len(x_test)), shuffle=False, drop_last=False
    ):
        loss, preds = eval_step(params, bx, by)
        total_loss += float(loss)
        preds_all.append(np.asarray(preds))
        labels_all.append(by)
    y_pred = np.concatenate(preds_all)
    y_true = np.concatenate(labels_all)
    accuracy = float((y_pred == y_true).mean())
    if verbose:
        print_classification_report(y_true, y_pred, CIFAR10_CLASSES, total_loss, accuracy)
    return total_loss, accuracy


def run_training_loop(
    *,
    model,
    state: TrainState,
    train_step: Callable,
    eval_step: Callable,
    data,
    args,
    logger: MetricsLogger,
    on_step: Optional[Callable] = None,
    ckpt=None,
    start_epoch: int = 0,
    start_iter: int = 0,
    scan_step: Optional[Callable] = None,
) -> TrainState:
    """Shared epoch/batch loop (reference ``example/main.py:57-93`` shape).

    ``on_step(state, epoch, i) -> state`` lets parallel strategies hook the
    between-steps boundary (e.g. the async-PS param swap) without forking the
    trainer — the backend-agnosticism SURVEY.md §7 calls for.

    ``ckpt`` (a ``utils.checkpoint.Checkpointer``) is offered every step after
    the update; its ``save_interval_steps`` decides which are accepted, and the
    saves are async so the next step launches while bytes drain to disk.
    ``start_epoch``/``start_iter`` fast-forward a resumed run to the exact
    batch (the shuffle order is a pure function of ``(seed, epoch)``).

    ``scan_step`` (``make_scan_train_step``-shaped) enables chunked dispatch:
    with ``--steps-per-dispatch K > 1``, up to K consecutive batches are
    stacked and trained in one compiled program. Chunks never cross a
    ``log_interval`` or ``--ckpt-every`` boundary (evals see exactly the
    params they would per-step; checkpoint steps land on exact multiples, as
    orbax requires), and per-step losses still land in the CSV row-for-row
    (the scan returns all K). Batches are uniform (``iterate_batches`` drops
    the last partial batch), so stacking is always well-shaped.
    """
    x_train, y_train, x_test, y_test = data
    dropout_rng = jax.random.key(getattr(args, "seed", 0) + 1)
    tracer = TraceWindow(
        getattr(args, "profile_dir", None),
        start=getattr(args, "profile_start", 10),
        n_steps=getattr(args, "profile_steps", 10),
    )
    # persistent step counter: resumed runs continue where the checkpoint
    # left off, so --profile-start addresses the same step numbering as
    # --ckpt-every and the CSV logs
    global_step = int(state.step)
    # one timer for the whole run: warmup-skip covers XLA compile, which
    # only happens on the first steps; per-epoch stats via reset_stats()
    timer = StepTimer(items_per_step=args.batch_size)
    chunk_k = int(getattr(args, "steps_per_dispatch", 1) or 1)
    use_scan = scan_step is not None and chunk_k > 1 and on_step is None

    def run_one(state, i, bx, by):
        """One per-step dispatch (the reference-shaped path)."""
        nonlocal global_step
        tracer.on_step(global_step)
        if on_step is not None:
            state = on_step(state, epoch, i)
        timer.start()
        with annotate_step("train", global_step):
            state, loss = train_step(state, bx, by, dropout_rng)
            loss_val = float(loss)  # blocks on the step's output
        timer.tick()
        if ckpt is not None:
            ckpt.save(int(state.step), state)
        global_step += 1
        tracer.after_step(global_step)
        return state, [(i, loss_val)]

    def run_chunk(state, chunk):
        """One scanned dispatch over len(chunk) stacked batches."""
        nonlocal global_step
        if len(chunk) == 1:
            return run_one(state, *chunk[0])
        tracer.on_step(global_step, n_steps=len(chunk))
        bxs = np.stack([c[1] for c in chunk])
        bys = np.stack([c[2] for c in chunk])
        timer.start()
        with annotate_step("train", global_step):
            state, losses = scan_step(state, bxs, bys, dropout_rng)
            losses = np.asarray(losses)  # blocks on the chunk's output
        timer.tick_n(len(chunk))
        if ckpt is not None:
            ckpt.save(int(state.step), state)
        global_step += len(chunk)
        tracer.after_step(global_step)
        return state, [(c[0], float(l)) for c, l in zip(chunk, losses)]

    def emit(records):
        """Per-step CSV rows + boundary evals (reference :83-89 telemetry)."""
        for i, loss_val in records:
            rec_extra = {}
            if i % args.log_interval == 0 and i > 0:  # reference :83-84
                test_loss, test_acc = evaluate(
                    eval_step, state.params, x_test, y_test, args.test_batch_size
                )
                rec_extra = {"test_loss": test_loss, "test_accuracy": test_acc}
            rec = logger.log_step(i, loss_val, **rec_extra)
            if rec_extra:
                print_eval_line(rec)

    try:
        for epoch in range(start_epoch, args.epochs):
            print("Training for epoch {}".format(epoch))
            skip = start_iter if epoch == start_epoch else 0
            pending = []  # buffered (i, bx, by) awaiting a chunk flush
            batch_iter = iterate_batches(
                x_train, y_train, args.batch_size,
                seed=getattr(args, "seed", 0), epoch=epoch, start_iter=skip,
            )
            prefetch_n = int(getattr(args, "prefetch", 2) or 0)
            if not use_scan and prefetch_n > 0:
                # per-step path: keep batches in flight so the H2D copy
                # overlaps the previous step's compute (the chunked path
                # stacks on host, so it stays on numpy batches)
                from distributed_ml_pytorch_tpu.data import prefetch_to_device

                batch_iter = prefetch_to_device(batch_iter, prefetch_n)
            for i, (bx, by) in enumerate(batch_iter, start=skip):
                if not use_scan:
                    state, records = run_one(state, i, bx, by)
                    emit(records)
                    continue
                pending.append((i, bx, by))
                # flush on a full chunk, at an eval boundary (so the eval sees
                # exactly the params after step i, never later ones), or at a
                # checkpoint boundary (orbax accepts saves only at exact
                # multiples of --ckpt-every, so a boundary must be a chunk end)
                at_eval = i % args.log_interval == 0 and i > 0
                at_ckpt = (
                    ckpt is not None
                    and (global_step + len(pending)) % ckpt.save_interval_steps == 0
                )
                if len(pending) >= chunk_k or at_eval or at_ckpt:
                    state, records = run_chunk(state, pending)
                    pending = []
                    emit(records)
            if pending:
                state, records = run_chunk(state, pending)
                pending = []
                emit(records)
            # a window straddling the epoch boundary is truncated here rather
            # than polluting the capture with the full-test-set eval below
            tracer.close()
            evaluate(eval_step, state.params, x_test, y_test, args.test_batch_size, verbose=True)
            line = timer.report("epoch {} train-step time".format(epoch))
            if line:
                print(line)
            timer.reset_stats()
    finally:
        tracer.close()
        tracer.warn_if_never_opened()
        # commit the last completed step even when interrupted mid-epoch —
        # the exact scenario checkpointing exists for. If the interruption
        # landed inside a donating train_step, `state` may reference deleted
        # buffers; never let that mask the original exception.
        if ckpt is not None:
            try:
                ckpt.save(int(state.step), state, force=True)
                ckpt.wait()
            except Exception as e:  # pragma: no cover - interrupt-timing dependent
                print(f"warning: final checkpoint save failed: {e}", file=sys.stderr)
    return state


def setup_checkpoint(args, state: TrainState, steps_per_epoch: int):
    """Build the Checkpointer from CLI flags and fast-forward a resumed run.

    Returns ``(ckpt, state, start_epoch, start_iter)``; ``ckpt`` is ``None``
    when ``--ckpt-dir`` is unset. Shared by the single-process and sync-DP
    trainers (orbax handles replicated/sharded arrays the same way).
    """
    if not getattr(args, "ckpt_dir", None):
        return None, state, 0, 0
    from distributed_ml_pytorch_tpu.utils.checkpoint import (
        Checkpointer,
        maybe_restore,
        resume_position,
    )

    ckpt = Checkpointer(
        args.ckpt_dir,
        max_to_keep=getattr(args, "ckpt_keep", 3),
        save_interval_steps=getattr(args, "ckpt_every", 500),
    )
    start_epoch = start_iter = 0
    if getattr(args, "resume", False):
        state, resume_step = maybe_restore(ckpt, state)
        if resume_step:
            start_epoch, start_iter = resume_position(resume_step, steps_per_epoch)
            print(
                "resumed from step {} → epoch {} iter {}".format(
                    resume_step, start_epoch, start_iter
                )
            )
    return ckpt, state, start_epoch, start_iter


def train_single(args) -> Tuple[TrainState, MetricsLogger]:
    """Single-process baseline training (reference ``make single``/``make gpu``,
    SURVEY.md §3.5). Runs on whatever backend jax selected — the TPU chip by
    default here, CPU under ``--backend=cpu``."""
    from distributed_ml_pytorch_tpu.data import get_dataset
    from distributed_ml_pytorch_tpu.models import get_model

    x_train, y_train, x_test, y_test = get_dataset(args)
    model = get_model(
        getattr(args, "model", "alexnet"),
        dtype=jnp.bfloat16 if getattr(args, "dtype", "float32") == "bfloat16" else jnp.float32,
    )
    steps_per_epoch = max(1, len(x_train) // args.batch_size)
    state, tx = state_from_args(args, model, steps_per_epoch)
    train_step = make_train_step(model, tx)
    scan_step = (
        make_scan_train_step(model, tx)
        if int(getattr(args, "steps_per_dispatch", 1) or 1) > 1
        else None
    )
    eval_step = make_eval_fn(model)
    logger = MetricsLogger(getattr(args, "log_dir", "log"))

    ckpt, state, start_epoch, start_iter = setup_checkpoint(args, state, steps_per_epoch)

    t0 = time.time()
    try:
        state = run_training_loop(
            model=model,
            state=state,
            train_step=train_step,
            eval_step=eval_step,
            data=(x_train, y_train, x_test, y_test),
            args=args,
            logger=logger,
            ckpt=ckpt,
            start_epoch=start_epoch,
            start_iter=start_iter,
            scan_step=scan_step,
        )
    finally:
        if ckpt is not None:
            ckpt.close()
    print("Finished Training ({:.1f}s)".format(time.time() - t0))
    return state, logger
