"""Pipeline-parallel LM training: GPipe, 1F1B, and interleaved (virtual-stage)
microbatch schedules over a ``stage`` mesh axis.

The reference has no pipeline parallelism (SURVEY.md §2.4 marks PP ABSENT) —
this is a capability extension, built the TPU-native way: the whole schedule
is one jitted ``shard_map`` program, differentiated end-to-end.

Design:

- The Transformer body is a **stack of identical blocks** whose parameters
  are stacked on a leading layer axis and sharded ``P(stage)`` — each of the
  ``S`` stages holds ``L/S`` contiguous layers in HBM. Embedding, final
  LayerNorm, and the LM head are replicated (small next to the blocks) but
  *applied* only where they belong: embed on stage 0, head + loss on the
  last stage.
- The GPipe schedule is a ``lax.scan`` over ``M + S - 1`` ticks. At tick
  ``t`` stage ``s`` holds microbatch ``t - s`` (when valid): it runs its
  local layers and ``ppermute``s the activation to stage ``s + 1``. Bubbles
  are masked, not branched — every stage executes the same program every
  tick (SPMD), selecting between "freshly embedded microbatch" (stage 0)
  and "activation received from the left neighbor".
- Losses accumulate on the last stage over its valid ticks and are ``psum``
  -broadcast; gradients come from differentiating straight through the
  scan + ppermute schedule (the transpose of ``ppermute`` is the reversed
  permutation, so backward activations flow right→left automatically — no
  hand-written backward schedule). Replicated params (embed/head) get their
  cross-stage gradient psum from ``shard_map``'s transpose of the broadcast.

- The 1F1B schedule (``schedule="1f1b"``) computes the same function with a
  hand-scheduled backward: forwards and explicit per-microbatch ``jax.vjp``
  backwards interleave in one scan, so a stage stashes at most ``S``
  activations (a static ring of stage inputs) instead of the all-``M``
  profile AD gives the scanned GPipe schedule — the difference between
  fitting and OOM at real depth. See :func:`_make_1f1b_step`.

- The INTERLEAVED schedule (``schedule="interleaved"``, Megatron-style
  virtual stages) gives each stage ``v`` strided layer chunks and runs
  chunk ``r`` of microbatch ``m`` on stage ``s`` at tick ``t = r·M + m + s``
  — still one differentiable scan, with the fill bubble shrunk from
  ``(S−1)/(M+S−1)`` to ``(S−1)/(vM+S−1)`` of the step (ticks are 1/v the
  work) at the price of ×v cross-stage traffic and a wrap FIFO. The two
  schedules compute the same function (tested: identical loss and grads).

Composes with data parallelism (``data_axis=...``): each data row of a
``(data, stage)`` mesh runs the full schedule on its shard of every
microbatch (``(M, B, S)`` split over B), the per-row losses ``pmean`` over
data, and the param cotangents — auto-psum'd over data by AD because the
``P(stage, ...)`` params enter data-invariant — are divided into the mean.
All three schedules are loss- and grad-identical to the pure-pp step on
the same global batch (tested).

Composes with TENSOR parallelism (``model_axis=...``): the canonical deep-LM
pairing — tp inside each stage, pp across stages, on a ``(stage, model)``
(optionally ``(data, stage, model)``) mesh. Block params gain Megatron
sharding WITHIN their stage shard (q/k/v column- / heads-split, o
row-split, MLP up column- / down row-split — :func:`pp_param_specs` with
``model_axis``), and the stage forward becomes the explicit-collective
Megatron block: two ``psum``s over ``model`` per layer (after the o
projection and after the MLP down projection), placed where the sharded
contraction ends, so activations stay model-INVARIANT at every hand-off
(ppermutes, stashes, and FIFOs carry no extra copies, and the carry's
varying axes don't change). Embedding, final LN, and head stay replicated
over ``model`` (vocab sharding belongs to the pure-tp path,
``tensor_parallel.py``). All three schedules accept it; loss and grads
match pure-pp numerically (tested).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_ml_pytorch_tpu.models.transformer import Block, default_attn_fn
from distributed_ml_pytorch_tpu.training.trainer import TrainState


class PipelineLMConfig:
    """Static config for the pipelined decoder LM (a plain data holder so the
    schedule code stays framework-free)."""

    def __init__(
        self,
        vocab_size: int = 64,
        d_model: int = 32,
        n_heads: int = 4,
        n_layers: int = 4,
        d_ff: int = 64,
        max_len: int = 1024,
    ):
        self.vocab_size = vocab_size
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_layers = n_layers
        self.d_ff = d_ff
        self.max_len = max_len

    def block(self) -> Block:
        return Block(self.d_model, self.n_heads, self.d_ff)


def init_pp_params(cfg: PipelineLMConfig, rng: jax.Array, sample_len: int = 8):
    """Init the pipelined param tree.

    ``blocks`` is the per-layer param tree *stacked on a leading layer axis*
    (vmapped init over per-layer rngs) — the axis that shards over ``stage``.
    """
    block = cfg.block()
    x = jnp.zeros((1, sample_len, cfg.d_model))
    layer_rngs = jax.random.split(jax.random.fold_in(rng, 0), cfg.n_layers)
    blocks = jax.vmap(lambda r: block.init(r, x)["params"])(layer_rngs)

    embed, pos_embed, head, ln_f = _lm_modules(cfg)
    tokens = jnp.zeros((1, sample_len), jnp.int32)
    return {
        "blocks": blocks,
        "tok_embed": embed.init(jax.random.fold_in(rng, 1), tokens)["params"],
        "pos_embed": pos_embed.init(jax.random.fold_in(rng, 2), tokens)["params"],
        "ln_f": ln_f.init(jax.random.fold_in(rng, 3), x)["params"],
        "head": head.init(jax.random.fold_in(rng, 4), x)["params"],
    }


def _is_blocks_path(path) -> bool:
    """THE stage-sharding rule: a leaf is stage-sharded iff its path crosses
    a ``"blocks"`` key. Shared by :func:`pp_param_specs` and the 1F1B
    localizer so the varying/replicated treatment cannot diverge."""
    return "blocks" in (
        getattr(k, "key", getattr(k, "name", str(k))) for k in path
    )


def _lm_modules(cfg: PipelineLMConfig):
    """The replicated (non-block) modules, one construction shared by every
    schedule builder: ``(tok_embed, pos_embed, head, ln_f)``."""
    from flax import linen as nn

    return (
        nn.Embed(cfg.vocab_size, cfg.d_model),
        nn.Embed(cfg.max_len, cfg.d_model),
        nn.Dense(cfg.vocab_size, use_bias=False),
        nn.LayerNorm(),
    )


def pp_param_specs(tree, stage_axis: str = "stage",
                   model_axis: str | None = None):
    """Spec tree: any leaf under a ``"blocks"`` key is layer-stacked on its
    leading axis → ``P(stage, ...)``; everything else replicated.

    Path-based, so it applies to the param tree and to any tree embedding
    param paths — a whole ``TrainState`` included (optimizer momentum mirrors
    the params), same single-rule design as
    ``tensor_parallel.tp_param_specs`` / ``expert_parallel.ep_param_specs``.

    With ``model_axis`` (pp×tp), block leaves ADDITIONALLY carry the
    Megatron sharding of ``tensor_parallel.tp_param_specs`` within their
    stage shard (leaf shapes have the leading stacked-layer axis):

    ==============================  ======================  ====================
    blocks leaf                     shape                   spec
    ==============================  ======================  ====================
    attn q/k/v kernels              (L, d_model, d_model)   P(stage, None, model)
    attn o kernel                   (L, d_model, d_model)   P(stage, model, None)
    MLP up kernel (Dense_0)         (L, d_model, d_ff)      P(stage, None, model)
    MLP up bias                     (L, d_ff)               P(stage, model)
    MLP down kernel (Dense_1)       (L, d_ff, d_model)      P(stage, model, None)
    MLP down bias / LayerNorms      (L, d_model)            P(stage, None)
    ==============================  ======================  ====================

    Embed / head / final LN stay ``P()`` (replicated over every axis).
    """

    def spec_for(path, leaf):
        if not _is_blocks_path(path):
            return P()
        if model_axis is not None:
            names = [getattr(k, "key", str(k)) for k in path]
            if "attn" in names:
                if names[-2] in ("q", "k", "v"):
                    return P(stage_axis, None, model_axis)
                if names[-2] == "o":
                    return P(stage_axis, model_axis, None)
            if "Dense_0" in names:
                return (P(stage_axis, None, model_axis) if leaf.ndim == 3
                        else P(stage_axis, model_axis))
            if "Dense_1" in names and leaf.ndim == 3:
                return P(stage_axis, model_axis, None)
        return P(*((stage_axis,) + (None,) * (leaf.ndim - 1)))

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def _check_tp_divisibility(cfg: PipelineLMConfig, n_model: int) -> None:
    for name, dim in (("n_heads", cfg.n_heads), ("d_ff", cfg.d_ff)):
        if dim % n_model:
            raise ValueError(
                f"cfg.{name}={dim} is not divisible by the tp axis size "
                f"{n_model} — the sharded dimension must split evenly")


def _wrap_pp_step(grad_fn, tx, mesh, stage_axis, data_axis=None,
                  model_axis=None):
    """``(state, tokens_mb, targets_mb) → (state, loss)`` from a shard_map-
    able ``grad_fn(params, tokens_mb, targets_mb) → (loss, grads)`` — the
    one optimizer-update epilogue shared by all three schedule builders.

    With ``data_axis`` (dp x pp): each data row of the mesh runs the full
    pipeline schedule on its shard of every microbatch (``(M, B, S)`` split
    over B). The per-row LOSS is ``pmean``ed over the data axis; the param
    GRADS are already auto-psum'd over data by AD (params enter
    data-invariant) and are divided by the data-axis size into the mean —
    do NOT replace the divide with a pmean (identity on the summed tree;
    measured to leave grads exactly 2x at dp=2). Params stay
    ``P(stage, ...)`` (replicated over data)."""

    def step(state: TrainState, tokens_mb, targets_mb):
        param_specs = pp_param_specs(state.params, stage_axis, model_axis)

        def fn(params, t, y):
            loss, grads = grad_fn(params, t, y)
            if data_axis is not None:
                # params enter data-INVARIANT, so AD has already psum'd
                # their cotangents over the data axis (a pmean here would be
                # an identity on the summed tree — measured to leave grads
                # exactly 2x at dp=2). Divide into the mean.
                grads = jax.tree.map(
                    lambda g: g / int(mesh.shape[data_axis]), grads)
                loss = jax.lax.pmean(loss, data_axis)
            return loss, grads

        batch_spec = P(None, data_axis) if data_axis is not None else P()
        loss, grads = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(param_specs, batch_spec, batch_spec),
            out_specs=(P(), param_specs),
        )(state.params, tokens_mb, targets_mb)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(
            params=params, opt_state=opt_state, step=state.step + 1
        ), loss

    return jax.jit(step, donate_argnums=(0,))


def create_pp_train_state(
    cfg: PipelineLMConfig,
    rng: jax.Array,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    stage_axis: str = "stage",
    model_axis: str | None = None,
) -> TrainState:
    """Init a ``TrainState`` with block layers sharded over the stages (and,
    with ``model_axis``, Megatron-sharded within each stage — pp×tp)."""
    n_stages = int(mesh.shape[stage_axis])
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide evenly over {n_stages} stages"
        )
    if model_axis is not None:
        _check_tp_divisibility(cfg, int(mesh.shape[model_axis]))

    def init_fn(rng):
        return TrainState.create(init_pp_params(cfg, rng), tx)

    state_shapes = jax.eval_shape(init_fn, rng)
    specs = pp_param_specs(state_shapes, stage_axis, model_axis)
    shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs, is_leaf=lambda x: isinstance(x, P)
    )
    from distributed_ml_pytorch_tpu.runtime.mesh import sharded_init

    # sharded_init, not a bare out_shardings jit: on non-partitionable-
    # threefry runtimes the same key gave different block kernels on multi-
    # axis meshes (the dryrun_multichip dp×pp×tp "loss divergence")
    return sharded_init(init_fn, rng, shardings)


def _stage_forward(cfg: PipelineLMConfig, block_params, h):
    """Run this stage's local layers (scan over the local stacked params)."""
    block = cfg.block()

    def body(h, layer_params):
        return block.apply({"params": layer_params}, h), None

    h, _ = jax.lax.scan(body, h, block_params)
    return h


def _make_stage_forward(cfg: PipelineLMConfig, mesh: Mesh,
                        model_axis: str | None):
    """``(block_params, h) → h`` for one stage — plain (``model_axis=None``)
    or tensor-parallel (tp width read off the mesh).

    The tp version is the explicit-collective Megatron block, written out
    because the schedules run inside ``shard_map`` (GSPMD annotations don't
    reach here): each device computes its ``n_heads/mp`` attention heads and
    its ``d_ff/mp`` MLP slice from its column-sharded kernels, the
    row-sharded o / down projections end the sharded contraction, and ONE
    ``psum`` over ``model`` after each closes the partial sums — the same
    two-all-reduces-per-layer count XLA derives for the pjit tp path
    (``tensor_parallel.tp_param_specs``). Replicated pieces (LayerNorms,
    down bias, residual adds) compute on model-INVARIANT values, so every
    activation crossing a stage boundary stays model-invariant. Math is
    identical to ``Block.apply`` (same flax submodule calls, same
    ``default_attn_fn`` on the local heads); loss/grad parity with the
    unsharded stage forward is tested to float tolerance (psum
    reassociation).
    """
    if model_axis is None:
        return partial(_stage_forward, cfg)

    from flax import linen as nn

    local_heads = cfg.n_heads // int(mesh.shape[model_axis])
    head_dim = cfg.d_model // cfg.n_heads

    def body(h, lp):
        b, s, _ = h.shape

        def split(t):  # (b, s, local_heads*hd) → (b, local_heads, s, hd)
            return t.reshape(b, s, local_heads, head_dim).transpose(0, 2, 1, 3)

        ln0 = nn.LayerNorm().apply({"params": lp["LayerNorm_0"]}, h)
        q, k, v = (split(ln0 @ lp["attn"][n]["kernel"]) for n in ("q", "k", "v"))
        out = default_attn_fn(q, k, v)  # causal, per-head → head-local
        out = out.transpose(0, 2, 1, 3).reshape(b, s, local_heads * head_dim)
        x = h + jax.lax.psum(out @ lp["attn"]["o"]["kernel"], model_axis)
        ln1 = nn.LayerNorm().apply({"params": lp["LayerNorm_1"]}, x)
        up = nn.gelu(ln1 @ lp["Dense_0"]["kernel"] + lp["Dense_0"]["bias"])
        down = jax.lax.psum(up @ lp["Dense_1"]["kernel"], model_axis)
        return x + down + lp["Dense_1"]["bias"], None

    def forward(block_params, h):
        h, _ = jax.lax.scan(body, h, block_params)
        return h

    return forward


def interleave_layer_order(n_layers: int, n_stages: int, v: int) -> np.ndarray:
    """Layer-axis permutation that makes CONTIGUOUS ``P(stage)`` sharding
    hand each stage its ``v`` STRIDED virtual-stage chunks.

    The interleaved schedule runs layer chunks in virtual-stage order
    ``V = r·S + s`` (round r, stage s), but the blocks array shards its
    leading axis contiguously — so chunk ``V`` must be STORED at position
    ``W = (V mod S)·v + V//S``. Returns ``order`` such that
    ``blocks[order]`` is the schedule-ready storage layout (apply the
    inverse to recover model order).
    """
    chunk_len = n_layers // (n_stages * v)
    order = []
    for s in range(n_stages):
        for r in range(v):
            V = r * n_stages + s
            order.extend(range(V * chunk_len, (V + 1) * chunk_len))
    return np.asarray(order)


def make_pp_train_step(
    cfg: PipelineLMConfig,
    tx: optax.GradientTransformation,
    mesh: Mesh,
    n_microbatches: int,
    stage_axis: str = "stage",
    schedule: str = "gpipe",
    virtual_stages: int = 1,
    data_axis: str | None = None,
    model_axis: str | None = None,
) -> Callable:
    """Build the jitted PP LM step: ``(state, tokens_mb, targets_mb) → (state, loss)``.

    ``tokens_mb``/``targets_mb`` are ``(M, mb, seq)`` int arrays (microbatched
    on the leading axis, replicated across stages). The loss is the global
    next-token CE over all M microbatches, masking the final position of each
    sequence (``seq_parallel.next_token_targets`` convention).

    ``model_axis`` (pp×tp, any schedule, composes with ``data_axis`` for
    dp×pp×tp): blocks are Megatron-sharded within their stage
    (:func:`pp_param_specs`), the stage forward runs the explicit-collective
    tp block (:func:`_make_stage_forward`), and everything crossing stage
    boundaries stays model-invariant, so the schedules themselves are
    untouched. The state must come from :func:`create_pp_train_state` with
    the same ``model_axis``.

    ``schedule="interleaved"`` with ``virtual_stages=v > 1`` runs the
    Megatron-style interleaved schedule: each stage holds ``v`` strided
    layer chunks (storage permuted by :func:`interleave_layer_order`), and
    chunk ``r`` of microbatch ``m`` executes on stage ``s`` at tick
    ``t = r·M + m + s`` — conflict-free, so the whole schedule stays ONE
    differentiable ``lax.scan``. The pipeline-fill bubble shrinks from
    GPipe's ``(S−1)/(M+S−1)`` of the step to ``(S−1)/(vM+S−1)`` (ticks are
    1/v the work): at M=8, S=4, v=2 that is 27% → 16% idle. Costs: the
    ring wrap (stage S−1 → 0 between rounds) needs a delay FIFO of depth
    ``M − S`` carried through the scan (the interleaved analog of GPipe's
    activation stash), and cross-stage comm volume is ×v. Requires
    ``M ≥ S`` and ``n_layers % (S·v) == 0``.
    """
    n_stages = int(mesh.shape[stage_axis])
    if cfg.n_layers % n_stages:
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide evenly over {n_stages} stages"
        )
    M = int(n_microbatches)
    if data_axis is not None and data_axis not in mesh.shape:
        raise ValueError(f"data_axis {data_axis!r} is not in the mesh "
                         f"(axes: {dict(mesh.shape)})")
    if model_axis is not None:
        if model_axis not in mesh.shape:
            raise ValueError(f"model_axis {model_axis!r} is not in the mesh "
                             f"(axes: {dict(mesh.shape)})")
        _check_tp_divisibility(cfg, int(mesh.shape[model_axis]))
    if schedule == "interleaved":
        return _make_interleaved_step(
            cfg, tx, mesh, M, stage_axis, int(virtual_stages), data_axis,
            model_axis)
    if schedule == "1f1b":
        return _make_1f1b_step(cfg, tx, mesh, M, stage_axis, data_axis,
                               model_axis)
    if schedule != "gpipe":
        raise ValueError(
            f"schedule must be 'gpipe', '1f1b' or 'interleaved', got {schedule!r}")
    embed, pos_embed, head, ln_f = _lm_modules(cfg)
    stage_fwd = _make_stage_forward(cfg, mesh, model_axis)
    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]
    # scan carries mix with batch activations, which vary over BOTH mesh
    # axes under dp x pp — the carry's varying axes must match. The model
    # axis is NOT in the carry's varying set: tp activations are
    # model-invariant at every stage boundary (psums close each layer's
    # sharded contraction inside the stage forward)
    vma_axes = (stage_axis,) if data_axis is None else (stage_axis, data_axis)

    def pipeline_loss(params, tokens_mb, targets_mb):
        s = jax.lax.axis_index(stage_axis)
        mb, seq = tokens_mb.shape[1], tokens_mb.shape[2]
        positions = jnp.arange(seq)[None, :]

        def embed_mb(m):
            m = jnp.clip(m, 0, M - 1)
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, m, axis=0, keepdims=False)
            x = embed.apply({"params": params["tok_embed"]}, toks)
            return x + pos_embed.apply({"params": params["pos_embed"]}, positions)

        def tick(carry, t):
            h_in, loss_sum, count = carry
            # stage 0 injects microbatch t; others use the received activation
            h = jnp.where(s == 0, embed_mb(t), h_in)
            m_here = t - s  # microbatch this stage holds at tick t
            valid = (m_here >= 0) & (m_here < M)
            h_out = stage_fwd(params["blocks"], h)
            h_out = jnp.where(valid, h_out, h)  # bubbles pass through untouched
            # last stage: head + loss for its microbatch (masked elsewhere)
            logits = head.apply(
                {"params": params["head"]},
                ln_f.apply({"params": params["ln_f"]}, h_out),
            )
            tgt = jax.lax.dynamic_index_in_dim(
                targets_mb, jnp.clip(m_here, 0, M - 1), axis=0, keepdims=False
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            mask = jnp.ones_like(ce).at[:, -1].set(0.0)
            take = valid & (s == n_stages - 1)
            loss_sum = loss_sum + jnp.where(take, jnp.sum(ce * mask), 0.0)
            count = count + jnp.where(take, jnp.sum(mask), 0.0)
            # hand the activation to the right neighbor for the next tick
            h_next = jax.lax.ppermute(h_out, stage_axis, fwd_perm)
            return (h_next, loss_sum, count), None

        # the carry varies per stage (each holds a different activation), so
        # the initial zeros must be cast to stage-varying for scan's
        # carry-type invariance under shard_map
        carry0 = jax.lax.pcast(
            (jnp.zeros((mb, seq, cfg.d_model)), jnp.zeros(()), jnp.zeros(())),
            vma_axes,
            to="varying",
        )
        (_, loss_sum, count), _ = jax.lax.scan(
            tick, carry0, jnp.arange(M + n_stages - 1)
        )
        # broadcast the last stage's totals to every stage
        loss_sum = jax.lax.psum(loss_sum, stage_axis)
        count = jax.lax.psum(count, stage_axis)
        return loss_sum / count

    return _wrap_pp_step(jax.value_and_grad(pipeline_loss), tx, mesh,
                         stage_axis, data_axis, model_axis)


def _make_interleaved_step(cfg, tx, mesh, M, stage_axis, v, data_axis=None,
                           model_axis=None):
    """The interleaved-schedule step (see make_pp_train_step's docstring)."""
    S = int(mesh.shape[stage_axis])
    if cfg.n_layers % (S * v):
        raise ValueError(
            f"n_layers={cfg.n_layers} must divide over {S} stages x {v} "
            "virtual chunks")
    if M < S:
        raise ValueError(
            f"interleaved schedule needs n_microbatches >= n_stages "
            f"({M} < {S}): the round-wrap activation would be consumed "
            "before it is produced")
    chunk_len = cfg.n_layers // (S * v)
    D = M - S  # wrap delay in ticks (0 → direct hand-off)
    B = D + 1  # FIFO depth: a value stored during tick a is read at a+D+1
    T = v * M + S - 1

    embed, pos_embed, head, ln_f = _lm_modules(cfg)
    stage_fwd = _make_stage_forward(cfg, mesh, model_axis)
    ring = [(i, (i + 1) % S) for i in range(S)]
    vma_axes = (stage_axis,) if data_axis is None else (stage_axis, data_axis)

    def pipeline_loss(params, tokens_mb, targets_mb):
        s = jax.lax.axis_index(stage_axis)
        mb, seq = tokens_mb.shape[1], tokens_mb.shape[2]
        positions = jnp.arange(seq)[None, :]
        # local blocks: v chunks of chunk_len layers, in round order —
        # the storage permutation (interleave_layer_order) guarantees
        # local chunk r IS virtual stage r·S + s
        local_blocks = jax.tree.map(
            lambda x: x.reshape((v, chunk_len) + x.shape[1:]),
            params["blocks"])

        def embed_mb(m):
            m = jnp.clip(m, 0, M - 1)
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, m, axis=0,
                                                keepdims=False)
            x = embed.apply({"params": params["tok_embed"]}, toks)
            return x + pos_embed.apply({"params": params["pos_embed"]},
                                       positions)

        def run_chunk(r, h):
            chunk = jax.tree.map(
                lambda x: jax.lax.dynamic_index_in_dim(x, r, axis=0,
                                                       keepdims=False),
                local_blocks)
            return stage_fwd(chunk, h)

        def tick(carry, t):
            h_in, buf, loss_sum, count = carry
            q = t - s
            valid = (q >= 0) & (q < v * M)
            qc = jnp.clip(q, 0, v * M - 1)
            r, m = qc // M, qc % M
            # stage 0's input: round 0 injects the embedding; later rounds
            # consume the wrap FIFO. The value stored during tick u is the
            # arrival of tick u+1; the consumer at tick t needs the arrival
            # of t−D, stored during tick t−D−1 — one slot index t % B with
            # B = D+1 makes read(t) hit exactly that store, and the same
            # tick's own store (after the read) safely reuses the slot
            wrapped = buf[t % B] if D > 0 else h_in
            h = jnp.where(s == 0, jnp.where(r == 0, embed_mb(m), wrapped), h_in)
            h_out = run_chunk(r, h)
            h_out = jnp.where(valid, h_out, h)
            # last virtual stage (s = S−1, r = v−1): head + masked CE
            logits = head.apply(
                {"params": params["head"]},
                ln_f.apply({"params": params["ln_f"]}, h_out))
            tgt = jax.lax.dynamic_index_in_dim(targets_mb, m, axis=0,
                                               keepdims=False)
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            mask = jnp.ones_like(ce).at[:, -1].set(0.0)
            take = valid & (s == S - 1) & (r == v - 1)
            loss_sum = loss_sum + jnp.where(take, jnp.sum(ce * mask), 0.0)
            count = count + jnp.where(take, jnp.sum(mask), 0.0)
            h_next = jax.lax.ppermute(h_out, stage_axis, ring)
            if D > 0:
                # store AFTER the read: this tick's wrap arrival rests here
                # for D+1 ticks (only stage 0's content is ever consumed)
                buf = buf.at[t % B].set(h_next)
            return (h_next, buf, loss_sum, count), None

        buf0 = jnp.zeros((B if D > 0 else 1, mb, seq, cfg.d_model))
        carry0 = jax.lax.pcast(
            (jnp.zeros((mb, seq, cfg.d_model)), buf0, jnp.zeros(()),
             jnp.zeros(())),
            vma_axes, to="varying")
        (_, _, loss_sum, count), _ = jax.lax.scan(
            tick, carry0, jnp.arange(T))
        loss_sum = jax.lax.psum(loss_sum, stage_axis)
        count = jax.lax.psum(count, stage_axis)
        return loss_sum / count

    return _wrap_pp_step(jax.value_and_grad(pipeline_loss), tx, mesh,
                         stage_axis, data_axis, model_axis)


def oneF1B_tick_roles(t, s, S: int, M: int):
    """The 1F1B timetable — the ONE copy, evaluable on host ints (the
    schedule property tests) AND on traced values (the compiled step calls
    it for ``(t, s)`` and ``(t−1, s−1)``), hence the branch-free boolean
    arithmetic. At tick ``t``, stage ``s`` does forward of microbatch
    ``m_f`` and/or backward of ``m_b`` (at most one is active; −1 = idle).

    Derivation (classic non-interleaved 1F1B, 1 tick per unit of work):
    warmup forwards ``F(s, m) = s + m`` for ``m < S − s``; steady-state
    forwards ``F(s, m) = 2m + s`` (each right after the backward it pairs
    with); backwards ``B(s, m) = 2S − 1 − s + 2m``. F and B land on opposite
    parities of ``t − s`` so a stage never does both in one tick; backward
    cotangents arrive exactly one tick after their producer (``B(s,m) =
    B(s+1,m) + 1``) while forward activations arrive at ``F(s−1,m) + 1 ≤
    F(s,m)`` and may rest in the arrivals ring. Total ticks:
    ``2(M + S − 1)``.
    """
    warm = t - s
    is_warm = (t >= s) & (warm < S - s) & (warm < M)
    steady = warm // 2
    is_steady = (warm % 2 == 0) & (t >= s) & (steady >= S - s) & (steady < M)
    do_f = is_warm | is_steady
    m_f = is_warm * warm + is_steady * steady + (do_f - 1)
    tb = t - (2 * S - 1 - s)
    do_b = (tb >= 0) & (tb % 2 == 0) & (tb // 2 < M)
    m_b = do_b * (tb // 2) + (do_b - 1)
    return m_f, m_b


def _make_1f1b_step(cfg, tx, mesh, M, stage_axis, data_axis=None,
                    model_axis=None):
    """The 1F1B schedule (VERDICT r3 #4): same function as GPipe, computed
    with a hand-scheduled backward so each stage stashes at most ``S``
    microbatch activations instead of all ``M``.

    GPipe here differentiates THROUGH the scanned schedule, so AD saves the
    forward carry of every tick — the all-M-activations-live memory profile
    that makes deep pipelines OOM at large M. 1F1B interleaves explicit
    per-microbatch backwards (``jax.vjp`` inside the scan) with forwards per
    :func:`oneF1B_tick_roles`; the only stashed state is the static
    ``(S+1, mb, seq, d)`` arrivals ring of stage INPUTS (slot ``m % S``
    holds the hand-off from its arrival through the forward until the
    BACKWARD rereads it — the next same-slot write, microbatch ``m+S``
    arriving at tick ``2m+2S+s``, is provably after ``B(s,m) = 2m+2S−1−s``;
    slot ``S`` is a trash slot so the per-tick update is unconditional),
    and each backward recomputes its stage forward under the vjp (the
    standard 1F1B-with-recompute trade: ~1 extra forward per microbatch for
    an activation footprint of ``S+1`` buffers instead of ``M``; stage 0
    recomputes its embedding input instead of using the ring).

    Per tick both streams ride one ``ppermute`` pair (forward activations
    right, cotangents left) kept OUTSIDE the ``lax.cond``s — collectives
    must run on every stage every tick; the conds only gate the local
    compute. Losses and gradients equal GPipe's (tested to float tolerance):
    the loss cotangent is seeded as ``1/Σmask`` on the last stage, embed /
    head / ln_f grads accumulate on the stages that own them and are
    psum-broadcast, and block grads stay ``P(stage)``-local.

    pp×tp note (``model_axis``): the tp stage forward's ``psum``s over
    ``model`` — and the model-axis collectives AD inserts when the inner
    ``jax.vjp``s transpose model-invariant values out of model-varying
    compute — DO run inside the ``lax.cond`` branches here, unlike the
    stage-axis collectives the docstring above banishes. That is safe, not
    a deadlock: the branch predicates (``do_fwd``/``do_bwd``) depend only
    on ``(t, s)``, so all model-peers of a stage — the only participants
    in a model-axis collective — always take the same branch together.
    The stage-axis argument doesn't transfer: stage-peers DO diverge.
    """
    S = int(mesh.shape[stage_axis])
    T = 2 * (M + S - 1)
    embed, pos_embed, head, ln_f = _lm_modules(cfg)
    stage_fwd = _make_stage_forward(cfg, mesh, model_axis)
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i + 1, i) for i in range(S - 1)]
    vma_axes = (stage_axis,) if data_axis is None else (stage_axis, data_axis)

    def pipeline_grads(params, tokens_mb, targets_mb):
        # Localize the replicated params (stage-varying view): otherwise the
        # jax.vjp transposes inside the cond branches would auto-psum their
        # cotangents (shard_map's invariant-input transpose rule), planting
        # collectives inside DIVERGENT control flow — a guaranteed deadlock
        # (collectives must run on every stage). With varying inputs the
        # cotangents stay local and the single explicit psum after the scan
        # does the cross-stage reduction.
        def localize(path, leaf):
            if _is_blocks_path(path):
                return leaf  # already stage-varying (P(stage) input)
            return jax.lax.pcast(leaf, stage_axis, to="varying")

        params = jax.tree_util.tree_map_with_path(localize, params)
        s = jax.lax.axis_index(stage_axis)
        mb, seq = tokens_mb.shape[1], tokens_mb.shape[2]
        positions = jnp.arange(seq)[None, :]
        n_mask = float(mb * (seq - 1))  # masked tokens per microbatch
        inv_total = 1.0 / (n_mask * M)  # d(loss)/d(ce_sum): loss = Σce/Σmask

        def embed_fn(tok_p, pos_p, m):
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, m, axis=0, keepdims=False)
            x = embed.apply({"params": tok_p}, toks)
            return x + pos_embed.apply({"params": pos_p}, positions)

        def stage_loss_fn(blocks_p, head_p, lnf_p, h, tgt):
            """Local layers + (masked-elsewhere) head CE — the unit of work
            whose vjp is one stage's backward."""
            h_out = stage_fwd(blocks_p, h)
            logits = head.apply(
                {"params": head_p}, ln_f.apply({"params": lnf_p}, h_out)
            )
            ce = optax.softmax_cross_entropy_with_integer_labels(logits, tgt)
            mask = jnp.ones_like(ce).at[:, -1].set(0.0)
            return h_out, jnp.sum(ce * mask)

        is_last = s == S - 1

        def tick(carry, t):
            h_send, g_send, arrivals, grads, loss_sum = carry
            # both streams hand off every tick (collectives outside the conds)
            h_fwd_in = jax.lax.ppermute(h_send, stage_axis, fwd_perm)
            g_bwd_in = jax.lax.ppermute(g_send, stage_axis, bwd_perm)

            # tick roles — the ONE timetable, called for this stage and for
            # the left neighbor's previous tick (arrival detection)
            m_f_raw, m_b_raw = oneF1B_tick_roles(t, s, S, M)
            do_fwd, do_bwd = m_f_raw >= 0, m_b_raw >= 0
            m_f = jnp.clip(m_f_raw, 0, M - 1)
            m_b = jnp.clip(m_b_raw, 0, M - 1)
            m_a_raw, _ = oneF1B_tick_roles(t - 1, s - 1, S, M)

            # -- park the arriving activation in the S-slot ring (m % S) --
            # Forward hand-offs are NOT always consumed the next tick (at the
            # warmup→steady boundary F(s,m) can exceed F(s−1,m)+1), and the
            # slot stays live until the BACKWARD reads it at B(s,m): the next
            # same-slot write (microbatch m+S arriving, tick 2m+2S+s) is
            # provably later. Non-arrivals write trash slot S, keeping the
            # update unconditional (no full-buffer select).
            arrived = (s > 0) & (m_a_raw >= 0)
            arrivals = jax.lax.dynamic_update_index_in_dim(
                arrivals, h_fwd_in,
                jnp.where(arrived, jnp.clip(m_a_raw, 0, M - 1) % S, S), axis=0,
            )

            def stage_input(m):
                """Microbatch m's input to this stage: the parked arrival
                (s > 0) or the recomputed embedding (stage 0). A nested cond
                (collective-free branches) so S−1 stages skip the embedding
                work instead of computing-and-masking it every tick."""
                return jax.lax.cond(
                    s == 0,
                    lambda: embed_fn(params["tok_embed"], params["pos_embed"], m),
                    lambda: jax.lax.dynamic_index_in_dim(arrivals, m % S, axis=0,
                                                         keepdims=False),
                )

            def fwd_branch(op):
                loss_sum, = op
                tgt = jax.lax.dynamic_index_in_dim(targets_mb, m_f, axis=0,
                                                   keepdims=False)
                h_out, ce = stage_loss_fn(
                    params["blocks"], params["head"], params["ln_f"],
                    stage_input(m_f), tgt
                )
                return h_out, (loss_sum + jnp.where(is_last, ce, 0.0),)

            h_send, (loss_sum,) = jax.lax.cond(
                do_fwd, fwd_branch, lambda op: (h_fwd_in, op), (loss_sum,)
            )

            def bwd_branch(op):
                g_bwd_in, grads = op
                tgt = jax.lax.dynamic_index_in_dim(targets_mb, m_b, axis=0,
                                                   keepdims=False)
                _, vjp_fn = jax.vjp(
                    lambda bp, hp, lp, h: stage_loss_fn(bp, hp, lp, h, tgt),
                    params["blocks"], params["head"], params["ln_f"],
                    stage_input(m_b),
                )
                # cotangents: the loss seeds the last stage; everyone else
                # transposes the activation hand-off
                g_h = jnp.where(is_last, jnp.zeros_like(g_bwd_in), g_bwd_in)
                g_ce = jnp.where(is_last, inv_total, 0.0)
                if data_axis is not None:
                    # the primal ce is data-varying under dp x pp; the seed
                    # must carry the same varying axes for the vjp call
                    g_ce = jax.lax.pcast(g_ce, data_axis, to="varying")
                d_blocks, d_head, d_lnf, d_h = vjp_fn((g_h, g_ce))
                # stage 0 transposes the embedding instead of sending left
                # (nested cond: the other stages skip the transpose work)
                def embed_transpose():
                    _, evjp = jax.vjp(
                        lambda tp, pp: embed_fn(tp, pp, m_b),
                        params["tok_embed"], params["pos_embed"],
                    )
                    return evjp(d_h)

                d_tok, d_pos = jax.lax.cond(
                    s == 0,
                    embed_transpose,
                    lambda: (jax.tree.map(jnp.zeros_like, params["tok_embed"]),
                             jax.tree.map(jnp.zeros_like, params["pos_embed"])),
                )
                grads = {
                    "blocks": jax.tree.map(jnp.add, grads["blocks"], d_blocks),
                    "head": jax.tree.map(jnp.add, grads["head"], d_head),
                    "ln_f": jax.tree.map(jnp.add, grads["ln_f"], d_lnf),
                    "tok_embed": jax.tree.map(jnp.add, grads["tok_embed"], d_tok),
                    "pos_embed": jax.tree.map(jnp.add, grads["pos_embed"], d_pos),
                }
                return d_h, grads

            g_send, grads = jax.lax.cond(
                do_bwd, bwd_branch, lambda op: op, (g_bwd_in, grads)
            )
            return (h_send, g_send, arrivals, grads, loss_sum), None

        zero_h = jnp.zeros((mb, seq, cfg.d_model))
        # zeros_like inherits varying axes: every params leaf is varying
        # after localize, so the grad accumulators are too — over STAGE
        # only: under dp x pp each inner jax.vjp's param cotangents are
        # auto-psum'd over the data axis (the localized params are
        # data-invariant), so the accumulators stay data-invariant and the
        # wrapper's /n_data turns the sum into the mean
        grads0 = jax.tree.map(jnp.zeros_like, params)
        carry0 = jax.lax.pcast(
            (zero_h, zero_h,
             jnp.zeros((S + 1, mb, seq, cfg.d_model)),  # arrivals (+trash slot)
             jnp.zeros(())),
            vma_axes, to="varying",
        )
        carry0 = carry0[:3] + (grads0, carry0[3])
        (_, _, _, grads, loss_sum), _ = jax.lax.scan(tick, carry0, jnp.arange(T))
        # scale the hand-accumulated ce sums into mean-loss gradients is
        # already folded in via inv_total; broadcast the single-owner grads
        grads = {
            "blocks": grads["blocks"],  # stays stage-local (P(stage))
            "tok_embed": jax.tree.map(lambda x: jax.lax.psum(x, stage_axis),
                                      grads["tok_embed"]),
            "pos_embed": jax.tree.map(lambda x: jax.lax.psum(x, stage_axis),
                                      grads["pos_embed"]),
            "ln_f": jax.tree.map(lambda x: jax.lax.psum(x, stage_axis),
                                 grads["ln_f"]),
            "head": jax.tree.map(lambda x: jax.lax.psum(x, stage_axis),
                                 grads["head"]),
        }
        loss = jax.lax.psum(loss_sum, stage_axis) / (n_mask * M)
        return loss, grads

    return _wrap_pp_step(pipeline_grads, tx, mesh, stage_axis, data_axis,
                         model_axis)


def microbatch(tokens, targets, n_microbatches: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side: split a (batch, seq) pair into (M, batch/M, seq)."""
    b = tokens.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} must divide into {n_microbatches} microbatches")
    shape = (n_microbatches, b // n_microbatches) + tuple(tokens.shape[1:])
    return tokens.reshape(shape), targets.reshape(shape)
