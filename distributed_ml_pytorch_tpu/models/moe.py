"""Mixture-of-Experts layers for the Transformer LM (Switch-style top-1).

The reference has no MoE (SURVEY.md §2.4 marks EP ABSENT) — this is a
capability extension, expressed the TPU way (GShard/Switch): routing is a
pair of dense one-hot einsums (dispatch and combine) over stacked expert
weights, so there is **no data-dependent control flow** — the whole layer is
three einsums XLA can partition. Sharding the stacked expert axis over an
``expert`` mesh mesh axis turns those einsums into all-to-all dispatch
/combine automatically (``parallel/expert_parallel.py``); unsharded, the same
code is a dense reference implementation.

Key shapes (B batch, S seq, D d_model, F d_ff, E experts, C capacity):

- router probs  ``[B, S, E]`` → top-1 expert per token
- dispatch      ``[B, S, E, C]`` one-hot (token → its slot in its expert)
- expert in     ``[E, B, C, D]`` = einsum(dispatch, x)
- expert FFN    ``[E, B, C, D]`` via stacked ``w_up [E, D, F]``, ``w_down [E, F, D]``
- combine       ``[B, S, D]`` = einsum(dispatch * router_prob, expert_out)

Tokens beyond an expert's capacity are *dropped* (pass through the residual
unchanged) — Switch semantics; the load-balance auxiliary loss pushes the
router toward uniform load so drops stay rare.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.experimental.pallas.ops.tpu.megablox import gmm as _gmm

from distributed_ml_pytorch_tpu.models.generate import COUNTERS
from distributed_ml_pytorch_tpu.models.hybrid import GatedFFN
from distributed_ml_pytorch_tpu.models.transformer import KV_READ, MultiHeadAttention


def switch_route(
    router_probs: jnp.ndarray, capacity: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 routing with per-expert capacity, no data-dependent shapes.

    Returns ``(dispatch [B,S,E,C], combine [B,S,E,C])``; ``combine`` carries
    the router probability so the gradient reaches the router (straight-
    through on the argmax, exactly Switch).
    """
    b, s, e = router_probs.shape
    expert_idx = jnp.argmax(router_probs, axis=-1)                 # [B,S]
    # queue positions are COUNTS — int32, never the activation dtype: a
    # bf16 cumsum loses integer exactness past 256 and collides slots
    onehot_i = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)
    # position of each token within its expert's queue (exclusive cumsum
    # over the sequence), computed densely per expert
    pos_in_expert = jnp.cumsum(onehot_i, axis=1) - onehot_i         # [B,S,E]
    kept = ((pos_in_expert < capacity) & (onehot_i > 0)).astype(
        router_probs.dtype
    )                                                               # [B,S,E]
    slot = jax.nn.one_hot(
        jnp.sum(pos_in_expert * onehot_i, axis=-1), capacity,
        dtype=router_probs.dtype,
    )                                                               # [B,S,C]
    dispatch = kept[..., None] * slot[:, :, None, :]                # [B,S,E,C]
    gate = jnp.sum(router_probs * kept, axis=-1)                    # [B,S]
    combine = dispatch * gate[:, :, None, None]
    return dispatch, combine


def topk_route(
    router_probs: jnp.ndarray,
    capacity: int,
    k: int = 2,
    normalize: bool = True,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """GShard-style top-k routing with per-expert capacity, dense shapes.

    Each token is dispatched to its ``k`` highest-probability experts.
    Capacity slots are granted **rank-major**: every token's rank-0 choice
    is queued before any token's rank-1 choice, so second choices are the
    first dropped under pressure (GShard's priority rule). ``normalize``
    rescales the k gates to sum to 1 (standard for k≥2); with ``k=1,
    normalize=False`` this reduces exactly to :func:`switch_route`.

    Returns ``(dispatch [B,S,E,C], combine [B,S,E,C])`` — identical
    contracts to :func:`switch_route`, so ``MoEMLP``'s einsums (and the
    ``expert``-axis sharding that turns them into all-to-alls) are unchanged.
    """
    b, s, e = router_probs.shape
    if not 1 <= k <= e:
        raise ValueError(f"top-k routing needs 1 <= k <= n_experts, got k={k}, e={e}")
    gate_sk, idx = jax.lax.top_k(router_probs, k)                   # [B,S,K], rank-sorted
    # queue positions are COUNTS — int32, never the activation dtype: a bf16
    # cumsum loses integer exactness past 256 and collides slots (the K·S
    # combined axis reaches that twice as fast as top-1)
    oh_ks = jnp.moveaxis(jax.nn.one_hot(idx, e, dtype=jnp.int32), 2, 1)  # [B,K,S,E]
    # queue position per (choice, token): exclusive cumsum over the combined
    # rank-major (K·S) axis — rank 0 occupies slots before any rank 1
    flat = oh_ks.reshape(b, k * s, e)
    pos = jnp.cumsum(flat, axis=1) - flat                           # [B,K*S,E]
    kept = ((pos < capacity) & (flat > 0)).astype(router_probs.dtype)
    slot = jax.nn.one_hot(
        jnp.sum(pos * flat, axis=-1), capacity,
        dtype=router_probs.dtype,
    )                                                               # [B,K*S,C]
    disp_flat = kept[..., None] * slot[:, :, None, :]               # [B,K*S,E,C]
    dispatch_k = disp_flat.reshape(b, k, s, e, capacity)
    dispatch = jnp.sum(dispatch_k, axis=1)                          # [B,S,E,C]
    gate_ks = jnp.moveaxis(gate_sk, 2, 1)                           # [B,K,S]
    if normalize:
        gate_ks = gate_ks / jnp.maximum(
            jnp.sum(gate_ks, axis=1, keepdims=True), 1e-9
        )
    combine = jnp.sum(dispatch_k * gate_ks[..., None, None], axis=1)
    return dispatch, combine


def load_balance_loss(router_probs: jnp.ndarray) -> jnp.ndarray:
    """Switch aux loss (eq. 4): E · Σ_e (fraction argmax-routed to e) · (mean prob of e).

    ``f_e`` uses the **pre-capacity** argmax assignment, not the truncated
    dispatch mask — under router collapse the hot expert's fraction must
    approach 1.0 (not saturate at capacity/seq) so the corrective gradient
    stays strong exactly when balancing matters most.
    """
    e = router_probs.shape[-1]
    expert_onehot = jax.nn.one_hot(
        jnp.argmax(router_probs, axis=-1), e, dtype=router_probs.dtype
    )
    frac_tokens = jnp.mean(expert_onehot, axis=(0, 1))               # [E]
    frac_probs = jnp.mean(router_probs, axis=(0, 1))                 # [E]
    return e * jnp.sum(frac_tokens * frac_probs)


class MoEMLP(nn.Module):
    """Switch FFN: top-1 router over ``n_experts`` stacked expert MLPs.

    The stacked leading expert axis of ``w_up``/``b_up``/``w_down``/``b_down``
    is the one ``parallel/expert_parallel.ep_param_specs`` shards over the
    ``expert`` mesh axis. The aux load-balance loss is ``sow``n under the
    ``"losses"`` collection (reduced by the train step).
    """

    d_model: int
    d_ff: int
    n_experts: int = 4
    capacity_factor: float = 2.0
    dtype: jnp.dtype = jnp.float32
    router_top_k: int = 1  # 1 = Switch; ≥2 = GShard top-k with gate renorm

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        e = self.n_experts
        # top-k emits k assignments per token, so capacity provisions k·S/E
        capacity = max(1, int(self.capacity_factor * self.router_top_k * s / e))
        router = nn.Dense(e, use_bias=False, dtype=self.dtype, name="router")
        probs = jax.nn.softmax(router(x).astype(jnp.float32), axis=-1).astype(x.dtype)
        if self.router_top_k == 1:
            dispatch, combine = switch_route(probs, capacity)
        else:
            dispatch, combine = topk_route(probs, capacity, k=self.router_top_k)
        self.sow("losses", "load_balance", load_balance_loss(probs))

        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(batch_axis=(0,)), (e, d, self.d_ff)
        )
        b_up = self.param("b_up", nn.initializers.zeros, (e, self.d_ff))
        w_down = self.param(
            "w_down", nn.initializers.lecun_normal(batch_axis=(0,)), (e, self.d_ff, d)
        )
        b_down = self.param("b_down", nn.initializers.zeros, (e, d))

        xin = jnp.einsum("bsec,bsd->ebcd", dispatch, x)              # dispatch
        h = jnp.einsum("ebcd,edf->ebcf", xin, w_up) + b_up[:, None, None, :]
        h = nn.gelu(h)
        out = jnp.einsum("ebcf,efd->ebcd", h, w_down) + b_down[:, None, None, :]
        return jnp.einsum("bsec,ebcd->bsd", combine, out)            # combine


class MoEBlock(nn.Module):
    """Pre-LN Transformer block with a Switch-MoE FFN."""

    d_model: int
    n_heads: int
    d_ff: int
    n_experts: int = 4
    capacity_factor: float = 2.0
    dtype: jnp.dtype = jnp.float32
    router_top_k: int = 1
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0
    kv_quant: bool = False
    fused_qkv: bool = False

    @nn.compact
    def __call__(self, x, positions=None):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + MultiHeadAttention(self.d_model, self.n_heads, self.dtype,
                                   self.attn_fn, decode=self.decode,
                                   cache_size=self.cache_size,
                                   decode_block=self.decode_block,
                                   kv_quant=self.kv_quant,
                                   fused_qkv=self.fused_qkv,
                                   name="attn")(h, positions)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + MoEMLP(
            self.d_model, self.d_ff, self.n_experts, self.capacity_factor,
            self.dtype, router_top_k=self.router_top_k, name="moe",
        )(h)
        return x


class MoETransformerLM(nn.Module):
    """Causal LM whose FFNs are Switch-MoE layers (every block)."""

    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    n_experts: int = 4
    capacity_factor: float = 2.0
    max_len: int = 131072
    dtype: jnp.dtype = jnp.float32
    remat: bool = False
    router_top_k: int = 1
    attn_fn: Optional[Callable] = None
    #: decode support (models/generate.py): same contract as TransformerLM —
    #: the attention caches K/V; the MoE FFN needs no cache at all (routing
    #: is per token, and a single-token step's capacity floor of 1 slot per
    #: expert can never drop the token). Semantic note: because decode
    #: steps never drop, decode logits match the teacher-forced forward
    #: exactly ONLY where the full forward didn't drop tokens to capacity —
    #: over-capacity prompts route more tokens through expert FFNs at
    #: decode time than they did in training's forward (tested with a
    #: drop-free capacity in tests/test_moe_topk.py)
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0
    kv_quant: bool = False
    fused_qkv: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None):
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])[None, :]
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="tok_embed")(tokens)
        x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype, name="pos_embed")(positions)
        block_cls = nn.remat(MoEBlock) if self.remat and not self.decode else MoEBlock
        for i in range(self.n_layers):
            x = block_cls(
                self.d_model, self.n_heads, self.d_ff, self.n_experts,
                self.capacity_factor, self.dtype,
                router_top_k=self.router_top_k, attn_fn=self.attn_fn,
                decode=self.decode, cache_size=self.cache_size,
                decode_block=self.decode_block, kv_quant=self.kv_quant,
                fused_qkv=self.fused_qkv,
                name=f"block_{i}",
            )(x, positions)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")(x)


# --------------------------------------------------------------------------
# Dropless routed experts (sigmoid scores, a selection bias, top-k of many,
# shared experts): the expert layer of ``models/latent_moe.py``.

def route_topk_sigmoid(x, w_router, bias, k: int, scale: float):
    """``(idx [n, k], weights [n, k] float32, scores [n, E])`` for rows ``x``
    ``[n, d]``: scores are ``sigmoid(W_g x)`` in float32 on a float32 copy of
    ``x``; the ``k`` largest of ``scores + bias`` are chosen; their weights are
    the scores WITHOUT the bias, divided by their sum and times ``scale``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    weights = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20) * scale
    return idx, weights, scores


#: rows a tile of the TPU's grouped-matmul kernel holds: a group of fewer rows
#: still costs a whole tile, and an admission's groups are 50-120 rows; a
#: decode step's 1-3 rows an expert share tiles, and what a group costs there
#: is the fetch of its expert (12.5 us at the published sizes, PR 36)
GROUP_TILE_ROWS = 128


def _whole_or_tile(n: int, cap: int = 2048) -> int:
    """``n`` if it fits a tile, else its largest divisor that is a multiple of
    128 and at most ``cap``."""
    return n if n <= cap else max(t for t in range(128, cap + 1, 128) if n % t == 0)


def grouped_dot(rows, w, sizes, out_dtype):
    """``rows`` ``[m, k]``, sorted by group, times each row's own group's
    matrix of ``w`` ``[g, k, n]``; ``sizes`` ``[g]`` sums to ``m``. On a TPU,
    for bfloat16 operands whose shapes tile, the Pallas grouped-matmul kernel
    of ``jax.experimental`` (megablox ``gmm``) with row tiles of
    ``GROUP_TILE_ROWS``: ``jax.lax.ragged_dot`` compiles there to a kernel with
    tiles of 512 rows, which an admission's groups fill a fifth of (PR 35: 30
    of an admission's 50 ms). Everywhere else ``jax.lax.ragged_dot``."""
    (m, k), n = rows.shape, w.shape[-1]
    w = w.astype(rows.dtype)
    if (jax.default_backend() == "tpu" and rows.dtype == jnp.bfloat16
            and m % GROUP_TILE_ROWS == 0 and k % 128 == 0 and n % 128 == 0):
        return _gmm(rows, w, sizes, preferred_element_type=out_dtype,
                    tiling=(GROUP_TILE_ROWS, _whole_or_tile(k), _whole_or_tile(n)))
    return jax.lax.ragged_dot(rows, w, sizes, preferred_element_type=out_dtype)


@jax.jit
def grouped_experts(x, idx, weights, live, w_gate, w_up, w_down):
    """``sum_j weights[:, j] * E_idx[:, j](x)`` for the rows of ``x`` ``[n, d]``
    that ``live`` ``[n]`` marks, as grouped products over their (row, choice)
    pairs sorted by expert (:func:`grouped_dot`: each expert's weights multiply
    its own rows and no others, however uneven the groups; an expert no live
    row chose has an empty group, and the TPU's kernel never fetches it). No
    capacity and no drop. A row that is not live leaves the groups: its pairs
    sort behind every group, count in no group's size, and its sum is exactly
    0. The pairs are padded to whole tiles of ``GROUP_TILE_ROWS`` with pairs of
    the same kind, so that a decode step's few rows take the kernel too.
    Returns float32 ``[n, d]``. Under ``jax.jit`` so that a model's expert
    layers share one trace: three kernel calls a layer traced anew cost the
    serving programs seconds of set-up."""
    n, k = idx.shape
    e = w_gate.shape[0]
    # a pair of a row that is not live, or of the padding, is in group ``e``: none
    flat = jnp.where(jnp.repeat(live, k), idx.reshape(-1), e)
    flat = jnp.pad(flat, (0, -flat.size % GROUP_TILE_ROWS), constant_values=e)
    order = jnp.argsort(flat, stable=True)          # pairs by expert
    rows = x[jnp.minimum(order // k, n - 1)]        # the pair's token row
    sizes = jnp.sum(jax.nn.one_hot(flat, e, dtype=jnp.int32), axis=0)
    hidden = (nn.silu(grouped_dot(rows, w_gate, sizes, rows.dtype))
              * grouped_dot(rows, w_up, sizes, rows.dtype))
    scale = jnp.pad(weights.reshape(-1), (0, flat.size - n * k))[order]
    # the kernel leaves rows past the last group unwritten: a select, not a product
    out = jnp.where((flat[order] < e)[:, None],
                    grouped_dot(hidden, w_down, sizes, jnp.float32) * scale[:, None], 0)
    # back to token order: pair p of the sorted list is at argsort(order)[p]
    return jnp.sum(out[jnp.argsort(order)[:n * k]].reshape(n, k, -1), axis=1)


@jax.custom_batching.custom_vmap
def pooled_experts(x, idx, weights, live, w_gate, w_up, w_down):
    """:func:`grouped_experts`, with a batching rule of its own: a caller that
    maps a model over lanes which share the experts' weights (a pool of cache
    slots decoding a token each) gets ONE set of grouped products over all
    lanes' live pairs, which reads an expert once for the whole pool and only
    if a live lane chose it. ``vmap``'s own rule would sort and gather inside
    each lane and read a copy of every chosen expert a lane. Called plainly it
    is :func:`grouped_experts` over the call's rows. (``custom_vmap`` has no
    reverse-mode rule, so the layer takes this only where it decodes.)"""
    return grouped_experts(x, idx, weights, live, w_gate, w_up, w_down)


@pooled_experts.def_vmap
def _pooled_experts_over_lanes(axis_size, in_batched, x, idx, weights, live, *experts):
    if any(in_batched[4:]):
        # a lane has experts of its own: nothing is shared, and a grouped product
        # has no batching rule for its matrices, so the lanes go one by one
        args = (x, idx, weights, live, *experts)
        lane = lambda i: grouped_experts(*[a[i] if batched else a
                                           for a, batched in zip(args, in_batched)])
        return jax.lax.map(lane, jnp.arange(axis_size)), True
    lanes = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
             for a, batched in zip((x, idx, weights, live), in_batched)]
    out = pooled_experts(*[a.reshape((-1,) + a.shape[2:]) for a in lanes], *experts)
    return out.reshape((axis_size, -1) + out.shape[1:]), True


class SigmoidTopKRouter(nn.Module):
    """:func:`route_topk_sigmoid` with its two parameters: ``kernel`` (``W_g``)
    and ``bias`` (float32; it moves the choice and never a weight; training
    adjusts it and no gradient reaches it). A module of its own so that a
    caller can read the choices of a pass (``capture_intermediates``).
    Returns ``(idx [n, k], weights [n, k] float32)``."""

    n_experts: int
    top_k: int
    scale: float = 1.0

    @nn.compact
    def __call__(self, rows):
        d = rows.shape[-1]
        kernel = self.param("kernel", nn.initializers.normal(1.0 / d ** 0.5),
                            (d, self.n_experts))
        bias = self.param("bias", nn.initializers.zeros, (self.n_experts,), jnp.float32)
        idx, weights, _ = route_topk_sigmoid(rows, kernel, bias, self.top_k, self.scale)
        return idx, weights


class DroplessExperts(nn.Module):
    """``y = sum_e w_e E_e(x) + S(x)``: ``top_k`` of ``n_experts`` gated SiLU
    FFNs ``d_expert`` wide chosen per token by :class:`SigmoidTopKRouter`
    (module ``router``), every chosen pair computed, plus ONE gated FFN
    ``n_shared * d_expert`` wide for every token.

    Every call takes :func:`grouped_experts`: a prefill over its rows, a decode
    step of ``generate()`` over its batch, and a decode step of a pool of cache
    slots, which maps the model over its lanes, ONE set of grouped products
    over the whole pool's pairs (:func:`pooled_experts`, the batching rule this
    layer brings; the layer is never told that it is in a pool). A padded row
    is routed like any other: no row competes with another for anything, so it
    changes no real row's result. A lane that is nobody's is not computed: a
    caller that maps the layer over lanes says which are live through the
    read-only collection ``kv_read`` (``live``, a boolean a lane, beside the
    attention layers' ``rows``); such a lane's pairs leave the groups, so an
    expert only idle lanes chose is not read, and its routed sum is 0. A layer
    that finds no such variable takes every row as live.

    **Counts.** Where the caller makes the ``"counters"`` collection mutable
    the layer writes ``expert_choices`` ``[n_experts]`` there: how often each
    expert was chosen by this call's REAL rows. With ``decode`` it declares
    the cache leaf ``prefill_len`` as ``models/hybrid.GatedDeltaNet`` does: a
    caller that pads a prefill writes the true length there first
    (``serving/cache._admit_jit`` does, by name) and rows at or past it are not
    counted; left at 0 every row counts.
    """

    d_model: int
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int = 0
    routed_scale: float = 1.0
    dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x):
        b, s, d = x.shape
        e, f = self.n_experts, self.d_expert
        rows = x.reshape(b * s, d)
        stacked = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("w_gate", stacked, (e, d, f))
        w_up = self.param("w_up", stacked, (e, d, f))
        w_down = self.param("w_down", stacked, (e, f, d))

        with jax.named_scope("moe/router"):
            idx, weights = SigmoidTopKRouter(
                e, self.top_k, self.routed_scale, name="router")(rows)
            self._count(idx, b, s)
        live = (jnp.broadcast_to(self.get_variable(KV_READ, "live"), (b * s,))
                if self.has_variable(KV_READ, "live") else jnp.ones((b * s,), bool))
        with jax.named_scope("moe/experts"):
            routed = (pooled_experts if self.decode else grouped_experts)(
                rows, idx, weights, live, w_gate, w_up, w_down)
        out = routed.astype(self.dtype).reshape(b, s, d)
        if self.n_shared:
            with jax.named_scope("moe/shared"):
                out = out + GatedFFN(d, self.n_shared * f, self.dtype, name="shared")(x)
        return out

    def _count(self, idx, b, s):
        n_valid = None
        if self.decode:
            prefill_len = self.variable(
                "cache", "prefill_len", lambda: jnp.zeros((), jnp.int32))
            if s != 1:
                n_valid = jnp.where(prefill_len.value > 0, prefill_len.value, s)
            prefill_len.value = jnp.zeros((), jnp.int32)
        if not self.is_mutable_collection(COUNTERS):
            return
        chosen = jax.nn.one_hot(idx, self.n_experts, dtype=jnp.int32).sum(axis=1)
        if n_valid is not None:
            real = jnp.tile(jnp.arange(s) < n_valid, b)
            chosen = jnp.where(real[:, None], chosen, 0)
        self.variable(COUNTERS, "expert_choices",
                      lambda: jnp.zeros((self.n_experts,), jnp.int32)).value = chosen.sum(axis=0)
