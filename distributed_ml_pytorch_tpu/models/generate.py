"""Autoregressive decoding for the Transformer LM family.

The reference is a CNN classifier framework with no text generation at all
(SURVEY.md §2, image models only) — this is a capability extension that
completes the LM story: train with ``parallel/seq_parallel.py`` (or tp/pp),
then sample from the trained params here.

TPU-native decode structure:

- **Prefill** runs the whole prompt through the model in ONE call, writing
  every layer's K/V into the cache (``models/transformer.MultiHeadAttention``
  with ``decode=True``) — the MXU-friendly bulk phase.
- **Generation** runs single-token steps under ``lax.scan`` with NO
  per-token Python dispatch and no growing shapes. Two compiled forms:
  the plain path (one scan, caches as carry, one-slot
  ``dynamic_update_slice`` appends) for short runs and edge shapes, and
  the ring-buffered BLOCKED path (``_generate_blocked_jit``) for runs of
  ``DECODE_BLOCK`` steps or more. The blocked path exists because the
  one-slot append lands in the TPU's tiled sublane dimension and XLA
  materializes full-cache copies inside the scan (profiled at GPT-2-small
  batch 32: ~10 × 18.9 MB copies per step; a pallas
  ``input_output_aliases`` append kernel also materialized copies on this
  runtime) — appends go to a small per-layer ring instead, merged into
  the big cache once per block, and the unrolled outer loop gives each
  block a static live-prefix cache read. Measured with the fused QKV
  projection: +53% decode throughput at batch 32 and 97% of the measured
  HBM streaming roofline at batch 8 (BASELINE.md #8).
- Sampling is temperature-controlled categorical (temperature 0 → greedy
  argmax) with optional top-k and/or nucleus (top-p) truncation
  (:func:`sample_tokens`), per-step rng folded from one key, fully
  deterministic given ``(params, prompt, rng)``.

Numerics contract: blocked and plain paths compute the same attention
mathematically and are bit-identical on CPU (tested). On the TPU's MXU the
blocked path's three-part score concat and the fused QKV matmul reorder
f32 accumulation in the low bits, so greedy tokens can diverge after a few
steps when a near-random model has logit near-ties — the standard fused-
kernel float-order caveat, quality-neutral on trained models.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def sample_tokens(
    logits: jnp.ndarray,
    rng: jax.Array,
    temperature: float = 0.0,
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """One sampling decision over ``[B, vocab]`` logits.

    ``temperature=0`` is greedy argmax (k/p ignored — argmax is already the
    1-token nucleus). Otherwise: optional top-k truncation (keep the k
    highest logits), then optional nucleus truncation (keep the smallest
    prefix of the sorted distribution whose probability mass reaches
    ``top_p``; the top token always survives), then categorical sampling at
    the given temperature. All static-shape ops (sort + masks), so the
    whole thing lives inside the scanned decode program. Tokens whose
    logit exactly ties the nucleus cut-off logit are kept (the mask maps
    back through a threshold compare), matching the usual top-p contract.
    """
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    logits = logits / temperature
    vocab = logits.shape[-1]
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    top_k = min(int(top_k), vocab) if top_k else 0
    if top_k > 0 or top_p < 1.0:
        # ONE descending sort serves both filters: the k-th entry is the
        # top-k threshold, and masking the sorted tail past k-1 gives the
        # nucleus pass the post-top-k distribution without re-sorting
        sort_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        if top_k > 0:
            kth = sort_desc[..., top_k - 1][..., None]
            logits = jnp.where(logits < kth, neg, logits)
            sort_desc = jnp.where(jnp.arange(vocab) >= top_k, neg, sort_desc)
        if top_p < 1.0:
            probs = jax.nn.softmax(sort_desc, axis=-1)
            # exclusive cumulative mass: a token is cut iff the mass BEFORE
            # it already reaches top_p — the argmax token can never be cut
            exceeded = (jnp.cumsum(probs, axis=-1) - probs) >= top_p
            exceeded = exceeded.at[..., 0].set(False)  # even at top_p = 0
            cut = jnp.where(exceeded, jnp.inf, sort_desc)
            thresh = jnp.min(cut, axis=-1, keepdims=True)
            logits = jnp.where(logits < thresh, neg, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def sampled_and_filtered_rows(temperature, top_k, top_p, live):
    """``(sampled, filtered)`` row masks of a batch, numpy or jax arrays
    alike: the ``live`` rows with a temperature, and those of them that ask
    for top-k or top-p. THE statement of what ``sample_tokens_dynamic``'s
    conditional goes by; the serving engine counts its blocks by the same."""
    sampled = live & (temperature > 0.0)
    return sampled, sampled & ((top_k > 0) | (top_p < 1.0))


def sample_tokens_dynamic(
    logits: jnp.ndarray,
    rngs: jax.Array,
    temperature: jnp.ndarray,
    top_k: jnp.ndarray,
    top_p: jnp.ndarray,
    live: jnp.ndarray,
) -> jnp.ndarray:
    """Per-row sampling over ``[B, vocab]`` logits with PER-ROW params.

    The serving engine's heterogeneous-batch face of :func:`sample_tokens`:
    every argument after ``logits`` is a length-``B`` array (one rng key,
    temperature, top-k, top-p per row), all TRACED — one compiled program
    serves any mix of greedy and sampled requests. Row semantics match
    :func:`sample_tokens` exactly: for a single row, the token equals
    ``sample_tokens(logits[None], key, t, k, p)[0]`` bit-for-bit on CPU
    (tested), because the masking math mirrors it op-for-op and a
    categorical draw over ``[vocab]`` consumes the same random bits as one
    over ``[1, vocab]``. ``temperature <= 0`` rows are greedy argmax.

    **What a call costs follows the batch**, by one ``lax.switch`` on a
    scalar computed from the traced parameters, taken outside the per-row
    ``vmap`` (under ``vmap`` a conditional is a select and every side runs):

    0. no row is sampled: an argmax over the logits and nothing else;
    1. some row is sampled, none of those asks for a filter (``top_k > 0``
       or ``top_p < 1``): besides, the scaling and one categorical draw a
       row (random bits for every logit);
    2. a sampled row asks for a filter: besides, a descending sort, a
       softmax and a cumulative sum over the whole ``[B, vocab]`` plane,
       for every row of the batch.

    Each tier returns for every row what tier 2 returns (tested bit for bit
    on CPU): a filter that is off leaves the scaled logits untouched, and a
    greedy row takes its argmax by the final select. ``live`` (``[B]`` bool)
    names the rows whose token somebody reads; the others' parameters do not
    raise the tier (``sampled_and_filtered_rows``), so a free slot that still
    holds an evicted request's top-p does not make the pool sort.
    """
    vocab = logits.shape[-1]
    neg = jnp.asarray(jnp.finfo(logits.dtype).min, logits.dtype)
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)

    def scale(lg, t):
        return lg / jnp.where(t > 0.0, t, 1.0).astype(lg.dtype)

    def draw(lg, key, t):
        sampled = jax.random.categorical(key, scale(lg, t), axis=-1)
        return jnp.where(t > 0.0, sampled, jnp.argmax(lg, axis=-1))

    def filter_and_draw(lg, key, t, k, p):
        greedy = jnp.argmax(lg, axis=-1)
        scaled = scale(lg, t)
        # ONE descending sort serves both filters (same as sample_tokens);
        # the filters gate on their own params so off rows pass through
        sort_desc = jnp.sort(scaled, axis=-1)[::-1]
        kk = jnp.clip(k, 0, vocab)
        kth = sort_desc[jnp.maximum(kk - 1, 0)]
        use_k = kk > 0
        scaled = jnp.where(use_k & (scaled < kth), neg, scaled)
        sort_desc = jnp.where(use_k & (jnp.arange(vocab) >= kk), neg, sort_desc)
        probs = jax.nn.softmax(sort_desc, axis=-1)
        exceeded = (jnp.cumsum(probs, axis=-1) - probs) >= p
        exceeded = exceeded.at[0].set(False)
        cut = jnp.where(exceeded, jnp.inf, sort_desc)
        thresh = jnp.min(cut, axis=-1)
        scaled = jnp.where((p < 1.0) & (scaled < thresh), neg, scaled)
        sampled = jax.random.categorical(key, scaled, axis=-1)
        return jnp.where(t > 0.0, sampled, greedy)

    with jax.named_scope("sample"):  # one path in a device trace
        sampled_rows, filtered_rows = sampled_and_filtered_rows(
            temperature, top_k, top_p, live)
        tier = (jnp.any(sampled_rows).astype(jnp.int32)
                + jnp.any(filtered_rows).astype(jnp.int32))
        return jax.lax.switch(tier, (
            lambda: jnp.argmax(logits, axis=-1),
            lambda: jax.vmap(draw)(logits, rngs, temperature),
            lambda: jax.vmap(filter_and_draw)(
                logits, rngs, temperature, top_k, top_p),
        ))


def _fuse_qkv_params(params, name: str = ""):
    """Rewrite a trained param tree into the ``fused_qkv`` module layout:
    every attention dict {q, k, v, o} becomes {qkv, o} with the three
    kernels concatenated on the output axis (``y[..., :d] == x @ W_q``
    etc., bit-compatible column blocks). Runs INSIDE the decode jit, so
    checkpoints and callers keep the unfused layout; the concat is
    loop-invariant and XLA hoists it out of the token scans.

    The rewrite is anchored on the attention module NAME ("attn", as
    ``TransformerBlock`` declares it) in addition to the {q,k,v,o} child
    keys, so an unrelated module that happens to have those child names is
    left alone — and the q/k/v kernels are checked 2-D and equal-shaped
    before concatenating (the MHA projections are all (d_model, d_model))."""
    if (
        isinstance(params, dict)
        and name == "attn"
        and {"q", "k", "v", "o"} <= set(params)
    ):
        kernels = [params[n]["kernel"] for n in ("q", "k", "v")]
        if not all(k.ndim == 2 and k.shape == kernels[0].shape for k in kernels):
            raise ValueError(
                "attn q/k/v kernels are not same-shaped 2-D: "
                f"{[k.shape for k in kernels]}")
        out = {n: v for n, v in params.items() if n not in ("q", "k", "v")}
        out["qkv"] = {"kernel": jnp.concatenate(kernels, axis=-1)}
        return out
    if isinstance(params, dict):
        return {n: _fuse_qkv_params(v, name=n) for n, v in params.items()}
    return params


def _decode_model(model, cache_size: int, decode_block: int = 0,
                  kv_quant: bool = False):
    kw = {}
    if decode_block and hasattr(model, "decode_block"):
        kw["decode_block"] = decode_block
        if hasattr(model, "fused_qkv"):
            kw["fused_qkv"] = True
        if kv_quant and hasattr(model, "kv_quant"):
            kw["kv_quant"] = True
    elif kv_quant:
        # never swallow the request: an int8 cache only exists under the
        # blocked path, and a caller sizing batch/context for the halved
        # footprint must not silently get the full-size exact cache
        raise ValueError(
            "kv_quant=True requires decode_block > 0 (int8 quantization "
            "happens at block merges; generate() enables both together)")
    return model.clone(decode=True, cache_size=cache_size, attn_fn=None, **kw)


#: ring size for blocked decode — measured sweet spot at batch 32 (merge
#: copies amortize to ~1 big-cache copy per 16 steps while the ring stays
#: small enough to copy cheaply inside the scan)
DECODE_BLOCK = 16

#: compile-size bound for the blocked path: its outer loop is UNROLLED (one
#: differently-shaped inner scan per block, which is what makes each
#: block's cache read a static live-prefix slice), so program size and
#: compile time grow linearly with the block count. Longer generations
#: fall back to the plain one-scan path — slower per token but O(1)
#: compile. 64 blocks = 1024 tokens at the default ring size.
MAX_UNROLLED_BLOCKS = 64


#: the collection a module writes what it counted into (an expert layer's
#: choices per expert); a caller that makes it mutable gets the counts back
#: with the call (``serving/cache.py`` does, in both of its programs)
COUNTERS = "counters"

#: the cache leaves that are BIG: one row a cached position (per-head K and V,
#: the int8 cache's scales, or ``models/latent_moe.py``'s one latent row)
BIG_CACHE_LEAVES = ("cached_k", "cached_v", "scale_k", "scale_v", "cached_latent")


def split_cache(cache):
    """Split a decode cache pytree into (big, small): the per-layer big K/V
    (or latent-row) caches vs everything else (rings, cursors, ring_base).
    The big part is closed over as a CONSTANT by the blocked scan's inner loop — carrying it
    would reintroduce the per-step full-cache copies the ring exists to
    avoid. Public: the serving slot pool (``serving/cache.py``) splits its
    stacked per-slot caches with the same name-based rule."""
    big, small = {}, {}
    for name, val in cache.items():
        if isinstance(val, dict):
            b, s = split_cache(val)
            if b:
                big[name] = b
            if s:
                small[name] = s
        elif name in BIG_CACHE_LEAVES:
            big[name] = val
        else:
            small[name] = val
    return big, small


def join_cache(big, small):
    """Inverse of :func:`split_cache`: reassemble the full cache pytree."""
    out = dict(small)
    for name, val in big.items():
        if isinstance(val, dict):
            out[name] = join_cache(val, small.get(name, {}))
        else:
            out[name] = val
    return out


def _check_max_len(model, total: int) -> None:
    """RoPE rotates by position instead of indexing a table, so max_len does
    not bound its positions — the guard protects only learned embeddings."""
    max_len = getattr(model, "max_len", None)
    if (
        max_len is not None
        and total > max_len
        and getattr(model, "pos_encoding", "learned") != "rope"
    ):
        raise ValueError(
            f"prompt + max_new_tokens = {total} exceeds the model's max_len "
            f"{max_len} — position embeddings would go out of range"
        )


def init_cache(model, batch: int, cache_size: int, decode_block: int = 0,
               kv_quant: bool = False):
    """Allocate the per-layer K/V cache (zeros, cursor at 0) for ``batch``
    sequences of total length ``cache_size``.

    ``kv_quant=True`` caches carry a SINGLE-PREFILL CONTRACT: the first
    multi-token apply must happen at cursor 0 (a fresh cache). A second
    multi-token prefill into a non-empty quantized cache returns NaN
    outputs by design (``MultiHeadAttention._block_cached_attention``) —
    the quant prefill attends with its exact in-hand K/V and deliberately
    does not read earlier blocks back. :func:`generate` always satisfies
    this; direct module users chaining prefills must re-init the cache
    (or use the exact bf16 cache, which has no such restriction). The
    serving slot pool (``serving/cache.py``) also satisfies it under slot
    REUSE: every admission prefills a fresh zeroed lane cache and scatters
    it over the recycled slot, so the contract holds per occupancy, not
    just per allocation."""
    dec = _decode_model(model, cache_size, decode_block=decode_block,
                        kv_quant=kv_quant)
    variables = jax.eval_shape(
        lambda: dec.init(
            jax.random.key(0),
            jnp.zeros((batch, 1), jnp.int32),
            jnp.zeros((batch, 1), jnp.int32),
        )
    )
    return jax.tree.map(jnp.zeros_like, variables["cache"])


def uses_block_decode(model, prompt_len: int,
                      max_new_tokens: int) -> Tuple[bool, int]:
    """Whether :func:`generate` will take the ring-buffered block path for
    this shape, plus the padded cache allocation it would use. Public so
    callers that REQUIRE block-path behavior (``kv_quant`` only applies
    there) can check instead of trusting a silent fallback.

    The blocked path pads the step loop to a multiple of ``DECODE_BLOCK``;
    it runs when the generation is long enough to amortize a block, short
    enough to bound the unrolled compile, the padding fits the learned
    position table (RoPE is unbounded), and the prompt has more than one
    token — a one-token prompt's prefill would be indistinguishable from a
    single-token decode step inside ``_block_cached_attention`` (``s == 1``
    is the branch discriminator) and its K/V would be orphaned in the ring.
    """
    T = DECODE_BLOCK
    n_steps = max_new_tokens - 1
    n_blocks = -(-n_steps // T)
    padded_total = prompt_len + n_blocks * T
    blocked = (
        hasattr(model, "decode_block")
        and n_steps >= T
        and n_blocks <= MAX_UNROLLED_BLOCKS
        and prompt_len > 1
        and (getattr(model, "pos_encoding", "learned") == "rope"
             or padded_total <= getattr(model, "max_len", padded_total))
    )
    return blocked, padded_total


def generate(
    model,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    top_k: int = 0,
    top_p: float = 1.0,
    kv_quant: bool = False,
) -> jnp.ndarray:
    """Sample ``max_new_tokens`` continuations of ``prompt`` ([B, P] int32).

    Returns ``[B, P + max_new_tokens]`` tokens. ``temperature=0`` is greedy;
    otherwise categorical sampling at the given temperature (``rng``
    required) with optional ``top_k`` / nucleus ``top_p`` truncation
    (:func:`sample_tokens`). Jit-compiled end-to-end: one prefill program +
    one scanned generation program, both cached across calls with the same
    shapes. ``kv_quant=True`` stores completed blocks' K/V as int8 with
    per-key scales (half the dominant decode HBM read; small quantization
    noise on cross-block attention only) — it applies only when the
    blocked path runs; shapes that fall back to the plain scan keep the
    exact full-size cache and a ``UserWarning`` is emitted (pre-check with
    :func:`uses_block_decode` to avoid the fallback). Quantized caches are
    single-prefill (see :func:`init_cache`); ``generate`` always satisfies
    that contract internally.
    """
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 sampling needs an rng key")
    rng = rng if rng is not None else jax.random.key(0)
    b, p = prompt.shape
    total = p + max_new_tokens
    _check_max_len(model, total)
    if max_new_tokens < 1:
        return prompt

    blocked, padded_total = uses_block_decode(model, p, max_new_tokens)
    if blocked:
        cache = init_cache(model, b, padded_total, decode_block=DECODE_BLOCK,
                           kv_quant=kv_quant)
        dec = _decode_model(model, padded_total, decode_block=DECODE_BLOCK,
                            kv_quant=kv_quant)
        return _generate_blocked_jit(
            dec, int(max_new_tokens), float(temperature), int(top_k),
            float(top_p), params, cache, prompt, rng
        )
    if kv_quant:
        # the plain scan keeps the exact full-size bf16 cache — more
        # accurate, but NOT the halved footprint the caller sized for, so
        # the fallback must be audible (callers can pre-check with
        # uses_block_decode())
        import warnings

        warnings.warn(
            "kv_quant=True requested but this shape falls back to the plain "
            "decode scan (int8 quantization only exists under the blocked "
            "path: needs prompt_len > 1 and "
            f"{DECODE_BLOCK} <= max_new_tokens - 1 <= "
            f"{DECODE_BLOCK * MAX_UNROLLED_BLOCKS}, within max_len) — using "
            "the exact FULL-SIZE bf16 cache; the halved-footprint capacity "
            "win does not apply",
            stacklevel=2,
        )
    # kv_quant needs the blocked structure (quantize-at-merge); the plain
    # scan keeps the exact full-size cache (warned above — more accurate,
    # but not the halved footprint the caller asked for)
    cache = init_cache(model, b, total)
    dec = _decode_model(model, total)
    return _generate_jit(
        dec, int(max_new_tokens), float(temperature), int(top_k), float(top_p),
        params, cache, prompt, rng
    )


def generate_tp(
    model,
    params,
    prompt: jnp.ndarray,
    max_new_tokens: int,
    mesh,
    temperature: float = 0.0,
    rng: Optional[jax.Array] = None,
    data_axis: str = "data",
    model_axis: str = "model",
    top_k: int = 0,
    top_p: float = 1.0,
) -> jnp.ndarray:
    """Tensor-parallel decode: ``generate`` semantics on a dp×tp mesh.

    Capability symmetry with the training-side TP
    (``parallel/tensor_parallel.py``): the same Megatron layout serves
    inference — params sharded by :func:`tp_param_specs` (q/k/v column-,
    o row-, lm_head vocab-sharded), batch over ``data_axis``, and the K/V
    cache sharded over *heads* on ``model_axis`` (heads follow the q/k/v
    column shards, so cache append + cached attention stay device-local;
    the per-block all-reduce on attention/MLP outputs is inserted by XLA).
    The compiled program is the same prefill+scan as :func:`generate` —
    GSPMD propagates the shardings through it; greedy decode is therefore
    bit-identical to the single-device path (tested).
    """
    from distributed_ml_pytorch_tpu.parallel.tensor_parallel import (
        _check_divisibility,
        tp_param_specs,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    _check_divisibility(model, int(mesh.shape[model_axis]))
    if temperature > 0.0 and rng is None:
        raise ValueError("temperature > 0 sampling needs an rng key")
    rng = rng if rng is not None else jax.random.key(0)
    b, p = prompt.shape
    total = p + max_new_tokens
    _check_max_len(model, total)  # same guard as generate()
    if max_new_tokens < 1:
        return prompt

    param_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), tp_param_specs(params, model_axis),
        is_leaf=lambda x: isinstance(x, P),
    )
    params = jax.device_put(params, param_shardings)

    def cache_spec(path, leaf):
        name = getattr(path[-1], "key", str(path[-1]))
        if name in ("cached_k", "cached_v"):  # (b, heads, cache, head_dim)
            return NamedSharding(mesh, P(data_axis, model_axis, None, None))
        return NamedSharding(mesh, P())  # cursor

    cache = init_cache(model, b, total)
    cache = jax.device_put(
        cache, jax.tree_util.tree_map_with_path(cache_spec, cache)
    )
    prompt = jax.device_put(prompt, NamedSharding(mesh, P(data_axis, None)))
    dec = _decode_model(model, total)
    return _generate_jit(
        dec, int(max_new_tokens), float(temperature), int(top_k), float(top_p),
        params, cache, prompt, rng
    )


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _generate_jit(dec, max_new_tokens, temperature, top_k, top_p,
                  params, cache, prompt, rng):
    b, p = prompt.shape

    # prefill: whole prompt in one pass; next token comes from the last logit
    positions = jnp.arange(p)[None, :]
    logits, mutated = dec.apply(
        {"params": params, "cache": cache}, prompt, positions, mutable=["cache"]
    )
    cache = mutated["cache"]

    def sample(logits, step_rng):
        return sample_tokens(
            logits, step_rng, temperature=temperature, top_k=top_k, top_p=top_p
        ).astype(prompt.dtype)

    first = sample(logits[:, -1], jax.random.fold_in(rng, 0))

    def step(carry, t):
        cache, tok = carry
        pos = jnp.full((b, 1), p, jnp.int32) + t
        logits, mutated = dec.apply(
            {"params": params, "cache": cache}, tok[:, None], pos, mutable=["cache"]
        )
        nxt = sample(logits[:, -1], jax.random.fold_in(rng, t + 1))
        return (mutated["cache"], nxt), tok

    (_, last), toks = jax.lax.scan(
        step, (cache, first), jnp.arange(max_new_tokens - 1)
    )
    generated = jnp.concatenate(
        [jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1
    )  # [B, max_new_tokens]
    return jnp.concatenate([prompt, generated], axis=1)


def _tree_slice_big(big, live):
    """Static live-prefix view of every big cache: (b, h, C, d) -> (b, h,
    live, d), and (b, h, C) scale arrays -> (b, h, live). A static slice
    fuses into the attention read, so each block reads exactly the K/V
    written so far instead of the full padded cache."""
    return jax.tree.map(
        lambda a: a[:, :, :live, :] if a.ndim == 4 else a[:, :, :live], big)


def merge_ring_caches(big, small, live):
    """Merge every layer's ring into its FULL big cache at offset ``live``;
    returns the updated big pytree (rings themselves are reused — the next
    block's strict ring mask hides stale slots). Quantized caches
    (``kv_quant``: int8 values + scale arrays present) quantize the exact
    bf16 ring here, once per block. ``live`` may be a static int (the
    blocked generate path — the static offset fuses) or a traced scalar
    (the serving slot pool vmaps this over slots with per-slot offsets)."""
    if "cached_k" in big:
        from distributed_ml_pytorch_tpu.models.transformer import quantize_kv

        out = dict(big)
        rk, rv = small["ring_k"], small["ring_v"]
        if "scale_k" in big:
            rk, ks = quantize_kv(rk)
            rv, vs = quantize_kv(rv)
            out["scale_k"] = jax.lax.dynamic_update_slice(
                big["scale_k"], ks, (0, 0, live))
            out["scale_v"] = jax.lax.dynamic_update_slice(
                big["scale_v"], vs, (0, 0, live))
        out["cached_k"] = jax.lax.dynamic_update_slice(
            big["cached_k"], rk, (0, 0, live, 0))
        out["cached_v"] = jax.lax.dynamic_update_slice(
            big["cached_v"], rv, (0, 0, live, 0))
        return out
    if "cached_latent" in big:  # one shared row a position: ring over cache alike
        return dict(big, cached_latent=jax.lax.dynamic_update_slice(
            big["cached_latent"], small["ring_latent"], (0, 0, live, 0)))
    return {
        name: (merge_ring_caches(val, small.get(name, {}), live)
               if isinstance(val, dict) else val)
        for name, val in big.items()
    }


def reset_ring_state(small, live):
    """Per-block small-state reset: cursor and ring_base both sit at the
    block's start position ``live`` (rings keep stale data — masked out).
    ``live`` may be static or traced, like :func:`merge_ring_caches`."""
    out = {}
    for name, val in small.items():
        if isinstance(val, dict):
            out[name] = reset_ring_state(val, live)
        elif name in ("cursor", "ring_base"):
            out[name] = jnp.asarray(live, jnp.int32)
        else:
            out[name] = val
    return out


@partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _generate_blocked_jit(dec, max_new_tokens, temperature, top_k, top_p,
                          params, cache, prompt, rng):
    """Ring-buffered decode: an UNROLLED outer loop over DECODE_BLOCK-token
    blocks, an inner scan over single-token steps. Three structural wins
    over the naive one-token scan (measured at GPT-2-small batch 32,
    device-true):

    - single-token steps write a small per-layer ring instead of the big
      cache, so the scan carries no big-cache copies (the naive scan paid
      ~10 full 18.9 MB copies per step — see ``decode_block`` in
      models/transformer.py);
    - the big caches cross each inner scan as closed-over constants and are
      merged once per block with a static-offset update;
    - because the outer loop is unrolled, each block's live cache length is
      STATIC: the block's attention reads a fused live-prefix slice
      (b, h, p + blk*T, d) instead of the full padded cache — the average
      read drops from the allocation size to the true live size.

    The step loop is padded to a whole number of blocks; padded steps
    sample garbage the caller never sees (their K/V lands after every real
    token's, so no real attention read touches it). Net effect at batch 32:
    2.43 ms/step -> ~1.26 ms/step with the fused QKV projection (see
    BASELINE.md #8)."""
    T = dec.decode_block
    b, p = prompt.shape
    n_steps = max_new_tokens - 1
    n_blocks = -(-n_steps // T)
    if getattr(dec, "fused_qkv", False):
        params = _fuse_qkv_params(params)

    positions = jnp.arange(p)[None, :]
    logits, mutated = dec.apply(
        {"params": params, "cache": cache}, prompt, positions, mutable=["cache"]
    )
    big, small = split_cache(mutated["cache"])

    def sample(logits, step_rng):
        return sample_tokens(
            logits, step_rng, temperature=temperature, top_k=top_k, top_p=top_p
        ).astype(prompt.dtype)

    tok = sample(logits[:, -1], jax.random.fold_in(rng, 0))
    all_toks = []
    for blk in range(n_blocks):
        live = p + blk * T
        dec_blk = dec.clone(cache_size=live)
        big_view = _tree_slice_big(big, live)
        small = reset_ring_state(small, live)

        def inner(carry, t, dec_blk=dec_blk, big_view=big_view, blk=blk):
            small, tok = carry
            step_idx = blk * T + t
            pos = jnp.full((b, 1), p, jnp.int32) + step_idx
            logits, mut = dec_blk.apply(
                {"params": params, "cache": join_cache(big_view, small)},
                tok[:, None], pos, mutable=["cache"],
            )
            _, small = split_cache(mut["cache"])
            nxt = sample(logits[:, -1], jax.random.fold_in(rng, step_idx + 1))
            return (small, nxt), tok

        (small, tok), toks = jax.lax.scan(inner, (small, tok), jnp.arange(T))
        big = merge_ring_caches(big, small, live)
        all_toks.append(jnp.moveaxis(toks, 0, 1))  # [B, T] inputs of each step

    generated = jnp.concatenate(all_toks + [tok[:, None]], axis=1)
    return jnp.concatenate([prompt, generated[:, :max_new_tokens]], axis=1)
