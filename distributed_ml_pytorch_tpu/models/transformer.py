"""Decoder-only Transformer LM — the long-context model family.

The reference's model zoo is two CIFAR CNNs (``example/models.py:5-49``); the
TPU framework adds a Transformer because long-context training is first-class
here (SURVEY.md §5.7 records the reference owes nothing — this is a
capability extension, not parity). The design is shaped by how it trains:

- **Attention is injectable.** ``attn_fn(q, k, v)`` defaults to the
  blockwise online-softmax kernel (``ops/attention.py``) over the local
  sequence; under sequence parallelism the trainer passes
  ``parallel/ring.ring_attention`` bound to the mesh axis, and the same
  module then computes exact full-sequence attention over sharded chunks.
  Nothing else in the model knows the sequence is distributed.
- **Positions are an input**, not ``arange(seq)``: a device holding chunk
  ``i`` of a sharded sequence feeds its global positions, so position
  encoding is correct under sharding — for the learned table AND for RoPE
  (``pos_encoding="rope"``), which rotates q/k by global position inside
  attention before any ring/Ulysses exchange.
- Pre-LN blocks, GELU MLP, bf16-friendly (dtype threads through every
  dense/embed); weights stay f32 (master copies), activations cast.
"""

from __future__ import annotations

from functools import cache, partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_ml_pytorch_tpu.ops.attention import auto_attention
from distributed_ml_pytorch_tpu.ops.slot_attention import kernel_runs_here, slot_rows_attention


def default_attn_fn(q, k, v):
    """Causal attention over the local (= full, when unsharded) sequence:
    the Pallas flash kernel on TPU when the shape fits its blocking (6.3×
    the scan forward and at splash-kernel parity incl. the fused backward,
    device-true — ops/attention.py), the differentiable blockwise scan
    everywhere else."""
    return auto_attention(q, k, v, causal=True)


def apply_rope(x: jax.Array, positions: jax.Array, base: float = 10000.0) -> jax.Array:
    """Rotary position embedding for one projection.

    ``x`` is ``(batch, heads, seq, head_dim)``; ``positions`` carries the
    GLOBAL position of every token ``(batch, seq)`` or ``(1, seq)`` — the
    same positions-are-an-input design that makes learned embeddings
    sharding-transparent makes RoPE exact under sequence sharding: each
    device rotates its local chunk by its global offsets BEFORE ring/Ulysses
    attention exchanges anything, and a decode step rotates by the cache
    cursor's absolute position. Rotation happens in f32 (angles lose
    precision fast in bf16); the result is cast back to ``x.dtype``.
    """
    half = x.shape[-1] // 2
    if 2 * half != x.shape[-1]:
        raise ValueError(f"rope needs an even head_dim, got {x.shape[-1]}")
    freqs = base ** (-jnp.arange(half, dtype=jnp.float32) / half)  # (half,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (b, s, half)
    cos = jnp.cos(angles)[:, None]  # (b, 1, s, half) — broadcast over heads
    sin = jnp.sin(angles)[:, None]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    ).astype(x.dtype)


def quantize_kv(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-key symmetric int8 quantization of a K or V block ``(..., d)``:
    returns ``(int8 values, f32 scale (...,))`` with ``x ≈ int8 * scale``.
    Absmax over the head dim — each cached position/head keeps its own
    scale, so one outlier key cannot crush every other key's resolution."""
    xf = x.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


#: the collection through which a caller tells a decoding attention layer
#: how many rows of its big cache anybody can need (``rows``, a scalar), and
#: a layer it maps over lanes whether the lane is anybody's (``live``, a
#: boolean a lane: ``models/moe.DroplessExperts`` reads it)
KV_READ = "kv_read"


def kv_read_chunk(cache_rows: int) -> int:
    """Rows a bounded read of a ``cache_rows``-row cache takes at a time: a
    quarter of the allocation, so the read stops at a quarter, a half, three
    quarters or all of it."""
    return -(-cache_rows // 4)


def _tpu_keeps_rows_minor(rows: int, head_dim: int) -> bool:
    """Whether this process runs on a TPU that holds a ``(..., rows,
    head_dim)`` buffer with ``rows``, not ``head_dim``, along its 128 lanes.
    The TPU runtime lays a buffer's last two dimensions out over tiles of
    8 x 128 and makes the minor one whichever pads less: a head of 64 would
    fill half of every tile's lanes, so a 1024 x 64 cache lies transposed,
    and a head of 128 lies as written (read from the entry layouts the TPU
    compiler assigns, PR 33). A wrong answer costs a copy of the buffer and
    changes no result; other backends keep what the shape says."""
    if jax.default_backend() != "tpu":
        return False
    pad = lambda n, tile: -(-n // tile) * tile
    return pad(rows, 128) * pad(head_dim, 8) < pad(head_dim, 128) * pad(rows, 8)


# jitted so that a model's layers share ONE trace of it: traced and lowered
# inline, a layer each, it cost the host seconds at every start (thirty-six
# copies of the loop at gpt2-large: set-up +7 s on the chip's host, PR 33)
@partial(jax.jit, static_argnames=("dtype", "turned"))
def bounded_cache_attention(bound, q, v, s_ring, s_self, scale, ring_base,
                            ring_v, cache_k, cache_v, scale_k, scale_v, *,
                            dtype, turned):
    """A single-token step's three-part attention (big cache, ring, self) with
    the big cache read only as far as somebody needs it: called plainly, as
    far as ``bound`` (:func:`_bounded_read`); mapped over a pool's lanes, each
    lane as far as its own ``ring_base`` (:func:`_pooled_read`)."""
    return _pooled_read(dtype, turned)(bound, q, v, s_ring, s_self, scale, ring_base,
                                       ring_v, cache_k, cache_v, scale_k, scale_v)


def _bounded_read(bound, q, v, s_ring, s_self, scale, ring_base, ring_v,
                  cache_k, cache_v, scale_k, scale_v, *, dtype, turned, lengths=None):
    """The read in a loop over ``kv_read_chunk`` rows at a time, as many as
    hold ``bound`` (at least every ``ring_base``): a trip count that is data.
    Given each sequence's own ``lengths``, one kernel over the rows they hold
    (``ops/slot_attention``). The softmax runs online, in float32, from the
    ring and self terms; the ``key_pos < ring_base`` mask hides a sequence's
    rows past its length. ``turned``: the device keeps the caches rows minor
    (``_tpu_keeps_rows_minor``), so they are handed over transposed, a bitcast."""
    rows = cache_k.shape[2]
    chunk = kv_read_chunk(rows)
    T = ring_v.shape[2]
    if turned:
        cache_k, cache_v = (jnp.swapaxes(c, 2, 3) for c in (cache_k, cache_v))
    k_product = "bhd,bhdc->bhc" if turned else "bhd,bhcd->bhc"
    v_product = "bhc,bhdc->bhd" if turned else "bhc,bhcd->bhd"
    # the one query position is dropped inside the loop: (b, h[, d]) carries
    qf = q[:, :, 0].astype(jnp.float32)
    s_rest = jnp.concatenate([s_ring, s_self[..., None]], axis=-1)[:, :, 0] / scale
    m = jnp.max(s_rest, axis=-1)  # finite: the self term
    p = jnp.exp(s_rest - m[..., None])
    total = jnp.sum(p, axis=-1)
    acc = (jnp.einsum("bht,bhtd->bhd", p[..., :T].astype(dtype), ring_v,
                      preferred_element_type=jnp.float32) + p[..., T:] * v[:, :, 0])
    if lengths is not None:
        return slot_rows_attention(qf, m, total, acc, lengths, cache_k, cache_v, scale,
                                   turned=turned)[:, :, None]
    # what the mask adds to a row's score, for the loop to cut chunks of
    unseen = jnp.where(jnp.arange(rows) < ring_base, 0.0, -jnp.inf)
    # the last chunk of an allocation that is no multiple of the chunk starts
    # early, and its overlap with the one before must not count
    ragged = rows % chunk != 0

    def read_chunk(i, carry):
        m, total, acc = carry
        first = i * chunk
        start = jnp.minimum(first, rows - chunk) if ragged else first
        cut = lambda a, axis=2: jax.lax.dynamic_slice_in_dim(
            a, start, chunk, axis=axis)
        cut_kv = lambda c: cut(c, 3 if turned else 2).astype(dtype)
        sc = jnp.einsum(k_product, qf, cut_kv(cache_k).astype(jnp.float32))
        if scale_k is not None:
            sc = sc * cut(scale_k)
        sc = sc / scale + cut(unseen, 0)
        if ragged:
            sc = jnp.where(start + jnp.arange(chunk) >= first, sc, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        shrink = jnp.exp(m - m_new)
        w = jnp.exp(sc - m_new[..., None])
        total = total * shrink + jnp.sum(w, axis=-1)
        if scale_v is not None:
            w = w * cut(scale_v)
        acc = acc * shrink[..., None] + jnp.einsum(
            v_product, w.astype(dtype), cut_kv(cache_v),
            preferred_element_type=jnp.float32)
        return m_new, total, acc

    _, total, acc = jax.lax.fori_loop(
        0, (bound + chunk - 1) // chunk, read_chunk, (m, total, acc))
    return (acc / total[..., None])[:, :, None]


class MultiHeadAttention(nn.Module):
    """Causal MHA; with ``decode=True`` it maintains a K/V cache (flax
    ``"cache"`` collection) for incremental autoregressive decoding: each call
    appends the new keys/values at the cache cursor and attends the (short)
    query block over everything written so far."""

    d_model: int
    n_heads: int
    dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    rope: bool = False
    #: >0 enables ring-buffered block decode: single-token steps write a
    #: small (b, h, decode_block, d) ring instead of the big cache, and the
    #: caller merges full rings into the big cache every decode_block steps
    #: (models/generate.py's blocked scan does this). Why: a one-slot
    #: dynamic_update_slice on the big cache lands in the TPU's tiled
    #: sublane dim and XLA materializes a full-cache copy per layer per
    #: step inside the decode scan (measured 83-100 us per 18.9 MB cache at
    #: batch 32 vs 46 us for BOTH attention reads at the HBM roofline);
    #: buffering appends in a ring the scan can copy cheaply and merging
    #: once per block amortizes the big-cache write to ~1 copy / T steps.
    #: The append itself is a select over the ring's T rows, not a
    #: dynamic_update_slice: under ``SlotKVPool``'s vmap over slots the
    #: offset is a per-slot vector, a dynamic_update_slice with a batched
    #: index is a scatter, and the TPU compiler runs that scatter as a
    #: sequential loop over the slots (32 iterations of eight small kernels
    #: for each of K and V in every layer of every step). A select has no
    #: index operand, so it stays one dense pass over the ring, batched or
    #: not, and leaves the same bytes there.
    decode_block: int = 0
    #: store the big decode cache as int8 with per-(batch, head, position)
    #: f32 scales (``quantize_kv``) — HALVES THE CACHE'S HBM FOOTPRINT
    #: (2x the decode batch or context per chip). Rings and the in-flight
    #: block stay exact (self.dtype); quantization happens once per block
    #: at merge time. Requires decode_block > 0. Throughput note (measured,
    #: GPT-2-small batch 32): isolated int8 cache reads run ~0.6x the bf16
    #: time, but inside the full decode program the fused
    #: convert+dequantize read drops to ~half the bf16 GB/s — bytes halve,
    #: read TIME stays ~flat, so this is a capacity knob on this runtime,
    #: not a speed knob (20.2k tok/s bf16 vs 18.8k int8, fused-QKV path).
    kv_quant: bool = False
    #: decode-path knob: compute q/k/v with ONE (d_model, 3*d_model) matmul
    #: instead of three — one weight DMA per layer per step instead of
    #: three, targeting the measured weight-stall share of the decode step.
    #: Param tree changes shape (attn/qkv instead of attn/{q,k,v});
    #: models/generate.py fuses trained q/k/v kernels on the fly
    #: (_fuse_qkv_params), so checkpoints stay in the unfused layout.
    fused_qkv: bool = False
    #: RMSNorm (learned scale, modules ``q_norm`` and ``k_norm``) over the
    #: whole q and k projections before they are split into heads: the QK-norm
    #: of ``models/hybrid.py``'s full-attention layers. Cached keys are stored
    #: normed.
    qk_norm: bool = False
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions=None):
        b, s, _ = x.shape
        head_dim = self.d_model // self.n_heads
        proj = lambda name: nn.Dense(self.d_model, use_bias=False, dtype=self.dtype, name=name)
        split = lambda t: t.reshape(b, s, self.n_heads, head_dim).transpose(0, 2, 1, 3)
        # the QK-norm goes between projection and split; without it ``normed``
        # is the identity and the program is what it was before the option
        normed = lambda name, t: t
        if self.qk_norm:
            normed = lambda name, t: t if name == "v" else nn.RMSNorm(
                epsilon=self.norm_eps, dtype=self.dtype, name=f"{name}_norm")(t)
        if self.fused_qkv:
            qkv = nn.Dense(3 * self.d_model, use_bias=False, dtype=self.dtype,
                           name="qkv")(x)
            q, k, v = (split(normed(n, qkv[..., i * self.d_model:(i + 1) * self.d_model]))
                       for i, n in enumerate("qkv"))
        else:
            q, k, v = (split(normed(n, proj(n)(x))) for n in ("q", "k", "v"))
        if self.rope:
            if positions is None:
                raise ValueError("rope=True needs the tokens' global positions")
            q = apply_rope(q, positions)
            k = apply_rope(k, positions)  # cached k (decode) is stored rotated
        if self.decode:
            if self.attn_fn is not None:
                raise ValueError(
                    "decode=True uses cached dense attention and cannot honor "
                    "an injected attn_fn — clone the model with attn_fn=None "
                    "for decoding (models/generate.py does this)"
                )
            out = self._cached_attention(q, k, v, b, s, head_dim)
        else:
            attn = self.attn_fn or default_attn_fn
            out = attn(q, k, v)  # (b, h, s, hd)
        out = out.transpose(0, 2, 1, 3).reshape(b, s, self.d_model)
        return nn.Dense(self.d_model, use_bias=False, dtype=self.dtype, name="o")(out)

    def _cached_attention(self, q, k, v, b, s, head_dim):
        if self.cache_size < 1:
            raise ValueError("decode=True needs cache_size > 0")
        if self.kv_quant and self.decode_block <= 0:
            raise ValueError(
                "kv_quant=True requires decode_block > 0 — the int8 cache "
                "is quantized at block-merge time (models/generate.py "
                "enables both together)")
        # cache lives in the model's activation dtype (half the HBM under
        # bf16), or int8 + per-key scales under kv_quant; scores/softmax
        # compute in f32 for stability
        store_dt = jnp.int8 if self.kv_quant else self.dtype
        shape = (b, self.n_heads, self.cache_size, head_dim)
        cache_k = self.variable("cache", "cached_k", jnp.zeros, shape, store_dt)
        cache_v = self.variable("cache", "cached_v", jnp.zeros, shape, store_dt)
        cursor = self.variable("cache", "cursor", lambda: jnp.zeros((), jnp.int32))
        scale_k = scale_v = None
        if self.kv_quant:
            sshape = (b, self.n_heads, self.cache_size)
            scale_k = self.variable("cache", "scale_k", jnp.zeros, sshape, jnp.float32)
            scale_v = self.variable("cache", "scale_v", jnp.zeros, sshape, jnp.float32)
        idx = cursor.value
        if self.decode_block > 0:
            return self._block_cached_attention(
                q, k, v, b, s, head_dim, cache_k, cache_v, cursor,
                scale_k, scale_v)
        ck = jax.lax.dynamic_update_slice(cache_k.value, k.astype(self.dtype), (0, 0, idx, 0))
        cv = jax.lax.dynamic_update_slice(cache_v.value, v.astype(self.dtype), (0, 0, idx, 0))
        cache_k.value, cache_v.value, cursor.value = ck, cv, idx + s
        # Scores accumulate in f32 ON THE MXU (preferred_element_type) with
        # the cache read at its stored bf16 — an ``astype(f32)`` here would
        # materialize a full f32 copy of the cache EVERY step per layer
        # (measured: the cast traffic alone was ~56 MB/layer/step at batch
        # 32, dominating the decode step). Same for the PV einsum: probs
        # drop to the cache dtype so the MXU reads cv directly.
        scores = jnp.einsum(
            "bhsd,bhcd->bhsc", q, ck, preferred_element_type=jnp.float32
        )
        scores = scores / jnp.sqrt(jnp.asarray(head_dim, jnp.float32))
        # causal over absolute positions: query i (at idx+i) sees keys ≤ idx+i
        key_pos = jnp.arange(self.cache_size)
        q_pos = idx + jnp.arange(s)
        mask = key_pos[None, :] <= q_pos[:, None]  # (s, cache)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return jnp.einsum(
            "bhsc,bhcd->bhsd", probs.astype(self.dtype), cv,
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)

    def _block_cached_attention(self, q, k, v, b, s, head_dim,
                                cache_k, cache_v, cursor,
                                scale_k=None, scale_v=None):
        """Ring-buffered decode (see ``decode_block``): single-token steps
        never write the big cache. They attend over three parts — the big
        cache masked to positions before ``ring_base``, the ring masked to
        slots written so far this block, and the fresh token — and append
        K/V to the ring. Multi-token (prefill) calls bulk-write the big
        cache and anchor ``ring_base`` at the end of the prompt; the
        CALLER must merge the ring into the big cache at
        ``ring_base`` and advance ``ring_base`` by ``decode_block`` every
        ``decode_block`` single-token steps (``models/generate.py``).

        How much of the big cache a single-token step reads is the caller's
        to say. Given nothing, it reads every row the cache it was handed
        has (blocked ``generate()`` hands over a cache cut to the rows
        written so far). Given ``kv_read/rows`` it reads whole chunks as far
        as that bound and no further (``bounded_cache_attention``;
        ``SlotKVPool``, whose sequences differ in length, gives the longest).

        Under ``kv_quant`` the big cache holds int8 + per-key f32 scales:
        K scales fold into the scores AFTER the int8→dtype einsum, V scales
        fold into the attention weights BEFORE theirs — both reads stream
        the int8 bytes. Prefill attention then uses the in-hand exact K/V
        (not a read-back of its own quantization), so prompt logits are
        exact and only cross-block reads see quantization noise."""
        T = self.decode_block
        quant = self.kv_quant
        ring_shape = (b, self.n_heads, T, head_dim)
        ring_k = self.variable("cache", "ring_k", jnp.zeros, ring_shape, self.dtype)
        ring_v = self.variable("cache", "ring_v", jnp.zeros, ring_shape, self.dtype)
        ring_base = self.variable(
            "cache", "ring_base", lambda: jnp.zeros((), jnp.int32))
        idx = cursor.value
        k = k.astype(self.dtype)
        v = v.astype(self.dtype)
        scale = jnp.sqrt(jnp.asarray(head_dim, jnp.float32))

        def big_k_scores(qq):
            """(b, h, s, C) scores against the big cache, dequantized."""
            sc = jnp.einsum("bhsd,bhcd->bhsc", qq,
                            cache_k.value.astype(self.dtype),
                            preferred_element_type=jnp.float32)
            if quant:
                sc = sc * scale_k.value[:, :, None, :]
            return sc

        def big_v_apply(weights):
            """(b, h, s, d) output from big-cache V under f32 weights."""
            if quant:
                weights = weights * scale_v.value[:, :, None, :]
            return jnp.einsum("bhsc,bhcd->bhsd", weights.astype(self.dtype),
                              cache_v.value.astype(self.dtype),
                              preferred_element_type=jnp.float32)

        if s != 1:  # prefill: bulk write straight to the big cache
            if quant:
                k8, ks = quantize_kv(k)
                v8, vs = quantize_kv(v)
                cache_k.value = jax.lax.dynamic_update_slice(
                    cache_k.value, k8, (0, 0, idx, 0))
                cache_v.value = jax.lax.dynamic_update_slice(
                    cache_v.value, v8, (0, 0, idx, 0))
                scale_k.value = jax.lax.dynamic_update_slice(
                    scale_k.value, ks, (0, 0, idx))
                scale_v.value = jax.lax.dynamic_update_slice(
                    scale_v.value, vs, (0, 0, idx))
            else:
                cache_k.value = jax.lax.dynamic_update_slice(
                    cache_k.value, k, (0, 0, idx, 0))
                cache_v.value = jax.lax.dynamic_update_slice(
                    cache_v.value, v, (0, 0, idx, 0))
            cursor.value = idx + s
            ring_base.value = idx + s
            if not quant:
                # attention over what's now in the big cache — identical
                # math to the unblocked path's prefill
                scores = big_k_scores(q) / scale
                key_pos = jnp.arange(self.cache_size)
                q_pos = idx + jnp.arange(s)
                mask = key_pos[None, :] <= q_pos[:, None]
                scores = jnp.where(mask[None, None], scores, -jnp.inf)
                probs = jax.nn.softmax(scores, axis=-1)
                return big_v_apply(probs).astype(q.dtype)
            # quant prefill: attend with the exact in-hand K/V — reading
            # back the just-written range would see its own quantization
            # noise. SINGLE-PREFILL CONTRACT: the cache must be empty
            # (cursor 0) — a big-cache read for an earlier prefill's keys
            # would burn two full-cache einsums that generate() (the only
            # in-tree caller, always cursor 0) never needs; misuse is
            # poisoned with NaN instead of silently dropping the past
            s_loc = jnp.einsum("bhsd,bhtd->bhst", q, k,
                               preferred_element_type=jnp.float32)
            causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]  # (s_q, s_k)
            s_loc = jnp.where(causal[None, None], s_loc, -jnp.inf)
            probs = jax.nn.softmax(s_loc / scale, axis=-1)
            out = jnp.einsum(
                "bhst,bhtd->bhsd", probs.astype(self.dtype), v,
                preferred_element_type=jnp.float32)
            out = jnp.where(idx == 0, out, jnp.nan)
            return out.astype(q.dtype)

        t = idx - ring_base.value  # slot in the current block, 0..T-1
        # a caller that says how far anybody's big cache is live gets a read
        # that stops there (``bounded_cache_attention``)
        bounded = self.has_variable(KV_READ, "rows")
        if not bounded:
            # part 1: completed blocks, read from the big cache (strict mask
            # — positions >= ring_base live in the ring, big-cache slots
            # there are stale)
            s_past = jnp.where(
                (jnp.arange(self.cache_size) < ring_base.value)[None, None, None, :],
                big_k_scores(q), -jnp.inf)
        # part 2: this block's earlier tokens, read from the ring
        s_ring = jnp.einsum(
            "bhsd,bhtd->bhst", q, ring_k.value,
            preferred_element_type=jnp.float32)
        s_ring = jnp.where(
            (jnp.arange(T) < t)[None, None, None, :], s_ring, -jnp.inf)
        # part 3: the fresh token attending to itself
        s_self = jnp.einsum(
            "bhsd,bhsd->bhs", q, k, preferred_element_type=jnp.float32)
        if bounded:
            out = bounded_cache_attention(
                self.get_variable(KV_READ, "rows"), q, v, s_ring, s_self,
                scale, ring_base.value, ring_v.value,
                cache_k.value, cache_v.value,
                *(None if sc is None else sc.value for sc in (scale_k, scale_v)),
                dtype=self.dtype,
                turned=_tpu_keeps_rows_minor(*cache_k.value.shape[2:]))
        else:
            scores = jnp.concatenate(
                [s_past, s_ring, s_self[..., None]], axis=-1) / scale
            probs = jax.nn.softmax(scores, axis=-1)
            p_dt = probs.astype(self.dtype)
            out = (
                big_v_apply(probs[..., : self.cache_size])
                + jnp.einsum("bhst,bhtd->bhsd",
                             p_dt[..., self.cache_size: self.cache_size + T],
                             ring_v.value, preferred_element_type=jnp.float32)
                + probs[..., self.cache_size + T:].astype(jnp.float32) * v
            )
        # append by select, not dynamic_update_slice (see ``decode_block``);
        # t is in 0..T-1 inside a block, so exactly one row is replaced
        here = (jnp.arange(T) == t)[None, None, :, None]
        ring_k.value = jnp.where(here, k, ring_k.value)
        ring_v.value = jnp.where(here, v, ring_v.value)
        cursor.value = idx + 1
        return out.astype(q.dtype)


class Block(nn.Module):
    d_model: int
    n_heads: int
    d_ff: int
    dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    rope: bool = False
    decode_block: int = 0
    kv_quant: bool = False
    fused_qkv: bool = False

    @nn.compact
    def __call__(self, x, positions=None):
        h = nn.LayerNorm(dtype=self.dtype)(x)
        x = x + MultiHeadAttention(
            self.d_model, self.n_heads, self.dtype, self.attn_fn,
            decode=self.decode, cache_size=self.cache_size, rope=self.rope,
            decode_block=self.decode_block, kv_quant=self.kv_quant,
            fused_qkv=self.fused_qkv, name="attn",
        )(h, positions)
        h = nn.LayerNorm(dtype=self.dtype)(x)
        h = nn.Dense(self.d_ff, dtype=self.dtype)(h)
        h = nn.gelu(h)
        x = x + nn.Dense(self.d_model, dtype=self.dtype)(h)
        return x


class TransformerLM(nn.Module):
    """Causal LM over token ids; ``positions`` carries global positions so the
    sequence axis can be sharded (each device passes its chunk's offsets)."""

    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 6
    d_ff: int = 2048
    max_len: int = 131072
    dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0
    kv_quant: bool = False
    fused_qkv: bool = False
    remat: bool = False
    pos_encoding: str = "learned"  # "learned" (table) | "rope" (rotary in-attn)
    #: head=False returns the post-LayerNorm hidden states instead of
    #: logits — the entry point for sequence-chunked losses that must not
    #: materialize the full (batch, seq, vocab) logits tensor at long
    #: context (training/trainer.chunked_lm_loss); the lm_head params stay
    #: in the tree (flax ignores unused subtrees) and are applied by the
    #: chunked loss itself
    head: bool = True

    @nn.compact
    def __call__(self, tokens, positions=None):
        if self.pos_encoding not in ("learned", "rope"):
            raise ValueError(f"unknown pos_encoding {self.pos_encoding!r}")
        use_rope = self.pos_encoding == "rope"
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])[None, :]
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="tok_embed")(tokens)
        if not use_rope:
            x = x + nn.Embed(self.max_len, self.d_model, dtype=self.dtype, name="pos_embed")(positions)
        # remat: recompute each block's intra-block intermediates (attention
        # scores, d_ff tensors) in the backward pass instead of keeping them
        # in HBM; only the n_layers block-boundary residuals stay resident —
        # the standard long-context trade of FLOPs for HBM (jax.checkpoint
        # per block)
        block_cls = nn.remat(Block) if self.remat and not self.decode else Block
        for i in range(self.n_layers):
            x = block_cls(
                self.d_model, self.n_heads, self.d_ff, self.dtype, self.attn_fn,
                decode=self.decode, cache_size=self.cache_size, rope=use_rope,
                decode_block=self.decode_block, kv_quant=self.kv_quant,
                fused_qkv=self.fused_qkv, name=f"block_{i}",
            )(x, positions)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        if not self.head:
            return x
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")(x)


@cache
def _pooled_read(dtype, turned):
    """:func:`_bounded_read` with a batching rule of its own, built once for
    each static pair, so that the ``jax.jit`` of ``bounded_cache_attention``
    keeps one trace for all of a model's layers.

    ``serving/cache.SlotKVPool`` maps the model over its slots, so a layer
    sees one lane, and a loop over chunks can stop at one bound for the pool
    alone: its longest active slot's, for every slot, idle ones too. Mapped
    over lanes, the rule is handed every lane's operands at once, the
    ``ring_base`` of each among them, and reads each slot's big cache as far
    as its own ring base (rounded up to the kernel's row block) and no
    further, by one kernel for the pool: a slot of length 0, as the pool
    leaves an idle one, reads nothing, and ``bound`` is not read. Where the
    kernel cannot run (no TPU, outside the tests' interpret mode) or would not
    fit the caches (int8 ones: it takes no scales), the rule maps the loop
    over the lanes as ``vmap``'s own rule would. (``custom_vmap`` has no
    reverse-mode rule; nobody differentiates a decode step.)"""
    loop = partial(_bounded_read, dtype=dtype, turned=turned)
    read = jax.custom_batching.custom_vmap(loop)

    @read.def_vmap
    def over_lanes(lanes, in_batched, *args):
        scale, ring_base, scale_k = args[5], args[6], args[10]
        if scale_k is not None or in_batched[5] or not kernel_runs_here():
            return jax.vmap(loop, in_axes=[0 if b else None for b in in_batched])(*args), True
        q, v, s_ring, s_self, ring_v, cache_k, cache_v = (
            a if batched else jnp.broadcast_to(a, (lanes,) + a.shape)
            for a, batched in zip(args[1:5] + args[7:10], in_batched[1:5] + in_batched[7:10]))
        b = q.shape[1]  # sequences a lane
        flat = lambda a: a.reshape((lanes * b,) + a.shape[2:])
        lengths = jnp.repeat(jnp.broadcast_to(ring_base, (lanes,)), b)
        out = loop(args[0], *map(flat, (q, v, s_ring, s_self)), scale, None,
                   *map(flat, (ring_v, cache_k, cache_v)), None, None, lengths=lengths)
        return out.reshape((lanes, b) + out.shape[1:]), True

    return read
