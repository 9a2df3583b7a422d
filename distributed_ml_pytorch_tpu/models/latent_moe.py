"""Decoder LM with latent attention and dropless routed experts.

The third model class beside ``TransformerLM`` and ``HybridLM``, built from the
keys of a published ``config.json`` (:meth:`LatentMoELM.from_config`,
``model_type`` ``deepseek_v3``). Per token ``x``::

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))

then one RMSNorm and an untied head; no biases. The first
``first_k_dense`` layers' FFN is ``models/hybrid.GatedFFN``, the others'
``models/moe.DroplessExperts`` (module ``moe``).

**Latent attention** (:class:`LatentAttention`, module ``mla``). A token's
keys and values of every head come from ONE row: ``a = W_kva x`` (``latent +
rope`` wide), ``c = RMSNorm(a[:latent])``, ``r = RoPE(a[latent:])``; head ``h``
has ``k_h = [W_UK,h c ; r]`` and ``v_h = W_UV,h c``. Two forms of one
mathematics:

- *expanded* (a call of more than one token: a prefill, or no cache at all):
  per-head K and V are made from ``c`` and causal attention runs over the
  call's own rows, a block of query rows at a time
  (:func:`causal_attention_in_blocks`; query and key heads are ``nope + rope``
  wide, value heads ``v`` wide, which ``ops/attention.py``'s kernel does not
  take, and no ``[heads, s, cache]`` score plane is built);
- *absorbed* (a decode step): ``W_UK`` is folded into the query (``qt_h =
  W_UK,h^T q_nope,h``) and ``W_UV`` into the output, so the step is
  multi-query attention of all heads over ONE shared key ``[c ; r]`` whose
  first ``latent`` lanes are also the value, and reads the cached rows only.

With ``decode=True`` the ``"cache"`` variables are ``cached_latent`` (``[b, 1,
cache_size, latent + rope]``, one row a token: 576 values where per-head K/V
would be 10,240 at the published sizes), ``ring_latent`` and the cursors of
``models/transformer.MultiHeadAttention``'s ring protocol, under the same
names: appends go to the ring by a select, the caller merges the ring into
the big cache once a block (``models/generate.merge_ring_caches``), and a
caller that says how far anybody's cache is live (``kv_read/rows``) gets a
read that stops there (:func:`bounded_latent_attention`). A prefill must
start at cursor 0 (it attends to its own rows only), which every caller here
satisfies (``generate()``, ``SlotKVPool``'s fresh lanes).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_ml_pytorch_tpu.models.hybrid import GatedFFN
from distributed_ml_pytorch_tpu.models.moe import DroplessExperts
from distributed_ml_pytorch_tpu.models.transformer import KV_READ, kv_read_chunk

#: query rows a block of the expanded attention takes at a time
ATTN_BLOCK_ROWS = 512


def apply_rope_interleaved(x, positions, base: float):
    """RoPE over the last axis with the rotated pairs ``(2i, 2i + 1)`` at
    angle ``t * base^(-2i / dim)`` (``rope_interleave``). ``x``: ``(b, s, ...,
    dim)``; ``positions``: ``(b, s)`` or ``(1, s)``. Float32 inside."""
    dim = x.shape[-1]
    freqs = base ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angles = positions[..., None].astype(jnp.float32) * freqs        # (b, s, dim/2)
    angles = angles.reshape(angles.shape[:2] + (1,) * (x.ndim - 3) + angles.shape[2:])
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (dim // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


def causal_attention_in_blocks(q, k, v, scale, block: int = ATTN_BLOCK_ROWS):
    """Causal self-attention, ``q``/``k`` ``(b, h, s, dq)`` and ``v`` ``(b, h,
    s, dv)`` with ``dq != dv`` allowed, a block of query rows at a time: block
    ``i`` scores against the keys up to its own end and no further, so the
    largest score plane is ``block x s`` a head and the blocks above the
    diagonal are never computed. Softmax in float32."""
    s = q.shape[2]
    out = []
    for start in range(0, s, block):
        end = min(start + block, s)
        sc = jnp.einsum("bhqd,bhkd->bhqk", q[:, :, start:end], k[:, :, :end],
                        preferred_element_type=jnp.float32) * scale
        seen = (start + jnp.arange(end - start))[:, None] >= jnp.arange(end)[None, :]
        p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
        out.append(jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v[:, :, :end],
                              preferred_element_type=jnp.float32))
    return jnp.concatenate(out, axis=2)


# jitted so that a model's layers share one trace of the loop
# (``models/transformer.bounded_cache_attention`` has the measurement)
@partial(jax.jit, static_argnames=("latent", "dtype"))
def bounded_latent_attention(bound, q, s_ring, ring, ring_base, cache, scale, *,
                             latent, dtype):
    """A decode step's absorbed attention over ring and big cache, the cache
    read only as far as ``bound`` rows, ``kv_read_chunk`` rows at a time (the
    loop of ``bounded_cache_attention`` over one shared key). ``q``: ``(b, h,
    latent + rope)``; ``s_ring``: ``(b, h, T)`` scaled scores against the ring,
    masked, the fresh row among them (so never empty); ``ring``: ``(b, T,
    latent + rope)``; ``cache``: ``(b, rows, latent + rope)``. The softmax runs
    online in float32; per sequence ``key_pos < ring_base`` hides what lies
    between its own length and the bound. Returns ``(b, h, latent)``
    float32: the weighted sum of the rows' first ``latent`` lanes."""
    rows = cache.shape[1]
    chunk = kv_read_chunk(rows)
    m = jnp.max(s_ring, axis=-1)
    p = jnp.exp(s_ring - m[..., None])
    total = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bht,btc->bhc", p.astype(dtype), ring[..., :latent],
                     preferred_element_type=jnp.float32)
    unseen = jnp.where(jnp.arange(rows)[None, :] < ring_base.reshape(-1, 1), 0.0, -jnp.inf)
    ragged = rows % chunk != 0  # the last chunk then starts early: its overlap must not count

    def read_chunk(i, carry):
        m, total, acc = carry
        first = i * chunk
        start = jnp.minimum(first, rows - chunk) if ragged else first
        kv = jax.lax.dynamic_slice_in_dim(cache, start, chunk, axis=1)
        sc = jnp.einsum("bhd,bcd->bhc", q, kv, preferred_element_type=jnp.float32) * scale
        sc = sc + jax.lax.dynamic_slice_in_dim(unseen, start, chunk, axis=1)[:, None, :]
        if ragged:
            sc = jnp.where(start + jnp.arange(chunk) >= first, sc, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
        shrink = jnp.exp(m - m_new)
        w = jnp.exp(sc - m_new[..., None])
        total = total * shrink + jnp.sum(w, axis=-1)
        acc = acc * shrink[..., None] + jnp.einsum(
            "bhc,bcd->bhd", w.astype(dtype), kv[..., :latent],
            preferred_element_type=jnp.float32)
        return m_new, total, acc

    _, total, acc = jax.lax.fori_loop(
        0, (bound + chunk - 1) // chunk, read_chunk, (m, total, acc))
    return acc / total[..., None]


class LatentAttention(nn.Module):
    """The latent-attention mixer (module docstring)."""

    d_model: int
    n_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    latent_dim: int
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0

    @nn.compact
    def __call__(self, x, positions):
        b, s, _ = x.shape
        h, dn, dr, dv, dc = (self.n_heads, self.nope_dim, self.rope_dim, self.v_dim,
                             self.latent_dim)
        dense = lambda name, n: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        scale = (dn + dr) ** -0.5
        q = dense("q", h * (dn + dr))(x).reshape(b, s, h, dn + dr)
        q_nope = q[..., :dn]
        q_rope = apply_rope_interleaved(q[..., dn:], positions, self.rope_theta)
        a = dense("kv_a", dc + dr)(x)
        c = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="kv_norm")(a[..., :dc])
        r = apply_rope_interleaved(a[..., dc:], positions, self.rope_theta)
        # head h: columns [h * (dn + dv), ...) hold W_UK,h then W_UV,h
        w_kvb = self.param("kv_b", nn.initializers.lecun_normal(), (dc, h * (dn + dv)))
        w_kvb = w_kvb.astype(self.dtype).reshape(dc, h, dn + dv)
        w_uk, w_uv = w_kvb[..., :dn], w_kvb[..., dn:]
        row = jnp.concatenate([c, r], axis=-1).astype(self.dtype)    # (b, s, dc + dr)

        if self.decode and s == 1:
            with jax.named_scope("mla/absorb"):
                qt = jnp.einsum("bhn,chn->bhc", q_nope[:, 0], w_uk,
                                preferred_element_type=jnp.float32)
                qa = jnp.concatenate([qt.astype(self.dtype), q_rope[:, 0]], axis=-1)
                u = self._cached_step(qa, row[:, 0], scale)           # (b, h, dc) float32
                o = jnp.einsum("bhc,chv->bhv", u.astype(self.dtype), w_uv,
                               preferred_element_type=jnp.float32)
            o = o.astype(self.dtype).reshape(b, 1, h * dv)
        else:
            if self.decode:
                self._write_prefill(row)
            with jax.named_scope("mla/expand"):
                kv = jnp.einsum("bsc,chd->bhsd", c, w_kvb)
                k = jnp.concatenate(
                    [kv[..., :dn], jnp.broadcast_to(r[:, None], (b, h, s, dr))], axis=-1)
                qe = jnp.concatenate([q_nope, q_rope], axis=-1).transpose(0, 2, 1, 3)
                o = causal_attention_in_blocks(qe, k, kv[..., dn:], scale)
            o = o.astype(self.dtype).transpose(0, 2, 1, 3).reshape(b, s, h * dv)
        return dense("o", self.d_model)(o)

    # ------------------------------------------------------------ the cache
    def _cache_vars(self, b):
        if self.cache_size < 1:
            raise ValueError("decode=True needs cache_size > 0")
        width = self.latent_dim + self.rope_dim
        cache = self.variable("cache", "cached_latent", jnp.zeros,
                              (b, 1, self.cache_size, width), self.dtype)
        cursor = self.variable("cache", "cursor", lambda: jnp.zeros((), jnp.int32))
        ring = ring_base = None
        if self.decode_block > 0:
            ring = self.variable("cache", "ring_latent", jnp.zeros,
                                 (b, 1, self.decode_block, width), self.dtype)
            ring_base = self.variable("cache", "ring_base", lambda: jnp.zeros((), jnp.int32))
        return cache, cursor, ring, ring_base

    def _write_prefill(self, row):
        """A prefill's rows go straight to the big cache at the cursor (0: the
        expanded attention that follows sees this call's rows only)."""
        cache, cursor, _ring, ring_base = self._cache_vars(row.shape[0])
        idx = cursor.value
        cache.value = jax.lax.dynamic_update_slice(cache.value, row[:, None], (0, 0, idx, 0))
        cursor.value = idx + row.shape[1]
        if ring_base is not None:
            ring_base.value = idx + row.shape[1]

    def _cached_step(self, qa, row, scale):
        """One token's absorbed attention over the cached rows and its own:
        ``qa`` ``(b, h, latent + rope)``, ``row`` ``(b, latent + rope)``;
        returns the weighted latent ``(b, h, latent)`` in float32 and appends
        the row (to the ring by a select over its rows, or, with no ring, to
        the big cache at the cursor)."""
        b = qa.shape[0]
        cache, cursor, ring, ring_base = self._cache_vars(b)
        idx = cursor.value
        cursor.value = idx + 1
        if ring is None:
            # no ring: the row is written first and the read is the whole cache
            cache.value = jax.lax.dynamic_update_slice(
                cache.value, row[:, None, None], (0, 0, idx, 0))
            rows = cache.value[:, 0]
            sc = jnp.einsum("bhd,bcd->bhc", qa, rows, preferred_element_type=jnp.float32) * scale
            sc = jnp.where((jnp.arange(self.cache_size) <= idx)[None, None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            return jnp.einsum("bhc,bcd->bhd", p.astype(self.dtype),
                              rows[..., :self.latent_dim], preferred_element_type=jnp.float32)
        T = self.decode_block
        t = idx - ring_base.value  # place in the current block, 0..T-1
        ring.value = jnp.where((jnp.arange(T) == t)[None, None, :, None],
                               row[:, None, None], ring.value)
        s_ring = jnp.einsum("bhd,btd->bht", qa, ring.value[:, 0],
                            preferred_element_type=jnp.float32) * scale
        s_ring = jnp.where((jnp.arange(T) <= t)[None, None], s_ring, -jnp.inf)
        bound = (self.get_variable(KV_READ, "rows") if self.has_variable(KV_READ, "rows")
                 else cache.value.shape[2])
        return bounded_latent_attention(
            bound, qa, s_ring, ring.value[:, 0], ring_base.value, cache.value[:, 0], scale,
            latent=self.latent_dim, dtype=self.dtype)


class LatentMoEBlock(nn.Module):
    """One layer: latent attention, then a dense gated FFN (``dense``) or the
    routed experts, each on the RMSNorm of its input."""

    dense: bool
    d_model: int
    n_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    latent_dim: int
    d_ff: int
    d_expert: int
    n_experts: int
    top_k: int
    n_shared: int
    routed_scale: float
    rope_theta: float
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0

    @nn.compact
    def __call__(self, x, positions):
        norm = lambda name: nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name=name)
        x = x + LatentAttention(
            self.d_model, self.n_heads, self.nope_dim, self.rope_dim, self.v_dim,
            self.latent_dim, self.rope_theta, self.norm_eps, self.dtype, decode=self.decode,
            cache_size=self.cache_size, decode_block=self.decode_block,
            name="mla")(norm("attn_norm")(x), positions)
        h = norm("ffn_norm")(x)
        if self.dense:
            return x + GatedFFN(self.d_model, self.d_ff, self.dtype, name="mlp")(h)
        return x + DroplessExperts(
            self.d_model, self.d_expert, self.n_experts, self.top_k, self.n_shared,
            self.routed_scale, self.dtype, decode=self.decode, name="moe")(h)


class LatentMoELM(nn.Module):
    """Causal LM over token ids. The fields the decode paths clone
    (``decode``, ``cache_size``, ``decode_block``, ``attn_fn``) and the call
    ``(tokens, positions=None)`` are ``TransformerLM``'s; ``attn_fn`` is
    accepted and unused (the mixer attends by its own two forms)."""

    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    n_layers: int = 4
    first_k_dense: int = 1
    d_ff: int = 1536
    d_expert: int = 192
    n_experts: int = 16
    top_k: int = 2
    n_shared: int = 1
    routed_scale: float = 1.0
    nope_dim: int = 32
    rope_dim: int = 16
    v_dim: int = 32
    latent_dim: int = 128
    rope_theta: float = 10000.0
    norm_eps: float = 1e-6
    max_len: int = 32768
    pos_encoding: str = "rope"  # rotary inside the mixer: no position table bounds a cache
    dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0

    @classmethod
    def from_config(cls, cfg: dict, **kw) -> "LatentMoELM":
        """The model a published ``config.json`` describes, its keys as they
        are spelt there; what this class cannot run raises. ``n_group =
        topk_group = 1`` makes the router's group limit keep its one group: a
        no-op that is not built."""
        want = {"hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False,
                "q_lora_rank": None, "rope_scaling": None, "rope_interleave": True,
                "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
                "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
                "num_key_value_heads": cfg["num_attention_heads"],
                "qk_head_dim": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]}
        for key, value in want.items():
            if cfg.get(key, value) != value:
                raise ValueError(
                    f"LatentMoELM runs {key}={value!r}, the configuration says {cfg[key]!r}")
        return cls(
            vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"], n_layers=cfg["num_hidden_layers"],
            first_k_dense=cfg["first_k_dense_replace"], d_ff=cfg["intermediate_size"],
            d_expert=cfg["moe_intermediate_size"], n_experts=cfg["n_routed_experts"],
            top_k=cfg["num_experts_per_tok"], n_shared=cfg["n_shared_experts"],
            routed_scale=cfg["routed_scaling_factor"], nope_dim=cfg["qk_nope_head_dim"],
            rope_dim=cfg["qk_rope_head_dim"], v_dim=cfg["v_head_dim"],
            latent_dim=cfg["kv_lora_rank"], rope_theta=float(cfg["rope_theta"]),
            norm_eps=cfg["rms_norm_eps"], max_len=cfg["max_position_embeddings"], **kw)

    @nn.compact
    def __call__(self, tokens, positions=None):
        if positions is None:
            positions = jnp.arange(tokens.shape[-1])[None, :]
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="tok_embed")(tokens)
        for i in range(self.n_layers):
            x = LatentMoEBlock(
                i < self.first_k_dense, self.d_model, self.n_heads, self.nope_dim,
                self.rope_dim, self.v_dim, self.latent_dim, self.d_ff, self.d_expert,
                self.n_experts, self.top_k, self.n_shared, self.routed_scale, self.rope_theta,
                self.norm_eps, self.dtype, decode=self.decode, cache_size=self.cache_size,
                decode_block=self.decode_block, name=f"layer_{i}")(x, positions)
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="final_norm")(x)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")(x)
