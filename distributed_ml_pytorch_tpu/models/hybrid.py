"""Hybrid decoder LM: linear-attention (gated delta rule) layers among
full-attention ones, the pattern given layer by layer (``layer_types``).

The second model class beside ``TransformerLM``, built from the keys of a
published ``config.json`` (:meth:`HybridLM.from_config`, ``model_type``
``olmo_hybrid``). Per token ``x``::

    x <- x + RMSNorm(mixer(x))                      # the norm is on the branch's OUTPUT
    x <- x + RMSNorm(W_down(SiLU(W_gate x) * (W_up x)))

then one RMSNorm and an untied head. No position encoding: the recurrent
layers order the tokens.

- A ``full_attention`` layer's mixer IS ``models/transformer.MultiHeadAttention``
  (module name ``attn``) with ``qk_norm``: its K/V cache, ring and merge are the
  ones ``SlotKVPool`` and ``generate()`` already drive, found by name.
- A ``linear_attention`` layer's mixer is :class:`GatedDeltaNet` (module name
  ``gdn``): q, k and v go through a short causal convolution and SiLU, q and k
  are normalised per head, and ``ops/gated_delta.py`` runs the rule. With
  ``decode=True`` its ``"cache"`` variables are what a sequence's past comes
  to: ``state`` (float32, ``value x key`` a head), ``conv_tail`` (the
  convolution's last inputs) and ``prefill_len``. As in the attention module,
  a call of one token is a decode step and a longer one a prefill.

**Padded prefills.** Attention hides a prompt's right-padding behind its
causal mask; a recurrence has no mask. A caller that pads sets the cache leaf
``prefill_len`` to the prompt's true length before the call
(``serving/cache._admit_jit`` does, by name): positions at or past it leave
``state`` as it is and ``conv_tail`` holds the last REAL inputs. Left at 0
(``generate()``, which never pads) the whole call counts. The mixer consumes
the leaf: it is 0 again after the call.
"""

from __future__ import annotations

from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from distributed_ml_pytorch_tpu.models.transformer import MultiHeadAttention
from distributed_ml_pytorch_tpu.ops import gated_delta

LAYER_KINDS = ("linear_attention", "full_attention")


def causal_conv(x, tail, weights, n_valid=None):
    """Depthwise causal convolution over time, then SiLU. ``x``: ``(batch,
    s, channels)``; ``tail``: the ``width - 1`` inputs before ``x`` (zeros at a
    sequence's start); ``weights``: ``(width, channels)``, the last row on the
    current token. Returns ``(y, tail)``: ``y`` float32, and the inputs that
    precede position ``n_valid`` (``s`` when left out), so that a padded call
    keeps the last REAL ones."""
    with jax.named_scope("gdn/conv"):
        width, s = weights.shape[0], x.shape[1]
        ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
        y = sum(ext[:, j:j + s].astype(jnp.float32) * weights[j].astype(jnp.float32)
                for j in range(width))
        end = s if n_valid is None else n_valid
        return nn.silu(y), jax.lax.dynamic_slice_in_dim(ext, end, width - 1, axis=1)


def l2_normalize(x, eps: float = 1e-6):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + eps)


def _decay_init(key, shape, dtype=jnp.float32):
    """``A = log U(1, 16)``, the rule's public initialiser."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``dt = exp(U(log 0.001, log 0.1))`` stored through the inverse of
    softplus: with ``A`` above, ``alpha`` starts between about 0.2 and 0.999."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(0.001), jnp.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer (equations in ``ops/gated_delta.py``)."""

    d_model: int
    n_heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    decode: bool = False

    @nn.compact
    def __call__(self, x):
        b, s, _ = x.shape
        h, dk, dv = self.n_heads, self.key_dim, self.value_dim
        dense = lambda name, n, dt=self.dtype: nn.Dense(n, use_bias=False, dtype=dt, name=name)
        sizes = {"q": h * dk, "k": h * dk, "v": h * dv}
        qkv = jnp.concatenate([dense(n, sizes[n])(x) for n in "qkv"], axis=-1)
        conv_init = nn.initializers.normal(0.3)
        weights = jnp.concatenate(
            [self.param(f"conv_{n}", conv_init, (self.conv_kernel, sizes[n])) for n in "qkv"],
            axis=-1)
        # the gates in float32: alpha = exp(-exp(A) softplus(.)) sits within
        # 1e-4 of 1 where a trained model remembers longest
        a_log = self.param("A_log", _decay_init, (h,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
        log_alpha = -jnp.exp(a_log) * jax.nn.softplus(dense("a", h, jnp.float32)(x) + dt_bias)
        beta = jax.nn.sigmoid(dense("b", h, jnp.float32)(x))
        if self.allow_neg_eigval:
            beta = 2.0 * beta

        ch = qkv.shape[-1]
        n_valid = None
        if self.decode:
            state = self.variable("cache", "state", jnp.zeros, (b, h, dv, dk), jnp.float32)
            tail = self.variable("cache", "conv_tail", jnp.zeros,
                                 (b, self.conv_kernel - 1, ch), self.dtype)
            prefill_len = self.variable(
                "cache", "prefill_len", lambda: jnp.zeros((), jnp.int32))
            state0, tail0 = state.value, tail.value
            if s != 1:
                n_valid = jnp.where(prefill_len.value > 0, prefill_len.value, s)
        else:
            state0 = jnp.zeros((b, h, dv, dk), jnp.float32)
            tail0 = jnp.zeros((b, self.conv_kernel - 1, ch), qkv.dtype)

        mixed, new_tail = causal_conv(qkv, tail0, weights, n_valid)
        heads = lambda t, d: t.reshape(b, s, h, d)
        q = l2_normalize(heads(mixed[..., :h * dk], dk)) * dk ** -0.5
        k = l2_normalize(heads(mixed[..., h * dk:2 * h * dk], dk))
        v = heads(mixed[..., 2 * h * dk:], dv)
        if self.decode and s == 1:
            o, new_state = gated_delta.gated_delta_step(
                q[:, 0], k[:, 0], v[:, 0], log_alpha[:, 0], beta[:, 0], state0)
            o = o[:, None]
        else:
            o, new_state = gated_delta.gated_delta_chunked(
                q, k, v, log_alpha, beta, state0, n_valid)
        if self.decode:
            state.value, tail.value = new_state, new_tail.astype(self.dtype)
            prefill_len.value = jnp.zeros((), jnp.int32)

        gate = heads(dense("g", h * dv)(x), dv)
        with jax.named_scope("gdn/gate_norm"):
            o = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="norm")(o)
            o = (o * nn.silu(gate)).reshape(b, s, h * dv)
        return dense("o", self.d_model)(o)


class GatedFFN(nn.Module):
    d_model: int
    d_ff: int
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        dense = lambda name, n: nn.Dense(n, use_bias=False, dtype=self.dtype, name=name)
        return dense("down", self.d_model)(
            nn.silu(dense("gate", self.d_ff)(x)) * dense("up", self.d_ff)(x))


class HybridBlock(nn.Module):
    """One layer: the mixer its ``kind`` names (module ``attn`` or ``gdn``),
    then the gated FFN, each branch's output normed before it is added."""

    kind: str
    d_model: int
    n_heads: int
    d_ff: int
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0

    @nn.compact
    def __call__(self, x, positions=None):
        norm = lambda name: nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name=name)
        if self.kind == "full_attention":
            mixed = MultiHeadAttention(
                self.d_model, self.n_heads, self.dtype, self.attn_fn, decode=self.decode,
                cache_size=self.cache_size, decode_block=self.decode_block,
                qk_norm=True, norm_eps=self.norm_eps, name="attn")(x, positions)
        elif self.kind == "linear_attention":
            mixed = GatedDeltaNet(
                self.d_model, self.linear_heads, self.linear_key_dim, self.linear_value_dim,
                self.conv_kernel, self.allow_neg_eigval, self.norm_eps, self.dtype,
                decode=self.decode, name="gdn")(x)
        else:
            raise ValueError(f"layer kind {self.kind!r}; known: {LAYER_KINDS}")
        x = x + norm("mixer_norm")(mixed)
        return x + norm("mlp_norm")(GatedFFN(self.d_model, self.d_ff, self.dtype, name="mlp")(x))


class HybridLM(nn.Module):
    """Causal LM over token ids whose layers differ by kind. The fields the
    decode paths clone (``decode``, ``cache_size``, ``decode_block``,
    ``attn_fn``) and the call ``(tokens, positions=None)`` are
    ``TransformerLM``'s; ``positions`` is accepted and unused."""

    vocab_size: int = 32000
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 1536
    layer_types: Tuple[str, ...] = LAYER_KINDS
    linear_heads: int = 8
    linear_key_dim: int = 48
    linear_value_dim: int = 96
    conv_kernel: int = 4
    allow_neg_eigval: bool = True
    norm_eps: float = 1e-6
    max_len: int = 65536
    dtype: jnp.dtype = jnp.float32
    attn_fn: Optional[Callable] = None
    decode: bool = False
    cache_size: int = 0
    decode_block: int = 0

    @classmethod
    def from_config(cls, cfg: dict, **kw) -> "HybridLM":
        """The model a published ``config.json`` describes, its keys as they
        are spelt there; what this class cannot run raises."""
        kinds = tuple(cfg["layer_types"])
        want = {"hidden_act": "silu", "attention_bias": False, "tie_word_embeddings": False,
                "num_key_value_heads": cfg["num_attention_heads"],
                "linear_num_value_heads": cfg["linear_num_key_heads"],
                "num_hidden_layers": len(kinds)}
        for key, value in want.items():
            if cfg.get(key, value) != value:
                raise ValueError(
                    f"HybridLM runs {key}={value!r}, the configuration says {cfg[key]!r}")
        if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise ValueError("HybridLM has no rotary position encoding (rope_theta must be null)")
        if cfg["hidden_size"] % cfg["num_attention_heads"]:
            raise ValueError("hidden_size must divide into num_attention_heads")
        return cls(
            vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"], d_ff=cfg["intermediate_size"],
            layer_types=kinds, linear_heads=cfg["linear_num_key_heads"],
            linear_key_dim=cfg["linear_key_head_dim"],
            linear_value_dim=cfg["linear_value_head_dim"],
            conv_kernel=cfg["linear_conv_kernel_dim"],
            allow_neg_eigval=bool(cfg.get("linear_allow_neg_eigval", False)),
            norm_eps=cfg["rms_norm_eps"], max_len=cfg["max_position_embeddings"], **kw)

    @nn.compact
    def __call__(self, tokens, positions=None):
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="tok_embed")(tokens)
        for i, kind in enumerate(self.layer_types):
            x = HybridBlock(
                kind, self.d_model, self.n_heads, self.d_ff, self.linear_heads,
                self.linear_key_dim, self.linear_value_dim, self.conv_kernel,
                self.allow_neg_eigval, self.norm_eps, self.dtype, self.attn_fn,
                decode=self.decode, cache_size=self.cache_size,
                decode_block=self.decode_block, name=f"layer_{i}")(x, positions)
        x = nn.RMSNorm(epsilon=self.norm_eps, dtype=self.dtype, name="final_norm")(x)
        return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype, name="lm_head")(x)
