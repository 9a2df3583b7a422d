"""Plain reference for the hybrid decoder of ``configs/olmo-hybrid-7b.json``:
gated-delta-rule linear-attention layers among full-attention ones.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernels, no cache, no batching, and NOT the program's algorithm
for the recurrence: the delta rule here is the token-by-token recurrence under
``lax.scan``, where the program prefills in chunks. It imports nothing of the
program and is given nothing the program made; its weights come from
:func:`make_params`, which the driver also hands to the program (the tree's
layout is ``models/hybrid.HybridLM``'s).

Per token ``x_t`` (what the published keys do not spell is listed under
``assumed`` in the configuration file)::

    x <- x + RMSNorm(mixer(x));  x <- x + RMSNorm(W_down(SiLU(W_gate x) * (W_up x)))

``full_attention``: ``q = RMSNorm(W_q x)``, ``k = RMSNorm(W_k x)`` over the
whole projection, ``v = W_v x``; heads of ``hidden / heads``; causal softmax;
``W_o``; no position encoding. ``linear_attention``: ``W_q x``, ``W_k x``,
``W_v x`` each through a causal depthwise convolution (a plain sum over the
shifted copies) and SiLU; per head ``q / |q| / sqrt(key)``, ``k / |k|``;
``beta = 2 sigmoid(w_b x)``, ``alpha = exp(-exp(A) softplus(w_a x + dt))``;
``S' = alpha S``, ``S = S' + beta (v - S' k) k^T``, ``o = S q``; ``W_o`` over
``RMSNorm(o) * SiLU(W_g x)``. After the last layer one RMSNorm and the head.

Weights are kept in the dtype they were made in (bfloat16 on the chip) and
raised to float32 one layer at a time, so that four billion parameters fit
beside the activations.

The control of "How ``correct`` is decided" is this same code with ``quant``
set: every matmul (projections, gates, MLP, head, QK^T and PV, the state's
two products) takes its operands rounded to int8 with one scale per row, the
precision below the bfloat16 the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02
CONV_STD = 0.3
L2_EPS = 1e-6


# ----------------------------------------------------------------- weights
def param_shapes(cfg: dict) -> dict:
    """The parameter tree's shapes, in the layout ``HybridLM`` uses."""
    d, ff, vocab = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    h, dk, dv = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    kernel = lambda i, o: {"kernel": (i, o)}
    attn = {"q": kernel(d, d), "k": kernel(d, d), "v": kernel(d, d), "o": kernel(d, d),
            "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)}}
    gdn = {"q": kernel(d, h * dk), "k": kernel(d, h * dk), "v": kernel(d, h * dv),
           "g": kernel(d, h * dv), "o": kernel(h * dv, d), "a": kernel(d, h), "b": kernel(d, h),
           "conv_q": (width, h * dk), "conv_k": (width, h * dk), "conv_v": (width, h * dv),
           "A_log": (h,), "dt_bias": (h,), "norm": {"scale": (dv,)}}
    rest = {"mixer_norm": {"scale": (d,)}, "mlp_norm": {"scale": (d,)},
            "mlp": {"gate": kernel(d, ff), "up": kernel(d, ff), "down": kernel(ff, d)}}
    tree = {}
    for i, kind in enumerate(cfg["layer_types"]):
        mixer = {"attn": attn} if kind == "full_attention" else {"gdn": gdn}
        tree[f"layer_{i}"] = {**mixer, **rest}
    tree["tok_embed"] = {"embedding": (vocab, d)}
    tree["final_norm"] = {"scale": (d,)}
    tree["lm_head"] = kernel(d, vocab)
    return tree


def make_params(key, cfg: dict, dtype=jnp.float32):
    """Seeded weights for ``cfg``, one traceable function. Matrices normal
    0.02 (residual projections scaled by ``1 / sqrt(2 layers)``), norm scales
    0.02 around 1, convolutions normal 0.3. The decay's two scalars a head are
    drawn as the rule's public initialiser draws them (``A = log U(1, 16)``,
    ``dt = exp(U(log 0.001, log 0.1))`` stored through the inverse softplus)
    and stay float32: ``alpha`` then lies where trained models put it, the
    state remembers hundreds of tokens, and an error in it is not forgotten
    before the comparison reads it."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))
    resid = INIT_STD / (2.0 * len(cfg["layer_types"])) ** 0.5
    out = []
    for i, (path, shape) in enumerate(leaves):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        if names[-1] == "A_log":
            out.append(jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0)))
            continue
        if names[-1] == "dt_bias":
            dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32, jnp.log(0.001), jnp.log(0.1)))
            out.append(dt + jnp.log(-jnp.expm1(-dt)))
            continue
        std = INIT_STD
        if names[-2:] in (["o", "kernel"], ["down", "kernel"]):
            std = resid
        elif names[-1].startswith("conv_"):
            std = CONV_STD
        x = std * jax.random.normal(k, shape, jnp.float32)
        if names[-1] == "scale":
            x = 1.0 + x
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- arithmetic
def _q8(x, axis):
    """Round to int8 with one absmax scale per row along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


def _mm(x, w, quant):
    return jnp.matmul(_q8(x, -1), _q8(w, -2)) if quant else jnp.matmul(x, w)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _full_attention(x, p, cfg, quant):
    s, d = x.shape
    n = cfg["num_attention_heads"]
    eps = cfg["rms_norm_eps"]
    heads = lambda t: t.reshape(s, n, d // n).transpose(1, 0, 2)
    q = heads(_rms_norm(_mm(x, p["q"]["kernel"], quant), p["q_norm"]["scale"], eps))
    k = heads(_rms_norm(_mm(x, p["k"]["kernel"], quant), p["k_norm"]["scale"], eps))
    v = heads(_mm(x, p["v"]["kernel"], quant))
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) / (d // n) ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = _mm(probs, v, quant).transpose(1, 0, 2).reshape(s, d)
    return _mm(out, p["o"]["kernel"], quant)


def _causal_conv(x, w):
    """``y_t = sum_j w_j x_{t - (width - 1) + j}``, zeros before the start."""
    width, s = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((width - 1, x.shape[1]), x.dtype), x], axis=0)
    return sum(padded[j:j + s] * w[j] for j in range(width))


def _linear_attention(x, p, cfg, quant):
    s, _ = x.shape
    h, dk, dv = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    mixed = lambda n, dim: _silu(_causal_conv(
        _mm(x, p[n]["kernel"], quant), p[f"conv_{n}"])).reshape(s, h, dim)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + L2_EPS)
    q, k, v = unit(mixed("q", dk)) / dk ** 0.5, unit(mixed("k", dk)), mixed("v", dv)
    beta = jax.nn.sigmoid(_mm(x, p["b"]["kernel"], quant))
    if cfg.get("linear_allow_neg_eigval"):
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(
        _mm(x, p["a"]["kernel"], quant) + p["dt_bias"]))
    state_times = lambda S, vec: _mm(S, vec[..., None], quant)[..., 0]     # (h, dv)

    def token(S, t):
        q_t, k_t, v_t, alpha_t, beta_t = t
        S = alpha_t[:, None, None] * S
        S = S + (beta_t[:, None] * (v_t - state_times(S, k_t)))[:, :, None] * k_t[:, None, :]
        return S, state_times(S, q_t)

    _, o = jax.lax.scan(token, jnp.zeros((h, dv, dk), jnp.float32), (q, k, v, alpha, beta))
    gate = _silu(_mm(x, p["g"]["kernel"], quant)).reshape(s, h, dv)
    o = _rms_norm(o, p["norm"]["scale"], cfg["rms_norm_eps"]) * gate
    return _mm(o.reshape(s, h * dv), p["o"]["kernel"], quant)


def _layer(x, p, kind, cfg, quant):
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)  # one layer's weights at a time
    eps = cfg["rms_norm_eps"]
    if kind == "full_attention":
        mixed = _full_attention(x, p["attn"], cfg, quant)
    elif kind == "linear_attention":
        mixed = _linear_attention(x, p["gdn"], cfg, quant)
    else:
        raise ValueError(f"unknown layer kind {kind!r}")
    x = x + _rms_norm(mixed, p["mixer_norm"]["scale"], eps)
    m = p["mlp"]
    ffn = _mm(_silu(_mm(x, m["gate"]["kernel"], quant)) * _mm(x, m["up"]["kernel"], quant),
              m["down"]["kernel"], quant)
    return x + _rms_norm(ffn, p["mlp_norm"]["scale"], eps)


def logits(params, tokens, cfg: dict, quant: bool = False):
    """Logits ``(seq, vocab)`` for one sequence of int tokens."""
    x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
    for i, kind in enumerate(cfg["layer_types"]):
        x = _layer(x, params[f"layer_{i}"], kind, cfg, quant)
    x = _rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"].astype(jnp.float32), quant)


# ---------------------------------------------------------------- serving
def sequence_logits(params, tokens, cfg: dict, quant: bool = False):
    """Logits of one full forward pass over ``tokens`` (a served request's
    prompt followed by what was emitted, padded to a fixed length): row ``i``
    is what token ``i + 1`` was chosen from."""
    with jax.default_matmul_precision("highest"):
        return logits(params, tokens, cfg, quant)


def served_token_stats(params, tokens, cfg: dict, control: bool = False) -> dict:
    """Per position ``i`` of a served sequence: the reference's best logit for
    token ``i + 1`` and the logit of the token that was served there. With
    ``control`` also the logit (the float32 reference's) of the token that the
    int8 pass over the same sequence puts first."""
    lg = sequence_logits(params, tokens, cfg)
    pick = lambda ids: jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
    out = {"best": lg.max(-1), "served": pick(jnp.roll(tokens, -1))}
    if control:
        out["control"] = pick(sequence_logits(params, tokens, cfg, quant=True).argmax(-1))
    return out
