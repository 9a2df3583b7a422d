"""Plain reference for the decoder of ``configs/kanana-2-30b-a3b.json``
(``model_type`` ``deepseek_v3``): latent attention and dropless routed experts.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest``
precision: no kernels, no cache, no batching, and NOT the program's
algorithms. Attention is the EXPANDED form only (per-head keys and values made
from the latent row; the program's decode step never makes them), and the
routed experts are a loop over ALL experts, each applied to every row and
kept by a mask for the rows that chose it (the program sorts rows by expert
and multiplies groups). It imports nothing of the program and is given nothing
the program made; its weights come from :func:`make_params`, which the driver
also hands to the program (the tree's layout is
``models/latent_moe.LatentMoELM``'s).

Per token ``x`` (what the published keys do not spell is listed under
``assumed`` in the configuration file)::

    h = x + Attn(RMSNorm(x));  y = h + FFN(RMSNorm(h))

**Attention**, ``H`` heads: ``q = W_q x`` split a head into ``q_nope`` (128)
and ``q_rope`` (64); ``a = W_kva x``; ``c = RMSNorm(a[:512])``; ``r =
RoPE(a[512:])``, one for all heads; ``W_kvb c`` gives each head ``k_nope``
(128) and ``v`` (128); RoPE rotates the pairs ``(2i, 2i + 1)`` by ``t *
theta^(-2i/64)`` (``rope_interleave``); scores ``(q_nope . k_nope + q_rope .
r) / sqrt(192)``, causal, softmax; ``W_o`` over the heads' ``sum p v``.

**FFN**: layer 0 ``W_down(SiLU(W_gate x) * W_up x)``; the others ``sum_e w_e
E_e(x) + S(x)`` with ``scores = sigmoid(W_g x)``, the chosen six the largest of
``scores + b``, ``w_e = scores_e / (sum of the six + 1e-20) * 2.448``, ``E_e``
a gated SiLU FFN 768 wide and ``S`` one of 1,536. ``n_group = topk_group = 1``:
the group limit keeps its one group and is not built. After the last layer one
RMSNorm and the head.

The pass runs in blocks (query rows of the attention, one expert at a time,
one layer's weights raised to float32 at a time) so that 3,072 tokens fit
beside 8.9 GB of weights.

The control of "How ``correct`` is decided" is this same code with ``quant``
set: every matmul (projections, router, experts, head, QK^T and PV) takes its
operands rounded to int8 with one scale per row, the precision below the
bfloat16 the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

INIT_STD = 0.02
ROUTER_LOGIT_STD = 1.0   # W_g is normal(ROUTER_LOGIT_STD / sqrt(hidden)): logits of order 1
ROUTER_BIAS_STD = 0.005  # b: moves the chosen set of some tokens and not of most
ATTN_BLOCK_ROWS = 1024


# ----------------------------------------------------------------- weights
def param_shapes(cfg: dict) -> dict:
    """The parameter tree's shapes, in the layout ``LatentMoELM`` uses."""
    d, vocab, h = cfg["hidden_size"], cfg["vocab_size"], cfg["num_attention_heads"]
    dn, dr, dv, dc = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"],
                      cfg["kv_lora_rank"])
    e, f, ff = cfg["n_routed_experts"], cfg["moe_intermediate_size"], cfg["intermediate_size"]
    kernel = lambda i, o: {"kernel": (i, o)}
    ffn = lambda width: {"gate": kernel(d, width), "up": kernel(d, width), "down": kernel(width, d)}
    mla = {"q": kernel(d, h * (dn + dr)), "kv_a": kernel(d, dc + dr), "kv_norm": {"scale": (dc,)},
           "kv_b": (dc, h * (dn + dv)), "o": kernel(h * dv, d)}
    moe = {"router": {"kernel": (d, e), "bias": (e,)}, "w_gate": (e, d, f), "w_up": (e, d, f),
           "w_down": (e, f, d), "shared": ffn(cfg["n_shared_experts"] * f)}
    tree = {}
    for i in range(cfg["num_hidden_layers"]):
        rest = {"mlp": ffn(ff)} if i < cfg["first_k_dense_replace"] else {"moe": moe}
        tree[f"layer_{i}"] = {"attn_norm": {"scale": (d,)}, "ffn_norm": {"scale": (d,)},
                              "mla": mla, **rest}
    tree["tok_embed"] = {"embedding": (vocab, d)}
    tree["final_norm"] = {"scale": (d,)}
    tree["lm_head"] = kernel(d, vocab)
    return tree


def make_params(key, cfg: dict, dtype=jnp.float32):
    """Seeded weights for ``cfg``, one traceable function. Matrices normal
    0.02 (residual projections, every expert's among them, scaled by ``1 /
    sqrt(2 layers)``), norm scales 0.02 around 1. The router's ``W_g`` is
    normal ``1 / sqrt(hidden)`` so that its logits are of order 1 and the
    sixth and seventh scores of nearly every token lie further apart than
    rounding; its selection bias ``b`` is normal 0.005 and stays float32 (128
    numbers a layer), like the buffer it is."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=lambda x: isinstance(x, tuple))
    resid = INIT_STD / (2.0 * cfg["num_hidden_layers"]) ** 0.5
    out = []
    for i, (path, shape) in enumerate(leaves):
        names = [p.key for p in path]
        k = jax.random.fold_in(key, i)
        if names[-2:] == ["router", "bias"]:
            out.append(ROUTER_BIAS_STD * jax.random.normal(k, shape, jnp.float32))
            continue
        std = INIT_STD
        if names[-2:] in (["o", "kernel"], ["down", "kernel"]) or names[-1] == "w_down":
            std = resid
        elif names[-2:] == ["router", "kernel"]:
            std = ROUTER_LOGIT_STD / cfg["hidden_size"] ** 0.5
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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rope(x, theta: float):
    """Rotate the pairs ``(2i, 2i + 1)`` of ``x`` ``(s, ..., dim)`` by ``t *
    theta^(-2i / dim)`` at position ``t``."""
    s, dim = x.shape[0], x.shape[-1]
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = angle.reshape((s,) + (1,) * (x.ndim - 2) + (dim // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    rotated = jnp.stack([even * jnp.cos(angle) - odd * jnp.sin(angle),
                         even * jnp.sin(angle) + odd * jnp.cos(angle)], axis=-1)
    return rotated.reshape(x.shape)


def _attention(x, p, cfg, quant):
    s, _ = x.shape
    h, dn, dr, dv, dc = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                         cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    theta = float(cfg["rope_theta"])
    q = _mm(x, p["q"]["kernel"], quant).reshape(s, h, dn + dr)
    a = _mm(x, p["kv_a"]["kernel"], quant)
    c = _rms_norm(a[:, :dc], p["kv_norm"]["scale"], cfg["rms_norm_eps"])
    r = _rope(a[:, dc:], theta)                                        # (s, dr), all heads'
    kv = _mm(c, p["kv_b"], quant).reshape(s, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(r[:, None], (s, h, dr))], axis=-1)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
    q, k, v = (t.transpose(1, 0, 2) for t in (q, k, kv[..., dn:]))      # (h, s, .)
    out = []
    for start in range(0, s, ATTN_BLOCK_ROWS):                         # a block of query rows
        rows = slice(start, min(start + ATTN_BLOCK_ROWS, s))
        scores = _mm(q[:, rows], jnp.swapaxes(k, -1, -2), quant) / (dn + dr) ** 0.5
        causal = jnp.arange(s)[None, :] <= jnp.arange(s)[rows, None]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        out.append(_mm(probs, v, quant))
    out = jnp.concatenate(out, axis=1).transpose(1, 0, 2).reshape(s, h * dv)
    return _mm(out, p["o"]["kernel"], quant)


def _gated_ffn(x, gate, up, down, quant):
    return _mm(_silu(_mm(x, gate, quant)) * _mm(x, up, quant), down, quant)


def route(x, p, cfg, quant: bool = False):
    """``(chosen [s, k] expert ids, weights [s, k])`` of rows ``x``."""
    scores = jax.nn.sigmoid(_mm(x, p["router"]["kernel"].astype(jnp.float32), quant))
    _, chosen = jax.lax.top_k(scores + p["router"]["bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-20) * cfg["routed_scaling_factor"]
    return chosen, weights


def _experts(x, p, cfg, quant):
    """Every expert over every row, one expert at a time, kept by a mask for
    the rows that chose it; the shared expert for every row."""
    chosen, weights = route(x, p, cfg, quant)

    def one_expert(acc, expert):
        e, gate, up, down = expert
        w_e = jnp.sum(jnp.where(chosen == e, weights, 0.0), axis=-1)  # 0 where not chosen
        y = _gated_ffn(x, *(w.astype(jnp.float32) for w in (gate, up, down)), quant)
        return acc + w_e[:, None] * y, None

    ids = jnp.arange(cfg["n_routed_experts"])
    routed, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                             (ids, p["w_gate"], p["w_up"], p["w_down"]))
    s = _f32(p["shared"])
    shared = _gated_ffn(x, s["gate"]["kernel"], s["up"]["kernel"], s["down"]["kernel"], quant)
    return routed + shared, chosen


def _layer(x, p, cfg, quant):
    """One layer; returns ``(y, chosen)``, ``chosen`` the routed experts of
    every row (an empty ``[s, 0]`` for a dense layer)."""
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["attn_norm"]["scale"].astype(jnp.float32), eps),
                       _f32(p["mla"]), cfg, quant)
    h = _rms_norm(x, p["ffn_norm"]["scale"].astype(jnp.float32), eps)
    if "mlp" in p:
        m = _f32(p["mlp"])
        ffn = _gated_ffn(h, m["gate"]["kernel"], m["up"]["kernel"], m["down"]["kernel"], quant)
        return x + ffn, jnp.zeros((x.shape[0], 0), jnp.int32)
    ffn, chosen = _experts(h, p["moe"], cfg, quant)   # the stacked experts rise one at a time
    return x + ffn, chosen


def logits_and_choices(params, tokens, cfg: dict, quant: bool = False):
    """Logits ``(seq, vocab)`` for one sequence of int tokens, and the routed
    experts of every row in every expert layer ``(layers, seq, k)``."""
    x = params["tok_embed"]["embedding"][tokens].astype(jnp.float32)
    chosen = []
    for i in range(cfg["num_hidden_layers"]):
        x, c = _layer(x, params[f"layer_{i}"], cfg, quant)
        if c.shape[1]:
            chosen.append(c)
    x = _rms_norm(x, params["final_norm"]["scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    return _mm(x, params["lm_head"]["kernel"].astype(jnp.float32), quant), jnp.stack(chosen)


def logits(params, tokens, cfg: dict, quant: bool = False):
    return logits_and_choices(params, tokens, cfg, quant)[0]


# ---------------------------------------------------------------- serving
def sequence_logits(params, tokens, cfg: dict, quant: bool = False):
    """Logits of one full forward pass over ``tokens`` (a served request's
    prompt followed by what was emitted, padded to a fixed length): row ``i``
    is what token ``i + 1`` was chosen from."""
    with jax.default_matmul_precision("highest"):
        return logits(params, tokens, cfg, quant)


def served_token_stats(params, tokens, cfg: dict, control: bool = False) -> dict:
    """Per position ``i`` of a served sequence: the reference's best logit for
    token ``i + 1``, the logit of the token that was served there, and the
    experts the reference's router chose for row ``i`` in every expert layer,
    each row's sorted (``chosen``: ``(seq, layers, k)``). With ``control``
    also the logit (the float32 reference's) of the token that the int8 pass
    over the same sequence puts first, and that pass's ``control_chosen``."""
    with jax.default_matmul_precision("highest"):
        lg, chosen = logits_and_choices(params, tokens, cfg)
        pick = lambda ids: jnp.take_along_axis(lg, ids[:, None], axis=-1)[:, 0]
        rows = lambda c: jnp.sort(c, axis=-1).transpose(1, 0, 2)
        out = {"best": lg.max(-1), "served": pick(jnp.roll(tokens, -1)), "chosen": rows(chosen)}
        if control:
            lg8, chosen8 = logits_and_choices(params, tokens, cfg, quant=True)
            out.update(control=pick(lg8.argmax(-1)), control_chosen=rows(chosen8))
    return out
