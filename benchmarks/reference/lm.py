"""Plain reference for the GPT-2 style decoder the benchmark's LM cells run.

Straightforward ``jax.numpy`` in float32 with matmuls at ``highest`` precision:
no kernels, no cache, no batching tricks. It imports nothing of the program
and is given nothing the program made; its weights come from
:func:`make_params`, the benchmark's own initialiser, which the drivers also
hand to the program.

Architecture as the program runs it (``models/transformer.py``), departures
from the published GPT-2 noted in the configuration files: pre-LN blocks,
tanh GELU, learned positions, attention projections without bias, output head
untied from the embedding, LayerNorm epsilon 1e-6, vocabulary rows padded.

The control of "How ``correct`` is decided" is this same code with ``quant``
set: every matmul (projections, MLP, head, QK^T and PV) takes its operands
rounded to int8 with one scale per row, forward and backward, which is the
precision below the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
INIT_STD = 0.02


# ----------------------------------------------------------------- weights
def param_shapes(cfg: dict) -> dict:
    """The parameter tree's shapes, in the layout ``TransformerLM`` uses."""
    d, ff = cfg["n_embd"], cfg.get("n_inner") or 4 * cfg["n_embd"]
    vocab = cfg.get("assumed", {}).get("padded_vocab_size", cfg["vocab_size"])
    block = {
        "LayerNorm_0": {"scale": (d,), "bias": (d,)},
        "attn": {n: {"kernel": (d, d)} for n in "qkvo"},
        "LayerNorm_1": {"scale": (d,), "bias": (d,)},
        "Dense_0": {"kernel": (d, ff), "bias": (ff,)},
        "Dense_1": {"kernel": (ff, d), "bias": (d,)},
    }
    tree = {f"block_{i}": block for i in range(cfg["n_layer"])}
    tree["tok_embed"] = {"embedding": (vocab, d)}
    tree["pos_embed"] = {"embedding": (cfg["n_positions"], d)}
    tree["LayerNorm_0"] = {"scale": (d,), "bias": (d,)}
    tree["lm_head"] = {"kernel": (d, vocab)}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_params(key, cfg: dict, dtype=jnp.float32):
    """Seeded weights for ``cfg``: one traceable function, so one jitted call
    makes the whole tree on the device. GPT-2's initialisation (normal 0.02,
    residual projections scaled by 1/sqrt(2 * layers)) except that biases and
    LayerNorm parameters are drawn too (0.02 around 0 and around 1), so that
    no leaf is a constant and every leaf's gradient is compared."""
    shapes = param_shapes(cfg)
    leaves, treedef = jax.tree.flatten_with_path(shapes, is_leaf=_is_shape)
    resid = INIT_STD / (2.0 * cfg["n_layer"]) ** 0.5
    out = []
    for i, (path, shape) in enumerate(leaves):
        names = [p.key for p in path]
        std = resid if names[-2:] in (["o", "kernel"], ["Dense_1", "kernel"]) else INIT_STD
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if names[-1] == "scale":
            x = 1.0 + x
        out.append(x.astype(dtype))
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- arithmetic
def _q8(x, axis):
    """Round to int8 with one absmax scale per row along ``axis``."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127.0
    return jnp.round(x / scale) * scale


@jax.custom_vjp
def _mm_q8(x, w):
    return jnp.matmul(_q8(x, -1), _q8(w, -2))


def _mm_q8_fwd(x, w):
    return _mm_q8(x, w), (x, w)


def _mm_q8_bwd(res, g):
    x, w = res
    gq = _q8(g, -1)
    dx = jnp.matmul(gq, jnp.swapaxes(_q8(w, -1), -1, -2))
    dw = jnp.matmul(jnp.swapaxes(_q8(x, -2), -1, -2), _q8(g, -2))
    # sum the leading dims a broadcast weight did not have
    while dw.ndim > w.ndim:
        dw = dw.sum(0)
    return dx, dw


_mm_q8.defvjp(_mm_q8_fwd, _mm_q8_bwd)


def _mm(x, w, quant):
    return _mm_q8(x, w) if quant else jnp.matmul(x, w)


def _layer_norm(x, p):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, quant):
    b, s, d = x.shape
    h = _layer_norm(x, p["LayerNorm_0"])
    heads = lambda n: _mm(h, p["attn"][n]["kernel"], quant).reshape(
        b, s, n_head, d // n_head).transpose(0, 2, 1, 3)
    q, k, v = heads("q"), heads("k"), heads("v")
    scores = _mm(q, jnp.swapaxes(k, -1, -2), quant) / (d // n_head) ** 0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = _mm(probs, v, quant).transpose(0, 2, 1, 3).reshape(b, s, d)
    x = x + _mm(out, p["attn"]["o"]["kernel"], quant)
    h = _layer_norm(x, p["LayerNorm_1"])
    h = _gelu(_mm(h, p["Dense_0"]["kernel"], quant) + p["Dense_0"]["bias"])
    return x + _mm(h, p["Dense_1"]["kernel"], quant) + p["Dense_1"]["bias"]


def hidden_states(params, tokens, cfg: dict, quant: bool = False):
    """Final-LayerNorm hidden states ``(batch, seq, d)`` for int tokens."""
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    s = tokens.shape[-1]
    x = params["tok_embed"]["embedding"][tokens] + params["pos_embed"]["embedding"][:s]
    block = jax.checkpoint(functools.partial(_block, n_head=cfg["n_head"], quant=quant))
    for i in range(cfg["n_layer"]):
        x = block(x, params[f"block_{i}"])
    return _layer_norm(x, params["LayerNorm_0"])


def logits(params, tokens, cfg: dict, quant: bool = False):
    h = hidden_states(params, tokens, cfg, quant)
    return _mm(h, params["lm_head"]["kernel"].astype(jnp.float32), quant)


def lm_loss(params, tokens, targets, cfg: dict, quant: bool = False):
    """Mean next-token cross-entropy, the last position of each row left out
    (its target is padding): the convention of the trainer under test."""
    lg = logits(params, tokens, cfg, quant)
    ce = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
        lg, targets[..., None], axis=-1)[..., 0]
    return ce[:, :-1].mean()


# --------------------------------------------------------------- training
def leaf_norms(tree) -> dict:
    """``{leaf path: l2 norm}`` of a parameter-shaped tree."""
    return {jax.tree_util.keystr(path): jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}


ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8  # optax.adam's defaults


def _adam(params, mu, nu, grad, n, t, lr):
    """One Adam step on the mean of ``grad`` over ``n`` blocks of rows."""
    g = jax.tree.map(lambda a: a / n, grad)
    mu = jax.tree.map(lambda m, a: ADAM_B1 * m + (1 - ADAM_B1) * a, mu, g)
    nu = jax.tree.map(lambda v, a: ADAM_B2 * v + (1 - ADAM_B2) * a * a, nu, g)
    step = lambda p, m, v: p - lr * (m / (1 - ADAM_B1 ** t)) / (
        jnp.sqrt(v / (1 - ADAM_B2 ** t)) + ADAM_EPS)
    return jax.tree.map(step, params, mu, nu), mu, nu


@functools.lru_cache(maxsize=None)
def _train_fns(cfg_json: str, lr: float, quant: bool):
    """The reference's jitted pieces, built once for a configuration."""
    cfg = json.loads(cfg_json)
    return {
        "init": jax.jit(lambda k: make_params(k, cfg)),
        "zeros": jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p)),
        "grad": jax.jit(jax.value_and_grad(lambda p, t, g: lm_loss(p, t, g, cfg, quant))),
        "accumulate": jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g), donate_argnums=0),
        "adam": jax.jit(lambda p, m, v, g, n, t: _adam(p, m, v, g, n, t, lr),
                        donate_argnums=(0, 1, 2)),
        "norms": jax.jit(leaf_norms),
        "diff_norms": jax.jit(lambda p, k: leaf_norms(
            jax.tree.map(jnp.subtract, p, make_params(k, cfg)))),
    }


def adam_steps(key, batches, cfg: dict, lr: float, *, rows: int = 2,
               quant: bool = False, keep_rows=None) -> dict:
    """Follow plain Adam (``optax.adam``'s defaults) from the seeded weights
    through ``batches`` (a list of ``(tokens, targets)`` arrays), the gradient
    of each step accumulated over blocks of ``rows`` sequences so that it fits
    the chip.

    ``keep_rows`` plants the half-batch fault for calibration: only the first
    ``keep_rows`` sequences of each batch are used, the mean taken over them.

    Returns ``losses``, ``grad_norms`` (step 1, per leaf) and
    ``change_norms`` (parameters after the last step minus the seeded ones).
    """
    f = _train_fns(json.dumps(cfg, sort_keys=True), float(lr), bool(quant))
    with jax.default_matmul_precision("highest"):
        params = f["init"](key)
        mu, nu = f["zeros"](params), f["zeros"](params)
        losses, grad_norms = [], None
        for t, (tokens, targets) in enumerate(batches, start=1):
            if keep_rows:
                tokens, targets = tokens[:keep_rows], targets[:keep_rows]
            n = max(1, tokens.shape[0] // rows)
            total, acc = 0.0, None
            for i in range(n):
                sl = slice(i * rows, (i + 1) * rows)
                loss, g = f["grad"](params, jnp.asarray(tokens[sl]), jnp.asarray(targets[sl]))
                total += float(loss)
                acc = g if acc is None else f["accumulate"](acc, g)
            losses.append(total / n)
            if grad_norms is None:
                grad_norms = {k: float(v) / n for k, v in f["norms"](acc).items()}
            params, mu, nu = f["adam"](params, mu, nu, acc, float(n), float(t))
            del acc
        change = {k: float(v) for k, v in f["diff_norms"](params, key).items()}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}


# ---------------------------------------------------------------- serving
def sequence_logits(params, tokens, cfg: dict, quant: bool = False):
    """Logits ``(seq, vocab)`` of one full forward pass over ``tokens``
    (a served request's prompt followed by what was emitted, padded to a
    fixed length): row ``i`` is what token ``i + 1`` was chosen from."""
    with jax.default_matmul_precision("highest"):
        return logits(params, tokens[None], cfg, quant)[0]


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
