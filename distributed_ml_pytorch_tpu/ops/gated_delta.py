"""The gated delta rule (arXiv:2412.06464), the recurrence of a
linear-attention layer, in the two forms serving needs.

Per head, with a state ``S`` of shape ``(value, key)`` that starts at zero::

    S' = alpha_t * S_{t-1}
    S_t = S' + beta_t * (v_t - S' k_t) k_t^T
    o_t = S_t q_t

``alpha_t`` in (0, 1] forgets, ``beta_t`` in [0, 2] writes (above 1 the
transition ``I - beta k k^T`` has a negative eigenvalue), ``k_t`` has unit
length. The state is the layer's whole memory of a sequence: a fixed
``value x key`` floats a head however long the sequence is, where a softmax
layer keeps a key and a value for every token.

- :func:`gated_delta_step` is one token: decode reads and rewrites the state.
- :func:`gated_delta_chunked` is a whole prompt in chunks of :data:`CHUNK`
  tokens (section 3 of the paper). With ``u_t = beta_t (v_t - S' k_t)`` the
  recurrence reads ``S_t = alpha_t S_{t-1} + u_t k_t^T``, and inside a chunk
  that starts from ``S_0``, with ``g_t`` the running sum of ``log alpha``::

      (I + A) U = diag(beta) (V - diag(exp g) K S_0^T)
      A[t, i]   = beta_t exp(g_t - g_i) (k_t . k_i)      for i < t, else 0
      O         = diag(exp g) Q S_0^T + (tril(Q K^T) * exp(g_t - g_i)) U
      S_C       = exp(g_C) S_0 + U^T diag(exp(g_C - g)) K

  ``I + A`` is unit lower triangular: it is inverted once a chunk, for every
  chunk at once, and applied to the right-hand side ``[beta V, beta exp(g) K]``,
  so that the scan from chunk to chunk carries the state through matrix
  products alone. The inverse is block forward substitution written as matrix
  products (:func:`_unit_lower_inverse`): the TPU's ``triangular_solve`` walks
  the rows one after the other and took a third of a prefill's device time.
  Every ratio ``exp(g_t - g_i)`` has ``i <= t`` and is at most 1.

Both take ``log alpha`` (at most 0) and compute in float32 with products at
``highest`` precision: on a TPU a float32 product at the default precision
rounds its operands to bfloat16, and the state is the one value of a layer
that every later token reads again. A position at or past ``n_valid`` is the
identity on the state (``alpha = 1``, ``beta = 0``): a prompt padded to a
bucket leaves the state its real tokens gave it. The scopes ``gdn/recur`` and
``gdn/chunk`` put both on the device trace.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: tokens a chunk; prompts are padded to a multiple with identity positions
CHUNK = 64

_HIGHEST = jax.lax.Precision.HIGHEST


def gated_delta_step(q, k, v, log_alpha, beta, state):
    """One token. ``q``, ``k``: ``(..., heads, key)``; ``v``: ``(..., heads,
    value)``; ``log_alpha``, ``beta``: ``(..., heads)``; ``state``: ``(...,
    heads, value, key)`` float32. Returns ``(o, state)``, ``o`` float32
    ``(..., heads, value)``."""
    with jax.named_scope("gdn/recur"):
        f32 = lambda a: a.astype(jnp.float32)
        q, k, v, log_alpha, beta = map(f32, (q, k, v, log_alpha, beta))
        state = state * jnp.exp(log_alpha)[..., None, None]
        kept = jnp.einsum("...vk,...k->...v", state, k, precision=_HIGHEST)
        u = beta[..., None] * (v - kept)
        state = state + u[..., :, None] * k[..., None, :]
        o = jnp.einsum("...vk,...k->...v", state, q, precision=_HIGHEST)
        return o, state


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` strictly lower triangular, ``(..., C, C)`` with
    ``C`` a power of two, by doubling the block size: with the inverses
    ``X11``, ``X22`` of two neighbouring diagonal blocks, the block of twice
    their size has ``-X22 L21 X11`` below them. ``x`` holds the inverses of
    all diagonal blocks of one size, so a level is two products of whole
    matrices: ``x - x (a under the mask of every L21) x``."""
    c = a.shape[-1]
    rows, cols = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    below = lambda s: ((rows // s) % 2 == 1) & (cols // s == rows // s - 1)
    x = jnp.eye(c, dtype=a.dtype) - jnp.where(below(1), a, 0.0)  # blocks of 2
    s = 2
    while s < c:
        l21 = jnp.where(below(s), a, 0.0)
        x = x - jnp.matmul(x, jnp.matmul(l21, x, precision=_HIGHEST), precision=_HIGHEST)
        s *= 2
    return x


def gated_delta_chunked(q, k, v, log_alpha, beta, state0, n_valid=None):
    """A run of ``T`` tokens from ``state0``. ``q``, ``k``: ``(batch, T,
    heads, key)``; ``v``: ``(batch, T, heads, value)``; ``log_alpha``,
    ``beta``: ``(batch, T, heads)``; ``state0``: ``(batch, heads, value,
    key)``; ``n_valid``: the tokens that count, a scalar or one a row (all of
    them when left out). Returns ``(o, state)``: ``o`` float32 ``(batch, T,
    heads, value)``, rows at or past ``n_valid`` meaningless; ``state`` after
    token ``n_valid - 1``."""
    with jax.named_scope("gdn/chunk"):
        b, T, h, dk = q.shape
        dv = v.shape[-1]
        f32 = lambda a: a.astype(jnp.float32)
        q, k, v, log_alpha, beta = map(f32, (q, k, v, log_alpha, beta))
        if n_valid is not None:
            real = jnp.arange(T)[None, :] < jnp.reshape(jnp.asarray(n_valid), (-1, 1))
            log_alpha = jnp.where(real[..., None], log_alpha, 0.0)
            beta = jnp.where(real[..., None], beta, 0.0)
        n = -(-T // CHUNK)
        pad = n * CHUNK - T  # identity positions: log alpha 0, beta 0
        # (batch, T, heads, x) -> (chunks, batch, heads, CHUNK, x)
        chunks = lambda a: jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)).reshape(
            (b, n, CHUNK) + a.shape[2:])
        q, k, v = (jnp.transpose(chunks(a), (1, 0, 3, 2, 4)) for a in (q, k, v))
        g, beta = (jnp.transpose(chunks(a), (1, 0, 3, 2)) for a in (log_alpha, beta))
        g = jnp.cumsum(g, axis=-1)                                   # (n, b, h, C)
        mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_HIGHEST)

        rows, cols = jnp.arange(CHUNK)[:, None], jnp.arange(CHUNK)[None, :]
        # exp(g_t - g_i) where i <= t, 0 above the diagonal (masked before the
        # exponential: above it the difference is positive and can overflow)
        ratio = jnp.exp(jnp.where(rows >= cols, g[..., :, None] - g[..., None, :], -jnp.inf))
        a = jnp.where(rows > cols, beta[..., :, None] * ratio * mm("...td,...id->...ti", k, k), 0.0)
        rhs = jnp.concatenate(
            [beta[..., None] * v, (beta * jnp.exp(g))[..., None] * k], axis=-1)
        solved = mm("...ti,...ix->...tx", _unit_lower_inverse(a), rhs)
        u_v, u_s = solved[..., :dv], solved[..., dv:]                # U = u_v - u_s S_0^T
        attn = ratio * mm("...td,...id->...ti", q, k)                # tril(Q K^T) * ratio
        q_in = jnp.exp(g)[..., None] * q                             # diag(exp g) Q
        k_out = jnp.exp(g[..., -1:, None] - g[..., None]) * k        # diag(exp(g_C - g)) K
        g_end = jnp.exp(g[..., -1])[..., None, None]

        def chunk(state, x):
            u_v, u_s, attn, q_in, k_out, g_end = x
            u = u_v - mm("...tk,...vk->...tv", u_s, state)
            o = mm("...tk,...vk->...tv", q_in, state) + mm("...ti,...iv->...tv", attn, u)
            return g_end * state + mm("...tv,...tk->...vk", u, k_out), o

        state, o = jax.lax.scan(chunk, f32(state0), (u_v, u_s, attn, q_in, k_out, g_end))
        o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(b, n * CHUNK, h, dv)[:, :T]
        return o, state
