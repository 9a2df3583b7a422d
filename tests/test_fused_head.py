"""The restructured lm_head train step (ops/fused_head.py) must compute the
SAME function as the AD step over fsdp.lm_loss_builder + plain SGD — loss
and every updated parameter — and its dW+update must be the gradient step
of the head cross-entropy written out plainly."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_ml_pytorch_tpu.models import TransformerLM
from distributed_ml_pytorch_tpu.ops.fused_head import (
    head_update_sgd,
    make_fused_head_sgd_step,
)
from distributed_ml_pytorch_tpu.parallel.fsdp import lm_loss_builder
from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
    create_lm_train_state,
    next_token_targets,
)


def _ref_step(lm, tx):
    loss_builder = lm_loss_builder(lm)

    @partial(jax.jit, donate_argnums=(0,))
    def step(state, tokens, targets):
        loss, grads = jax.value_and_grad(
            loss_builder(state, tokens, targets))(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(params=params, opt_state=opt_state,
                             step=state.step + 1), loss

    return step


@pytest.mark.slow  # two compiled LM train worlds
def test_fused_head_step_matches_ad_step():
    lm = TransformerLM(vocab_size=640, d_model=64, n_heads=4, n_layers=2,
                       d_ff=128, max_len=4096)
    lr = 0.05
    tx = optax.sgd(lr)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 640, (2, 1024)), jnp.int32)
    targets = next_token_targets(tokens)

    state = create_lm_train_state(lm, jax.random.key(0), tx)
    ref_state, ref_loss = _ref_step(lm, tx)(state, tokens, targets)

    state2 = create_lm_train_state(lm, jax.random.key(0), tx)
    new_state, loss = make_fused_head_sgd_step(lm, lr)(state2, tokens, targets)

    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-6)
    for (p, a), (_, b) in zip(
        jax.tree_util.tree_leaves_with_path(jax.device_get(ref_state.params)),
        jax.tree_util.tree_leaves_with_path(jax.device_get(new_state.params)),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7,
            err_msg=jax.tree_util.keystr(p))
    assert int(new_state.step) == 1


def test_head_update_is_the_sgd_step_of_the_written_out_head_ce():
    """``head_update_sgd`` against ``jax.grad`` of the masked head
    cross-entropy written out from the logits, at a toy size: the only
    cover of its arithmetic that is not ``slow``."""
    rng = np.random.default_rng(1)
    n, d, v, lr = 96, 16, 40, 0.05
    W = jnp.asarray(rng.normal(size=(d, v)) * 0.1, jnp.float32)
    h2 = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
    mask = jnp.ones((n,), jnp.float32).at[::7].set(0.0)  # masked rows
    gscale = mask / jnp.sum(mask)

    def head_ce(W):
        logp = jax.nn.log_softmax(h2 @ W, axis=-1)
        return -jnp.sum(logp[jnp.arange(n), labels] * gscale)

    logits = h2 @ W
    got = head_update_sgd(W, h2, logits, jax.nn.logsumexp(logits, axis=-1),
                          labels, gscale, lr)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(W - lr * jax.grad(head_ce)(W)),
                               rtol=1e-5, atol=1e-7)
    assert float(jnp.max(jnp.abs(got - W))) > 1e-4  # a step was taken
