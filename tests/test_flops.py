"""MFU numerator audit (VERDICT r2 #8): the hybrid FLOPs count and the
scaling-book 6ND analytic count are independent methods and must agree."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_ml_pytorch_tpu.utils.flops import (
    check_flops_agreement,
    compiled_flops,
    device_peak_flops,
    flash_attention_train_flops,
    lm_train_flops_6nd,
)


def test_unknown_tpu_kind_is_an_error_not_a_missing_mfu():
    """A TPU the peak table does not hold must raise (MFU used to vanish
    quietly); only a host CPU has no peak."""
    from types import SimpleNamespace

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peak_flops(v5e) == 197e12
    assert device_peak_flops(jax.devices()[0]) is None  # the CPU test mesh
    with pytest.raises(KeyError, match="TPU v99"):
        device_peak_flops(SimpleNamespace(platform="tpu", device_kind="TPU v99"))
    with pytest.raises(KeyError, match="fp8"):
        device_peak_flops(v5e, dtype="fp8")


def test_flash_flops_fused_vs_split_ratio():
    # fused backward recomputes scores once: 7 matmuls vs split's 9
    fused = flash_attention_train_flops(2, 4, 512, 64, 3, bwd_impl="fused")
    split = flash_attention_train_flops(2, 4, 512, 64, 3, bwd_impl="split")
    assert split / fused == pytest.approx(9 / 7)
    # remat adds the 2 forward matmuls
    remat = flash_attention_train_flops(2, 4, 512, 64, 3, remat=True)
    assert remat / fused == pytest.approx(9 / 7)


def test_check_flops_agreement_boundaries():
    assert check_flops_agreement(1.0e12, 1.1e12) is None  # ~9% apart: ok
    warn = check_flops_agreement(1.0e12, 2.0e12)
    assert warn is not None and "cross-check FAILED" in warn
    assert check_flops_agreement(None, 1.0e12) is None  # no hybrid count


def test_xla_count_agrees_with_6nd_for_a_real_lm_step():
    """End-to-end: XLA's cost_analysis over a full LM train step (scan
    attention on CPU — visible to the compiler) must land within 15% of
    the 6ND analytic count, the assertion bench_lm runs at bench time."""
    from distributed_ml_pytorch_tpu.models.transformer import TransformerLM
    from distributed_ml_pytorch_tpu.parallel.fsdp import lm_loss_builder
    from distributed_ml_pytorch_tpu.parallel.seq_parallel import (
        create_lm_train_state,
        next_token_targets,
    )

    lm = TransformerLM(vocab_size=512, d_model=256, n_heads=4, n_layers=4,
                       d_ff=1024, max_len=256, pos_encoding="rope")
    tx = optax.sgd(1e-3)
    state = create_lm_train_state(lm, jax.random.key(0), tx)
    tokens = np.random.default_rng(0).integers(0, 512, size=(4, 256)).astype(np.int32)
    targets = jnp.asarray(next_token_targets(tokens))
    tokens = jnp.asarray(tokens)
    loss_builder = lm_loss_builder(lm)

    @jax.jit
    def step(state, tokens, targets):
        loss, grads = jax.value_and_grad(
            loss_builder(state, tokens, targets))(state.params)
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        return state.replace(params=params, opt_state=opt_state), loss

    hybrid = compiled_flops(step, state, tokens, targets)
    assert hybrid is not None
    n_params = sum(p.size for p in jax.tree.leaves(state.params))
    embed = sum(
        leaf.size
        for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]
        if any("embed" in str(getattr(k, "key", k)).lower() for k in path)
    )
    analytic = lm_train_flops_6nd(n_params - embed, 4, 256, 4, 64, 4)
    assert check_flops_agreement(hybrid, analytic) is None, (
        f"hybrid {hybrid:.3e} vs analytic {analytic:.3e}")
