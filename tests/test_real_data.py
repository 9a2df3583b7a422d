"""Network-marked real-data tests (VERDICT r2 #6).

These run ONLY where egress exists: ``pytest -m network tests/test_real_data.py``.
In this sandbox (zero egress) they skip cleanly — the point is that the
moment the suite runs somewhere with network, the real-CIFAR-10 claims
close themselves with no code changes.
"""

import pytest


@pytest.fixture(scope="module")
def real_cifar(tmp_path_factory):
    from distributed_ml_pytorch_tpu.data import load_cifar10

    root = str(tmp_path_factory.mktemp("cifar_real"))
    try:
        data = load_cifar10(root=root, synthetic=False, download=True)
    except Exception as e:
        pytest.skip(f"real CIFAR-10 unavailable (no egress?): {e}")
    return data


@pytest.mark.network
def test_real_cifar10_downloads_and_has_canonical_shapes(real_cifar):
    x, y, xt, yt, is_synth = real_cifar
    assert not is_synth
    assert x.shape == (50000, 32, 32, 3) and xt.shape == (10000, 32, 32, 3)
    assert set(y.tolist()) == set(range(10))


@pytest.mark.network
def test_real_data_short_training_learns(real_cifar):
    """A few hundred reference-recipe steps on the genuine data must beat
    chance decisively — the sanity gate before the full parity run."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_ml_pytorch_tpu.models import AlexNet
    from distributed_ml_pytorch_tpu.training.trainer import (
        create_train_state,
        make_eval_fn,
        make_scan_train_step,
    )

    x, y, xt, yt, _ = real_cifar
    model = AlexNet()
    state, tx = create_train_state(model, jax.random.key(0), lr=0.008)
    scan = make_scan_train_step(model, tx)
    ev = make_eval_fn(model)
    idx = np.random.default_rng(0).integers(0, len(x), size=(8, 50, 64))
    for sel in idx:
        state, _ = scan(state, jnp.asarray(x[sel]), jnp.asarray(y[sel]),
                        jax.random.key(1))
    _, preds = ev(state.params, jnp.asarray(xt[:2000]), jnp.asarray(yt[:2000]))
    acc = float((np.asarray(preds) == yt[:2000]).mean())
    assert acc > 0.25, f"400 real-data steps only reached {acc:.3f}"
