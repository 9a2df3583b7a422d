"""The program's ``HybridLM`` for a configuration file: the one place the
benchmark maps a published hybrid ``config.json`` onto the program's model
(what ``lm_model.py`` is for the GPT-2 configurations)."""


def hybrid_lm(cfg: dict):
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models.hybrid import HybridLM

    return HybridLM.from_config(cfg, dtype=jnp.bfloat16)
