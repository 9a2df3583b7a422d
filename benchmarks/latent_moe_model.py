"""The program's ``LatentMoELM`` for a configuration file: the one place the
benchmark maps a published ``deepseek_v3`` ``config.json`` onto the program's
model (what ``hybrid_model.py`` is for the hybrid configuration)."""


def latent_moe_lm(cfg: dict):
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models.latent_moe import LatentMoELM

    return LatentMoELM.from_config(cfg, dtype=jnp.bfloat16)
