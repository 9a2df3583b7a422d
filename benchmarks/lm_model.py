"""The program's ``TransformerLM`` for a configuration file: the one place the
benchmark maps a published GPT-2 ``config.json`` onto the program's model."""

from benchmarks import counts


def transformer_lm(cfg: dict):
    import jax.numpy as jnp

    from distributed_ml_pytorch_tpu.models import TransformerLM

    return TransformerLM(
        vocab_size=counts.vocab_rows(cfg), d_model=cfg["n_embd"], n_heads=cfg["n_head"],
        n_layers=cfg["n_layer"], d_ff=counts.d_ff(cfg), max_len=cfg["n_positions"],
        dtype=jnp.bfloat16)
