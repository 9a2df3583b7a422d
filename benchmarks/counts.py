"""Operations and bytes the algorithm needs, computed from shapes.

The numerators of every MFU and roofline share the benchmark reports. They
count what the mathematics requires and nothing a particular implementation
adds: no recomputation (flash attention's second pass over the scores, remat),
no padding (a prompt's bucket, idle slots), no logits that are thrown away.
The arithmetic is that of ``utils/flops.py::lm_train_flops_6nd`` and
``flash_attention_train_flops``, copied so that a PR to the program cannot
move the yardstick.
"""

from __future__ import annotations


def vocab_rows(cfg: dict) -> int:
    return cfg.get("assumed", {}).get("padded_vocab_size", cfg["vocab_size"])


def d_ff(cfg: dict) -> int:
    return cfg.get("n_inner") or 4 * cfg["n_embd"]


def block_matmul_params(cfg: dict) -> int:
    """Weights of one block that a token is multiplied by: q, k, v, o and the
    two MLP matrices."""
    d = cfg["n_embd"]
    return 4 * d * d + 2 * d * d_ff(cfg)


def matmul_params(cfg: dict) -> int:
    """All weights a token is multiplied by: the blocks and the output head.
    The embedding tables are lookups, not matmuls."""
    return cfg["n_layer"] * block_matmul_params(cfg) + cfg["n_embd"] * vocab_rows(cfg)


def total_params(cfg: dict) -> int:
    d, L = cfg["n_embd"], cfg["n_layer"]
    per_block = block_matmul_params(cfg) + 4 * d + d_ff(cfg) + d  # two LayerNorms, two biases
    return (L * per_block + vocab_rows(cfg) * d + cfg["n_positions"] * d + 2 * d
            + d * vocab_rows(cfg))


# ------------------------------------------------------------- training
def attention_train_flops(cfg: dict, batch: int, seq: int) -> float:
    """Causal attention, forward and backward, of one step: two matmuls over
    the score plane forward (QK^T, PV), four backward (dV, dP, dQ, dK), two
    FLOPs a multiply-add, half the plane masked away."""
    return 6 * 2 * batch * seq * seq * cfg["n_embd"] * cfg["n_layer"] / 2


def train_step_flops(cfg: dict, batch: int, seq: int) -> float:
    """6 x matmul parameters x tokens, plus causal attention."""
    return 6.0 * matmul_params(cfg) * batch * seq + attention_train_flops(cfg, batch, seq)


# -------------------------------------------------------------- serving
def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` tokens: every block over every token,
    causal attention over the prompt, the head for the last token only."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    return (2.0 * L * block_matmul_params(cfg) * prompt_len
            + 2 * 2 * prompt_len * prompt_len * d * L / 2
            + 2.0 * d * vocab_rows(cfg))


def decode_flops(cfg: dict, context: int) -> float:
    """One token decoded over ``context`` cached positions."""
    return 2.0 * matmul_params(cfg) + 2 * 2 * context * cfg["n_embd"] * cfg["n_layer"]


def weight_stream_bytes(cfg: dict, bytes_per_weight: int) -> float:
    """What one decode step reads of the weights whatever the batch: every
    matmul weight, bias and LayerNorm once (embedding rows are negligible)."""
    d, L = cfg["n_embd"], cfg["n_layer"]
    small = L * (4 * d + d_ff(cfg) + d) + 2 * d
    return float(bytes_per_weight) * (matmul_params(cfg) + small)


def kv_row_bytes(cfg: dict, bytes_per_value: int) -> int:
    """Key and value of one cached position over all layers."""
    return 2 * cfg["n_embd"] * cfg["n_layer"] * bytes_per_value


def decode_step_bytes(cfg: dict, live_rows: int, bytes_per_weight: int = 2,
                      bytes_per_value: int = 2) -> float:
    """Bytes one decode step must stream: the weights once and the cached
    rows that are live over all slots (not the allocation)."""
    return (weight_stream_bytes(cfg, bytes_per_weight)
            + float(live_rows) * kv_row_bytes(cfg, bytes_per_value))
