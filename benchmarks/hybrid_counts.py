"""Operations and bytes the hybrid decoder's mathematics needs, from shapes
(what ``counts.py`` is for the GPT-2 configurations).

The numerators of the MFU and roofline shares of the ``olmo-hybrid-7b``
cells. They count what the equations of ``reference/olmo_hybrid.py`` require
and nothing an implementation adds: no padding (a prompt's bucket, idle
slots), none of the chunkwise form's extra products, live slots and live rows
only, the head for the one prompt position that is sampled from.
"""

from __future__ import annotations


def _linear(cfg: dict) -> tuple:
    return (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
            cfg["linear_value_head_dim"])


def n_layers(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def full_mixer_matmul_params(cfg: dict) -> int:
    return 4 * cfg["hidden_size"] ** 2


def linear_mixer_matmul_params(cfg: dict) -> int:
    """q, k, v, the output gate, o, and the two gates' rows."""
    d = cfg["hidden_size"]
    h, dk, dv = _linear(cfg)
    return d * (2 * h * dk + 2 * h * dv) + h * dv * d + 2 * d * h


def linear_mixer_small_params(cfg: dict) -> int:
    """The three convolutions, A and dt a head, the gated norm's scale."""
    h, dk, dv = _linear(cfg)
    return cfg["linear_conv_kernel_dim"] * h * (2 * dk + dv) + 2 * h + dv


def layer_matmul_params(cfg: dict, kind: str) -> int:
    mixer = full_mixer_matmul_params(cfg) if kind == "full_attention" \
        else linear_mixer_matmul_params(cfg)
    return mixer + mlp_params(cfg)


def matmul_params(cfg: dict) -> int:
    """All weights a token is multiplied by: the layers and the output head
    (the embedding is a lookup)."""
    return (sum(layer_matmul_params(cfg, k) for k in cfg["layer_types"])
            + cfg["hidden_size"] * cfg["vocab_size"])


def small_params(cfg: dict) -> int:
    """Norm scales and the linear mixers' small leaves."""
    d = cfg["hidden_size"]
    return (len(cfg["layer_types"]) * 2 * d + n_layers(cfg, "full_attention") * 2 * d
            + n_layers(cfg, "linear_attention") * linear_mixer_small_params(cfg) + d)


def total_params(cfg: dict) -> int:
    return matmul_params(cfg) + small_params(cfg) + cfg["vocab_size"] * cfg["hidden_size"]


# --------------------------------------------------------------- the rule
def rule_flops_per_token(cfg: dict) -> float:
    """The gated delta rule, one token of one linear layer: ``S k``, the
    rank-one update and ``S q`` at two FLOPs a multiply-add each."""
    h, dk, dv = _linear(cfg)
    return 6.0 * dk * dv * h


def conv_flops_per_token(cfg: dict) -> float:
    h, dk, dv = _linear(cfg)
    return 2.0 * cfg["linear_conv_kernel_dim"] * h * (2 * dk + dv)


def state_bytes(cfg: dict) -> int:
    """One sequence's recurrent state in one linear layer, float32."""
    h, dk, dv = _linear(cfg)
    return 4 * h * dk * dv


def conv_step_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """What one token's convolution moves of a layer's tail: the three inputs
    before it read, its own written."""
    h, dk, dv = _linear(cfg)
    return cfg["linear_conv_kernel_dim"] * h * (2 * dk + dv) * bytes_per_value


def state_step_bytes(cfg: dict, live_slots: int) -> float:
    """One decode step's state traffic over every linear layer: each live
    slot's state read once and written once."""
    return float(live_slots) * n_layers(cfg, "linear_attention") * 2 * state_bytes(cfg)


def rule_prefill_flops(cfg: dict, prompt_len: int) -> float:
    """The rule over the real tokens of one prompt, every linear layer."""
    return n_layers(cfg, "linear_attention") * rule_flops_per_token(cfg) * prompt_len


# -------------------------------------------------------------- serving
def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` tokens: every layer's matmuls over every
    token, causal attention over the prompt in the full layers, rule and
    convolution in the linear ones, the head for the last token only."""
    d = cfg["hidden_size"]
    layers = sum(layer_matmul_params(cfg, k) for k in cfg["layer_types"])
    linear = n_layers(cfg, "linear_attention") * (
        rule_flops_per_token(cfg) + conv_flops_per_token(cfg))
    return (2.0 * layers * prompt_len
            + 2 * 2 * prompt_len * prompt_len * d * n_layers(cfg, "full_attention") / 2
            + linear * prompt_len
            + 2.0 * d * cfg["vocab_size"])


def decode_flops(cfg: dict, context: int) -> float:
    """One token decoded over ``context`` earlier positions."""
    linear = n_layers(cfg, "linear_attention") * (
        rule_flops_per_token(cfg) + conv_flops_per_token(cfg))
    return (2.0 * matmul_params(cfg)
            + 2 * 2 * context * cfg["hidden_size"] * n_layers(cfg, "full_attention")
            + linear)


def weight_stream_bytes(cfg: dict, bytes_per_weight: int = 2) -> float:
    """What one decode step reads of the weights whatever the batch."""
    return float(bytes_per_weight) * (matmul_params(cfg) + small_params(cfg))


def kv_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """Key and value of one cached position over the full-attention layers."""
    return 2 * cfg["hidden_size"] * n_layers(cfg, "full_attention") * bytes_per_value


def decode_step_bytes(cfg: dict, live_rows: int, live_slots: int) -> float:
    """Bytes one decode step must move: the weights once, the live K/V rows
    of the full layers, and each live slot's state (read and rewritten) and
    convolution tail in the linear ones."""
    return (weight_stream_bytes(cfg)
            + float(live_rows) * kv_row_bytes(cfg)
            + state_step_bytes(cfg, live_slots)
            + float(live_slots) * n_layers(cfg, "linear_attention") * conv_step_bytes(cfg))
