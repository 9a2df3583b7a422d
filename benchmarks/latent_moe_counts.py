"""Operations and bytes the latent-attention, routed-expert decoder's
mathematics needs, from shapes and from the program's own counts of choices
(what ``hybrid_counts.py`` is for the hybrid configuration).

The numerators of the MFU and roofline shares of the ``kanana-2-30b-a3b``
cell. They count what the equations of ``reference/deepseek_v3_mla_moe.py``
require and nothing an implementation adds: no padding (a prompt's bucket, idle
slots), no expert that no real row chose, live slots and live rows only, the
head for the one prompt position that is sampled from. An expert's weights are
counted once a decode step for each expert that an ACTIVE slot chose, whatever
the program reads, so that a roofline reads the same work whatever implements
it; attention's work is the absorbed form's in a decode step (latent rows read
once for all heads) and the expanded form's in a prefill.
"""

from __future__ import annotations


def _attn(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["kv_lora_rank"])


def n_moe_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def attention_params(cfg: dict) -> int:
    """q, the latent projection and its norm, the expansion, o."""
    d = cfg["hidden_size"]
    h, dn, dr, dv, dc = _attn(cfg)
    return d * h * (dn + dr) + d * (dc + dr) + dc + dc * h * (dn + dv) + h * dv * d


def absorbed_weight_params(cfg: dict) -> int:
    """What a decode step's ``mla/absorb`` scope multiplies by: ``W_UK`` and
    ``W_UV`` (the expansion, folded into query and output)."""
    h, dn, _dr, dv, dc = _attn(cfg)
    return dc * h * (dn + dv)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up, down."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg: dict) -> int:
    return cfg["n_shared_experts"] * expert_params(cfg)


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["n_routed_experts"] + cfg["n_routed_experts"]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def layer_params(cfg: dict, dense: bool) -> int:
    """Every parameter of one layer (the two norms among them)."""
    ffn = dense_ffn_params(cfg) if dense else (
        cfg["n_routed_experts"] * expert_params(cfg) + shared_params(cfg) + router_params(cfg))
    return attention_params(cfg) + 2 * cfg["hidden_size"] + ffn


def total_params(cfg: dict) -> int:
    dense = cfg["first_k_dense_replace"]
    return (dense * layer_params(cfg, True) + n_moe_layers(cfg) * layer_params(cfg, False)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"] + cfg["hidden_size"])


def active_matmul_params(cfg: dict) -> int:
    """Weights ONE token is multiplied by: every layer's attention and its
    dense FFN or its router, ``num_experts_per_tok`` experts and the shared
    one, and the head (the embedding is a lookup)."""
    per_moe = (cfg["num_experts_per_tok"] * expert_params(cfg) + shared_params(cfg)
               + cfg["hidden_size"] * cfg["n_routed_experts"])
    attn = attention_params(cfg) - cfg["kv_lora_rank"]
    return (cfg["num_hidden_layers"] * attn + cfg["first_k_dense_replace"] * dense_ffn_params(cfg)
            + n_moe_layers(cfg) * per_moe + cfg["hidden_size"] * cfg["vocab_size"])


# ---------------------------------------------------------------- the cache
def latent_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """One cached position of ONE layer: the latent and the rotated key."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * bytes_per_value


def per_head_row_bytes(cfg: dict, bytes_per_value: int = 2) -> int:
    """What the same position would hold as per-head K and V."""
    h, dn, dr, dv, _dc = _attn(cfg)
    return h * (dn + dr + dv) * bytes_per_value


# ------------------------------------------------------------------ serving
def prefill_attention_flops(cfg: dict, prompt_len: int) -> float:
    """Causal expanded attention of one layer over one prompt: QK^T over heads
    of ``nope + rope``, PV over heads of ``v``, half the plane."""
    h, dn, dr, dv, _dc = _attn(cfg)
    return 2.0 * h * (dn + dr + dv) * prompt_len * prompt_len / 2


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """One prompt of ``prompt_len`` tokens: every token through its layers'
    matmuls (six experts and the shared one in an expert layer), causal
    attention over the prompt, the head for the last token only."""
    head = cfg["hidden_size"] * cfg["vocab_size"]
    return (2.0 * (active_matmul_params(cfg) - head) * prompt_len
            + cfg["num_hidden_layers"] * prefill_attention_flops(cfg, prompt_len)
            + 2.0 * head)


def decode_attention_flops(cfg: dict, context: int) -> float:
    """One token's absorbed attention in one layer over ``context`` rows:
    scores over ``latent + rope`` lanes and the weighted sum over ``latent``
    lanes, every head."""
    h, _dn, dr, _dv, dc = _attn(cfg)
    return 2.0 * h * (2 * dc + dr) * context


def decode_flops(cfg: dict, context: int) -> float:
    """One token decoded over ``context`` earlier positions."""
    return (2.0 * active_matmul_params(cfg)
            + cfg["num_hidden_layers"] * decode_attention_flops(cfg, context))


def expert_choice_flops(cfg: dict, choices: float) -> float:
    """The routed experts' FLOPs for ``choices`` (row, expert) pairs."""
    return 2.0 * expert_params(cfg) * choices


def expert_touch_bytes(cfg: dict, touched: float, bytes_per_weight: int = 2) -> float:
    """Bytes of ``touched`` experts' weights, each read once."""
    return float(bytes_per_weight) * expert_params(cfg) * touched


def fixed_stream_bytes(cfg: dict, bytes_per_weight: int = 2) -> float:
    """What one decode step reads of the weights whoever is routed where:
    everything but the routed experts and the embedding's table."""
    routed = n_moe_layers(cfg) * cfg["n_routed_experts"] * expert_params(cfg)
    return float(bytes_per_weight) * (
        total_params(cfg) - routed - cfg["vocab_size"] * cfg["hidden_size"])


def decode_step_bytes(cfg: dict, live_rows: int, touched: float) -> float:
    """Bytes one decode step must move: the fixed weights once, the experts
    that an active slot chose once each (``touched``: summed over the expert
    layers), and the live latent rows of every layer."""
    return (fixed_stream_bytes(cfg) + expert_touch_bytes(cfg, touched)
            + float(live_rows) * cfg["num_hidden_layers"] * latent_row_bytes(cfg))


def absorb_step_bytes(cfg: dict, live_rows: int, bytes_per_value: int = 2) -> float:
    """What the absorbed attention of one decode step moves over every layer:
    the live latent rows and the absorbed projections' weights."""
    return cfg["num_hidden_layers"] * (
        float(live_rows) * latent_row_bytes(cfg, bytes_per_value)
        + float(bytes_per_value) * absorbed_weight_params(cfg))
