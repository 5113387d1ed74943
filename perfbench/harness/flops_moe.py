"""Operations of the measured work of a DeepSeek-V3-style configuration
(Moonlight), computed from shapes.

Model FLOPs follow ``harness.flops``'s PaLM convention: 6 x the matmul
parameters a token passes through, forward and backward, plus attention's
two matmuls, 6 x layers x heads x sequence length x (query-key width +
value width) per token. A token passes through the untied output head, each
layer's latent attention, the dense layer's SwiGLU, each MoE layer's router
and shared experts and, on average, ``k x held / experts`` of the held
experts (the share of its k choices that land on this chip). Recomputed
work is not counted."""
from __future__ import annotations


def _mla(c: dict) -> int:
    d, h = c["hidden_size"], c["num_attention_heads"]
    rank, rope = c["kv_lora_rank"], c["qk_rope_head_dim"]
    nope, v = c["qk_nope_head_dim"], c["v_head_dim"]
    return (d * h * (nope + rope) + d * (rank + rope)
            + rank * h * (nope + v) + h * v * d)


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def active_matmul_params(c: dict) -> float:
    d = c["hidden_size"]
    n_lead = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_lead
    E = c["router_experts"]
    routed = c["num_experts_per_tok"] * c["n_routed_experts"] / E
    moe = (d * E + c["n_shared_experts"] * expert_params(c)
           + routed * expert_params(c))
    return (c["vocab_size"] * d + c["num_hidden_layers"] * _mla(c)
            + n_lead * 3 * d * c["intermediate_size"] + n_moe * moe)


def train_flops_per_token(c: dict, seq_len: int) -> float:
    attn = (6 * c["num_hidden_layers"] * c["num_attention_heads"] * seq_len
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]))
    return 6.0 * active_matmul_params(c) + attn


def train_step_flops(c: dict, global_batch: int, seq_len: int) -> float:
    return train_flops_per_token(c, seq_len) * global_batch * seq_len


def expert_flops_per_row(c: dict) -> float:
    """The held experts' three matmuls for one routed row, forward and both
    backward products: 2 x 3 x hidden x expert width x 3."""
    return 2.0 * expert_params(c) * 3
