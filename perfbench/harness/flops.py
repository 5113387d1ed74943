"""Operations and bytes of the measured work, computed from shapes.

Model FLOPs follow the PaLM convention (Chowdhery et al. 2022, appendix B):
6 x (non-embedding matmul parameters + the tied output head) per token for
the forward and backward passes, plus 12 x layers x heads x head size x
sequence length per token for attention's two matmuls. Recomputed
(rematerialised) work is not counted."""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    """Matmul parameters of a dense GQA transformer with a SwiGLU MLP and a
    tied head: per layer q, k, v and o projections and the three MLP
    matrices; the head is the vocab x hidden embedding used once more."""
    d, hd = c["hidden_size"], c["head_dim"]
    h, k = c["num_attention_heads"], c["num_key_value_heads"]
    attn = d * h * hd + 2 * d * k * hd + h * hd * d
    mlp = 3 * d * c["intermediate_size"]
    head = c["vocab_size"] * d
    return c["num_hidden_layers"] * (attn + mlp) + head


def train_flops_per_token(c: dict, seq_len: int) -> float:
    attn = (12 * c["num_hidden_layers"] * c["num_attention_heads"]
            * c["head_dim"] * seq_len)
    return 6.0 * matmul_params(c) + attn


def train_step_flops(c: dict, global_batch: int, seq_len: int) -> float:
    return train_flops_per_token(c, seq_len) * global_batch * seq_len


def assemble_bytes(global_batch: int, seq_len: int, itemsize: int = 4) -> int:
    """Bytes the batch assembly must move on the device per step: the staged
    window of ``global_batch x (seq_len + 1)`` tokens read once, and the
    inputs and labels, ``global_batch x seq_len`` tokens each, written."""
    return itemsize * (global_batch * (seq_len + 1)
                       + 2 * global_batch * seq_len)
