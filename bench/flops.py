"""Operations and bytes the algorithms require, from shapes alone.

Training (per token, forward and backward): 6 FLOP per matrix parameter
-- the attention projections, the three SwiGLU matrices and the output
head; the embedding is a gather and counts nothing -- plus causal
attention: query i reads i + 1 keys, 2 FLOP per (query, key, dim) for
q k^T and as many for p v, so 2 * H * hd * (T + 1) per token forward and
three times that with the backward pass.  Recomputation counts nothing.

Serving (forward only): a prompt of n tokens costs 2 FLOP per layer
matrix parameter per token, causal attention 2 * H * hd * n * (n + 1)
per layer, and the output head once (only the last position's logits
are taken); a decode token over ``a`` attended positions costs 2 FLOP per
layer parameter, 4 * H * hd * a per layer, and the head.

Paged decode attention (one kernel call, one layer): each active row
reads its K and V pages for ``length`` positions and does 4 * H * hd
FLOP per position; the f32 query and output add 8 * H * hd bytes a row.
"""
from __future__ import annotations


def matmul_params(dims) -> int:
    d, hd = dims.d_model, dims.head_dim
    per_layer = (d * dims.n_heads * hd + 2 * d * dims.n_kv_heads * hd
                 + dims.n_heads * hd * d + 3 * d * dims.d_ff)
    return dims.n_layers * per_layer + d * dims.vocab


def train_flops_per_token(dims, seq_len: int) -> float:
    attn = 6 * dims.n_heads * dims.head_dim * (seq_len + 1) * dims.n_layers
    return 6.0 * matmul_params(dims) + attn


def _layer_params(dims) -> int:
    return matmul_params(dims) - dims.d_model * dims.vocab


def prefill_flops(dims, n: int) -> float:
    hd, h, L = dims.head_dim, dims.n_heads, dims.n_layers
    return (2.0 * n * _layer_params(dims) + 2.0 * h * hd * n * (n + 1) * L
            + 2.0 * dims.d_model * dims.vocab)


def decode_flops(dims, attended: int) -> float:
    hd, h, L = dims.head_dim, dims.n_heads, dims.n_layers
    return (2.0 * _layer_params(dims) + 4.0 * h * hd * attended * L
            + 2.0 * dims.d_model * dims.vocab)


def paged_attention_cost(dims, lengths, kv_bytes: int) -> tuple:
    """(FLOPs, bytes) of one layer's kernel call over active rows whose
    attended lengths (cached positions + the new token) are ``lengths``."""
    h, kv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    n = sum(lengths)
    flops = 4.0 * h * hd * n
    nbytes = 2.0 * kv * hd * kv_bytes * n + 8.0 * h * hd * len(lengths)
    return flops, nbytes
