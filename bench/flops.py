"""Operations and bytes of the program's kernels, from shapes alone.  The
FLOPs a model requires belong to its family (``bench/families/``).

Paged decode attention (one kernel call, one layer): each active row
reads its K and V pages for ``length`` positions and does 4 * H * hd
FLOP per position; the f32 query and output add 8 * H * hd bytes a row.
"""
from __future__ import annotations


def paged_attention_cost(dims, lengths, kv_bytes: int) -> tuple:
    """(FLOPs, bytes) of one layer's kernel call over active rows whose
    attended lengths (cached positions + the new token) are ``lengths``."""
    h, kv, hd = dims.n_heads, dims.n_kv_heads, dims.head_dim
    n = sum(lengths)
    flops = 4.0 * h * hd * n
    nbytes = 2.0 * kv * hd * kv_bytes * n + 8.0 * h * hd * len(lengths)
    return flops, nbytes
