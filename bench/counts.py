"""Operations and bytes of the served model's work, from shapes alone.

These are the yardstick's own counts, kept with the benchmark so that no
change to the program can change them: model FLOPs per token at a given
context, and the HBM bytes a decode pass must read.
"""

from __future__ import annotations

from bench.weights import Sizes

BF16 = 2


def matmul_params(sz: Sizes) -> int:
    """Weights that take part in a matmul for every token: every layer's
    projections and feed-forward, and the output projection (the
    embedding matrix itself when tied). The input embedding is a row
    lookup and takes no FLOPs."""
    attn = sz.d_model * sz.head_dim * 2 * (sz.heads + sz.kv_heads)
    return sz.layers * (attn + 3 * sz.d_model * sz.d_ff) \
        + sz.d_model * sz.vocab


def positions_flops(sz: Sizes, positions) -> float:
    """Model FLOPs of processing one token at each of ``positions``
    (0-based): two per matmul weight, plus attention's score and value
    products over the ``position + 1`` keys each attends (two FLOPs per
    multiply-add each)."""
    n = len(positions)
    s = sum(positions) + n
    return (2.0 * matmul_params(sz) * n
            + 4.0 * sz.layers * sz.heads * sz.head_dim * s)


def weight_bytes(sz: Sizes) -> int:
    """Bytes of weights one decode pass reads from HBM (bfloat16): every
    layer, the final norm and the output projection; the input embedding
    is a gather of a few rows and is left out."""
    norms = (2 * sz.layers + 1) * sz.d_model
    return BF16 * (matmul_params(sz) + norms)


def kv_bytes_per_token(sz: Sizes) -> int:
    """Key and value bytes one cached token holds over all layers."""
    return BF16 * 2 * sz.layers * sz.kv_heads * sz.head_dim


def context_kv_bytes(sz: Sizes, positions) -> float:
    """Bytes of cached keys and values read to process one token at each
    of ``positions``: its real context, ``position + 1`` tokens."""
    return float(kv_bytes_per_token(sz)) * (sum(positions) + len(positions))
