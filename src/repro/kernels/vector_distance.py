"""Batched gather + L2 distance kernel — the vector-search data plane.

The vector-search tenant stores its dataset in the serving KV pool's
blocks: a block of shape ``(T, D)`` holds T vectors of dimension D. An
HNSW-style walk visits a handful of blocks per step; after the pool makes
them resident (duplex-paged like any other tenant's traffic), this kernel
computes all query-to-candidate distances for the visited blocks in one
grid pass — the compute half of the paper's §6.5 vector-database workload.

Grid: one program instance per visited block. The query batch stays in
VMEM across the whole pass while candidate blocks stream through — the
same stationary/streaming split as flash attention's q/kv tiles. Distances
use the matmul expansion ``|q - b|^2 = |q|^2 + |b|^2 - 2 q·bᵀ`` so the
MXU carries the inner products.

Validated in interpret mode against ``ref.l2_distance``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _l2_kernel(q_ref, blk_ref, out_ref):
    q = q_ref[...].astype(jnp.float32)            # (Q, D)
    b = blk_ref[...][0].astype(jnp.float32)       # (T, D)
    qq = jnp.sum(q * q, axis=-1)[:, None]         # (Q, 1)
    bb = jnp.sum(b * b, axis=-1)[None, :]         # (1, T)
    qb = jax.lax.dot_general(
        q, b, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)       # (Q, T) on the MXU
    out_ref[0] = qq + bb - 2.0 * qb


@functools.partial(jax.jit, static_argnames=("interpret",))
def l2_distance(queries, blocks, *, interpret: bool = False):
    """Squared L2 distances from every query to every block-resident vector.

    queries: (Q, D) float; blocks: (N, T, D) bf16 pool blocks.
    Returns (N, Q, T) float32 distances.
    """
    Q, D = queries.shape
    N, T, _ = blocks.shape
    return pl.pallas_call(
        _l2_kernel,
        grid=(N,),
        in_specs=[
            pl.BlockSpec((Q, D), lambda i: (0, 0)),
            pl.BlockSpec((1, T, D), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, T), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Q, T), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(queries, blocks)
