"""Blockwise (flash) attention Pallas-TPU kernel.

VMEM-tiled online-softmax attention with GQA, causal / sliding-window /
prefix-LM masking, and *block skipping*: grid cells whose (q-block, kv-block)
pair is fully masked are skipped before any MXU work — on TPU the DMA for a
skipped block still pipelines, so skipping converts masked FLOPs directly
into roofline headroom (§Perf iteration 1 for the attention-bound cells).

Grid: (B, H, num_q_blocks, num_kv_blocks); kv is the innermost (sequential)
dimension so the f32 scratch accumulators persist across kv steps.

Targets TPU (MXU-aligned 128×128 default tiles); validated on CPU via
``interpret=True`` against ``ref.attention``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: int | None,
                  prefix_len: int, qb: int, kb: int, nk: int):
    i = pl.program_id(2)
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # --- block-level visibility (skip fully-masked blocks) -----------------
    q_lo = i * qb
    q_hi = q_lo + qb - 1
    k_lo = j * kb
    k_hi = k_lo + kb - 1
    needed = jnp.bool_(True)
    if causal:
        needed = needed & (k_lo <= q_hi)
    if window is not None:
        in_window = k_hi > q_lo - window
        if prefix_len > 0:
            in_window = in_window | (k_lo < prefix_len)
        needed = needed & in_window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # (qb, hd)
        k = k_ref[0, 0].astype(jnp.float32)             # (kb, hd)
        v = v_ref[0, 0].astype(jnp.float32)             # (kb, hd)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (qb, kb)

        q_pos = q_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kv_pos = k_lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        visible = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            visible = kv_pos <= q_pos
            if prefix_len > 0:
                visible = visible | (kv_pos < prefix_len)
        if window is not None:
            in_win = kv_pos > q_pos - window
            if prefix_len > 0:
                in_win = in_win | (kv_pos < prefix_len)
            visible = visible & in_win
        s = jnp.where(visible, s, NEG_INF)

        m_prev = m_ref[...]                              # (qb,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot(p.astype(v.dtype), v,
                                      preferred_element_type=jnp.float32))
        m_ref[...] = m_cur

    @pl.when(j == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-20)[:, None]
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix_len", "q_block", "kv_block",
                     "interpret"))
def flash_attention(q, k, v, *, causal: bool = True,
                    window: int | None = None, prefix_len: int = 0,
                    q_block: int = 128, kv_block: int = 128,
                    interpret: bool = False):
    """q: (B, S, H, hd); k, v: (B, S, KV, hd) -> (B, S, H, hd)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qb = min(q_block, S)
    kb = min(kv_block, S)
    if S % qb or S % kb:
        raise ValueError(f"S={S} must be divisible by blocks ({qb},{kb})")
    nq, nk = S // qb, S // kb

    # (B, H, S, hd) layout: heads ahead of sequence for contiguous blocks.
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _flash_kernel, scale=1.0 / math.sqrt(hd), causal=causal,
        window=window, prefix_len=prefix_len, qb=qb, kb=kb, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, qb, hd), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda b, h, i, j, G=G: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, hd), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),      # m
            pltpu.VMEM((qb,), jnp.float32),      # l
            pltpu.VMEM((qb, hd), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)
