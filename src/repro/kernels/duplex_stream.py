"""Duplex-pipelined streaming transform — the paper's insight as a kernel.

CXLAimPod's core claim: software that phase-separates reads from writes
leaves one direction of a full-duplex channel idle. At kernel level the
channel is the HBM↔VMEM DMA pair. A phase-separated KV-cache migration does

    kernel A: read quantized page-in blocks  -> dequantize -> write bf16
    kernel B: read bf16 page-out blocks      -> quantize   -> write int8

serially — during A the writeback direction carries only A's own output,
during B the prefetch direction only B's input. The *fused duplex kernel*
below processes both streams in one grid: every pipeline step concurrently
DMAs the next page-in block (read), the next page-out block (read), the
previous dequantized block (write) and the previous quantized block (write)
— both DMA directions stay busy with useful traffic for the whole pass,
exactly ``duplex_select_cpu``'s co-location applied to transfer streams.

Used by the serving runtime for KV-cache paging between the HBM working set
and the (int8-compressed) host pool. Validated in interpret mode against
``ref.duplex_kv_stream``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dequant_block(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _quant_block(x):
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _duplex_kernel(in_q_ref, in_scale_ref, out_x_ref,
                   in_deq_ref, out_q_ref, out_scale_ref):
    # page-in: dequantize the incoming block (HBM read -> VMEM -> HBM write)
    in_deq_ref[...] = _dequant_block(in_q_ref[...], in_scale_ref[...],
                                     in_deq_ref.dtype)
    # page-out: quantize the outgoing block (concurrent opposite direction)
    q, scale = _quant_block(out_x_ref[...])
    out_q_ref[...] = q
    out_scale_ref[...] = scale


def _dequant_kernel(in_q_ref, in_scale_ref, in_deq_ref):
    in_deq_ref[...] = _dequant_block(in_q_ref[...], in_scale_ref[...],
                                     in_deq_ref.dtype)


def _quant_kernel(out_x_ref, out_q_ref, out_scale_ref):
    q, scale = _quant_block(out_x_ref[...])
    out_q_ref[...] = q
    out_scale_ref[...] = scale


def _specs(n_blocks: int, T: int, D: int, stage: int = 1):
    """Per-grid-step block specs. ``stage`` is the staging-buffer depth:
    each grid step DMAs a slab of ``stage`` pages per stream into VMEM
    while the previous slab is being transformed (Pallas pipelines grid
    steps through double-buffered staging automatically — a deeper slab
    amortizes the per-transfer latency across more pages, the classic
    double-buffer granularity knob)."""
    blk = lambda *shape: pl.BlockSpec(shape, lambda i: (i,) + (0,) * (
        len(shape) - 1))
    return {
        "q": blk(stage, T, D),
        "scale": blk(stage, T, 1),
        "x": blk(stage, T, D),
    }


@functools.partial(jax.jit, static_argnames=("interpret",))
def dequant_stream(in_q, in_scale, *, interpret: bool = False):
    """Page-in-only half: dequantize arriving int8 pages to bf16.

    Used stand-alone when a paging step has no evictions — no zero blocks
    are streamed through a dead page-out half of the fused grid."""
    N, T, D = in_q.shape
    s = _specs(N, T, D)
    return pl.pallas_call(
        _dequant_kernel,
        grid=(N,),
        in_specs=[s["q"], s["scale"]],
        out_specs=s["x"],
        out_shape=jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(in_q, in_scale)


@functools.partial(jax.jit, static_argnames=("interpret",))
def quant_stream(out_x, *, interpret: bool = False):
    """Page-out-only half: quantize departing bf16 pages to int8 + scale.

    Used stand-alone when a paging step has no page-ins."""
    N, T, D = out_x.shape
    s = _specs(N, T, D)
    return pl.pallas_call(
        _quant_kernel,
        grid=(N,),
        in_specs=[s["x"]],
        out_specs=[s["q"], s["scale"]],
        out_shape=[
            jax.ShapeDtypeStruct((N, T, D), jnp.int8),
            jax.ShapeDtypeStruct((N, T, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(out_x)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "fused", "stage_blocks"))
def duplex_kv_stream(in_q, in_scale, out_x, *, interpret: bool = False,
                     fused: bool = True, stage_blocks: int = 1):
    """Fused duplex page-in/page-out transform.

    in_q: (N, T, D) int8 pages arriving from the host pool;
    in_scale: (N, T, 1) f32 their quantization scales;
    out_x: (N, T, D) bf16 pages being evicted to the host pool.

    Returns (in_deq (N,T,D) bf16, out_q (N,T,D) int8, out_scale (N,T,1) f32).
    ``fused=False`` runs the phase-separated two-kernel baseline — the
    stand-alone dequant/quant halves back to back (identical math; used
    for the §Perf A/B and in tests for equivalence).

    ``stage_blocks`` is the staging-buffer variant used by the serving
    pool's megastep paging: each pipelined grid step stages a slab of
    that many pages per stream (both directions), so the automatic
    double buffering prefetches the next slab of *both* streams while
    the current one transforms — fewer, deeper DMA transfers for the
    same elementwise math (N must be a multiple of ``stage_blocks``;
    callers pad with zero pages they later drop).
    """
    N, T, D = in_q.shape
    if N % stage_blocks:
        raise ValueError(
            f"duplex stream length {N} is not a multiple of the staging "
            f"depth {stage_blocks}; pad the streams")
    s = _specs(N, T, D, stage=stage_blocks)
    dim_sem = pltpu.CompilerParams(dimension_semantics=("arbitrary",))

    if fused:
        return pl.pallas_call(
            _duplex_kernel,
            grid=(N // stage_blocks,),
            in_specs=[s["q"], s["scale"], s["x"]],
            out_specs=[s["x"], s["q"], s["scale"]],
            out_shape=[
                jax.ShapeDtypeStruct((N, T, D), jnp.bfloat16),
                jax.ShapeDtypeStruct((N, T, D), jnp.int8),
                jax.ShapeDtypeStruct((N, T, 1), jnp.float32),
            ],
            compiler_params=dim_sem,
            interpret=interpret,
        )(in_q, in_scale, out_x)

    in_deq = dequant_stream(in_q, in_scale, interpret=interpret)
    out_q, out_scale = quant_stream(out_x, interpret=interpret)
    return in_deq, out_q, out_scale
