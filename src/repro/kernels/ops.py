"""jit'd public wrappers for the Pallas kernels.

On the CPU backend the wrappers default to ``interpret=True`` so the
kernel bodies execute in Python for correctness validation; on any other
backend they compile natively (a kernel that cannot compile there fails
loudly instead of silently interpreting). The pure-jnp oracles live in
``ref.py``.
"""

from __future__ import annotations

import jax

from repro.kernels import duplex_stream as _ds
from repro.kernels import flash_attention as _fa
from repro.kernels import rwkv6_scan as _rs
from repro.kernels import vector_distance as _vd


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def flash_attention(q, k, v, *, causal=True, window=None, prefix_len=0,
                    q_block=128, kv_block=128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               prefix_len=prefix_len, q_block=q_block,
                               kv_block=kv_block, interpret=interpret)


def duplex_kv_stream(in_q, in_scale, out_x, *, fused=True, interpret=None,
                     stage_blocks=1):
    if interpret is None:
        interpret = _default_interpret()
    return _ds.duplex_kv_stream(in_q, in_scale, out_x, fused=fused,
                                interpret=interpret,
                                stage_blocks=stage_blocks)


def dequant_kv_stream(in_q, in_scale, *, interpret=None):
    """Single-direction page-in transform (no page-out stream to fuse)."""
    if interpret is None:
        interpret = _default_interpret()
    return _ds.dequant_stream(in_q, in_scale, interpret=interpret)


def quant_kv_stream(out_x, *, interpret=None):
    """Single-direction page-out transform (no page-in stream to fuse)."""
    if interpret is None:
        interpret = _default_interpret()
    return _ds.quant_stream(out_x, interpret=interpret)


def l2_distance(queries, blocks, *, interpret=None):
    """Batched query-to-block L2 distances (vector-search tenant)."""
    if interpret is None:
        interpret = _default_interpret()
    return _vd.l2_distance(queries, blocks, interpret=interpret)


def wkv6(r, k, v, w, u, *, chunk=128, interpret=None):
    if interpret is None:
        interpret = _default_interpret()
    return _rs.wkv6(r, k, v, w, u, chunk=chunk, interpret=interpret)
