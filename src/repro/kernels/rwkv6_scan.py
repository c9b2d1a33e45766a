"""Chunked WKV6 scan Pallas-TPU kernel.

The RWKV6 recurrence S ← diag(w_t)·S + k_tᵀv_t is the serving hot-spot of
the attention-free arch (rwkv6-7b decode is the paper-workload analogue of
its LLM evaluation). The kernel tiles time into chunks; the (hs × hs) f32
state lives in VMEM scratch and persists across the sequential chunk grid
dimension, so HBM traffic is exactly one read of (r,k,v,w) and one write of
the output — the state never round-trips.

Grid: (B*H, num_chunks); chunk dim innermost/sequential.
Validated in interpret mode against ``ref.wkv6``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, state_ref, *,
                chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    u = u_ref[0].astype(jnp.float32)                       # (hs,)

    def step(t, state):
        r_t = r_ref[0, t].astype(jnp.float32)              # (hs,)
        k_t = k_ref[0, t].astype(jnp.float32)
        v_t = v_ref[0, t].astype(jnp.float32)
        w_t = w_ref[0, t].astype(jnp.float32)
        kv = k_t[:, None] * v_t[None, :]                   # (hs, hs)
        out = (r_t[None, :] @ (state + u[:, None] * kv))[0]
        o_ref[0, t] = out.astype(o_ref.dtype)
        return w_t[:, None] * state + kv

    state_ref[...] = jax.lax.fori_loop(0, chunk, step, state_ref[...])


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 128, interpret: bool = False):
    """r,k,v,w: (B, S, H, hs); u: (H, hs). Returns out (B, S, H, hs).

    Time is tiled into ``chunk``-length blocks; the per-(b,h) state persists
    in VMEM across blocks (sequential grid dim).
    """
    B, S, H, hs = r.shape
    ch = min(chunk, S)
    if S % ch:
        raise ValueError(f"S={S} must be divisible by chunk={ch}")
    nc = S // ch

    def flat(x):  # (B,S,H,hs) -> (B*H, S, hs)
        return jnp.moveaxis(x, 2, 1).reshape(B * H, S, hs)

    rf, kf, vf, wf = flat(r), flat(k), flat(v), flat(w)

    seq_spec = pl.BlockSpec((1, ch, hs), lambda bh, c: (bh, c, 0))
    u_spec = pl.BlockSpec((1, hs), lambda bh, c, H=H: (bh % H, 0))

    out = pl.pallas_call(
        functools.partial(_wkv_kernel, chunk=ch),
        grid=(B * H, nc),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec],
        out_specs=seq_spec,
        out_shape=jax.ShapeDtypeStruct((B * H, S, hs), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, u.astype(jnp.float32))
    return jnp.moveaxis(out.reshape(B, H, S, hs), 1, 2)
