"""Plain reference for a dense decoder LM: the whole sequence, in float32.

Follows the published description of a llama-style decoder, as the
configuration file states it is run: RMSNorm (epsilon from the file),
rotary position embedding over the first ``rope_pct`` of each head (the
two halves of that span rotated against each other), causal grouped
query attention (query head ``h`` reads key/value head ``h // (heads /
kv_heads)``), a SwiGLU feed-forward, and tied or untied output
projection. Every matmul runs at ``highest`` precision; weights are
read in bfloat16 and widened one layer at a time inside the layer scan.

``quant="fp8"`` is the control: the same arithmetic with every matmul's
operands rounded to float8 (e4m3), scaled per output channel for weights
and per row for activations, as an fp8 serving path would. It imports
nothing of the served program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fq(x, axis, quant):
    """Round ``x`` to float8 with one scale per slice along ``axis``
    (the contracted axis), or leave it when ``quant`` is None."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, quant):
    """(..., din) @ (din, dout) in float32."""
    return jnp.einsum("...i,io->...o", _fq(x, -1, quant),
                      _fq(w.astype(jnp.float32), 0, quant), precision=HI)


def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * scale.astype(jnp.float32))


def _rope(x, sz):
    """x: (n, S, heads, hd); rotate the first rope_pct of each head."""
    hd = x.shape[-1]
    rot = int(hd * sz.rope_pct)
    half = rot // 2
    if half == 0:
        return x
    pos = jnp.arange(x.shape[1], dtype=jnp.float32)
    freqs = sz.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _layers(params, sz, tokens, quant):
    """Run every layer; returns the final hidden states (n, S, D) and the
    per-layer post-rotary keys and values (L, n, S, KV, hd) each."""
    n, S = tokens.shape
    H, KV, hd = sz.heads, sz.kv_heads, sz.head_dim
    G = H // KV
    x = params["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = _rms(x, p["ln1"]["scale"], sz.norm_eps)
        q = _rope(_mm(h, p["attn"]["wq"], quant).reshape(n, S, H, hd), sz)
        k = _rope(_mm(h, p["attn"]["wk"], quant).reshape(n, S, KV, hd), sz)
        v = _mm(h, p["attn"]["wv"], quant).reshape(n, S, KV, hd)
        qg = q.reshape(n, S, KV, G, hd)
        s = jnp.einsum("nqkgd,nskd->nkgqs", _fq(qg, -1, quant),
                       _fq(k, -1, quant), precision=HI) * hd ** -0.5
        s = jnp.where(causal, s, -jnp.inf)
        prob = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("nkgqs,nskd->nqkgd", _fq(prob, -1, quant),
                       _fq(v, 1, quant), precision=HI)
        x = x + _mm(o.reshape(n, S, H * hd), p["attn"]["wo"], quant)
        h = _rms(x, p["ln2"]["scale"], sz.norm_eps)
        g = _mm(h, p["mlp"]["w_gate"], quant)
        u = _mm(h, p["mlp"]["w_up"], quant)
        x = x + _mm(jax.nn.silu(g) * u, p["mlp"]["w_down"], quant)
        return x, (k, v)

    x, (ks, vs) = jax.lax.scan(layer, x, params["layers"])
    return x, ks, vs


def _logits(params, sz, x, quant):
    x = _rms(x, params["ln_f"]["scale"], sz.norm_eps)
    head = params["embed"].T if sz.tied else params["lm_head"]
    return _mm(x, head, quant)


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def score(params, tokens, targets, *, sz, quant=None):
    """For each position of ``tokens`` (n, S): how far the logit of
    ``targets`` (n, S) lies below the best logit, and which token is
    best. Both (n, S), float32 and int32."""
    x, _, _ = _layers(params, sz, tokens, quant)
    logits = _logits(params, sz, x, quant)
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return best - got, jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def keys_values(params, tokens, *, sz, quant=None):
    """Post-rotary keys and values of every layer at every position of
    ``tokens`` (n, S): (L, n, S, 2, KV, hd) float32."""
    _, ks, vs = _layers(params, sz, tokens, quant)
    return jnp.stack([ks, vs], axis=3)
