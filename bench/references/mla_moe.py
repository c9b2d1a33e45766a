"""Plain reference for an MLA and MoE decoder (DeepSeek-V3 style, as
Kimi-K2 publishes it): the whole sequence, in float32.

Follows DeepSeek-V3's ``modeling_deepseek.py``, for one chip's share of
an expert-parallel deployment as the configuration states it:

  * RMSNorm (epsilon from the file) before attention and before the
    feed-forward, and on the query and key-value latents;
  * latent attention in the plain form: ``q = W_qb rms(W_qa h)``;
    ``[c_kv, k_pe] = W_kva h``; keys and values decompressed from the
    normalised ``c_kv`` by ``W_kvb``, per head; ``k_pe`` one rotary head
    shared by every head; causal softmax at ``(nope + rope)^-0.5`` times
    the YaRN ``mscale`` squared;
  * YaRN rotary (``yarn_find_correction_range``, ``yarn_linear_ramp_mask``,
    ``yarn_get_mscale``), applied as ``apply_rotary_pos_emb`` does: the
    rotary dimensions de-interleaved, then ``x cos + rotate_half(x) sin``;
  * a dense SwiGLU in the first layers; in the others, sigmoid router
    scores over every routed expert, the top ``top_k`` of score plus
    correction bias chosen, weights the unbiased scores normalised and
    scaled, the held experts' outputs weighted and summed, plus the
    shared expert;
  * an untied head over the vocabulary slice.

Every matmul runs at ``highest`` precision; weights are read in bfloat16
and widened one layer at a time inside the layer scans.

``score`` leaves out a position where some MoE layer has a held expert
within ``ROUTE_MARGIN`` of the top-``top_k`` boundary: there a served
program in bfloat16 may choose a held expert that float32 leaves out, or
the reverse, and the token's output moves by that expert's whole share.

``quant="fp8"`` is the control: the same arithmetic with every matmul's
operands rounded to float8 (e4m3), scaled per output channel for weights
and per row for activations, as an fp8 serving path would. It imports
nothing of the served program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
#: the least ``held_margin`` at which ``score`` compares a position: 1.25
#: times the widest that bfloat16 moved a held expert's margin in the
#: served program (against this reference, at positions where no earlier
#: layer had flipped; 12 seeds on the chip at the configuration's sizes,
#: PERF.md), rounded up
ROUTE_MARGIN = 0.032


def _fq(x, axis, quant):
    """Round ``x`` to float8 with one scale per slice along ``axis``
    (the contracted axis, or axes), or leave it when ``quant`` is None."""
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _f32(w):
    return w.astype(jnp.float32)


def _mm(x, w, quant):
    """(..., din) @ (din, dout) in float32."""
    return jnp.einsum("...i,io->...o", _fq(x, -1, quant),
                      _fq(_f32(w), 0, quant), precision=HI)


def _rms(x, scale, eps):
    return (x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
            * _f32(scale))


def _yarn_get_mscale(scale, mscale):
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_tables(sz, S):
    """cos and sin (S, rope_dim), as DeepseekV3YarnRotaryEmbedding makes
    them: frequencies interpolated by the factor above the correction
    range, kept below it, concatenated twice."""
    dim, base, f = sz.rope_dim, sz.rope_theta, sz.yarn_factor

    def correction_dim(rot):
        return (dim * math.log(sz.yarn_original / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(sz.beta_fast)), 0)
    high = min(math.ceil(correction_dim(sz.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freq_extra = 1.0 / pos_freqs
    freq_inter = 1.0 / (f * pos_freqs)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = jnp.outer(jnp.arange(S, dtype=jnp.float32), inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    m = (_yarn_get_mscale(f, sz.mscale)
         / _yarn_get_mscale(f, sz.mscale_all_dim))
    return jnp.cos(emb) * m, jnp.sin(emb) * m


def softmax_scale(sz):
    scale = (sz.nope_dim + sz.rope_dim) ** -0.5
    if sz.mscale_all_dim:
        m = _yarn_get_mscale(sz.yarn_factor, sz.mscale_all_dim)
        scale = scale * m * m
    return scale


def _rotate_half(x):
    h = x.shape[-1] // 2
    return jnp.concatenate([-x[..., h:], x[..., :h]], axis=-1)


def _apply_rope(x, cos, sin):
    """x: (n, S, heads, d); the pairs (2i, 2i+1) are split into halves
    first, as the published ``apply_rotary_pos_emb`` does."""
    n, S, h, d = x.shape
    x = x.reshape(n, S, h, d // 2, 2).swapaxes(-1, -2).reshape(n, S, h, d)
    return x * cos[None, :, None, :] + _rotate_half(x) * sin[None, :, None,
                                                              :]


def _attention(a, x, sz, cos, sin, quant):
    n, S, _ = x.shape
    H, nope, rope, vd = sz.heads, sz.nope_dim, sz.rope_dim, sz.v_dim
    q = _mm(_rms(_mm(x, a["wq_a"], quant), a["q_norm"]["scale"],
                 sz.norm_eps), a["wq_b"], quant).reshape(n, S, H, nope + rope)
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    kv_a = _mm(x, a["wkv_a"], quant)
    c_kv = _rms(kv_a[..., :sz.kv_rank], a["kv_norm"]["scale"], sz.norm_eps)
    k_pe = kv_a[..., sz.kv_rank:][:, :, None, :]               # one head
    kv = _mm(c_kv, a["wkv_b"], quant).reshape(n, S, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q_pe = _apply_rope(q_pe, cos, sin)
    k_pe = _apply_rope(k_pe, cos, sin)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (n, S, H, rope))],
                        axis=-1)
    s = jnp.einsum("nqhd,nkhd->nhqk", _fq(q, -1, quant), _fq(k, -1, quant),
                   precision=HI) * softmax_scale(sz)
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    prob = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nhqk,nkhd->nqhd", _fq(prob, -1, quant),
                   _fq(v, 1, quant), precision=HI)
    return _mm(o.reshape(n, S, H * vd), a["wo"], quant)


def _swiglu(p, x, quant):
    g = _mm(x, p["w_gate"], quant)
    u = _mm(x, p["w_up"], quant)
    return _mm(jax.nn.silu(g) * u, p["w_down"], quant)


def held_margin(sel, sz):
    """How far ``sel`` (score plus bias, (..., E)) must move before a held
    expert changes sides of the top-``top_k`` boundary: for each held
    expert, a chosen one's lead over the first expert left out, or an
    unchosen one's shortfall from the last expert chosen; the least over
    the held experts, (...)."""
    top = jax.lax.top_k(sel, sz.top_k + 1)[0]
    last, first_out = top[..., -2:-1], top[..., -1:]
    held = sel[..., :sz.held]
    return jnp.min(jnp.where(held >= last, held - first_out, last - held),
                   axis=-1)


def _routed(p, x, sz, quant):
    """The held experts' part of the routed output for every token, and
    each token's ``held_margin``."""
    logits = _mm(x, p["router"], quant)
    scores = jax.nn.sigmoid(logits)                         # (n, S, E)
    sel = scores + _f32(p["bias"])
    _, idx = jax.lax.top_k(sel, sz.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if sz.top_k > 1 and sz.norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * sz.scaling
    out = jnp.zeros_like(x)
    for e in range(sz.held):       # expert e of the held experts 0 .. held
        we = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)   # (n, S)
        expert = {k: p[k][e] for k in ("w_gate", "w_up", "w_down")}
        out = out + _swiglu(expert, x, quant) * we[..., None]
    return out, held_margin(sel, sz)


def _layers(params, sz, tokens, quant):
    """Run every layer; returns the final hidden states (n, S, D) and
    each MoE layer's ``held_margin`` of each position (moe layers, n, S)."""
    S = tokens.shape[1]
    x = _f32(params["embed"][tokens])
    cos, sin = yarn_tables(sz, S)

    def block(ffn):
        def layer(x, p):
            h = _rms(x, p["ln1"]["scale"], sz.norm_eps)
            x = x + _attention(p["attn"], h, sz, cos, sin, quant)
            h = _rms(x, p["ln2"]["scale"], sz.norm_eps)
            y, margin = ffn(p, h)
            return x + y, margin
        return layer

    def moe(p, h):
        y, margin = _routed(p["moe"], h, sz, quant)
        return y + _swiglu(p["shared"], h, quant), margin

    x, _ = jax.lax.scan(
        block(lambda p, h: (_swiglu(p["mlp"], h, quant), None)), x,
        params["dense_layers"])
    return jax.lax.scan(block(moe), x, params["layers"])


def _forward(params, sz, tokens, quant):
    x, margins = _layers(params, sz, tokens, quant)
    return (_mm(_rms(x, params["ln_f"]["scale"], sz.norm_eps),
                params["lm_head"], quant), jnp.min(margins, axis=0))


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def logits(params, tokens, *, sz, quant=None):
    """Logits (n, S, vocab slice) of every position of ``tokens``."""
    return _forward(params, sz, tokens, quant)[0]


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def route_margins(params, tokens, *, sz, quant=None):
    """Each position's least ``held_margin`` over the MoE layers (n, S)."""
    return jnp.min(_layers(params, sz, tokens, quant)[1], axis=0)


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def score(params, tokens, targets, *, sz, quant=None):
    """For each position of ``tokens`` (n, S): how far the logit of
    ``targets`` (n, S) lies below the best logit, and which token is
    best. Both (n, S), float32 and int32. The gap reads 0 at a position
    where some MoE layer has a held expert within ``ROUTE_MARGIN`` of
    the top-``top_k`` boundary: there bfloat16 may choose otherwise than
    float32, and either choice is the model's."""
    lg, margin = _forward(params, sz, tokens, quant)
    best = jnp.max(lg, axis=-1)
    got = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    gap = jnp.where(margin >= ROUTE_MARGIN, best - got, 0.0)
    return gap, jnp.argmax(lg, axis=-1).astype(jnp.int32)
