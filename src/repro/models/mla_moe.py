"""DeepSeek-V3-style decoder: latent attention (MLA) and sparse experts.

Serves Kimi-K2 (``configs/kimi_k2_1t.py``). The equations are those of
DeepSeek-V3's ``modeling_deepseek.py``:

  * MLA. ``q = W_qb · rms_q(W_qa · h)``, split per head into ``q_nope``
    and ``q_pe``; ``[c_kv, k_pe] = W_kva · h`` with ``c_kv`` normalised
    by ``rms_kv`` and ``k_pe`` one rotary head that every head shares;
    ``[k_nope, v] = W_kvb · c_kv`` per head. Rotary embedding is YaRN over
    ``qk_rope_head_dim`` (``yarn_inv_freq``), applied to de-interleaved
    pairs as the published code does, and the softmax scale carries the
    YaRN factor squared (``softmax_scale``).
  * MoE layers. Sigmoid router scores over every routed expert, in
    float32; the top ``top_k`` of score plus correction bias are chosen,
    and their weights are the scores without the bias, normalised and
    scaled by ``routed_scaling_factor``. A shared expert is always added.
    The first ``first_k_dense`` layers have a dense SwiGLU instead.

The layer holds ``held_experts = (first, count)`` of the routed experts,
as one chip of an expert-parallel deployment does: the router routes over
all of them and the layer adds only its held experts' part. It drops
nothing, so a token's output never depends on the other rows.

Decode keeps a latent ring cache (``c_kv`` and the rotated ``k_pe`` per
layer, layer-first and batch-second like every cache the engine serves)
and attends in the absorbed form: ``W_UK`` folds into the query and
``W_UV`` into the output, so a step reads only the latent cache. The
cache rides through the layer loop and each layer writes its new token
in place. ``forward`` (training and tests) decompresses keys and values
and attends in the plain form.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as nn
from repro.models import runconfig


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    name: str
    num_layers: int                  # every decoder layer, dense ones first
    d_model: int
    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    d_ff: int                        # the dense layers' SwiGLU width
    moe_d_ff: int                    # each routed and shared expert's width
    n_routed_experts: int
    top_k: int
    vocab: int
    n_shared_experts: int = 1
    first_k_dense: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    # YaRN (``rope_scaling``); factor 1 is plain rotary
    rope_factor: float = 1.0
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    norm_eps: float = 1e-6
    # the routed experts this chip holds, (first, count); None holds all
    held_experts: tuple[int, int] | None = None
    dtype: jnp.dtype = jnp.bfloat16

    def __post_init__(self):
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_routed_experts):
            raise ValueError(f"held experts {self.held} outside the "
                             f"{self.n_routed_experts} routed experts")
        if not 0 <= self.first_k_dense <= self.num_layers:
            raise ValueError("first_k_dense exceeds num_layers")

    @property
    def held(self) -> tuple[int, int]:
        return self.held_experts or (0, self.n_routed_experts)

    @property
    def num_moe_layers(self) -> int:
        return self.num_layers - self.first_k_dense

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def _attn_params(self) -> int:
        D, H, r = self.d_model, self.num_heads, self.kv_lora_rank
        return (D * self.q_lora_rank + self.q_lora_rank
                + self.q_lora_rank * H * self.qk_head_dim
                + D * (r + self.qk_rope_head_dim) + r
                + r * H * (self.qk_nope_head_dim + self.v_head_dim)
                + H * self.v_head_dim * D)

    def _counts(self, experts: int) -> int:
        D = self.d_model
        expert = 3 * D * self.moe_d_ff
        dense = self._attn_params() + 3 * D * self.d_ff + 2 * D
        moe = (self._attn_params() + D * self.n_routed_experts
               + self.n_routed_experts
               + (experts + self.n_shared_experts) * expert + 2 * D)
        embed = 2 * self.vocab * D          # untied: embedding and head
        return (self.first_k_dense * dense + self.num_moe_layers * moe
                + embed + D)

    def param_count(self) -> int:
        """Parameters of the model as configured, every routed expert
        counted (not only the held ones)."""
        return self._counts(self.n_routed_experts)

    def active_param_count(self) -> int:
        """Parameters one token touches: ``top_k`` routed experts."""
        return self._counts(self.top_k)


# ---------------------------------------------------------------------------
# YaRN rotary embedding (DeepSeek-V3's DeepseekV3YarnRotaryEmbedding)
# ---------------------------------------------------------------------------

def yarn_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_correction_range(cfg: MLAMoEConfig) -> tuple[int, int]:
    """The rotary pairs below ``low`` keep their frequency, those above
    ``high`` are divided by the factor (``yarn_find_correction_range``)."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta

    def correction_dim(rotations):
        return (dim * math.log(cfg.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = math.floor(correction_dim(cfg.beta_fast))
    high = math.ceil(correction_dim(cfg.beta_slow))
    return max(low, 0), min(high, dim - 1)


def yarn_inv_freq(cfg: MLAMoEConfig) -> np.ndarray:
    """(qk_rope_head_dim / 2,) float32 inverse frequencies."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    exps = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = (1.0 / base ** exps).astype(np.float32)
    if cfg.rope_factor <= 1:
        return extra
    inter = (1.0 / (cfg.rope_factor * base ** exps)).astype(np.float32)
    low, high = yarn_correction_range(cfg)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp                # 1 where the frequency is kept
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def rotary_mscale(cfg: MLAMoEConfig) -> float:
    """The factor on cos and sin (``_mscale``)."""
    return (yarn_mscale(cfg.rope_factor, cfg.mscale)
            / yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim))


def softmax_scale(cfg: MLAMoEConfig) -> float:
    scale = cfg.qk_head_dim ** -0.5
    if cfg.rope_factor > 1 and cfg.mscale_all_dim:
        m = yarn_mscale(cfg.rope_factor, cfg.mscale_all_dim)
        scale *= m * m
    return scale


def _rotary(cfg: MLAMoEConfig, positions):
    """cos, sin of shape positions.shape + (rope / 2,), float32."""
    ang = (positions.astype(jnp.float32)[..., None]
           * jnp.asarray(yarn_inv_freq(cfg)))
    m = rotary_mscale(cfg)
    return jnp.cos(ang) * m, jnp.sin(ang) * m


def _rope(x, cos, sin):
    """Rotate the interleaved pairs (2i, 2i+1) of ``x``'s last axis by
    frequency i and return them de-interleaved (first elements, then
    second), as DeepSeek-V3's ``apply_rotary_pos_emb`` does. ``cos`` and
    ``sin`` broadcast against ``x[..., ::2]``."""
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _attn_init(key, cfg: MLAMoEConfig):
    ks = jax.random.split(key, 5)
    D, H, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    dt = cfg.dtype
    return {
        "wq_a": nn.dense_init(ks[0], D, cfg.q_lora_rank, dt),
        "q_norm": nn.rmsnorm_init(cfg.q_lora_rank, dt),
        "wq_b": nn.dense_init(ks[1], cfg.q_lora_rank, H * cfg.qk_head_dim,
                              dt),
        "wkv_a": nn.dense_init(ks[2], D, r + cfg.qk_rope_head_dim, dt),
        "kv_norm": nn.rmsnorm_init(r, dt),
        "wkv_b": nn.dense_init(
            ks[3], r, H * (cfg.qk_nope_head_dim + cfg.v_head_dim), dt),
        "wo": nn.dense_init(ks[4], H * cfg.v_head_dim, D, dt),
    }


def _dense_layer_init(key, cfg: MLAMoEConfig):
    ka, kf = jax.random.split(key)
    return {"ln1": nn.rmsnorm_init(cfg.d_model, cfg.dtype),
            "attn": _attn_init(ka, cfg),
            "ln2": nn.rmsnorm_init(cfg.d_model, cfg.dtype),
            "mlp": nn.swiglu_init(kf, cfg.d_model, cfg.d_ff, cfg.dtype)}


def _moe_layer_init(key, cfg: MLAMoEConfig):
    ka, kr, ke, ks = jax.random.split(key, 4)
    D, F, dt = cfg.d_model, cfg.moe_d_ff, cfg.dtype
    _, count = cfg.held
    kg, ku, kd = jax.random.split(ke, 3)

    def experts(k, a, b):
        return (jax.random.normal(k, (count, a, b), jnp.float32)
                / math.sqrt(a)).astype(dt)

    return {"ln1": nn.rmsnorm_init(D, dt),
            "attn": _attn_init(ka, cfg),
            "ln2": nn.rmsnorm_init(D, dt),
            "moe": {"router": nn.dense_init(kr, D, cfg.n_routed_experts, dt),
                    "bias": jnp.zeros((cfg.n_routed_experts,), dt),
                    "w_gate": experts(kg, D, F), "w_up": experts(ku, D, F),
                    "w_down": experts(kd, F, D)},
            "shared": nn.swiglu_init(ks, D, F * cfg.n_shared_experts, dt)}


def init(key, cfg: MLAMoEConfig):
    ke, kd, km, kh = jax.random.split(key, 4)
    return {
        "embed": nn.embed_init(ke, cfg.vocab, cfg.d_model, cfg.dtype),
        "dense_layers": jax.vmap(lambda k: _dense_layer_init(k, cfg))(
            jax.random.split(kd, cfg.first_k_dense)),
        "layers": jax.vmap(lambda k: _moe_layer_init(k, cfg))(
            jax.random.split(km, cfg.num_moe_layers)),
        "ln_f": nn.rmsnorm_init(cfg.d_model, cfg.dtype),
        "lm_head": nn.dense_init(kh, cfg.d_model, cfg.vocab, cfg.dtype),
    }


# ---------------------------------------------------------------------------
# experts
# ---------------------------------------------------------------------------

def route(p, x, cfg: MLAMoEConfig):
    """Top-``top_k`` experts of each row of ``x`` (T, D) and their
    weights: (T, k) int32 ids over all routed experts, (T, k) float32."""
    logits = jnp.dot(x, p["router"], preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + p["bias"].astype(jnp.float32),
                           cfg.top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg.top_k > 1 and cfg.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return idx, w * cfg.routed_scaling_factor


def held_weights(idx, w, cfg: MLAMoEConfig):
    """(T, count) float32: each held expert's routing weight for each
    row, 0 where the row did not choose it."""
    first, count = cfg.held
    held = first + jnp.arange(count)
    return jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)


def held_experts(p, x, comb):
    """The held experts' part of the layer for rows ``x`` (T, D): each
    row's SwiGLU through every held expert, weighted by ``comb`` (T,
    count), 0 for an expert the row did not choose. Each row is computed
    alone, so nothing is dropped and no row depends on another."""
    g = jnp.einsum("td,edf->tef", x, p["w_gate"],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("td,edf->tef", x, p["w_up"],
                   preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u * comb[:, :, None]).astype(x.dtype)
    return jnp.einsum("tef,efd->td", h, p["w_down"])


def moe(layer, x, cfg: MLAMoEConfig):
    """Routed (held share) plus shared experts, x: (B, S, D)."""
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("moe/route"):
        comb = held_weights(*route(layer["moe"], xt, cfg), cfg)
    with jax.named_scope("moe/experts"):
        y = held_experts(layer["moe"], xt, comb).reshape(B, S, D)
    with jax.named_scope("moe/shared"):
        return y + nn.swiglu(layer["shared"], x)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _project(a, h, cfg: MLAMoEConfig, cos, sin):
    """Queries and the latent of ``h`` (..., D): q_nope (..., H, nope),
    rotated q_pe (..., H, rope), c_kv (..., r), rotated k_pe (..., rope)."""
    H = cfg.num_heads
    q = nn.rmsnorm(a["q_norm"], h @ a["wq_a"], cfg.norm_eps) @ a["wq_b"]
    q = q.reshape(h.shape[:-1] + (H, cfg.qk_head_dim))
    q_nope = q[..., :cfg.qk_nope_head_dim]
    q_pe = _rope(q[..., cfg.qk_nope_head_dim:], cos[..., None, :],
                 sin[..., None, :])
    kv = h @ a["wkv_a"]
    c_kv = nn.rmsnorm(a["kv_norm"], kv[..., :cfg.kv_lora_rank],
                      cfg.norm_eps)
    k_pe = _rope(kv[..., cfg.kv_lora_rank:], cos, sin)
    return q_nope, q_pe, c_kv, k_pe


def _split_kvb(a, cfg: MLAMoEConfig):
    """W_kvb as (r, H, nope) for keys and (r, H, v) for values."""
    w = a["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_heads,
                           cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def mla_forward(a, h, cfg: MLAMoEConfig, cos, sin):
    """Causal MLA over a whole sequence in the plain form. h: (B, S, D);
    cos/sin: (B, S, rope / 2)."""
    B, S, _ = h.shape
    H = cfg.num_heads
    with jax.named_scope("mla/project"):
        q_nope, q_pe, c_kv, k_pe = _project(a, h, cfg, cos, sin)
        w_uk, w_uv = _split_kvb(a, cfg)
        k_nope = jnp.einsum("bsr,rhn->bshn", c_kv, w_uk)
        v = jnp.einsum("bsr,rhv->bshv", c_kv, w_uv)
    with jax.named_scope("mla/attend"):
        s = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhp,bkp->bhqk", q_pe, k_pe,
                          preferred_element_type=jnp.float32))
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, s * softmax_scale(cfg), nn.NEG_INF)
        prob = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bhqk,bkhv->bqhv", prob, v)
    with jax.named_scope("mla/project"):
        return o.reshape(B, S, H * cfg.v_head_dim) @ a["wo"]


def mla_decode(a, h, cfg: MLAMoEConfig, cache, layer, pos, cos, sin):
    """One token per row in the absorbed form. h: (B, D); pos: (B,);
    cache leaves (L, B, W, ...) with this layer at index ``layer``.
    Writes the token's latent into its ring slot first, then attends the
    layer's slots whose stored position is at most ``pos``."""
    B = h.shape[0]
    W = cache["pos"].shape[2]
    brange = jnp.arange(B)
    slot = (pos % W).astype(jnp.int32)
    with jax.named_scope("mla/project"):
        q_nope, q_pe, c_new, k_new = _project(a, h, cfg, cos, sin)
        cache = {
            "c_kv": cache["c_kv"].at[layer, brange, slot].set(c_new),
            "k_pe": cache["k_pe"].at[layer, brange, slot].set(k_new),
            "pos": cache["pos"].at[layer, brange, slot].set(
                pos.astype(jnp.int32)),
        }
    with jax.named_scope("mla/attend"):
        c_kv, k_pe, kv_pos = (cache["c_kv"][layer], cache["k_pe"][layer],
                              cache["pos"][layer])
        w_uk, w_uv = _split_kvb(a, cfg)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(h.dtype)
        s = (jnp.einsum("bhr,bwr->bhw", q_lat, c_kv,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bhp,bwp->bhw", q_pe, k_pe,
                          preferred_element_type=jnp.float32))
        visible = (kv_pos >= 0) & (kv_pos <= pos[:, None])
        s = jnp.where(visible[:, None, :], s * softmax_scale(cfg),
                      nn.NEG_INF)
        prob = jax.nn.softmax(s, axis=-1).astype(h.dtype)
        o_lat = jnp.einsum("bhw,bwr->bhr", prob, c_kv,
                           preferred_element_type=jnp.float32
                           ).astype(h.dtype)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, w_uv)
    with jax.named_scope("mla/project"):
        return o.reshape(B, -1) @ a["wo"], cache


# ---------------------------------------------------------------------------
# forward (training / tests)
# ---------------------------------------------------------------------------

def forward(params, cfg: MLAMoEConfig, tokens):
    """tokens: (B, S) int32 -> logits (B, S, V)."""
    B, S = tokens.shape
    x = params["embed"][tokens]
    cos, sin = _rotary(cfg, jnp.broadcast_to(jnp.arange(S), (B, S)))

    def block(ffn):
        def body(x, layer):
            x = runconfig.constrain(x, ("dp", None, None))
            h = nn.rmsnorm(layer["ln1"], x, cfg.norm_eps)
            x = x + mla_forward(layer["attn"], h, cfg, cos, sin)
            h = nn.rmsnorm(layer["ln2"], x, cfg.norm_eps)
            return x + ffn(layer, h), None
        return body

    x, _ = runconfig.scan(block(lambda l, h: nn.swiglu(l["mlp"], h)), x,
                          params["dense_layers"])
    x, _ = runconfig.scan(block(lambda l, h: moe(l, h, cfg)), x,
                          params["layers"])
    x = nn.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return runconfig.constrain(x @ params["lm_head"], ("dp", None, "tp"))


def loss_fn(params, cfg: MLAMoEConfig, batch):
    ce = nn.cross_entropy(forward(params, cfg, batch["tokens"]),
                          batch["labels"])
    return ce, {"ce": ce}


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: MLAMoEConfig, batch: int, cache_len: int):
    L, W = cfg.num_layers, cache_len
    return {
        "c_kv": jnp.zeros((L, batch, W, cfg.kv_lora_rank), cfg.dtype),
        "k_pe": jnp.zeros((L, batch, W, cfg.qk_rope_head_dim), cfg.dtype),
        "pos": -jnp.ones((L, batch, W), jnp.int32),
    }


def decode_step(params, cfg: MLAMoEConfig, cache, tokens, pos):
    """One decode step. tokens, pos: (B,) int32. Returns (logits (B, V),
    the cache with each layer's new latent written in place)."""
    x = params["embed"][tokens][:, None, :]                 # (B, 1, D)
    cos, sin = _rotary(cfg, pos)

    def block(ffn, first_layer):
        def body(carry, scanned):
            x, cache = carry
            layer, i = scanned
            h = nn.rmsnorm(layer["ln1"], x, cfg.norm_eps)
            y, cache = mla_decode(layer["attn"], h[:, 0], cfg, cache,
                                  first_layer + i, pos, cos, sin)
            x = x + y[:, None, :]
            h = nn.rmsnorm(layer["ln2"], x, cfg.norm_eps)
            return (x + ffn(layer, h), cache), None
        return body

    k = cfg.first_k_dense
    (x, cache), _ = runconfig.scan(
        block(lambda l, h: nn.swiglu(l["mlp"], h), 0), (x, cache),
        (params["dense_layers"], jnp.arange(k)))
    (x, cache), _ = runconfig.scan(
        block(lambda l, h: moe(l, h, cfg), k), (x, cache),
        (params["layers"], jnp.arange(cfg.num_moe_layers)))
    x = nn.rmsnorm(params["ln_f"], x[:, 0], cfg.norm_eps)
    return runconfig.constrain(x @ params["lm_head"], ("dp", "tp")), cache
