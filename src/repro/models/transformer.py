"""Decoder-only transformer LM (dense + MoE + SWA + prefix-LM).

Covers six of the ten assigned architectures: smollm-135m, stablelm-3b,
qwen2.5-14b, llama3.2-3b, mixtral-8x7b, paligemma-3b (the VLM: a gemma
decoder with prefix-LM masking over stubbed patch embeddings). Kimi-K2's
latent attention and sparse experts are ``models/mla_moe.py``.

Layers are stacked with a leading L axis and consumed by ``lax.scan`` so a
deep config lowers to a compact HLO (critical for multi-pod dry-run
compile times).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.models import layers as nn
from repro.models import runconfig
from repro.models.layers import AttnSpec, MoESpec


@dataclasses.dataclass(frozen=True)
class LMConfig:
    name: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: int = 0                   # 0 -> d_model // num_heads
    qkv_bias: bool = False
    moe: MoESpec | None = None
    window: int | None = None           # sliding-window attention
    rope_theta: float = 10000.0
    prefix_len: int = 0                 # prefix-LM prefix (paligemma)
    embed_scale: bool = False           # gemma-style sqrt(d) embed scaling
    tie_embeddings: bool = True
    dtype: jnp.dtype = jnp.bfloat16

    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.num_heads

    def attn_spec(self, prefix_len: int | None = None) -> AttnSpec:
        return AttnSpec(
            num_heads=self.num_heads,
            num_kv_heads=self.num_kv_heads,
            head_dim=self.resolved_head_dim(),
            causal=True,
            window=self.window,
            prefix_len=self.prefix_len if prefix_len is None else prefix_len,
            qkv_bias=self.qkv_bias,
            rope_theta=self.rope_theta,
        )

    def param_count(self) -> int:
        """Analytic parameter count (for 6·N·D roofline math)."""
        hd = self.resolved_head_dim()
        attn = self.d_model * hd * (self.num_heads * 2
                                    + self.num_kv_heads * 2)
        if self.moe is not None:
            ffn = (self.d_model * self.moe.num_experts
                   + 3 * self.moe.num_experts * self.d_model * self.d_ff)
        else:
            ffn = 3 * self.d_model * self.d_ff
        per_layer = attn + ffn + 2 * self.d_model
        embed = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + embed + self.d_model

    def active_param_count(self) -> int:
        """Params touched per token (MoE: top-k experts only)."""
        if self.moe is None:
            return self.param_count()
        hd = self.resolved_head_dim()
        attn = self.d_model * hd * (self.num_heads * 2
                                    + self.num_kv_heads * 2)
        ffn = (self.d_model * self.moe.num_experts
               + 3 * self.moe.top_k * self.d_model * self.d_ff)
        per_layer = attn + ffn + 2 * self.d_model
        embed = self.vocab * self.d_model * (1 if self.tie_embeddings else 2)
        return self.num_layers * per_layer + embed + self.d_model


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(key, cfg: LMConfig):
    ks = jax.random.split(key, 4)
    p = {
        "ln1": nn.rmsnorm_init(cfg.d_model, cfg.dtype),
        "attn": nn.attn_init(ks[0], cfg.d_model, cfg.attn_spec(), cfg.dtype),
        "ln2": nn.rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if cfg.moe is not None:
        p["moe"] = nn.moe_init(ks[1], cfg.d_model, cfg.d_ff, cfg.moe,
                               cfg.dtype)
    else:
        p["mlp"] = nn.swiglu_init(ks[1], cfg.d_model, cfg.d_ff, cfg.dtype)
    return p


def init(key, cfg: LMConfig):
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params = {
        "embed": nn.embed_init(k_embed, cfg.vocab, cfg.d_model, cfg.dtype),
        "layers": jax.vmap(lambda k: _layer_init(k, cfg))(layer_keys),
        "ln_f": nn.rmsnorm_init(cfg.d_model, cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = nn.dense_init(k_head, cfg.d_model, cfg.vocab,
                                          cfg.dtype)
    return params


# ---------------------------------------------------------------------------
# forward (training / prefill)
# ---------------------------------------------------------------------------

def _embed_tokens(params, cfg: LMConfig, tokens, prefix_embeds):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if prefix_embeds is not None:
        P = prefix_embeds.shape[1]
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x[:, P:]], axis=1)
    return x


def _unembed(params, cfg: LMConfig, x):
    if cfg.tie_embeddings:
        return x @ params["embed"].T
    return x @ params["lm_head"]


def forward(params, cfg: LMConfig, tokens, prefix_embeds=None,
            use_kernel: bool = False, return_kv: bool = False):
    """tokens: (B, S) int32 -> logits (B, S, V) [+ stacked per-layer (k, v)].

    ``prefix_embeds`` (B, P, D) replaces the first P embedding rows and the
    attn mask makes those P kv positions bidirectionally visible (prefix-LM).
    """
    B, S = tokens.shape
    spec = cfg.attn_spec()
    x = _embed_tokens(params, cfg, tokens, prefix_embeds)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))

    def body(x, layer):
        x = runconfig.constrain(x, ("dp", None, None))
        h = nn.rmsnorm(layer["ln1"], x)
        if return_kv:
            # recompute k, v for cache building (prefill path)
            kproj = h @ layer["attn"]["wk"]
            vproj = h @ layer["attn"]["wv"]
            if cfg.qkv_bias:
                kproj = kproj + layer["attn"]["bk"]
                vproj = vproj + layer["attn"]["bv"]
            kv = (nn.rope(kproj.reshape(B, S, spec.num_kv_heads,
                                        spec.head_dim),
                          positions, spec.rope_theta),
                  vproj.reshape(B, S, spec.num_kv_heads, spec.head_dim))
        else:
            kv = None
        x = x + nn.attn_apply(layer["attn"], h, spec, positions, use_kernel)
        h = nn.rmsnorm(layer["ln2"], x)
        if cfg.moe is not None:
            y = nn.moe_apply(layer["moe"], h, cfg.moe)
            aux = nn.moe_aux_loss(layer["moe"], h, cfg.moe)
        else:
            y = nn.swiglu(layer["mlp"], h)
            aux = jnp.float32(0.0)
        return x + y, (aux, kv)

    x, (aux_losses, kvs) = runconfig.scan(body, x, params["layers"])
    x = nn.rmsnorm(params["ln_f"], x)
    logits = runconfig.constrain(_unembed(params, cfg, x),
                                 ("dp", None, "tp"))
    aux = jnp.mean(aux_losses)
    if return_kv:
        return logits, aux, kvs
    return logits, aux


def loss_fn(params, cfg: LMConfig, batch, use_kernel: bool = False,
            aux_weight: float = 0.01):
    logits, aux = forward(params, cfg, batch["tokens"],
                          batch.get("prefix_embeds"), use_kernel)
    ce = nn.cross_entropy(logits, batch["labels"])
    return ce + aux_weight * aux, {"ce": ce, "aux": aux}


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def cache_width(cfg: LMConfig, cache_len: int) -> int:
    return min(cache_len, cfg.window) if cfg.window else cache_len


def init_cache(cfg: LMConfig, batch: int, cache_len: int):
    W = cache_width(cfg, cache_len)
    spec = cfg.attn_spec()

    def one(_):
        return nn.attn_cache_init(batch, W, spec, cfg.dtype)

    return jax.vmap(one)(jnp.arange(cfg.num_layers))


def decode_step(params, cfg: LMConfig, cache, tokens, pos,
                prefix_embeds=None):
    """One decode step. tokens: (B,) int32; pos: (B,) absolute positions.

    The stacked ring cache (leaves (L, B, W, ...)) rides in the layer
    scan's carry and each layer writes only its new token's K, V and
    position in place (``nn.attn_decode_layer``): one token per layer per
    pass, never a rebuilt copy of the whole cache. Returns (logits (B, V),
    the updated cache). The prefix mask is irrelevant at decode (all
    cached positions are visible to the new token).
    """
    spec = cfg.attn_spec(prefix_len=0)
    x = params["embed"][tokens][:, None, :]
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)

    def body(carry, scanned):
        x, cache = carry
        layer, i = scanned
        h = nn.rmsnorm(layer["ln1"], x)
        y, cache = nn.attn_decode_layer(layer["attn"], h, cache, i, pos,
                                        spec)
        x = x + y
        h = nn.rmsnorm(layer["ln2"], x)
        if cfg.moe is not None:
            x = x + nn.moe_apply(layer["moe"], h, cfg.moe)
        else:
            x = x + nn.swiglu(layer["mlp"], h)
        return (x, cache), None

    (x, cache), _ = runconfig.scan(
        body, (x, cache), (params["layers"], jnp.arange(cfg.num_layers)))
    x = nn.rmsnorm(params["ln_f"], x)
    logits = runconfig.constrain(_unembed(params, cfg, x[:, 0, :]),
                                 ("dp", "tp"))
    return logits, cache


def prefill(params, cfg: LMConfig, tokens, prefix_embeds=None,
            cache_len: int | None = None):
    """Full-sequence forward that also builds the decode cache."""
    B, S = tokens.shape
    W = cache_width(cfg, cache_len or S)
    logits, aux, kvs = forward(params, cfg, tokens, prefix_embeds,
                               return_kv=True)
    k_all, v_all = kvs   # (L, B, S, KV, hd)
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    take = min(S, W)
    # last `take` positions land in ring slots pos % W.
    sl = slice(S - take, S)
    pos_tail = positions[:, sl]
    slots = (pos_tail % W).astype(jnp.int32)            # (B, take)
    cache = init_cache(cfg, B, W)
    bidx = jnp.arange(B)[:, None]

    def scatter(lcache, k_l, v_l):
        return {
            "k": lcache["k"].at[bidx, slots].set(k_l[:, sl]),
            "v": lcache["v"].at[bidx, slots].set(v_l[:, sl]),
            "pos": lcache["pos"].at[bidx, slots].set(pos_tail.astype(
                jnp.int32)),
        }

    cache = jax.vmap(scatter)(cache, k_all, v_all)
    return logits, cache
