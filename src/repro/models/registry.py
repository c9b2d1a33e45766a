"""Model registry — uniform API over the ten assigned architectures.

``build(arch_id)`` returns a ``ModelAPI`` whose members close over the arch
config; ``input_specs(api, shape)`` returns weak-type-correct
ShapeDtypeStruct stand-ins for every model input of that (arch × shape)
cell — the dry-run lowers against these without allocating (the kimi-k2
config is 1T params; nothing at full scale is ever materialized on CPU).

Shape cells (assignment):
  train_4k     seq 4,096   gbatch 256   -> train_step
  prefill_32k  seq 32,768  gbatch 32    -> serve prefill (full forward)
  decode_32k   seq 32,768  gbatch 128   -> serve_step (1 token, 32k cache)
  long_500k    seq 524,288 gbatch 1     -> serve_step; SSM/SWA/hybrid only
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import configs as configs_lib
from repro.models import encdec, hybrid, mla_moe, rwkv6, transformer
from repro.models.transformer import LMConfig


class ShapeCell(NamedTuple):
    name: str
    seq_len: int
    global_batch: int
    kind: str              # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeCell] = {
    "train_4k": ShapeCell("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeCell("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeCell("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeCell("long_500k", 524_288, 1, "decode"),
}

# archs whose decode state is sub-quadratic-safe at 500k (DESIGN §6).
LONG_CONTEXT_OK = frozenset({"rwkv6-7b", "mixtral-8x7b", "zamba2-7b"})

FAMILY = {
    "smollm-135m": "dense", "stablelm-3b": "dense", "qwen2.5-14b": "dense",
    "llama3.2-3b": "dense", "rwkv6-7b": "ssm", "mixtral-8x7b": "moe",
    "kimi-k2-1t-a32b": "moe", "whisper-base": "audio",
    "zamba2-7b": "hybrid", "paligemma-3b": "vlm",
}


class ModelAPI(NamedTuple):
    arch_id: str
    family: str
    cfg: Any
    init: Callable                # (key) -> params
    loss_fn: Callable             # (params, batch) -> (loss, metrics)
    forward: Callable             # (params, batch) -> logits
    init_cache: Callable          # (batch, cache_len) -> cache
    decode_step: Callable         # (params, cache, tokens, pos) -> (logits, cache)
    param_count: int
    active_param_count: int
    # "ring": every cache leaf is token-indexed (a K/V ring overwrites a
    # stale entry before it is read); "recurrent": the cache carries state
    # that any decode_step advances irreversibly (RWKV wkv/shifts, Mamba).
    cache_kind: str = "ring"
    # Serving donation / multi-step contract: ``decode_step`` must be a
    # pure function of (params, cache, tokens, pos) — safe to (a) invoke
    # repeatedly inside one jitted ``lax.scan``/``lax.cond`` (the engine's
    # fused step loop runs prefill_chunk micro-steps in one XLA program
    # with on-device argmax feedback) and (b) have its cache argument
    # buffer-donated, i.e. the returned cache may alias the input's
    # buffers and the caller rebinds (``jax.jit(decode_step,
    # donate_argnums=(1,))``). Every registry family satisfies this; an
    # arch that cannot (host callbacks, per-call RNG, external cache
    # aliasing) must set it False and ``ServeEngine`` will refuse it.
    fused_decode: bool = True


def runnable(arch_id: str, shape: str) -> bool:
    """Whether this (arch × shape) cell is assigned to run (DESIGN §6)."""
    if shape == "long_500k":
        return arch_id in LONG_CONTEXT_OK
    return True


def skip_reason(arch_id: str, shape: str) -> str | None:
    if runnable(arch_id, shape):
        return None
    return ("full-attention arch: O(S^2) prefill / unbounded KV at 500k; "
            "run only for SSM/SWA/hybrid archs per assignment")


def cells(shapes: tuple[str, ...] = tuple(SHAPES)) -> list[tuple[str, str]]:
    """All runnable (arch, shape) cells, in table order."""
    return [(a, s) for a in configs_lib.ARCH_IDS for s in shapes
            if runnable(a, s)]


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def _lm_api(arch_id: str, cfg: LMConfig) -> ModelAPI:
    is_vlm = cfg.prefix_len > 0

    def loss(params, batch):
        return transformer.loss_fn(params, cfg, batch)

    def fwd(params, batch):
        logits, _ = transformer.forward(params, cfg, batch["tokens"],
                                        batch.get("prefix_embeds"))
        return logits

    return ModelAPI(
        arch_id=arch_id, family=FAMILY.get(arch_id, "dense"), cfg=cfg,
        init=functools.partial(transformer.init, cfg=cfg),
        loss_fn=loss, forward=fwd,
        init_cache=lambda batch, cache_len: transformer.init_cache(
            cfg, batch, cache_len),
        decode_step=lambda params, cache, tokens, pos: transformer.
        decode_step(params, cfg, cache, tokens, pos),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
    )


def _rwkv_api(arch_id: str, cfg) -> ModelAPI:
    return ModelAPI(
        arch_id=arch_id, family="ssm", cfg=cfg,
        init=functools.partial(rwkv6.init, cfg=cfg),
        loss_fn=lambda params, batch: rwkv6.loss_fn(params, cfg, batch),
        forward=lambda params, batch: rwkv6.forward(
            params, cfg, batch["tokens"])[0],
        init_cache=lambda batch, cache_len: rwkv6.init_cache(
            cfg, batch, cache_len),
        decode_step=lambda params, cache, tokens, pos: rwkv6.decode_step(
            params, cfg, cache, tokens, pos),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
        cache_kind="recurrent",
    )


def _hybrid_api(arch_id: str, cfg) -> ModelAPI:
    return ModelAPI(
        arch_id=arch_id, family="hybrid", cfg=cfg,
        init=functools.partial(hybrid.init, cfg=cfg),
        loss_fn=lambda params, batch: hybrid.loss_fn(params, cfg, batch),
        forward=lambda params, batch: hybrid.forward(
            params, cfg, batch["tokens"])[0],
        init_cache=lambda batch, cache_len: hybrid.init_cache(
            cfg, batch, cache_len),
        decode_step=lambda params, cache, tokens, pos: hybrid.decode_step(
            params, cfg, cache, tokens, pos),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
        cache_kind="recurrent",
    )


def _encdec_api(arch_id: str, cfg) -> ModelAPI:
    def cache_init(batch, cache_len):
        # cross-KV sized to the encoder length (== cache_len cell semantics)
        return encdec.init_cache(cfg, batch, cache_len, enc_len=cache_len)

    return ModelAPI(
        arch_id=arch_id, family="audio", cfg=cfg,
        init=functools.partial(encdec.init, cfg=cfg),
        loss_fn=lambda params, batch: encdec.loss_fn(params, cfg, batch),
        forward=lambda params, batch: encdec.forward(
            params, cfg, batch["tokens"], batch["frames"])[0],
        init_cache=cache_init,
        decode_step=lambda params, cache, tokens, pos: encdec.decode_step(
            params, cfg, cache, tokens, pos),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
    )


def _mla_moe_api(arch_id: str, cfg: mla_moe.MLAMoEConfig) -> ModelAPI:
    return ModelAPI(
        arch_id=arch_id, family=FAMILY.get(arch_id, "moe"), cfg=cfg,
        init=functools.partial(mla_moe.init, cfg=cfg),
        loss_fn=lambda params, batch: mla_moe.loss_fn(params, cfg, batch),
        forward=lambda params, batch: mla_moe.forward(params, cfg,
                                                      batch["tokens"]),
        init_cache=lambda batch, cache_len: mla_moe.init_cache(
            cfg, batch, cache_len),
        decode_step=lambda params, cache, tokens, pos: mla_moe.decode_step(
            params, cfg, cache, tokens, pos),
        param_count=cfg.param_count(),
        active_param_count=cfg.active_param_count(),
    )


def build(arch_id: str, smoke: bool = False) -> ModelAPI:
    cfg = configs_lib.get_config(arch_id, smoke=smoke)
    if isinstance(cfg, LMConfig):
        return _lm_api(arch_id, cfg)
    if isinstance(cfg, mla_moe.MLAMoEConfig):
        return _mla_moe_api(arch_id, cfg)
    if isinstance(cfg, rwkv6.RWKVConfig):
        return _rwkv_api(arch_id, cfg)
    if isinstance(cfg, hybrid.HybridConfig):
        return _hybrid_api(arch_id, cfg)
    if isinstance(cfg, encdec.EncDecConfig):
        return _encdec_api(arch_id, cfg)
    raise TypeError(f"unknown config type {type(cfg)} for {arch_id}")


# ---------------------------------------------------------------------------
# input specs (ShapeDtypeStruct stand-ins; never allocates)
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def input_specs(api: ModelAPI, shape_name: str,
                batch_override: int | None = None) -> dict[str, Any]:
    """Inputs for the cell's step function, as ShapeDtypeStructs.

    train/prefill: {"tokens", "labels"[, "frames"|"prefix_embeds"]}
    decode: {"cache", "tokens", "pos"} where cache comes from
    ``jax.eval_shape`` over ``init_cache`` (no allocation).
    """
    cell = SHAPES[shape_name]
    B = batch_override or cell.global_batch
    S = cell.seq_len
    cfg = api.cfg
    tok = jnp.int32

    if cell.kind in ("train", "prefill"):
        specs: dict[str, Any] = {"tokens": _sds((B, S), tok)}
        if cell.kind == "train":
            specs["labels"] = _sds((B, S), tok)
        if api.family == "audio":
            specs["frames"] = _sds((B, S, cfg.d_model), jnp.bfloat16)
        if api.family == "vlm":
            specs["prefix_embeds"] = _sds((B, cfg.prefix_len, cfg.d_model),
                                          jnp.bfloat16)
        return specs

    # decode: one new token against a seq_len-deep cache
    cache = jax.eval_shape(lambda: api.init_cache(B, S))
    return {
        "cache": cache,
        "tokens": _sds((B,), tok),
        "pos": _sds((B,), tok),
    }
