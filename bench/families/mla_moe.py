"""The latent-attention, sparse-expert family (DeepSeek-V3 style, as
Kimi-K2 publishes it): its sizes, the program it is served by, its random
weights, and the yardstick's counts of its work.

A configuration file names this module in ``program.family`` and states
one chip's share of an expert-parallel deployment: ``num_hidden_layers``
(the dense layers first), ``n_routed_experts`` as the experts this chip
holds (experts ``0 .. n - 1``) and ``vocab_size`` as its slice of the
vocabulary; ``published`` gives the uncut values, and the router keeps
the published expert count as its width. The weight tree is the served
program's (``repro.models.mla_moe``: ``embed``, stacked ``dense_layers``
and MoE ``layers``, ``ln_f``, ``lm_head``); the benchmark checks it
against the program's own ``init`` shapes before serving, and the plain
reference (``bench/references/mla_moe.py``) reads the same tree. One
jitted call makes every leaf, in bfloat16, directly on the device.

The counts are kept with the benchmark so that no change to the program
can change them: model FLOPs per token at a given context, and the HBM
bytes a decode pass must read.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from bench.weights import seed_key

BF16 = 2

#: keys whose value the program implements and does not read: anything
#: else in the file is refused
FIXED = {"hidden_act": "silu", "attention_bias": False, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc",
         "scoring_func": "sigmoid", "moe_layer_freq": 1,
         "num_nextn_predict_layers": 0, "tie_word_embeddings": False}


@dataclasses.dataclass(frozen=True)
class Sizes:
    """One chip's share of an MLA and MoE decoder, read from a
    configuration file."""
    layers: int               # all layers here, the dense ones first
    dense_layers: int
    d_model: int
    heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    d_ff: int                 # the dense layers' width
    expert_ff: int            # each routed and shared expert's width
    router_experts: int       # the router's width: every routed expert
    held: int                 # routed experts held here, 0 .. held - 1
    shared: int
    top_k: int
    scaling: float
    norm_topk: bool
    vocab: int                # the vocabulary rows held here
    rope_theta: float
    yarn_factor: float
    yarn_original: int
    beta_fast: float
    beta_slow: float
    mscale: float
    mscale_all_dim: float
    norm_eps: float

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @classmethod
    def of(cls, config: dict) -> "Sizes":
        for key, want in FIXED.items():
            if config[key] != want:
                raise ValueError(f"the served program has {key}={want!r}, "
                                 f"the configuration {config[key]!r}")
        if config["num_key_value_heads"] != config["num_attention_heads"]:
            raise ValueError("MLA decompresses one key and value per head")
        yarn = config["rope_scaling"]
        if yarn["type"] != "yarn":
            raise ValueError("the served program's rotary scaling is YaRN")
        return cls(
            layers=config["num_hidden_layers"],
            dense_layers=config["first_k_dense_replace"],
            d_model=config["hidden_size"],
            heads=config["num_attention_heads"],
            q_rank=config["q_lora_rank"], kv_rank=config["kv_lora_rank"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"],
            d_ff=config["intermediate_size"],
            expert_ff=config["moe_intermediate_size"],
            router_experts=config["published"]["n_routed_experts"],
            held=config["n_routed_experts"],
            shared=config["n_shared_experts"],
            top_k=config["num_experts_per_tok"],
            scaling=float(config["routed_scaling_factor"]),
            norm_topk=bool(config["norm_topk_prob"]),
            vocab=config["vocab_size"],
            rope_theta=float(config["rope_theta"]),
            yarn_factor=float(yarn["factor"]),
            yarn_original=int(yarn["original_max_position_embeddings"]),
            beta_fast=float(yarn["beta_fast"]),
            beta_slow=float(yarn["beta_slow"]),
            mscale=float(yarn["mscale"]),
            mscale_all_dim=float(yarn["mscale_all_dim"]),
            norm_eps=float(config["rms_norm_eps"]))


def sizes(config: dict) -> Sizes:
    return Sizes.of(config)


def build(config: dict, sz: Sizes):
    """The program's model API for the chip's share, checked against the
    program's own entry for the architecture: every key the file does
    not list as reduced must be the program's."""
    from repro.configs import get_config
    from repro.models import registry
    prog = get_config(config["program"]["arch"])
    want = dict(
        d_model=sz.d_model, num_heads=sz.heads, q_lora_rank=sz.q_rank,
        kv_lora_rank=sz.kv_rank, qk_nope_head_dim=sz.nope_dim,
        qk_rope_head_dim=sz.rope_dim, v_head_dim=sz.v_dim, d_ff=sz.d_ff,
        moe_d_ff=sz.expert_ff, n_routed_experts=sz.router_experts,
        n_shared_experts=sz.shared, top_k=sz.top_k,
        first_k_dense=sz.dense_layers, routed_scaling_factor=sz.scaling,
        norm_topk_prob=sz.norm_topk, rope_theta=sz.rope_theta,
        rope_factor=sz.yarn_factor, original_max_position=sz.yarn_original,
        beta_fast=sz.beta_fast, beta_slow=sz.beta_slow, mscale=sz.mscale,
        mscale_all_dim=sz.mscale_all_dim, norm_eps=sz.norm_eps)
    # the cut keys: the published value is the program's, and the file's
    # own value is the program's unless the file lists it as reduced
    cut = {"num_hidden_layers": ("num_layers", sz.layers),
           "vocab_size": ("vocab", sz.vocab),
           "n_routed_experts": ("n_routed_experts", sz.held)}
    for key, (f, v) in cut.items():
        if config["published"][key] != getattr(prog, f):
            raise ValueError(f"published {key} {config['published'][key]}"
                             f", the program's {f} {getattr(prog, f)}")
        if key not in config["reduced"] and v != getattr(prog, f):
            raise ValueError(f"{key} {v} is not the program's "
                             f"{getattr(prog, f)} and not listed reduced")
    for f, v in want.items():
        if getattr(prog, f) != v:
            raise ValueError(f"the program's {prog.name} has {f}="
                             f"{getattr(prog, f)}, the configuration "
                             f"file {v}")
    cfg = dataclasses.replace(prog, num_layers=sz.layers, vocab=sz.vocab,
                              held_experts=(0, sz.held))
    return registry._mla_moe_api(config["program"]["arch"], cfg)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, sz: Sizes):
    D, H, r = sz.d_model, sz.heads, sz.kv_rank
    keys = iter(jax.random.split(key, 40))
    bf = jnp.bfloat16

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, bf)
                * bf(fan_in ** -0.5))

    def scale(shape):
        # near 1, not 1: a path that skipped a norm's scale would differ
        return 1 + jax.random.normal(next(keys), shape, bf) * bf(0.05)

    def layer(n):
        return {
            "ln1": {"scale": scale((n, D))},
            "attn": {
                "wq_a": dense((n, D, sz.q_rank), D),
                "q_norm": {"scale": scale((n, sz.q_rank))},
                "wq_b": dense((n, sz.q_rank,
                               H * (sz.nope_dim + sz.rope_dim)), sz.q_rank),
                "wkv_a": dense((n, D, r + sz.rope_dim), D),
                "kv_norm": {"scale": scale((n, r))},
                "wkv_b": dense((n, r, H * (sz.nope_dim + sz.v_dim)), r),
                "wo": dense((n, H * sz.v_dim, D), H * sz.v_dim)},
            "ln2": {"scale": scale((n, D))},
        }

    def swiglu(n, width):
        return {"w_gate": dense((n, D, width), D),
                "w_up": dense((n, D, width), D),
                "w_down": dense((n, width, D), width)}

    L, E, F = sz.moe_layers, sz.held, sz.expert_ff
    moe = layer(L)
    moe["moe"] = {
        "router": dense((L, D, sz.router_experts), D),
        # a small correction bias: it moves which experts are chosen
        "bias": jax.random.normal(next(keys), (L, sz.router_experts), bf)
        * bf(0.01),
        "w_gate": dense((L, E, D, F), D), "w_up": dense((L, E, D, F), D),
        "w_down": dense((L, E, F, D), F)}
    moe["shared"] = swiglu(L, F * sz.shared)
    first = layer(sz.dense_layers)
    first["mlp"] = swiglu(sz.dense_layers, sz.d_ff)
    return {
        "embed": jax.random.normal(next(keys), (sz.vocab, D), bf)
        * bf(0.02),
        "dense_layers": first,
        "layers": moe,
        "ln_f": {"scale": scale((D,))},
        "lm_head": dense((D, sz.vocab), D),
    }


def make_params(sz: Sizes, seed: int):
    """Every weight of the chip's share, bfloat16, on the default device."""
    return _make(seed_key(seed), sz)


def attention_params(sz: Sizes) -> int:
    """One layer's MLA projection weights: W_qa, W_qb, W_kva, W_kvb (as
    the absorbed W_UK and W_UV, the same multiply-adds) and W_o."""
    H = sz.heads
    return (sz.d_model * sz.q_rank
            + sz.q_rank * H * (sz.nope_dim + sz.rope_dim)
            + sz.d_model * (sz.kv_rank + sz.rope_dim)
            + sz.kv_rank * H * (sz.nope_dim + sz.v_dim)
            + H * sz.v_dim * sz.d_model)


def expert_params(sz: Sizes) -> int:
    """One routed or shared expert's SwiGLU weights."""
    return 3 * sz.d_model * sz.expert_ff


def positions_flops(sz: Sizes, positions) -> float:
    """Model FLOPs of processing one token at each of ``positions``
    (0-based), two per multiply-add. Per token: every MLA projection and
    dense feed-forward weight; in each MoE layer the router at its full
    width, the shared expert, and the routed experts at their expected
    share of this chip's work, ``top_k * held / router_experts`` experts'
    weights (as if routing were uniform); the head over the vocabulary
    slice. The input embedding is a row lookup. Attention in the absorbed
    form over the ``position + 1`` keys each token reads: per layer and
    head, ``kv_rank + rope_dim`` multiply-adds for the score and
    ``kv_rank`` for the value, i.e. ``2 * layers * heads * (2 * kv_rank +
    rope_dim)`` FLOPs per key."""
    n = len(positions)
    keys = sum(positions) + n
    routed = sz.top_k * sz.held / sz.router_experts * expert_params(sz)
    per_token = (sz.layers * attention_params(sz)
                 + sz.dense_layers * 3 * sz.d_model * sz.d_ff
                 + sz.moe_layers * (sz.d_model * sz.router_experts
                                    + sz.shared * expert_params(sz)
                                    + routed)
                 + sz.d_model * sz.vocab)
    return (2.0 * per_token * n
            + 2.0 * sz.layers * sz.heads * (2 * sz.kv_rank + sz.rope_dim)
            * keys)


def weight_bytes(sz: Sizes) -> int:
    """Bytes of weights one decode pass reads from HBM (bfloat16): every
    layer's MLA weights and norms, the dense feed-forward, every MoE
    layer's router and correction bias, all ``held`` experts (a pass of a
    full batch reaches each of them) and the shared expert, the final
    norm and the head. The input embedding is a gather of a few rows and
    is left out."""
    D = sz.d_model
    norms = sz.layers * (2 * D + sz.q_rank + sz.kv_rank) + D
    moe = (D * sz.router_experts + sz.router_experts
           + (sz.held + sz.shared) * expert_params(sz))
    return BF16 * (sz.layers * attention_params(sz) + norms
                   + sz.dense_layers * 3 * D * sz.d_ff
                   + sz.moe_layers * moe + D * sz.vocab)


def latent_bytes_per_token(sz: Sizes) -> int:
    """Latent cache bytes one token holds over all layers: ``c_kv`` and
    the rotated ``k_pe``, bfloat16."""
    return BF16 * (sz.kv_rank + sz.rope_dim) * sz.layers


def context_kv_bytes(sz: Sizes, positions) -> float:
    """Bytes of latent cache read to process one token at each of
    ``positions``: its real context, ``position + 1`` tokens of ``c_kv``
    and ``k_pe`` in every layer (the absorbed form reads nothing else)."""
    return (float(latent_bytes_per_token(sz))
            * (sum(positions) + len(positions)))
