"""The dense decoder LM family: its sizes, the program it is served by,
its random weights, and the yardstick's counts of its work.

A configuration file names this module in ``program.family``. The
harness reads its sizes with ``sizes``, asks ``build`` for the program's
model API, makes the weights with ``make_params`` and counts FLOPs and
bytes with ``weight_bytes``, ``context_kv_bytes`` and
``positions_flops``; a family of another architecture is another module
with the same six names.

The weight tree is the one the served program takes (``embed``, stacked
``layers`` with ``ln1``/``attn``/``ln2``/``mlp``, ``ln_f`` and, untied,
``lm_head``); the benchmark checks it against the program's own
``init`` shapes before serving, and the plain reference
(``bench/references/dense_lm.py``) reads the same tree. One jitted call
makes every leaf, in bfloat16, directly on the device.

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


@dataclasses.dataclass(frozen=True)
class Sizes:
    """The shape of a dense decoder LM, read from a configuration file's
    published keys."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    rope_theta: float
    rope_pct: float
    norm_eps: float

    @classmethod
    def of(cls, config: dict) -> "Sizes":
        d, h = config["hidden_size"], config["num_attention_heads"]
        eps = config.get("rms_norm_eps", config.get("norm_eps"))
        if eps is None:
            raise KeyError("configuration states no norm epsilon")
        return cls(layers=config["num_hidden_layers"], d_model=d, heads=h,
                   kv_heads=config["num_key_value_heads"],
                   head_dim=config.get("head_dim", d // h),
                   d_ff=config["intermediate_size"],
                   vocab=config["vocab_size"],
                   tied=bool(config["tie_word_embeddings"]),
                   rope_theta=float(config.get("rope_theta", 10000.0)),
                   rope_pct=float(config.get("rope_pct", 1.0)),
                   norm_eps=float(eps))

    def param_count(self) -> int:
        attn = self.d_model * self.head_dim * 2 * (self.heads
                                                   + self.kv_heads)
        per_layer = attn + 3 * self.d_model * self.d_ff + 2 * self.d_model
        embed = self.vocab * self.d_model * (1 if self.tied else 2)
        return self.layers * per_layer + embed + self.d_model


def sizes(config: dict) -> Sizes:
    return Sizes.of(config)


def build(config: dict, sz: Sizes):
    """The program's model API for the configuration, checked against the
    program's own entry for the architecture."""
    from repro.configs import get_config
    from repro.models import registry
    from repro.models.transformer import LMConfig
    if sz.rope_pct != 1.0:
        raise ValueError("the served program rotates whole heads; this "
                         "configuration states partial rotary")
    lm = LMConfig(name=config["program"]["arch"], num_layers=sz.layers,
                  d_model=sz.d_model, num_heads=sz.heads,
                  num_kv_heads=sz.kv_heads, head_dim=sz.head_dim,
                  d_ff=sz.d_ff, vocab=sz.vocab, rope_theta=sz.rope_theta,
                  tie_embeddings=sz.tied)
    prog = get_config(config["program"]["arch"])
    for f in ("num_layers", "d_model", "num_heads", "num_kv_heads",
              "d_ff", "vocab", "tie_embeddings", "rope_theta"):
        if getattr(prog, f) != getattr(lm, f):
            raise ValueError(f"the program's {prog.name} has {f}="
                             f"{getattr(prog, f)}, the configuration "
                             f"file {getattr(lm, f)}")
    return registry._lm_api(config["program"]["arch"], lm)


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, sz: Sizes):
    L, D, F, V = sz.layers, sz.d_model, sz.d_ff, sz.vocab
    qd, kvd = sz.heads * sz.head_dim, sz.kv_heads * sz.head_dim
    keys = iter(jax.random.split(key, 16))
    bf = jnp.bfloat16

    def dense(shape, fan_in):
        return (jax.random.normal(next(keys), shape, bf)
                * bf(fan_in ** -0.5))

    def scale(shape):
        # near 1, not 1: a path that skipped a norm's scale would differ
        return 1 + jax.random.normal(next(keys), shape, bf) * bf(0.05)

    params = {
        "embed": jax.random.normal(next(keys), (V, D), bf) * bf(0.02),
        "layers": {
            "ln1": {"scale": scale((L, D))},
            "attn": {"wq": dense((L, D, qd), D), "wk": dense((L, D, kvd), D),
                     "wv": dense((L, D, kvd), D),
                     "wo": dense((L, qd, D), qd)},
            "ln2": {"scale": scale((L, D))},
            "mlp": {"w_gate": dense((L, D, F), D),
                    "w_up": dense((L, D, F), D),
                    "w_down": dense((L, F, D), F)},
        },
        "ln_f": {"scale": scale((D,))},
    }
    if not sz.tied:
        params["lm_head"] = dense((D, V), D)
    return params


def make_params(sz: Sizes, seed: int):
    """Every weight of the model, bfloat16, on the default device."""
    return _make(seed_key(seed), sz)


def matmul_params(sz: Sizes) -> int:
    """Weights that take part in a matmul for every token: every layer's
    projections and feed-forward, and the output projection (the
    embedding matrix itself when tied). The input embedding is a row
    lookup and takes no FLOPs."""
    attn = sz.d_model * sz.head_dim * 2 * (sz.heads + sz.kv_heads)
    return sz.layers * (attn + 3 * sz.d_model * sz.d_ff) \
        + sz.d_model * sz.vocab


def positions_flops(sz: Sizes, positions) -> float:
    """Model FLOPs of processing one token at each of ``positions``
    (0-based): two per matmul weight, plus attention's score and value
    products over the ``position + 1`` keys each attends (two FLOPs per
    multiply-add each)."""
    n = len(positions)
    s = sum(positions) + n
    return (2.0 * matmul_params(sz) * n
            + 4.0 * sz.layers * sz.heads * sz.head_dim * s)


def weight_bytes(sz: Sizes) -> int:
    """Bytes of weights one decode pass reads from HBM (bfloat16): every
    layer, the final norm and the output projection; the input embedding
    is a gather of a few rows and is left out."""
    norms = (2 * sz.layers + 1) * sz.d_model
    return BF16 * (matmul_params(sz) + norms)


def kv_bytes_per_token(sz: Sizes) -> int:
    """Key and value bytes one cached token holds over all layers."""
    return BF16 * 2 * sz.layers * sz.kv_heads * sz.head_dim


def context_kv_bytes(sz: Sizes, positions) -> float:
    """Bytes of cached keys and values read to process one token at each
    of ``positions``: its real context, ``position + 1`` tokens."""
    return float(kv_bytes_per_token(sz)) * (sum(positions) + len(positions))
