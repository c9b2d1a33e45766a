"""Random weights for a dense decoder LM, made on the device from a seed.

The tree is the one the served program takes (``embed``, stacked
``layers`` with ``ln1``/``attn``/``ln2``/``mlp``, ``ln_f`` and, untied,
``lm_head``); the benchmark checks it against the program's own
``init`` shapes before serving, and the plain reference reads the same
tree. One jitted call makes every leaf, in bfloat16, directly on the
device.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp


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


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also past 32 bits."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 32)),
                              seed >> 32)


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
