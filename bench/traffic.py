"""The one traffic generator: reads a traffic file, makes requests from a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

  ``loop``     ``"open"``: arrivals on the wall clock at ``rate_per_s``
               (Poisson gaps), each request timed from when it was due;
               ``"closed"``: ``clients`` callers, each sending its next
               request when the last token of its previous one arrived.
  ``lead_s``   seconds the traffic runs before the measured window
               opens (served, not counted), so that the window starts
               from a steady state.
  ``prompt``, ``output``
               token lengths, lognormal: ``median``, ``sigma`` (natural
               log), clipped to ``[min, max]``.

Lengths and arrival gaps are the distribution's quantiles at
``(i + 0.5) / n``, in a low-discrepancy order (the radical inverse of the
request's index, in base 2 for outputs, 3 for prompts and 5 for gaps), so
that any run of consecutive requests holds short and long ones in their
proportions while the three sequences do not line up with each other.
The sequence of sizes and gaps is the same for every seed: when the seed
chose the order, a window of a closed loop carried different work from
seed to seed and its throughput swung by a tenth, where two runs of one
seed agreed to a fifth of a percent. The seed draws the token ids,
uniformly from the vocabulary, and the weights.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

#: requests a closed-loop client may send in one run, at most.
CLOSED_POOL_PER_CLIENT = 256


@dataclasses.dataclass
class Planned:
    """One request as the traffic makes it, before it is sent."""
    prompt: np.ndarray        # (P,) int32
    max_new: int
    due_s: float | None       # open loop: seconds after the traffic starts


def _check_lengths(spec: dict, what: str) -> None:
    for key in ("median", "sigma", "min", "max"):
        if key not in spec:
            raise ValueError(f"traffic {what} lacks {key!r}")
    if not 1 <= spec["min"] <= spec["median"] <= spec["max"]:
        raise ValueError(f"traffic {what}: need 1 <= min <= median <= max")


def radical_inverse(i: int, base: int) -> float:
    """``i``'s digits in ``base``, mirrored about the radix point."""
    out, f = 0.0, 1.0 / base
    while i:
        i, d = divmod(i, base)
        out += d * f
        f /= base
    return out


def spread_order(n: int, base: int) -> np.ndarray:
    """A permutation of ``range(n)`` that puts the sorted values' ranks in
    low-discrepancy order: position ``i`` takes the rank of the radical
    inverse of ``i + 1`` among all ``n``."""
    keys = np.array([radical_inverse(i + 1, base) for i in range(n)])
    return np.argsort(np.argsort(keys, kind="stable"), kind="stable")


def stratified_lengths(spec: dict, n: int, base: int = 2) -> np.ndarray:
    """``n`` lognormal lengths at evenly spaced quantiles, clipped, in
    ``spread_order``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    x = np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)
    return x[spread_order(n, base)]


def stratified_gaps(rate: float, n: int, base: int = 5) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (mean ``1 / rate``) at evenly
    spaced quantiles, in ``spread_order``."""
    q = (np.arange(n) + 0.5) / n
    return (-np.log1p(-q) / rate)[spread_order(n, base)]


def max_tokens(traffic: dict) -> int:
    """The longest request the traffic can make (prompt plus output)."""
    return int(traffic["prompt"]["max"] + traffic["output"]["max"])


class Traffic:
    """Requests for one run of a cell, made from its traffic file and a
    seed."""

    def __init__(self, traffic: dict, seed: int, vocab: int,
                 seconds: float):
        _check_lengths(traffic["prompt"], "prompt")
        _check_lengths(traffic["output"], "output")
        self.spec = traffic
        self.loop = traffic["loop"]
        self.vocab = int(vocab)
        rng = np.random.default_rng(seed)
        if self.loop == "open":
            rate = float(traffic["rate_per_s"])
            if rate <= 0:
                raise ValueError("open-loop traffic needs rate_per_s > 0")
            n = math.ceil(rate * seconds * 1.25) + 8
            gaps = stratified_gaps(rate, n)
            due = np.cumsum(gaps) - gaps[0]   # the first is due at once
        elif self.loop == "closed":
            self.clients = int(traffic["clients"])
            if self.clients < 1:
                raise ValueError("closed-loop traffic needs clients >= 1")
            n = self.clients * CLOSED_POOL_PER_CLIENT
            due = [None] * n
        else:
            raise ValueError(f"unknown traffic loop {self.loop!r}")
        plen = stratified_lengths(traffic["prompt"], n, base=3)
        olen = stratified_lengths(traffic["output"], n, base=2)
        ids = rng.integers(0, self.vocab, size=int(plen.sum()),
                           dtype=np.int64).astype(np.int32)
        cuts = np.cumsum(plen)[:-1]
        self.requests = [
            Planned(prompt=p, max_new=int(o),
                    due_s=None if d is None else float(d))
            for p, o, d in zip(np.split(ids, cuts), olen, due)]

    def __len__(self) -> int:
        return len(self.requests)
