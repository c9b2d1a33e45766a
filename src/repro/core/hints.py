"""Hierarchical memory-access hints — the cgroup mechanism of CXLAimPod §4.5.

The paper conveys application hints through the cgroup filesystem because it
is standardized, hierarchical (system defaults -> container -> process), and
secure. The JAX-framework analogue is a ``HintTree``: a tree of named scopes
(``/`` = system, ``/train``, ``/train/attention``, ``/serve/kv_cache``...)
each optionally carrying a ``MemoryHint``. Unset fields inherit from the
nearest ancestor that sets them, mirroring cgroup hierarchical composition.

Model configs and offload streams attach hint paths; the scheduler resolves
them at plan-build time. ``HintTree`` is plain Python (config-level); the
resolved numeric hints are lowered to arrays for the jit'd scheduler.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Iterator


_UNSET = None


@dataclasses.dataclass(frozen=True)
class MemoryHint:
    """Declared expectations for one scope. ``None`` = inherit.

    Attributes:
      read_fraction: expected fraction of traffic (by bytes) that is reads.
      sequential: access pattern (True sequential / False random).
      priority: scheduling weight (vruntime weight in Algorithm 1).
      phase_period_us: if the workload alternates direction phases, their
        period; lets the time-series policy seed its forecast.
      duplex_opt_in: scopes may opt out of duplex intervention entirely
        (the paper's answer to the Redis read-heavy regression).
      tier: host-memory tier preference for this scope's spilled blocks
        ("ddr5" | "cxl"). A tier set on the path wins; otherwise
        ``resolved()`` derives one from the resolved traffic mix at the
        leaf, and a derived tier is never inherited.
    """

    read_fraction: float | None = None
    sequential: bool | None = None
    priority: float | None = None
    phase_period_us: float | None = None
    duplex_opt_in: bool | None = None
    tier: str | None = None

    FIELDS = ("read_fraction", "sequential", "priority", "phase_period_us",
              "duplex_opt_in", "tier")

    def __post_init__(self):
        if self.tier is not None:
            from repro.core.channel import TIER_PRESETS
            if self.tier not in TIER_PRESETS:
                raise ValueError(
                    f"unknown tier {self.tier!r}; known tier kinds: "
                    f"{','.join(sorted(TIER_PRESETS))}")

    def merged_over(self, parent: "MemoryHint") -> "MemoryHint":
        """Child values win; unset child fields inherit from parent."""
        values = {}
        for f in self.FIELDS:
            mine = getattr(self, f)
            values[f] = mine if mine is not _UNSET else getattr(parent, f)
        return MemoryHint(**values)

    def resolved(self) -> "MemoryHint":
        """Fill remaining unset fields with system defaults, and an unset
        ``tier`` with the one the resolved traffic mix asks for."""
        h = self.merged_over(SYSTEM_DEFAULT)
        if h.tier is not None:
            return h
        return dataclasses.replace(h, tier=_derived_tier(h))


SYSTEM_DEFAULT = MemoryHint(read_fraction=0.5, sequential=False,
                            priority=1.0, phase_period_us=0.0,
                            duplex_opt_in=True)


def _derived_tier(h: MemoryHint) -> str:
    """The §3 placement rule for a scope that names no tier: mixed
    read/write scopes belong on full-duplex CXL channels, where their
    opposing directions overlap; unidirectional (read- or write-mostly,
    past the ~4:1 point where the paper's withdrawal doctrine kicks in)
    and duplex-withdrawn scopes gain nothing from duplexing and go to the
    low-latency half-duplex DDR5 channels, which serve a single direction
    at full rate with no turnaround tax. ``h`` has every other field
    resolved."""
    if h.duplex_opt_in is False:
        return "ddr5"
    rf = float(h.read_fraction)
    return "ddr5" if (rf >= 0.8 or rf <= 0.2) else "cxl"


def preferred_tier(hint: MemoryHint) -> str:
    """Host-tier preference for a scope's spilled blocks (§3 placement):
    the tier its resolved hint carries."""
    return hint.resolved().tier


def _split(path: str) -> list[str]:
    if not path.startswith("/"):
        raise ValueError(f"hint path must be absolute, got {path!r}")
    return [p for p in path.split("/") if p]


class HintTree:
    """A cgroup-like hierarchy of MemoryHints."""

    def __init__(self) -> None:
        self._hints: dict[str, MemoryHint] = {"/": MemoryHint()}

    # -- mutation ----------------------------------------------------------
    def set(self, path: str, hint: MemoryHint) -> None:
        parts = _split(path)
        # materialize intermediate scopes so iteration order is stable
        for i in range(1, len(parts)):
            inter = "/" + "/".join(parts[:i])
            self._hints.setdefault(inter, MemoryHint())
        self._hints["/" + "/".join(parts)] = hint

    def remove(self, path: str) -> None:
        if path == "/":
            self._hints["/"] = MemoryHint()
        else:
            self._hints.pop(path, None)

    # -- resolution --------------------------------------------------------
    def resolve(self, path: str) -> MemoryHint:
        """Walk root->leaf merging hints, then fill system defaults.

        Paths need not have been ``set``; they resolve through ancestors,
        exactly like reading an unset cgroup attribute.
        """
        parts = _split(path) if path != "/" else []
        merged = self._hints.get("/", MemoryHint())
        prefix = ""
        for part in parts:
            prefix += "/" + part
            node = self._hints.get(prefix)
            if node is not None:
                merged = node.merged_over(merged)
        return merged.resolved()

    def paths(self) -> Iterator[str]:
        return iter(sorted(self._hints))

    # -- serialization (the "filesystem interface") -------------------------
    def to_json(self) -> str:
        payload = {
            path: {f: getattr(h, f) for f in MemoryHint.FIELDS
                   if getattr(h, f) is not None}
            for path, h in sorted(self._hints.items())
        }
        return json.dumps(payload, indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "HintTree":
        tree = cls()
        for path, fields in json.loads(text).items():
            tree.set(path, MemoryHint(**fields))
        return tree


def default_training_hints() -> HintTree:
    """Framework defaults for a training job (DESIGN.md §4).

    Scopes mirror where traffic originates: forward activations are
    write-then-read, gradient reduce-scatter is TX-heavy, optimizer offload
    reads+writes host memory, checkpoint writes are pure-write sequential.
    """
    t = HintTree()
    t.set("/train", MemoryHint(priority=1.0))
    t.set("/train/fwd", MemoryHint(read_fraction=0.6))
    t.set("/train/bwd", MemoryHint(read_fraction=0.45))
    t.set("/train/grads", MemoryHint(read_fraction=0.1, sequential=True))
    t.set("/train/opt_offload",
          MemoryHint(read_fraction=0.5, sequential=True, priority=0.8))
    t.set("/train/checkpoint",
          MemoryHint(read_fraction=0.0, sequential=True, priority=0.2))
    return t


def default_serving_hints() -> HintTree:
    """Serving job defaults, per the paper's §6.4 layer analysis.

    Scopes now span the engine's three tenant families (§6.3-6.5): LLM
    decode (``/serve/llm``), the Redis-style KV store (``/serve/redis``
    with one child per Fig. 5 access pattern), and the vector-search
    tenant (``/serve/vectordb``). ``ServeEngine.submit`` and each
    ``WorkloadAPI`` tag their requests with these paths; the queue's
    admission policy reads the resolved read fractions / priorities and
    the ``PagedKVPool`` gates duplex intervention per scope
    (``duplex_opt_in=False`` == the paper's withdrawal mechanism).
    """
    t = HintTree()
    t.set("/serve", MemoryHint(priority=1.0))
    t.set("/serve/attention",
          MemoryHint(read_fraction=0.85, phase_period_us=64.0))
    t.set("/serve/ffn", MemoryHint(read_fraction=0.60, phase_period_us=64.0))
    t.set("/serve/kv_cache/page_in",
          MemoryHint(read_fraction=1.0, sequential=True))
    t.set("/serve/kv_cache/page_out",
          MemoryHint(read_fraction=0.0, sequential=True))
    # read-heavy prompt processing opts out (paper: intervention withdrawn).
    t.set("/serve/prefill", MemoryHint(read_fraction=0.95,
                                       duplex_opt_in=False))

    # -- LLM tenant: prompt processing opts out, decode is the §6.4 mix.
    # KV paging round-trips every block (page-in + page-out = mixed by
    # construction), so decode KV explicitly prefers the CXL tier even
    # though its compute-side read fraction leans high; withdrawn prefill
    # spills to DDR5.
    t.set("/serve/llm", MemoryHint(priority=1.0))
    t.set("/serve/llm/prefill", MemoryHint(read_fraction=0.95,
                                           duplex_opt_in=False,
                                           tier="ddr5"))
    t.set("/serve/llm/decode",
          MemoryHint(read_fraction=0.85, phase_period_us=64.0,
                     tier="cxl"))
    t.set("/serve/kv_cache", MemoryHint(tier="cxl"))

    # -- Redis-style KV-store tenant: one scope per Fig. 5 pattern. The
    # unidirectional patterns withdraw (paper: -22% read-heavy / -16%
    # write-heavy without withdrawal); the mixed-direction patterns stay
    # opted in and declare their phase structure.
    t.set("/serve/redis", MemoryHint(priority=1.0))
    t.set("/serve/redis/read_heavy",
          MemoryHint(read_fraction=10.0 / 11.0, duplex_opt_in=False))
    t.set("/serve/redis/write_heavy",
          MemoryHint(read_fraction=1.0 / 11.0, duplex_opt_in=False))
    t.set("/serve/redis/pipelined",
          MemoryHint(read_fraction=0.5, phase_period_us=8.0))
    t.set("/serve/redis/gaussian", MemoryHint(read_fraction=0.5))
    t.set("/serve/redis/seq",
          MemoryHint(read_fraction=0.5, sequential=True,
                     phase_period_us=64.0))
    # phase-offset sub-streams of the sequential sweep: declared leaning
    # lets the duplex-aware policy co-schedule opposite phases (+150%).
    t.set("/serve/redis/seq/read",
          MemoryHint(read_fraction=0.95, sequential=True))
    t.set("/serve/redis/seq/write",
          MemoryHint(read_fraction=0.05, sequential=True))

    # -- vector-search tenant: read-dominated HNSW walk with write bursts
    # for distance caching / result aggregation (§6.5).
    t.set("/serve/vectordb",
          MemoryHint(read_fraction=0.85, phase_period_us=32.0))
    t.set("/serve/vectordb/build",
          MemoryHint(read_fraction=0.05, sequential=True))
    t.set("/serve/vectordb/results", MemoryHint(read_fraction=0.1))
    return t
