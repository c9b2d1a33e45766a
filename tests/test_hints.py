"""Hint tree (cgroup analogue) — inheritance, override, serialization."""

import dataclasses

import pytest

pytest.importorskip("hypothesis")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core.hints import HintTree, MemoryHint, SYSTEM_DEFAULT, \
    default_serving_hints, default_training_hints


class TestInheritance:
    def test_unset_resolves_to_system_default(self):
        t = HintTree()
        h = t.resolve("/anything/nested/deep")
        assert h.read_fraction == SYSTEM_DEFAULT.read_fraction
        assert h.duplex_opt_in is True

    def test_child_overrides_parent(self):
        t = HintTree()
        t.set("/job", MemoryHint(read_fraction=0.9, priority=2.0))
        t.set("/job/writer", MemoryHint(read_fraction=0.1))
        h = t.resolve("/job/writer")
        assert h.read_fraction == 0.1
        assert h.priority == 2.0          # inherited from /job

    def test_sibling_isolation(self):
        t = HintTree()
        t.set("/job/a", MemoryHint(read_fraction=0.9))
        assert t.resolve("/job/b").read_fraction == \
            SYSTEM_DEFAULT.read_fraction

    def test_opt_out_inherits_down(self):
        t = HintTree()
        t.set("/serve", MemoryHint(duplex_opt_in=False))
        assert t.resolve("/serve/prefill/attn").duplex_opt_in is False

    def test_derived_tier_not_inherited(self):
        """A scope that names no tier gets one from its own traffic mix:
        the parent's derived tier does not flow down, its set one does."""
        t = HintTree()
        t.set("/job", MemoryHint(read_fraction=0.5))
        t.set("/job/writer", MemoryHint(read_fraction=0.1))
        assert t.resolve("/job").tier == "cxl"
        assert t.resolve("/job/writer").tier == "ddr5"
        t.set("/job", MemoryHint(read_fraction=0.5, tier="cxl"))
        assert t.resolve("/job/writer").tier == "cxl"

    def test_intermediate_scopes_materialized(self):
        t = HintTree()
        t.set("/a/b/c", MemoryHint(priority=3.0))
        assert "/a/b" in list(t.paths())


class TestSerialization:
    def test_roundtrip(self):
        t = default_training_hints()
        t2 = HintTree.from_json(t.to_json())
        for path in t.paths():
            assert t.resolve(path) == t2.resolve(path)

    @settings(max_examples=20, deadline=None)
    @given(rf=st.one_of(st.none(), st.floats(0, 1)),
           pri=st.one_of(st.none(), st.floats(0.1, 10)),
           opt=st.one_of(st.none(), st.booleans()))
    def test_roundtrip_property(self, rf, pri, opt):
        t = HintTree()
        t.set("/x/y", MemoryHint(read_fraction=rf, priority=pri,
                                 duplex_opt_in=opt))
        t2 = HintTree.from_json(t.to_json())
        assert t2.resolve("/x/y") == t.resolve("/x/y")


_hints = st.builds(
    MemoryHint,
    read_fraction=st.one_of(st.none(), st.floats(0, 1)),
    sequential=st.one_of(st.none(), st.booleans()),
    priority=st.one_of(st.none(), st.floats(0.1, 10)),
    phase_period_us=st.one_of(st.none(), st.floats(0, 1e4)),
    duplex_opt_in=st.one_of(st.none(), st.booleans()),
    tier=st.one_of(st.none(), st.sampled_from(["ddr5", "cxl"])),
)
_segments = st.lists(st.sampled_from(["a", "b", "serve", "llm", "x1"]),
                     min_size=1, max_size=5)


def _path(segments):
    return "/" + "/".join(segments)


def _derived_tier(r):
    """The placement rule for a path that sets no tier, restated from
    the paper's §3: withdrawn or unidirectional scopes go to DDR5, mixed
    ones to CXL."""
    if r.duplex_opt_in is False:
        return "ddr5"
    return "ddr5" if (r.read_fraction >= 0.8 or r.read_fraction <= 0.2) \
        else "cxl"


class TestResolutionProperties:
    """Property-based contracts of hierarchical resolution: inheritance
    is idempotent, children win, re-registration replaces, and
    ``resolved()`` never leaves an unset field — at any depth. A tier
    set on the path wins; an unset one is derived at the leaf and never
    inherited."""

    @settings(max_examples=50, deadline=None)
    @given(segs=_segments, hints=st.lists(_hints, min_size=1, max_size=5))
    def test_resolved_never_none(self, segs, hints):
        t = HintTree()
        # register hints along every prefix of the path, then resolve a
        # strictly deeper, never-registered leaf.
        for i, h in enumerate(hints):
            t.set(_path(segs[:1 + i % len(segs)]), h)
        deep = _path(segs) + "/unregistered/leaf"
        for path in [deep] + [_path(segs[:i + 1])
                              for i in range(len(segs))]:
            r = t.resolve(path)
            assert all(getattr(r, f) is not None for f in MemoryHint.FIELDS)

    @settings(max_examples=50, deadline=None)
    @given(h=_hints)
    def test_merge_is_idempotent(self, h):
        assert h.merged_over(h) == h
        assert h.resolved().resolved() == h.resolved()

    @settings(max_examples=50, deadline=None)
    @given(segs=_segments, parent=_hints, child=_hints)
    def test_child_wins_unset_inherits(self, segs, parent, child):
        t = HintTree()
        t.set(_path(segs), parent)
        t.set(_path(segs + ["leaf"]), child)
        r = t.resolve(_path(segs + ["leaf"]))
        for f in MemoryHint.FIELDS:
            want = getattr(child, f)
            if want is None:
                want = getattr(parent, f)
            if want is None:
                want = getattr(SYSTEM_DEFAULT, f)
            if want is None and f == "tier":
                want = _derived_tier(r)
            assert getattr(r, f) == want

    @settings(max_examples=50, deadline=None)
    @given(segs=_segments, first=_hints, second=_hints)
    def test_reregistration_replaces(self, segs, first, second):
        """set() on an existing scope fully replaces its hint — the
        resolution equals a tree that only ever saw the second hint."""
        t = HintTree()
        t.set(_path(segs), first)
        t.set(_path(segs), second)
        fresh = HintTree()
        fresh.set(_path(segs), second)
        deep = _path(segs) + "/below"
        assert t.resolve(_path(segs)) == fresh.resolve(_path(segs))
        assert t.resolve(deep) == fresh.resolve(deep)

    @settings(max_examples=50, deadline=None)
    @given(segs=_segments, hints=st.lists(_hints, min_size=2, max_size=5))
    def test_resolution_equals_stepwise_merge(self, segs, hints):
        """Root-to-leaf resolution is exactly the left fold of
        merged_over along the registered ancestry."""
        t = HintTree()
        for i in range(len(segs)):
            t.set(_path(segs[:i + 1]), hints[i % len(hints)])
        merged = MemoryHint().merged_over(SYSTEM_DEFAULT)
        for i in range(len(segs)):
            merged = hints[i % len(hints)].merged_over(merged)
        if merged.tier is None:
            merged = dataclasses.replace(merged, tier=_derived_tier(merged))
        assert t.resolve(_path(segs)) == merged


class TestDefaults:
    def test_training_defaults(self):
        t = default_training_hints()
        assert t.resolve("/train/checkpoint").read_fraction == 0.0
        assert t.resolve("/train/grads").sequential is True

    def test_serving_defaults_match_paper(self):
        """§6.4: attention 85% reads, FFN 60/40; prefill opts out."""
        t = default_serving_hints()
        assert t.resolve("/serve/attention").read_fraction == 0.85
        assert t.resolve("/serve/ffn").read_fraction == 0.60
        assert t.resolve("/serve/prefill").duplex_opt_in is False

    def test_tenant_scopes(self):
        """Multi-tenant serving scopes: the unidirectional Redis patterns
        withdraw duplex intervention; the mixed ones stay opted in."""
        t = default_serving_hints()
        assert t.resolve("/serve/llm/prefill").duplex_opt_in is False
        assert t.resolve("/serve/redis/read_heavy").duplex_opt_in is False
        assert t.resolve("/serve/redis/write_heavy").duplex_opt_in is False
        for scope in ("/serve/redis/seq", "/serve/redis/pipelined",
                      "/serve/redis/gaussian", "/serve/vectordb"):
            assert t.resolve(scope).duplex_opt_in is True
        assert t.resolve("/serve/redis/seq/read").read_fraction == 0.95
        assert t.resolve("/serve/redis/seq/write").read_fraction == 0.05
        assert t.resolve("/serve/vectordb/results").read_fraction == 0.1
