"""PagedKVPool: residency invariants, vectorized LRU, int8 round-trip,
batched duplex paging, single-direction kernel halves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.kv_pool import PagedKVPool


def _pool(n=16, hbm=4, shape=(8, 32)):
    return PagedKVPool(n_blocks=n, hbm_blocks=hbm, block_shape=shape)


def _rand(b, shape=(8, 32)):
    return jax.random.normal(jax.random.PRNGKey(b), shape).astype(
        jnp.bfloat16)


def _fill(pool, blocks):
    """Make ``blocks`` resident and write real data into them."""
    blocks = list(blocks)
    pool.step(blocks)
    pool.write(blocks, jnp.stack([_rand(b, pool.block_shape)
                                  for b in blocks]))


class TestResidency:
    def test_invariants_hold_through_churn(self):
        pool = _pool()
        for step in range(12):
            pool.step([(step * 3 + i) % 16 for i in range(3)])
            pool.check_invariants()
        assert len(pool.resident_blocks()) <= pool.hbm_capacity

    def test_demand_over_capacity_rejected(self):
        pool = _pool(hbm=4)
        with pytest.raises(ValueError, match="demands"):
            pool.step([0, 1, 2, 3, 4])

    def test_write_requires_residency(self):
        pool = _pool()
        with pytest.raises(ValueError, match="non-resident"):
            pool.write([3], jnp.zeros((1, 8, 32)))

    def test_free_releases_hbm(self):
        pool = _pool(hbm=4)
        pool.step([0, 1, 2, 3])
        pool.free([0, 1])
        pool.check_invariants()
        assert not pool.is_resident([0, 1]).any()
        # freed slots absorb new blocks without evictions
        before = pool.stats["page_outs"]
        pool.step([4, 5])
        assert pool.stats["page_outs"] == before

    def test_alloc_exhaustion(self):
        pool = _pool(n=4)
        pool.alloc(4)
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc(1)
        pool.free([0])
        assert pool.alloc(1) == [0]

    def test_fresh_blocks_not_billed_as_page_ins(self):
        """First-ever residency of a block has no host copy to stream:
        no page-in count, no kernel call, no modelled duplex time. Only
        *written* data ever moves — evicting a never-written block is
        silent too, and its re-demand is another free install."""
        pool = _pool(n=8, hbm=4)
        pool.step([0, 1, 2])
        assert pool.stats["page_ins"] == 0
        assert pool.stats["kernel_calls"] == 0
        assert pool.stats["duplex_us"] == 0.0
        pool.write([0], _rand(0)[None])
        pool.step([3, 4, 5, 6])   # only written block 0 really pages out
        assert pool.stats["page_ins"] == 0
        assert pool.stats["page_outs"] == 1
        pool.step([0])            # real host copy: a real page-in
        assert pool.stats["page_ins"] == 1
        pool.step([1])            # never written: still a free install
        assert pool.stats["page_ins"] == 1

    def test_fresh_install_reads_zeros_not_stale(self):
        """A reused HBM slot must not leak the previous occupant's data
        into a brand-new block."""
        pool = _pool(n=8, hbm=2)
        pool.step([0])
        pool.write([0], _rand(0)[None])
        pool.step([1, 2])                # evicts 0; fresh blocks reuse slot
        assert np.all(np.asarray(pool.read([1]), np.float32) == 0)
        assert np.all(np.asarray(pool.read([2]), np.float32) == 0)


class TestLRU:
    @pytest.mark.parametrize("n,shape", [(16, (8, 32)), (8, (4, 4))])
    def test_eviction_order(self, n, shape):
        pool = _pool(n=n, hbm=2, shape=shape)
        pool.step([0])
        pool.step([1])
        pool.step([0])          # 0 is now most-recent
        pool.step([2])          # evicts 1 (LRU), not 0
        assert pool.is_resident([0]).all() and pool.is_resident([2]).all()
        assert not pool.is_resident([1]).any()

    def test_needed_blocks_never_evicted(self):
        pool = _pool(hbm=3)
        pool.step([0, 1, 2])
        pool.step([0, 1, 3])    # must evict 2, not a needed block
        assert pool.is_resident([0, 1, 3]).all()
        assert not pool.is_resident([2]).any()

    def test_freed_block_forgets_recency(self):
        """free() zeroes the LRU clock — hygiene so a reused block id
        never exposes the previous request's recency (eviction choice
        itself only ever considers resident, freshly-touched blocks)."""
        pool = _pool(hbm=4)
        pool.step([0, 1])
        pool.step([2])                       # 2 is most-recent
        pool.free([2])
        assert int(np.asarray(pool.last_use)[2]) == 0
        # a new occupant of id 2 competes on its own touches only
        pool.step([2])
        pool.step([3, 4, 5])                 # forces one eviction
        assert not pool.is_resident([0]).any() or \
            not pool.is_resident([1]).any()
        assert pool.is_resident([2]).all()   # freshly touched, kept


class TestRoundTrip:
    @pytest.mark.parametrize("n,hbm,shape,n_data", [
        (8, 2, (8, 32), 4),
        (12, 4, (8, 16), 8),
    ])
    def test_int8_roundtrip_tolerance(self, n, hbm, shape, n_data):
        pool = _pool(n=n, hbm=hbm, shape=shape)
        data = {b: _rand(b, shape) for b in range(n_data)}
        for b, x in data.items():
            pool.step([b])
            pool.write([b], x[None])     # later steps evict earlier blocks
        for b, x in data.items():
            pool.step([b])
            back = pool.read([b])[0]
            amax = float(jnp.max(jnp.abs(x.astype(jnp.float32))))
            err = float(jnp.max(jnp.abs(back.astype(jnp.float32)
                                        - x.astype(jnp.float32))))
            assert err <= amax / 127.0 + 0.02


class TestBatchedPaging:
    def test_one_kernel_call_per_step(self):
        pool = _pool(n=32, hbm=8)
        _fill(pool, range(8))                  # fresh installs: no traffic
        assert pool.stats["kernel_calls"] == 0
        for start in range(8, 32, 4):
            _fill(pool, range(start, start + 4))  # 4 fresh + 4 real outs
        assert pool.stats["steps"] == 7
        assert pool.stats["kernel_calls"] == 6    # one per traffic step
        assert pool.stats["page_outs"] == 24
        pool.step(range(8))                    # 8 evicted blocks: real ins
        assert pool.stats["kernel_calls"] == 7    # still one for the batch
        assert pool.stats["page_ins"] == 8

    @pytest.mark.parametrize("shape,fill_group", [((8, 32), 8),
                                                   ((8, 16), 1)])
    def test_duplex_speedup_on_mixed_batches(self, shape, fill_group):
        pool = _pool(n=32, hbm=8, shape=shape)
        for start in range(0, 32, fill_group):     # fill + spill to host
            _fill(pool, range(start, start + fill_group))
        pool.reset_stats()
        for start in range(0, 24, 4):          # real ins co-issued w/ outs
            _fill(pool, range(start, start + 4))   # rewrite -> dirty evict
        assert pool.stats["page_ins"] > 0 and pool.stats["page_outs"] > 0
        assert pool.duplex_speedup() >= 1.0
        assert pool.duplex_speedup() > 1.3    # ins co-issued with outs

    def test_clean_eviction_is_silent(self):
        """A block paged in and not rewritten still has a byte-identical
        host copy — evicting it again moves no data and bills nothing."""
        pool = _pool(n=8, hbm=2)
        _fill(pool, [0, 1])
        pool.step([2, 3])            # evicts dirty 0,1 -> real outs
        assert pool.stats["page_outs"] == 2
        pool.step([0, 1])            # real page-ins; 0,1 now clean
        assert pool.stats["page_ins"] == 2
        pool.step([2, 3])            # evicts clean 0,1: silent
        assert pool.stats["page_outs"] == 2
        pool.step([0])               # host copy still valid: pages back in
        assert pool.stats["page_ins"] == 3

    def test_unidirectional_paging_no_slowdown(self):
        pool = _pool(n=16, hbm=8)
        _fill(pool, range(8))
        pool.step(range(8, 16))               # spills written 0..7 to host
        pool.free(list(range(8, 16)))         # all HBM slots free
        pool.reset_stats()
        pool.step(range(8))                   # pure page-in, no evictions
        assert pool.stats["page_ins"] == 8
        assert pool.stats["page_outs"] == 0
        assert pool.duplex_speedup() >= 1.0


class TestSingleDirectionPaths:
    """When one stream is empty the pool calls the dequant-only /
    quant-only kernel half — no zero blocks padded through the dead half
    of the fused grid — with billing identical to before. The
    ``kernel_call_counter`` fixture (conftest) records every stream-kernel
    entry point as (name, n_blocks)."""

    def test_pure_page_in_uses_dequant_half(self, kernel_call_counter):
        pool = _pool(n=16, hbm=4)
        _fill(pool, range(4))
        pool.step(range(4, 8))               # spill 0..3 to host
        pool.free(list(range(4, 8)))         # all slots free again
        pool.reset_stats()
        del kernel_call_counter[:]
        pool.step([0, 1, 2])                 # page-in only
        assert kernel_call_counter == [("dequant_kv_stream", 3)]
        assert pool.stats["page_ins"] == 3
        assert pool.stats["page_outs"] == 0
        assert pool.stats["kernel_calls"] == 1
        # the data really arrived
        x = np.asarray(pool.read([0]), np.float32)
        ref = np.asarray(_rand(0), np.float32)
        assert np.abs(x[0] - ref).max() <= np.abs(ref).max() / 127.0 + 0.02

    def test_pure_page_out_uses_quant_half(self, kernel_call_counter):
        pool = _pool(n=16, hbm=4)
        _fill(pool, range(4))                # dirty residents, empty host
        del kernel_call_counter[:]
        pool.step([4, 5])                    # evicts 2 dirty: page-out only
        assert kernel_call_counter == [("quant_kv_stream", 2)]
        assert pool.stats["page_outs"] == 2
        assert pool.stats["page_ins"] == 0
        assert pool.stats["kernel_calls"] == 1
        assert pool.stats["duplex_us"] > 0   # billing unchanged

    def test_mixed_traffic_still_fused(self, kernel_call_counter):
        pool = _pool(n=16, hbm=4)
        _fill(pool, range(4))
        pool.step(range(4, 8))               # spill 0..3
        _fill(pool, range(4, 8))             # dirty residents again
        del kernel_call_counter[:]
        pool.step([0, 1])                    # ins co-issued with outs
        assert [name for name, _ in kernel_call_counter] == \
            ["duplex_kv_stream"]
