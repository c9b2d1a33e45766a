"""Set-up: compile every program the window will run, before it opens.

The window runs the megastep program at K in {1, 2, 4, 8} (``run()``
quantizes its adaptive width to powers of two), admission, the policy's
programs, the write-through of each K's staging slab, and the pool's
fresh-install commit for each number of blocks a step can fill. The
megastep, admission and policy programs are compiled by serving a full
batch through ``megastep(k)`` for each K; the pool's programs, whose
shapes follow the number of blocks a transaction moves, are compiled by
calling them once per shape on the empty pool, before any request holds
a block.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.serve import kv_pool as pool_mod

WARM_PROMPT = 16


def megastep_widths(megastep: int) -> list[int]:
    return [1 << i for i in range(max(1, megastep).bit_length())]


def warm_pool(engine) -> None:
    """Compile the pool's write-through of each K's staging slab and its
    fresh-install commit for 1 .. B * fills-per-step blocks. Writes
    nothing a request will read: every write-through row is a dropped
    sentinel, and the fresh installs zero slots no block holds yet."""
    pool, cfg = engine.pool, engine.cfg
    if pool.block_at.max() >= 0:
        raise RuntimeError("warm_pool needs an empty pool")
    bt = cfg.block_tokens
    fills = -(-max(1, cfg.prefill_chunk) // bt)
    width = cfg.max_batch * fills
    sentinel = np.full((width,), pool.hbm_capacity, np.int32)
    for k in megastep_widths(cfg.megastep):
        staged = jnp.zeros((k, width) + pool.block_shape, jnp.bfloat16)
        pool.hbm = pool_mod._write_blocks_at(pool.hbm, jnp.asarray(sentinel),
                                             staged, np.int32(0))
        del staged
    empty = np.zeros((0,), np.int32)
    for n in range(1, min(width, pool.hbm_capacity) + 1):
        pool.hbm, pool.host_q, pool.host_scale = pool_mod._commit_paging(
            pool.hbm, pool.host_q, pool.host_scale, None, None, None,
            jnp.asarray(empty), jnp.asarray(empty),
            jnp.asarray(np.arange(n, dtype=np.int32)))
    pool.hbm.block_until_ready()


def warm_engine(engine, vocab: int) -> None:
    """Serve a full batch, and one request waiting behind it, through
    ``megastep(k)`` for each K, then drain: compiles the megastep
    programs, admission and the policy's schedule and fold."""
    cfg = engine.cfg
    rng = np.random.default_rng(0)

    def submit(output):
        engine.submit(rng.integers(0, vocab, WARM_PROMPT, dtype=np.int64
                                   ).astype(np.int32), output,
                      arrival_step=engine.step_count)

    widths = megastep_widths(cfg.megastep)
    live = sum(widths)                    # steps the megasteps below take
    submit(1)                             # frees its slot early
    for _ in range(cfg.max_batch - 1):
        submit(live)
    engine.megastep(widths[0])            # admits the whole batch
    submit(1)                             # waits for the freed slot
    for k in widths[1:]:
        engine.megastep(k)
    engine.run()


def warm(engine, vocab: int) -> None:
    if engine.paged:
        warm_pool(engine)
    warm_engine(engine, vocab)
