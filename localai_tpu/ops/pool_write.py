"""A decode block's window written into the page pool by DMA, in place
(Pallas, TPU; ISSUE 44).

`llama.write_block_to_pool` puts the n rows a slot decoded in a block where
the page table says: `pool.at[:, pid, off].set(window)`, an XLA scatter whose
update slice is `[L, 1, 1, K, D]`. Where a token's row of the pool is
narrower than the TPU's 8-sublane tile (fewer than 8 rows of D: 2 or 4 KV
heads a chip, four packed rows, MLA's one latent row), XLA stores the pool
tiled `T(K,128)` over (K, D) and runs the scatter in a layout of its own with
the LAYER axis on the sublanes (`{4,0,3,2,1:T(8,128)}`, whenever L is a
multiple of the tile's rows), so every block copied the whole pool into that
layout and back, for K and for V: four pool-sized copies a block, 5.4% of the
four-chip cell's decode block (PERF.md §6 "PR 44").

This kernel computes nothing. It takes the rows' page ids and offsets as
`write_block_to_pool` computes them (scalar prefetch), the window already in
the pool's dtype, and the pool in HBM aliased onto its result, and copies
HBM to HBM: a grid step a slot, and within it

- ONE strided copy `[L, n, K, D]` when the slot's n rows lie in one page
  (they are consecutive rows, so unless they straddle a page boundary), or
- n row copies `[L, K, D]`, all in flight at once, each to its own
  (page, offset), on a straddle. That is also where rows clamped to the
  table's last row go (`MP·page − 1`: idle slots, rows past a reservation),
  several to one address as under the scatter, whose order was unspecified
  too; those rows are never read.

The pool is never relaid: what moves is the window's bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Rows of D a token below which XLA:TPU stores a pool tiled `T(K,128)` and
# gives the block scatter a layout of its own (compiled for a described v5e:
# bfloat16 and fp8 at K = 2 and 4 copy the pool, K = 8 of either width runs
# the scatter as stored): the tile's sublanes, whatever the itemsize packs
# into one.
TILE_ROWS = 8


def in_place_rows(pool_shape, dtype) -> bool:
    """Does the block write of this `[L, P, page, K, D]` pool (one chip's
    part of it) take the kernel? A token's K rows of D are fewer than the
    native tile's (`TILE_ROWS`), and they are something a DMA can slice,
    which is what Mosaic takes for a described v5e: a power of two of rows
    (XLA then tiles the pool `T(K,128)`; 6 rows are tiled by 8, 3 by 4, and
    refused, "must be aligned to tiling"), filling whole 32-bit words of a
    sublane (as `paged_flash._flat_rows` asks of a page), of whole
    128-lane tiles. So: 2 or 4 KV heads a chip in bfloat16, 4 in fp8, 1 to
    4 in float32, LFM2's four packed rows. MLA's latent pool, ONE 16-bit
    row a token, is narrower than the tile too, and its scatter copies the
    pool too (Kimi-Linear's `[7, 513, 128, 1, 640]`), but a row that is
    half a word cannot be a DMA's slice (refused as `[L, n, 1, D]`, and
    without the head axis, the row offset then being a tiled dimension): it
    keeps the scatter (PERF.md §7), as 2 fp8 heads a chip do."""
    rows, width = pool_shape[3:]
    return (rows < TILE_ROWS and rows & (rows - 1) == 0
            and (rows * jnp.dtype(dtype).itemsize) % 4 == 0
            and width % 128 == 0)


def _pool_write_kernel(pid_ref, off_ref, win_hbm, pool_in, pool_out, sem, *,
                       n: int, page: int):
    """pid_ref / off_ref [B, n] i32 (scalar prefetch); win_hbm
    [L, B, n, K, D] and pool_out [L, P, page, K, D] in HBM (ANY), pool_in
    the same buffer as pool_out (aliased); sem [n] DMA semaphores."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del pool_in  # the result IS the pool: rows not written stay as they are
    b = pl.program_id(0)
    off0 = off_ref[b, 0]
    # n consecutive rows from off0 fit the page: then none was clamped
    # either (the clamp is the last row of the table's last page)
    one_run = off0 + n <= page

    @pl.when(one_run)
    def _():
        dma = pltpu.make_async_copy(
            win_hbm.at[:, b],
            pool_out.at[:, pid_ref[b, 0], pl.ds(off0, n)], sem.at[0])
        dma.start()
        dma.wait()

    if n == 1:
        return

    @pl.when(jnp.logical_not(one_run))
    def _():
        rows = [
            pltpu.make_async_copy(
                win_hbm.at[:, b, r],
                pool_out.at[:, pid_ref[b, r], off_ref[b, r]], sem.at[r])
            for r in range(n)
        ]
        for dma in rows:
            dma.start()
        for dma in rows:
            dma.wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def pool_write(pool, win, pid, off, interpret: bool = False):
    """`pool.at[:, pid, off].set(win)` by DMA, in place (donate the pool).
    Jitted on its own, so a block's K and V pools, equal in shape, share
    one trace of the kernel.

    pool [L, P, page, K, D]; win [L, B, n, K, D] in the pool's dtype;
    pid, off [B, n] int32: row (b, r) of the window lands at
    `pool[:, pid[b, r], off[b, r]]`, where a slot's rows are consecutive
    rows of its pages as `llama.write_block_to_pool` resolves them."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, B, n = win.shape[:3]
    if (win.dtype != pool.dtype or win.shape[3:] != pool.shape[3:]
            or pool.shape[0] != L or pid.shape != (B, n)
            or off.shape != (B, n)):
        raise ValueError(
            f"pool_write: window {win.shape} {win.dtype}, pool {pool.shape} "
            f"{pool.dtype}, pid {pid.shape}, off {off.shape}")
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_pool_write_kernel, n=n, page=pool.shape[2]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[any_space, any_space],
            out_specs=any_space,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
        ),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={3: 0},  # after the two prefetched tables
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="pool_write",
    )(pid.astype(jnp.int32), off.astype(jnp.int32), win, pool)
