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

A LATENT pool (MLA: ONE 16-bit row a token, `[L, P, page, 1, W]`) has no row
a DMA can slice: a row is half a sublane word, and a tile of the pool as it
is stored is 16 token rows of 128 lanes. `latent_pool_write` (ISSUE 49)
stages the write through VMEM: a slot's n <= 16 consecutive rows lie in at
most two 16-row tile groups (of one page, or of two on a straddle); a grid
step a slot reads the aligned groups `[L, 16, W]`, replaces the rows that
are the window's, and writes the groups back. 2 x L x 16 x W x 2 B each way
a slot (1.9 MB at 47 layers of 640) where the scatter relaid the whole pool
twice a block (`copy.280 bf16[513,128,7,1,640]` and back at Kimi-Linear's
0.59 GB; 6.9 GB at GLM-4.7-Flash's, which did not fit).
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
            and width > 0 and width % 128 == 0)


# Token rows in a tile of a 16-bit pool as it is stored, `T(8,128)(2,1)`: 8
# sublanes of two rows a word. What `latent_pool_write` reads and writes.
GROUP_ROWS = 16


def staged_rows(pool_shape, dtype, n: int) -> bool:
    """Does the block write of this pool take the staged kernel
    (`latent_pool_write`)? One 16-bit row a token of whole 128-lane tiles
    (MLA's latent pool, which `in_place_rows` refuses), pages of whole
    16-row tile groups, and a window of at most one group's rows, so that a
    slot's rows lie in two groups at most (decode blocks of 16, 4 and 1).
    Not MLA's zero-width V pool, whose scatter moves nothing."""
    page, rows, width = pool_shape[2:]
    return (rows == 1 and jnp.dtype(dtype).itemsize == 2
            and width > 0 and width % 128 == 0 and page % GROUP_ROWS == 0
            and 1 <= n <= GROUP_ROWS)


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


def _latent_write_kernel(pid_ref, off_ref, win_ref, pool_in, pool_out, buf,
                         sem, *, n: int):
    """pid_ref / off_ref [B, n] i32 (scalar prefetch); win_ref [L, 1, n, W]
    (this slot's window, VMEM); pool_out [L, P, page, W] in HBM (ANY),
    pool_in the same buffer (aliased); buf [2, L, GROUP_ROWS, W] VMEM, the
    two tile groups; sem [2] DMA semaphores.

    Row r of the window lands at row r0 + r of the 32 rows of the two
    groups, r0 the first row's place in its group. The second group is the
    one the window's row GROUP_ROWS - r0 starts, if the window has such a
    row and it does start a group: a row clamped to the table's last row
    (rows past a reservation, never read) starts none and is dropped, where
    the scatter sent every such row to that one address."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del pool_in  # the result IS the pool: rows not written stay as they are
    G = GROUP_ROWS
    b = pl.program_id(0)
    off0 = off_ref[b, 0]
    r0 = off0 % G
    rb = jnp.minimum(G - r0, n - 1)
    off1 = off_ref[b, rb]
    second = jnp.logical_and(G - r0 < n, off1 % G == 0)
    at = ((pid_ref[b, 0], pl.multiple_of(off0 - r0, G)),
          (pid_ref[b, rb], pl.multiple_of(off1 - off1 % G, G)))

    def copies(to_pool: bool):
        out = []
        for j, (p, o) in enumerate(at):
            group = pool_out.at[:, p, pl.ds(o, G)]
            out.append(pltpu.make_async_copy(
                *((buf.at[j], group) if to_pool else (group, buf.at[j])),
                sem.at[j]))
        return out

    def both(dmas, what):
        getattr(dmas[0], what)()

        @pl.when(second)
        def _():
            getattr(dmas[1], what)()

    reads, writes = copies(False), copies(True)
    both(reads, "start")
    both(reads, "wait")
    row = jax.lax.broadcasted_iota(jnp.int32, (2 * G, 1), 0)
    mine = jnp.logical_and(row >= r0, row < r0 + n)
    W = win_ref.shape[-1]

    def layer(l, carry):
        # 16-bit -> float32 and back is exact; a float32 row is a sublane
        w = jnp.concatenate(
            [win_ref[l, 0].astype(jnp.float32),
             jnp.zeros((2 * G - n, W), jnp.float32)], axis=0)  # n <= G
        w = pltpu.roll(w, r0, 0)
        for j in range(2):
            old = buf[j, l].astype(jnp.float32)
            new = jnp.where(mine[j * G:(j + 1) * G], w[j * G:(j + 1) * G], old)
            buf[j, l] = new.astype(buf.dtype)
        return carry

    jax.lax.fori_loop(0, win_ref.shape[0], layer, 0)
    both(writes, "start")
    both(writes, "wait")


@functools.partial(jax.jit, static_argnames=("interpret",))
def latent_pool_write(pool, win, pid, off, interpret: bool = False):
    """`pool.at[:, pid, off].set(win)` for a latent pool `[L, P, page, 1, W]`
    of 16-bit rows, in place through VMEM (donate the pool): arguments as
    `pool_write`'s, a slot's rows consecutive rows of its pages. Rows that
    `llama.write_block_to_pool` clamped to the table's last row are not
    written (module docstring); every other row lands where the scatter
    puts it, and nothing else of the pool changes."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    L, B, n = win.shape[:3]
    if (win.dtype != pool.dtype or win.shape[3:] != pool.shape[3:]
            or pool.shape[0] != L or pid.shape != (B, n)
            or off.shape != (B, n) or not staged_rows(pool.shape, pool.dtype, n)):
        raise ValueError(
            f"latent_pool_write: window {win.shape} {win.dtype}, pool "
            f"{pool.shape} {pool.dtype}, pid {pid.shape}, off {off.shape}")
    W = pool.shape[-1]
    flat = pool.reshape(*pool.shape[:3], W)  # K = 1: the pool as it is stored
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_latent_write_kernel, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((L, 1, n, W), lambda b, *_: (0, b, 0, 0)),
                      any_space],
            out_specs=any_space,
            scratch_shapes=[pltpu.VMEM((2, L, GROUP_ROWS, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=jax.ShapeDtypeStruct(flat.shape, flat.dtype),
        input_output_aliases={3: 0},  # after the two prefetched tables
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="latent_pool_write",
    )(pid.astype(jnp.int32), off.astype(jnp.int32),
      win.reshape(L, B, n, W), flat)
    return out.reshape(pool.shape)
