"""Fused dequant-matmul Pallas kernels for quantized decode (ISSUE 9).

Why this exists: models/quant.py stores weights int8/int4 and relies on XLA
folding the int→float convert into the dot's operand load. That folding is
reliable ONLY for the flat per-channel int8 form. The grouped int8 and
packed-nibble int4 forms go through reshape → unpack lo/hi → concat → scale
→ dot, and XLA materializes the dequantized bf16 copy in HBM first — int4
decode streams ~2.5 bytes/weight instead of ~0.5, which is the whole ballgame
for an HBM-bound decode step (r04: 85.3% of roofline; the gap is exactly
these extra passes).

These kernels do the unpack + affine scale in VMEM registers on the weight
block the Pallas pipeline is already streaming HBM→VMEM (double-buffered
block DMA between grid steps), and accumulate in f32 on the MXU — each
packed weight byte crosses HBM exactly once. Decode-shape only: the row
count (batch × window) is small enough that x and the f32 accumulator sit
whole in VMEM, so the grid walks (expert, out-block, k-chunk) with the
k-chunk axis innermost, revisiting one out block per (expert, out-block).

The block rule (ISSUE 36, `_blocks`): a grid step's weight block is sized in
bytes and laid along the weight's own rows. A leaf is [B, in, out] int8,
row-major, so `kc` whole rows (bo = out) are ONE contiguous run of kc x out
bytes; the rule takes the full out width and as many rows as keep the block
within BLOCK_BYTES (2.5 MB: a whole expert matrix of the MoE cells, 128 rows
of mistral's 14,336-wide ffn), counts everything a step holds against one
VMEM budget, and narrows `out` to a lane multiple only where a full-width
step does not fit (256 rows x 14,336). x stays resident whole instead of
being fetched again per out block or expert. Inside the step the kernel
walks the block in sub-tiles with rolled loops, so the converted float32
tile does not grow with the DMA. On the v5e (my chip runs, PR 36) a step
costs 0.22 us before it moves a byte; 512 x 512 column strips copied at
65-72% of the HBM's rate and the whole kernel ran at 53-59% (33-36% where an
axis of 2304 or 1280 forced 128 KB blocks); whole-row blocks run at 85-90%.

The grouped kernel (ISSUE 38, `_gmm_call`): admission's rows are too many
for that layout and each needs top-k experts of E, so llama._moe_ragged
sorts the (row, pick) pairs by expert and `int8_grouped_matmul`
(`int4_grouped_matmul`) walks the sorted rows' groups over the same stack,
still stacked over layers: the grid is (out-block, visit, k-chunk), a visit
one (row tile, expert) pair that shares a row, in sorted order
(`group_visits`, scalar-prefetched with the group offsets). Consecutive
visits of one expert find its weight block resident, so a visited expert's
bytes cross HBM once (once a visit where the matrix takes several k-chunks);
a tile that straddles groups is visited once a group and writes only that
group's rows; a tile wholly in no group (under an expert share the picks
held elsewhere sort last) and an expert no row chose are never visited. The
step's body, block rule and arithmetic are the stacked kernel's
(`_accumulate`, `_blocks` at a row tile of GROUP_ROWS): no dequantized copy,
the per-channel scale on the float32 accumulator at the final write.

Forms served (matching models/quant.py representations):
- flat int8      {"q": [in, out] i8,      "s": [1, out] f32}
- grouped int8   {"gq": [G, gs, out] i8,  "gs": [G, 1, out] f32}
- packed int4    {"g4": [G, gs/2, out] u8, "gs", "gz": [G, 1, out] f32}
  (value = nibble·s − z; the −z side is a rank-1 correction: −Σᵢx·z per
  group, one extra tiny MXU dot on the per-group x sums)
- MoE variants of all three with a leading expert axis, for the two
  _moe_dense einsum shapes (shared-x and per-expert-x), and for
  expert-sorted rows (the grouped kernel)
- unembed        {"q": [V, D] i8, "s": [V, 1] f32} used transposed (h @ qᵀ·s)

Who slices what (ISSUE 25; the convention is ops/stacked.py). A layer's
weights live stacked over layers ([L, ...] leaves), and a pallas_call's
operand has to be a buffer: a slice in front of it is a copy of the whole
matrix, every layer of every step (it was a third of the int8 decode step).
So llama._scan_stack does not slice quantized leaves; it hands the layer
body a StackedLayer (the stack and the layer index), and the dispatchers
here take `layer=`: engaged, the kernel gets the stack with every leading
axis merged into one block axis and the index as a scalar-prefetch operand,
and its BlockSpec index maps read block layer·E + e. Same blocks, grid order
and arithmetic as on a slice, so the result is bit-identical. Not engaged
(prefill-scale rows, impl xla, CPU auto, a shape _shardable refuses) the
dispatcher returns None and the caller slices at its own call site
(layer_slice) in front of the XLA form. stacked.SiteCounts tallies the
choice per traced program. The
grouped forms' [L, G, 1, out] scales and zeros are re-laid to [L, G, out]
for the kernel, which XLA hoists out of the layer loop (one pass over them
per program run, held as a temporary; PERF.md §7).

Sharding (ISSUE 7 shard_map wrapping): pallas_call is opaque to GSPMD, so
under a tp>1 mesh the kernels run inside shard_map with the weight specs
parallel/sharding.py already assigns to the q/s/g4 forms — column-parallel
weights shard their out axis ("tp" on the last dim of every leaf),
row-parallel weights shard the group/in axis, and the row-parallel partial
sums psum over "tp" inside the declared boundary below (the same ICI
boundary GSPMD would have placed at the o/down projection). A stacked
weight keeps its layer axis whole on every shard; the index is replicated.

Dispatch: models/quant.matmul / unembed_matmul and models/llama._moe_mm call
the dispatch_* helpers here (llama._mlp asks `grouped_engaged` once, where
it picks the MoE form; llama._moe_ragged then calls `group_visits` once and
`grouped_moe_mm` for its three projections); a None return
means "not engaged" and the
caller falls through to its XLA form, which stays the numeric oracle
(tests/test_quant.py runs these kernels in interpret mode on CPU against
it, exactly like ops/paged_flash vs the XLA page walk).
"""

from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from localai_tpu.ops.stacked import note_blocks, note_grouped, note_site

# The ONLY function here allowed to issue cross-chip collectives: the
# row-parallel shard_map closure psums its partial products over "tp" —
# the same o/down-projection boundary GSPMD places for the XLA path
# (lint: sharding-consistency C3).
COLLECTIVE_BOUNDARY = ("_sharded_quant_matmul",)

# Rows (flattened leading dims of x) above which the kernels disengage and
# the XLA path serves: prefill-scale matmuls are compute-bound (the dequant
# copy amortizes over S·D² FLOPs) and their x/accumulator would not fit the
# VMEM-resident decode layout below. Decode blocks (B ≤ max_slots), spec
# verify chunks (B·(k+1)) and short cached-admit tails all sit far under it.
QUANT_PALLAS_MAX_ROWS = 256

# The MoE rule's crossover (llama._mlp): the most rows at which an ADMISSION
# program (prefill, a cached tail, a prefill chunk) runs quantized experts
# all-experts on the stacked kernel where the grouped kernel below can serve
# the rows above it. Measured on the v5e (my chip runs 1-2,
# PR 38; the three MoE cells' stacks, uniform routing, the whole expert path
# a layer): at 64 rows all-experts is 7-14% faster on two stacks and 3%
# slower on the third; at 128 rows it costs 1.2-1.3 x its 64-row time
# (arithmetic starts to bind) and sort + grouped kernel is 9-12% faster on
# two stacks and 3% slower on the third; at 256 rows 1.5-1.8 x faster on all
# three. The decode entry points (a block of any slot count, a verify chunk
# of B·(k+1) rows) do not take this bound: they stay all-experts up to
# QUANT_PALLAS_MAX_ROWS as before (no cell runs them wider, and a kernel
# that skips idle experts would gain there from the synthetic routing's
# collapse: ROADMAP S4).
MOE_ALL_EXPERTS_MAX_ROWS = 64

# Row tile of the grouped kernel. A visit converts its whole weight block
# whatever the rows it multiplies (1.3 us of a 2 MB block) and its dot costs
# by the tile's rows, so a sorted run of M rows over G groups costs about
# (M / tile + G) x (1.3 us + 0.02 us x tile): 64 is within 5% of the best
# tile from 1,024 to 8,192 sorted rows on all three cells' stacks (my chip
# run 1, PR 38: 64 / 128 / 256 / 512 rows a tile read 322 / 326 / 509 / 871
# us a 64 x 2048 x 1024 stack at 4,096 rows).
GROUP_ROWS = 64

def use_pallas_quant(impl: str = "auto") -> bool:
    """Resolve the quantized-matmul kernel choice.

    impl: "auto" (Pallas on TPU, XLA dequant elsewhere), "pallas", or
    "xla". The LOCALAI_QUANT_KERNEL env var overrides — same escape hatch
    as LOCALAI_PAGED_KERNEL for the paged decode kernel. "pallas" off-TPU
    runs in interpret mode (slow; tests only).
    """
    impl = os.environ.get("LOCALAI_QUANT_KERNEL", "") or impl or "auto"
    if impl == "auto":
        return jax.default_backend() == "tpu"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"quant kernel impl {impl!r}: use auto|pallas|xla")
    return impl == "pallas"


# The block rule (ISSUE 36). A grid step of these kernels costs 0.22 us
# before it moves a byte, and a DMA runs near the HBM's rate only over long
# contiguous runs. A weight leaf is [B, in, out] int8, row-major: `kc` WHOLE
# rows are one run of kc x out bytes, where a 512-wide column strip is kc
# runs of 512 bytes (65-72% of the HBM's rate alone; whole rows 84-91%). So
# a step's weight block is sized in bytes and laid along the rows, and x
# stays in VMEM whole rather than being fetched again for every out block
# or expert (6-12% of an expert matmul's traffic); see `_blocks`.
BLOCK_BYTES = 2560 << 10  # weight bytes one grid step moves (the chip's sweep)
TILE_ELEMS = 1 << 20    # weights converted to float32 at once inside a step
# All the blocks and tiles a step holds at once. The kernels ask for NO scoped
# VMEM limit of their own, so a step has to fit Mosaic's default (16 MiB on
# the v5e) with real room: `_held` counts the buffers Mosaic allocates (the
# pipelined blocks, the accumulator) and one float32 copy of the sub-tile on
# top, which is not exactly what Mosaic allocates (9.9 MiB at most where the
# count reads up to 11.9, compiled for the v5e at 70 shapes), so the budget
# stands a quarter under the limit. Every cell's expert, ffn and attention
# block at 32-64 rows fits it at 1.5-2.5 MB. A raised limit
# is not free: XLA parks what it likes in VMEM BESIDE a custom call
# (kimi-linear's [163840, 1] float32 head scale is 80 MiB there, tiled
# 8 x 128), and with 48 MiB asked for, then 32, that model's admission
# programs never came back on the chip (PERF.md section 6, PR 36).
VMEM_BUDGET = 12 << 20


class Blocks(NamedTuple):
    """What one grid step of `_qmm_call` holds: the weight block is `kc`
    rows of the in axis by `bo` out channels (gc = kc / gs groups for the
    grouped forms, else 1); x is resident `xk` wide (the whole in axis, or
    kc where that does not fit); inside the step the kernel converts and
    multiplies the block `sk` x `so` at a time."""

    kc: int
    bo: int
    xk: int
    sk: int
    so: int
    gc: int


def _divisors(n: int, unit: int, cap: int | None = None) -> list[int]:
    """Multiples of `unit` that divide n (up to cap), ascending; n itself
    where there is none (tiny shapes: the whole axis is always a legal
    block)."""
    top = n if cap is None else min(n, cap)
    ds = [d for d in range(unit, top + 1, unit) if n % d == 0]
    return ds or [n]


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _fit(widths: list[int], lengths: list[int], held, nbytes) -> tuple[int, int]:
    """(length, width) of a block: the first of `widths` (widest first) at
    which some of the ascending `lengths` fits (`held(length, width)` <=
    VMEM_BUDGET), and of those the longest whose block is BLOCK_BYTES at
    most, else the shortest that fits. Where nothing fits the count, the
    smallest legal block and not a failure."""
    for width in widths:
        fits = [c for c in lengths if held(c, width) <= VMEM_BUDGET]
        small = [c for c in fits if nbytes(c, width) <= BLOCK_BYTES]
        if fits:
            return (small[-1] if small else fits[0]), width
    return lengths[0], widths[-1]


def _sub_tile(kc: int, bo: int, gs: int = 0) -> tuple[int, int]:
    """(sk, so): the part of a kc x bo block converted to float32 at once,
    TILE_ELEMS weights at most (a quarter for the grouped forms, which hold
    more copies of it), whole groups, lane multiples."""
    k_unit = 128 if not gs else math.lcm(128, 8 * gs)
    sk = _divisors(kc, k_unit, max(512, k_unit))[-1]
    tile = TILE_ELEMS // (4 if gs else 1)
    return sk, _divisors(bo, 128, max(128, tile // sk))[-1]


def _x_whole(n: int, kin: int, x_bytes: int) -> bool:
    """x stays resident whole where that takes a quarter of the budget at
    most (else a k-chunk a step)."""
    return 2 * _up(n, 16) * _up(kin, 128) * x_bytes <= VMEM_BUDGET // 4


def _held(n: int, kin: int, kc: int, bo: int, *, gs: int = 0,
          packed: bool = False, zeros: bool = False, x_bytes: int = 2,
          out_bytes: int = 2) -> int:
    """Bytes a grid step of `_qmm_call` holds at a kc x bo weight block:
    the double-buffered weight, x, scale/zero and out blocks, the float32
    accumulator [n, bo], and the converted float32 sub-tile (and its scaled
    copy, grouped) the kernel walks the block in."""
    rows, lanes = _up(n, 16), _up(bo, 128)
    sk, so = _sub_tile(kc, bo, gs)
    xk = kin if _x_whole(n, kin, x_bytes) else kc
    scales = (_up(kc // gs, 8) if gs else 8) * lanes * 4 * (2 if zeros else 1)
    return int(
        2 * kc * (0.5 if packed else 1) * lanes
        + 2 * rows * _up(xk, 128) * x_bytes
        + 2 * scales
        + rows * lanes * 4
        + 2 * rows * lanes * out_bytes
        + _up(sk, 32) * _up(so, 128) * 4 * (2 if gs else 1))


def _blocks(n: int, kin: int, out: int, *, gs: int = 0, packed: bool = False,
            zeros: bool = False, x_bytes: int = 2, out_bytes: int = 2) -> Blocks:
    """Size a grid step's weight block in bytes and in whole rows: a pure
    function of what the call can see (rows of x, the two widths, the
    leaf's byte width, the group size) and the one VMEM budget.

    Prefer bo = out, the weight's whole contiguous rows, and take the most
    rows kc whose block stays within BLOCK_BYTES; narrow bo (to the widest
    multiple of 128 lanes that divides out) only where a full-width step
    cannot be held (`_held`) in VMEM_BUDGET. kc is a multiple of 128 (x's
    lane tile; it covers the int8 sublane tile of 32, packed or not) and of
    8 groups for the grouped forms (the scale block's sublane tile), or the
    whole in axis."""
    kcs = _divisors(kin, 128 if not gs else math.lcm(128, 8 * gs))
    if kcs[-1] != kin:
        kcs.append(kin)
    widths = [out] + [d for d in reversed(_divisors(out, 128)) if d != out]
    kc, bo = _fit(
        widths, kcs,
        functools.partial(_held, n, kin, gs=gs, packed=packed, zeros=zeros,
                          x_bytes=x_bytes, out_bytes=out_bytes),
        lambda c, w: c * w // (2 if packed else 1))
    return Blocks(kc, bo, kin if _x_whole(n, kin, x_bytes) else kc,
                  *_sub_tile(kc, bo, gs), kc // gs if gs else 1)


def _unembed_blocks(n: int, v: int, d: int, *,
                    x_bytes: int = 2) -> tuple[int, int, int]:
    """(bv, kc, sv) for the vocab-major head [V, D], by the same rule
    (`_fit`) on the other axis: a row of the head is one out channel, so
    whole rows are kc = d and the block is `bv` of them (a multiple of 128:
    the out block's lane tile); the kernel converts `sv` rows at a time. d
    is cut (to a multiple of 128 that divides it) only where 128 whole rows
    do not fit."""
    rows = _up(n, 16)

    def sub(bv, kc):
        return _divisors(bv, 128, max(128, TILE_ELEMS // kc))[-1]

    def held(bv, kc):  # weight, h, acc + out, the [bv, 1] scale, the tile
        return (2 * bv * _up(kc, 128) + 2 * rows * _up(kc, 128) * x_bytes
                + 3 * rows * _up(bv, 128) * 4 + 2 * _up(bv, 8) * 128 * 4
                + 2 * sub(bv, kc) * _up(kc, 128) * 4)

    bv, kc = _fit(list(reversed(_divisors(d, 128))), _divisors(v, 128), held,
                  lambda b, c: b * c)
    return bv, kc, sub(bv, kc)


def _rows(x: jnp.ndarray, tail: int = 1) -> int:
    r = 1
    for d in x.shape[: x.ndim - tail]:
        r *= int(d)
    return r


def _leaf(w: dict):
    """The weight array of a quantized dict (None if it is not one)."""
    return w.get("q", w.get("gq", w.get("g4")))


def _tp_degree(mesh) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("tp", 1))


# --------------------------------------------------------------------------- #
# Kernels
# --------------------------------------------------------------------------- #


def _span(i, size: int, whole: bool):
    """The i-th run of `size` along a block's axis (i may be traced: the
    start is a multiple of size), or the axis whole where one run spans it
    (tiny shapes whose axes are no multiple of a tile)."""
    import jax.experimental.pallas as pl

    return slice(None) if whole else pl.ds(pl.multiple_of(i * size, size), size)


def _accumulate(k, x_ref, w_ref, s_ref, z_ref, acc_ref, *, gs: int, sk: int,
                so: int, packed: bool):
    """acc += x @ dequant(w) for k-chunk `k` of one weight block (acc zeroed
    at k == 0): the body the dequant-matmul kernels share.

    Blocks: x (1, N, kc | Kin) float, w (1, kc[/2], bo) i8/u8, s (1, gc|1, bo)
    f32, optional z (1, gc, bo) f32, acc scratch (N, bo) f32. gs == 0 means
    the flat per-channel form (the caller scales once at its final write);
    packed means two nibbles per weight byte along the in-group axis (low
    nibble = first gs/2 elements — models/quant.py), shipped bitcast to int8.

    The DMA is the whole block (`_blocks`); the arithmetic walks it in
    sk x so sub-tiles with rolled loops (convert + dot a sub-tile,
    accumulate), so the converted float32 tile is bounded (TILE_ELEMS)
    and the body does not grow with the block.
    """
    import jax.experimental.pallas as pl

    bo = acc_ref.shape[-1]
    kc = w_ref.shape[1] * (2 if packed else 1)
    nks, nos = kc // sk, bo // so
    # x is resident whole (its block spans every k-chunk) or a chunk a step
    x_whole = x_ref.shape[-1] != kc
    sgc = sk // gs if gs else 1  # groups a sub-tile holds
    sk_w = sk // 2 if packed else sk

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def sub_tile(rows, cols):
        """acc[:, cols] += x[:, rows] @ dequant(w[rows, cols]); `rows`
        indexes sub-tiles of sk along in, `cols` of so along out."""
        r_x = (_span(k * nks + rows, sk, False) if x_whole
               else _span(rows, sk, nks == 1))
        r_w = _span(rows, sk_w, nks == 1)
        r_g = _span(rows, sgc, nks == 1)
        c = _span(cols, so, nos == 1)
        xb = x_ref[0, :, r_x].astype(jnp.float32)  # [N, sk]
        wb = w_ref[0, r_w, c]  # [sk(,/2), so] int8 (packed: two nibbles a byte)
        if packed:
            # Widen to 32 bits BEFORE any reshape/bit op: Mosaic has no
            # uint8→f32 convert and no 8-bit shifts, and the (sgc, gs/2, so)
            # regroup only lands on whole sublane tiles at 32-bit width (the
            # int8 tile is 32 rows, the half-group is 16). The wrapper
            # bitcasts the uint8 bytes to int8, so the sign-extended
            # arithmetic shift is masked back to the nibble.
            half = gs // 2
            wi = wb.astype(jnp.int32).reshape(sgc, half, so)
            nib = jnp.concatenate([wi & 0xF, (wi >> 4) & 0xF], axis=1)
            wf = nib.astype(jnp.float32)  # [sgc, gs, so]
        elif gs:
            wf = wb.astype(jnp.float32).reshape(sgc, gs, so)
        else:
            wf = wb.astype(jnp.float32)  # flat: [sk, so]
        if gs:
            # Dequant in registers: the scaled f32 weight tile exists only
            # in VMEM for this one MXU pass — never written back to HBM.
            sb = s_ref[0, r_g, c].astype(jnp.float32)  # [sgc, so]
            wf = (wf * sb[:, None, :]).reshape(sk, so)
        part = jax.lax.dot_general(
            xb, wf, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if z_ref is not None:
            # Affine zero point: −Σᵢ x_{g,i} · z_{g,o} per group. The
            # per-group x sums ride the MXU against a 0/1 group-membership
            # matrix — a lane-splitting reshape of x is not something
            # Mosaic lowers.
            zb = z_ref[0, r_g, c].astype(jnp.float32)  # [sgc, so]
            row = jax.lax.broadcasted_iota(jnp.int32, (sk, sgc), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (sk, sgc), 1)
            member = ((row >= col * gs) & (row < (col + 1) * gs)).astype(
                jnp.float32)
            xs = jax.lax.dot_general(
                xb, member, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [N, sgc]
            part -= jax.lax.dot_general(
                xs, zb, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        acc_ref[:, c] += part

    if nks == 1 and nos == 1:
        sub_tile(0, 0)
    else:  # rolled: an unrolled walk would pay in compile time (setup_s)
        @pl.loop(0, nos)
        def _cols(j):
            @pl.loop(0, nks)
            def _rows_of(i):
                sub_tile(i, j)


def _result(acc_ref, s_ref, gs: int):
    """The accumulator as a step's result: the flat form's per-channel scale
    is applied here, once ([1, bo] broadcasts); the grouped forms scaled
    inside the step."""
    res = acc_ref[...]
    return res if gs else res * s_ref[0].astype(jnp.float32)


def _qmm_kernel(_layer_ref, x_ref, w_ref, s_ref, *rest, gs: int, sk: int,
                so: int, packed: bool):
    """One (expert, out-block, k-chunk) grid step of the dequant-matmul
    (`_accumulate`; out (1, N, bo) written at the last k-chunk).

    `_layer_ref` is the scalar-prefetched layer index: only the BlockSpec
    index maps read it (they pick this layer's blocks out of the stack).
    """
    import jax.experimental.pallas as pl

    z_ref = rest[0] if len(rest) == 3 else None
    o_ref, acc_ref = rest[-2], rest[-1]
    k = pl.program_id(2)
    nk = pl.num_programs(2)
    _accumulate(k, x_ref, w_ref, s_ref, z_ref, acc_ref, gs=gs, sk=sk, so=so,
                packed=packed)

    @pl.when(k == nk - 1)
    def _emit():
        o_ref[0] = _result(acc_ref, s_ref, gs).astype(o_ref.dtype)


def _gmm_kernel(_layer_ref, nvis_ref, gid_ref, tid_ref, off_ref, x_ref, w_ref,
                s_ref, *rest, gs: int, sk: int, so: int, packed: bool):
    """One (out-block, visit, k-chunk) grid step of the grouped
    dequant-matmul: visit v multiplies row tile `tid[v]` of the
    expert-sorted rows by expert `gid[v]`'s block (`_accumulate`, as the
    stacked kernel) and at the last k-chunk writes the rows of the tile
    that lie in that expert's group, [off[g], off[g + 1]); the tile's other
    rows keep what an earlier visit of the same out block wrote (the block
    stays in VMEM while consecutive visits name it). Visits from `nvis` on
    are padding of the static grid: they name the last real visit's blocks,
    so they move no byte, and do nothing.

    Blocks: x (1, tm, kc | Kin), out (1, tm, bo), acc (tm, bo); w, s, z as
    `_accumulate` takes them. Scalar prefetch: the layer index (index maps
    only), nvis [1], gid / tid [V], off [E + 1].
    """
    import jax.experimental.pallas as pl

    z_ref = rest[0] if len(rest) == 3 else None
    o_ref, acc_ref = rest[-2], rest[-1]
    v, k = pl.program_id(1), pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(v < nvis_ref[0])
    def _visit():
        _accumulate(k, x_ref, w_ref, s_ref, z_ref, acc_ref, gs=gs, sk=sk,
                    so=so, packed=packed)

        @pl.when(k == nk - 1)
        def _emit():
            res = _result(acc_ref, s_ref, gs)
            g = gid_ref[v]
            row = tid_ref[v] * res.shape[0] + jax.lax.broadcasted_iota(
                jnp.int32, res.shape, 0)
            mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
            o_ref[0] = jnp.where(mine, res.astype(o_ref.dtype), o_ref[0])


def _unembed_kernel(h_ref, w_ref, s_ref, o_ref, acc_ref, *, sv: int):
    """h @ qᵀ · s for the vocab-major lm_head layout {"q": [V, D],
    "s": [V, 1]} — each out block streams contiguous weight ROWS, so the
    transpose never materializes. Blocks: h (N, kc), w (bv, kc), s (bv, 1),
    out (N, bv) f32; the block is converted and multiplied `sv` rows at a
    time (a rolled loop, as in _qmm_kernel)."""
    import jax.experimental.pallas as pl

    k = pl.program_id(1)
    nk = pl.num_programs(1)
    bv = w_ref.shape[0]

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    hb = h_ref[...].astype(jnp.float32)  # [N, kc]

    def rows(i):
        r = _span(i, sv, sv == bv)
        wb = w_ref[r, :].astype(jnp.float32)  # [sv, kc]
        acc_ref[:, r] += jax.lax.dot_general(
            hb, wb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if sv == bv:
        rows(0)
    else:
        pl.loop(0, bv // sv)(rows)

    @pl.when(k == nk - 1)
    def _emit():
        o_ref[...] = acc_ref[...] * s_ref[...][:, 0][None, :]


# --------------------------------------------------------------------------- #
# pallas_call wrappers (local shapes — shard_map hands these per-chip views)
# --------------------------------------------------------------------------- #


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _layer_operand(layer):
    """The scalar-prefetch operand [1] int32 of a layer index (0 without)."""
    if layer is None:
        return jnp.zeros((1,), jnp.int32)
    return jnp.asarray(layer, jnp.int32).reshape(1)


def _qmm_call(x3, wq, s3, z3, *, gs: int, packed: bool, out_dtype,
              x_per_expert: bool, experts: int = 1, layer=None):
    """Grid launch over (E, out-tiles, k-chunks), E = `experts`.

    x3 [Ex, N, Kin] float (Ex = E when per-expert, else 1); wq [B, Kin(/2),
    out] int; s3 [B, G|1, out] f32; z3 [B, G, out] f32 or None. B is E, or
    L·E for weights still stacked over L layers: `layer` (traced int32
    scalar) rides scalar prefetch and the weight/scale/zero index maps read
    block `layer·E + e`, so the kernel's DMA takes this layer's blocks
    straight out of the stack and no per-layer copy of it exists. Without
    `layer` the index is 0. Returns [E, N, out] in out_dtype.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    E = experts
    out = wq.shape[-1]
    _, N, kin = x3.shape
    if packed:  # same bytes; the kernel masks the nibbles out of int32
        wq = jax.lax.bitcast_convert_type(wq, jnp.int8)
    blk = _blocks(
        N, kin, out, gs=gs, packed=packed, zeros=z3 is not None,
        x_bytes=x3.dtype.itemsize, out_bytes=jnp.dtype(out_dtype).itemsize)
    kc, bo, gc = blk.kc, blk.bo, blk.gc
    kc_w = kc // 2 if packed else kc
    note_blocks(wholerow=bo == out)
    grid = (E, out // bo, kin // kc)

    def xi(e, j, k, li):
        kx = k if blk.xk == kc else 0  # resident whole: fetched once
        return ((e, 0, kx) if x_per_expert else (0, 0, kx))

    def wi(e, j, k, li):
        return (li[0] * E + e, k, j)

    def si(e, j, k, li):  # flat: one scale row per out channel
        return (li[0] * E + e, k if gs else 0, j)

    in_specs = [
        pl.BlockSpec((1, N, blk.xk), xi),
        pl.BlockSpec((1, kc_w, bo), wi),
        pl.BlockSpec((1, gc, bo), si),
    ]
    args = [x3, wq, s3]
    if z3 is not None:
        in_specs.append(pl.BlockSpec((1, gc, bo), si))
        args.append(z3)
    li = _layer_operand(layer)
    kernel = functools.partial(
        _qmm_kernel, gs=gs, sk=blk.sk, so=blk.so, packed=packed)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, N, bo), lambda e, j, k, li: (e, 0, j)),
            scratch_shapes=[pltpu.VMEM((N, bo), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((E, N, out), out_dtype),
        interpret=_interpret(),
        name="int4_matmul" if packed else "int8_matmul",
    )(li, *args)


class Visits(NamedTuple):
    """The grouped kernel's walk over expert-sorted rows (`group_visits`):
    int32 nvis [1], gid / tid [V], off [E + 1], its scalar-prefetch
    operands after the layer index."""

    nvis: jax.Array
    gid: jax.Array
    tid: jax.Array
    off: jax.Array


def group_visits(sizes, m: int) -> Visits:
    """The grouped kernel's walk over `m` expert-sorted rows: `sizes` [E]
    rows a group, in sorted order from row 0 (rows past their sum, off[-1],
    lie in no group); tiles of `_row_tile(m)` rows. One visit a (row tile,
    group) pair that shares a row, in sorted order: consecutive visits of
    one group find its weight block resident, a tile that straddles groups
    is visited once a group, a tile wholly in no group and a group of no
    rows not at all. V = tiles + E - 1, the most visits there can be;
    entries from nvis on repeat the last real visit. One walk serves every
    matmul over the same sorted rows (llama._moe_ragged's three)."""
    e, tm = sizes.shape[0], _row_tile(m)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm  # a group's first tile
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    vend = jnp.cumsum(count)
    nvis = vend[-1]
    v = jnp.minimum(jnp.arange(-(-m // tm) + e - 1, dtype=jnp.int32),
                    jnp.maximum(nvis - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(vend, v, side="right"), e - 1)
    tid = first[gid] + v - (vend - count)[gid]
    off = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return Visits(nvis.reshape(1), gid.astype(jnp.int32),
                  tid.astype(jnp.int32), off)


def _row_tile(m: int) -> int:
    """Rows a visit of the grouped kernel multiplies, of `m` sorted rows."""
    return min(GROUP_ROWS, m)


@functools.partial(jax.jit, static_argnames=(
    "tm", "blk", "gs", "packed", "experts", "interpret"))
def _gmm_call(li, visits: Visits, xg, wq, s3, z3, *, tm: int, blk: Blocks,
              gs: int, packed: bool, experts: int, interpret: bool):
    """Grid launch over (out-tiles, visits, k-chunks) of the grouped
    dequant-matmul: expert-sorted rows xg [M, Kin] (any M: x is tiled
    `tm` = `_row_tile(M)` at a time, never resident whole) against the stack wq
    [B, Kin(/2), out], B = E or L·E with the layer index `li` [1]
    scalar-prefetched as in `_qmm_call` (block `layer·E + e`: no slice of
    the stack, no dequantized copy). The walk is `group_visits`' over M
    rows; `blk` the weight block `_blocks` gives a row tile. Returns
    [M, out] in xg's dtype; rows in no group are NOT written (uninitialised
    memory: the caller zeroes them).

    Jitted on its own inside the program that calls it, so that calls of
    one shape share one trace of the kernel and one Mosaic lowering: an
    admission program's gate and up projections, and in a hybrid model
    those of both layer kinds (a call costs the warm-up some 0.15 s of
    Python a program otherwise, `setup_s`: PERF.md §6 PR 38). Everything
    the trace depends on besides the operands is a static argument."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    E = experts
    out = wq.shape[-1]
    M, kin = xg.shape
    if packed:  # same bytes; the kernel masks the nibbles out of int32
        wq = jax.lax.bitcast_convert_type(wq, jnp.int8)
    kc, bo, gc = blk.kc, blk.bo, blk.gc
    kc_w = kc // 2 if packed else kc
    nk = kin // kc

    def kq(v, k, nv):  # a padding visit stays on the last block fetched
        return jnp.where(v < nv[0], k, nk - 1)

    def xi(j, v, k, li, nv, gid, tid, off):
        return (0, tid[v], kq(v, k, nv) if blk.xk == kc else 0)

    def wi(j, v, k, li, nv, gid, tid, off):
        return (li[0] * E + gid[v], kq(v, k, nv), j)

    def si(j, v, k, li, nv, gid, tid, off):
        return (li[0] * E + gid[v], kq(v, k, nv) if gs else 0, j)

    in_specs = [
        pl.BlockSpec((1, tm, blk.xk), xi),
        pl.BlockSpec((1, kc_w, bo), wi),
        pl.BlockSpec((1, gc, bo), si),
    ]
    args = [xg[None], wq, s3]
    if z3 is not None:
        in_specs.append(pl.BlockSpec((1, gc, bo), si))
        args.append(z3)
    kernel = functools.partial(
        _gmm_kernel, gs=gs, sk=blk.sk, so=blk.so, packed=packed)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(out // bo, visits.gid.shape[0], nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, tm, bo),
                lambda j, v, k, li, nv, gid, tid, off: (0, tid[v], j)),
            scratch_shapes=[pltpu.VMEM((tm, bo), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((1, M, out), xg.dtype),
        interpret=interpret,
        name="int4_grouped_matmul" if packed else "int8_grouped_matmul",
    )(li, *visits, *args)[0]


def _operands(w: dict, lead: int):
    """A quantized dict's leaves as the kernel takes them: every leading
    axis (layer stack, experts; `lead` of them) merged into one block axis.
    Returns (wq [B, Kin(/2), out], s3 [B, G|1, out], z3 | None, group width
    or 0 for the flat form, packed)."""
    if "q" in w:
        q, s = w["q"], w["s"]
        return (q.reshape(-1, *q.shape[lead:]), s.reshape(-1, *s.shape[lead:]),
                None, 0, False)
    packed = "g4" in w
    wq = w["g4"] if packed else w["gq"]  # [.., G, gs(/2), out]
    g, gsw, out = wq.shape[lead:]

    def per_group(a):  # [.., G, 1, out] → [B, G, out]
        return a.reshape(-1, g, out)

    return (wq.reshape(-1, g * gsw, out), per_group(w["gs"]),
            per_group(w["gz"]) if "gz" in w else None,
            gsw * (2 if packed else 1), packed)


def _plain_matmul(x: jnp.ndarray, w: dict, layer=None) -> jnp.ndarray:
    """Non-MoE quantized x @ w on local (possibly shard-local) shapes; with
    `layer`, w's leaves are the stack over layers."""
    lead = x.shape[:-1]
    x3 = x.reshape(1, _rows(x), x.shape[-1])
    wq, s3, z3, gs, packed = _operands(w, 0 if layer is None else 1)
    out = _qmm_call(
        x3, wq, s3, z3, gs=gs, packed=packed, out_dtype=x.dtype,
        x_per_expert=False, layer=layer,
    )
    return out.reshape(*lead, -1)


def _plain_moe_mm(x: jnp.ndarray, w: dict, sub: str, layer=None) -> jnp.ndarray:
    """MoE dequant-matmul for the two _moe_dense einsum shapes; with
    `layer`, w's leaves are [L, E, ...]."""
    per_expert = sub == "...ef,efd->...ed"
    if per_expert:
        lead = x.shape[:-2]
        e = x.shape[-2]
        n = _rows(x, tail=2)
        # [.., E, F] → [E, N, F]
        x3 = jnp.moveaxis(x.reshape(n, e, x.shape[-1]), 1, 0)
    else:
        lead = x.shape[:-1]
        n = _rows(x)
        x3 = x.reshape(1, n, x.shape[-1])
    axes = 1 if layer is None else 2  # leaves [E, ...] or [L, E, ...]
    wq, s3, z3, gs, packed = _operands(w, axes)
    out = _qmm_call(
        x3, wq, s3, z3, gs=gs, packed=packed, out_dtype=x.dtype,
        x_per_expert=per_expert, experts=_leaf(w).shape[axes - 1],
        layer=layer,
    )
    # out [E, N, F|D] → [.., E, F|D]
    y = jnp.moveaxis(out, 0, 1)  # [N, E, F|D]
    return y.reshape(*lead, y.shape[1], y.shape[2])


def _plain_unembed(h: jnp.ndarray, w: dict) -> jnp.ndarray:
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lead = h.shape[:-1]
    n = _rows(h)
    d = h.shape[-1]
    v = w["q"].shape[0]
    h2 = h.reshape(n, d)
    bv, kc, sv = _unembed_blocks(n, v, d, x_bytes=h.dtype.itemsize)
    out = pl.pallas_call(
        functools.partial(_unembed_kernel, sv=sv),
        grid=(v // bv, d // kc),
        in_specs=[
            pl.BlockSpec((n, kc), lambda j, k: (0, k)),
            pl.BlockSpec((bv, kc), lambda j, k: (j, k)),
            pl.BlockSpec((bv, 1), lambda j, k: (j, 0)),
        ],
        out_specs=pl.BlockSpec((n, bv), lambda j, k: (0, j)),
        out_shape=jax.ShapeDtypeStruct((n, v), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n, bv), jnp.float32)],
        interpret=_interpret(),
        name="int8_unembed",
    )(h2, w["q"], w["s"].astype(jnp.float32))
    return out.reshape(*lead, v)


# --------------------------------------------------------------------------- #
# Sharded dispatch (tp>1 — shard_map over the weight's own partitioning)
# --------------------------------------------------------------------------- #


def _w_specs(w: dict, part: str, lead: int):
    """PartitionSpecs for a quantized dict's leaves, mirroring
    parallel/sharding.param_shardings_for: col shards every leaf's out
    (last) axis; row shards the group/in axis, which sits behind `lead`
    leading axes (layer stack, experts); the flat scale is per-out and
    stays replicated."""
    from jax.sharding import PartitionSpec as P

    specs = {}
    for key, leaf in w.items():
        ax = [None] * leaf.ndim
        if part in ("col", "unembed"):
            # unembed's out axis is the leading V axis of [V, D]/[V, 1].
            ax[0 if part == "unembed" else -1] = "tp"
        elif key != "s":  # row: q in-axis / grouped G-axis; flat s replicated
            ax[lead] = "tp"
        specs[key] = P(*ax)
    return specs


def _sharded_quant_matmul(x, w, mesh, part: str, moe_sub=None, layer=None):
    """Run the local kernel per tp shard; row-parallel partials psum over
    "tp" here (the declared ICI boundary — see COLLECTIVE_BOUNDARY). With
    `layer`, w's leaves keep their leading layer axis, unsharded, and the
    index is replicated."""
    from jax.sharding import PartitionSpec as P

    row = part == "row"
    x_ax = [None] * x.ndim
    if row:
        x_ax[-1] = "tp"
    out_ndim = x.ndim + 1 if moe_sub == "...d,edf->...ef" else x.ndim
    o_ax = [None] * out_ndim
    if not row:
        o_ax[-1] = "tp"

    def local(xl, wl, li):
        if part == "unembed":
            y = _plain_unembed(xl, wl)
        elif moe_sub is not None:
            y = _plain_moe_mm(xl, wl, moe_sub, layer=li)
        else:
            y = _plain_matmul(xl, wl, layer=li)
        if row:
            y = jax.lax.psum(y, "tp")
        return y

    lead = (moe_sub is not None) + (layer is not None)
    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(*x_ax), _w_specs(w, part, lead), None if layer is None else P()),
        out_specs=P(*o_ax),
        check_vma=False,
    )
    return fn(x, w, layer)


# --------------------------------------------------------------------------- #
# Dispatchers (return None → caller falls back to its XLA oracle form)
# --------------------------------------------------------------------------- #


def _engaged(x, impl: str, tail: int = 1) -> bool:
    return (
        use_pallas_quant(impl)
        and jnp.issubdtype(x.dtype, jnp.floating)
        and _rows(x, tail) <= QUANT_PALLAS_MAX_ROWS
        and _rows(x, tail) > 0
    )


def _shardable(x, w: dict, part: str, tp: int, lead: int = 0) -> bool:
    """Every axis a tp shard_map would split must divide by tp — otherwise
    fall back to the XLA path (which GSPMD partitions or replicates as it
    can). col splits the out axis; row splits x's reduction axis and the
    weight's in/group axis, behind `lead` leading axes."""
    leaf = _leaf(w)
    if part in ("col", "unembed"):
        out_ax = 0 if part == "unembed" else leaf.ndim - 1
        return leaf.shape[out_ax] % tp == 0
    return (x.shape[-1] % tp == 0
            and leaf.shape[lead] % tp == 0)


def dispatch_matmul(x, w: dict, impl: str = "auto", mesh=None, part=None,
                    layer=None):
    """Fused x @ w for the non-MoE quantized forms, or None to fall back.
    With `layer`, w's leaves are stacked over layers and the kernel reads
    that layer in place; a None return leaves the slicing to the caller."""
    y = _dispatch_matmul(x, w, impl, mesh, part, layer)
    note_site(stacked=y is not None and layer is not None)
    return y


def _dispatch_matmul(x, w, impl, mesh, part, layer):
    leaf = _leaf(w)
    stacked = layer is not None
    if leaf is None or leaf.ndim != (2 if "q" in w else 3) + stacked:
        return None
    if not _engaged(x, impl):
        return None
    tp = _tp_degree(mesh)
    if tp > 1 and part in ("col", "row"):
        if not _shardable(x, w, part, tp, lead=int(stacked)):
            return None
        return _sharded_quant_matmul(x, w, mesh, part, layer=layer)
    return _plain_matmul(x, w, layer=layer)


def dispatch_moe_mm(x, w: dict, sub: str, impl: str = "auto", mesh=None,
                    layer=None):
    """Fused MoE dequant-matmul for _moe_dense's two einsum shapes, or
    None to fall back; `layer` as in dispatch_matmul. Part is implied by
    the shape: edf projects OUT to the tp-sharded F axis (col), efd
    contracts the sharded F axis (row). Expert-parallel (ep>1) meshes fall
    back to the XLA path."""
    y = _dispatch_moe_mm(x, w, sub, impl, mesh, layer)
    note_site(stacked=y is not None and layer is not None)
    return y


def _dispatch_moe_mm(x, w, sub, impl, mesh, layer):
    if sub not in ("...d,edf->...ef", "...ef,efd->...ed"):
        return None
    per_expert = sub == "...ef,efd->...ed"
    if not _engaged(x, impl, tail=2 if per_expert else 1):
        return None
    tp = _tp_degree(mesh)
    if tp > 1:
        part = "row" if per_expert else "col"
        if int(mesh.shape.get("ep", 1)) > 1:
            return None
        if not _shardable(x, w, part, tp, lead=1 + (layer is not None)):
            return None
        return _sharded_quant_matmul(x, w, mesh, part, moe_sub=sub, layer=layer)
    return _plain_moe_mm(x, w, sub, layer=layer)


def grouped_engaged(x, w: dict, impl: str = "auto", mesh=None,
                    layer=None) -> bool:
    """Does the grouped kernel take expert-sorted rows like x [.., in]
    against the expert stack w ([E, ...] leaves, [L, E, ...] with `layer`)?
    Any quantized form, any row count; not under a tp or ep mesh (Pallas is
    opaque to GSPMD and the sort is over the whole batch), off the TPU only
    when asked for by name (interpret mode)."""
    leaf = _leaf(w)
    if leaf is None or leaf.ndim != (3 if "q" in w else 4) + (layer is not None):
        return False
    if mesh is not None and (_tp_degree(mesh) > 1
                             or int(mesh.shape.get("ep", 1)) > 1):
        return False
    return (use_pallas_quant(impl) and jnp.issubdtype(x.dtype, jnp.floating)
            and _rows(x) > 0)


def grouped_moe_mm(xg, w: dict, visits: Visits, layer=None):
    """xg [M, in] expert-sorted rows times their experts' matrices, on the
    quantized stack as it is stored (`grouped_engaged` said yes): `visits`
    the walk of the M rows' groups (`group_visits`). Returns [M, out]; rows
    in no group (from visits.off[-1] on) are not written."""
    axes = 1 if layer is None else 2  # leaves [E, ...] or [L, E, ...]
    wq, s3, z3, gs, packed = _operands(w, axes)
    tm = _row_tile(xg.shape[0])
    blk = _blocks(
        tm, xg.shape[1], wq.shape[-1], gs=gs, packed=packed,
        zeros=z3 is not None, x_bytes=xg.dtype.itemsize,
        out_bytes=xg.dtype.itemsize)
    note_grouped()
    note_blocks(wholerow=blk.bo == wq.shape[-1])
    return _gmm_call(
        _layer_operand(layer), visits, xg, wq, s3, z3, tm=tm, blk=blk, gs=gs,
        packed=packed, experts=_leaf(w).shape[axes - 1],
        interpret=_interpret())


def dispatch_unembed(h, w: dict, impl: str = "auto", mesh=None):
    """Fused h @ qᵀ·s for the quantized lm_head, or None to fall back."""
    if "q" not in w or w["q"].ndim != 2 or w["s"].shape[-1] != 1:
        return None
    if not _engaged(h, impl):
        return None
    tp = _tp_degree(mesh)
    if tp > 1:
        if not _shardable(h, w, "unembed", tp):
            return None
        return _sharded_quant_matmul(h, w, mesh, "unembed")
    return _plain_unembed(h, w)
