"""Fused ragged paged-attention decode kernel (Pallas, TPU).

The paged decode hot op. The XLA reference path
(ops/attention._paged_cache_partials) gathers page tiles into HBM scratch
each fori_loop step — `k_pool[pids]` materializes a [B, CH·page, K, D]
buffer per chunk, so every live KV byte is read from HBM, written back to
HBM, and read again by the einsum (3x the traffic that the math needs), and
the gather itself cannot overlap the matmul. BENCH_r04 put paged decode at
0.73x of the dense cache for exactly this reason.

This kernel walks each slot's page table IN-KERNEL ("Ragged Paged
Attention", PAPERS.md): the pool stays in HBM (memory_space=ANY), and the
kernel streams the listed pages through a ring of VMEM page buffers with
explicit async DMAs: the visits of ALL the slots are ONE STREAM in the
grid's order, and visits g+1 .. g+ring-1 of it are in flight while visit g
is scored against the online-softmax running state (`_ring_depth`; `top_up`
in the kernel body), whatever slots they belong to, so the wire does not
wait for a slot's last dots, its write-back and the next program's
prologue, which a walk of one or two visits a slot otherwise spends a fifth
to a third of its time on (the dots themselves hide under the copies:
PERF.md §6 PRs 50, 54). Each live KV byte crosses HBM→VMEM exactly once,
the walk stops at the slot's OWN live-prefix bound (ragged, not the batch
max), and idle slots (limits == 0) cost nothing.

What a page visit computes (ISSUE 32; docs/PAGED_ATTENTION.md). A page of a
16- or 8-bit pool goes to the MXU AS IT IS STORED: its [page, K, D] tile is
the [page·K, D] matrix of (token, head) rows, so one dot a pool a page
scores every head's query rows against all of it and the columns of the
other heads are masked with the dead rows (`_flat_rows` says when; the
decode block of every paged model). Cutting each head's [page, D] tile out
of that layout was most of a visit: 2 us over a 0.87 us DMA at K = 8, 4 us
at K = 16. q and p enter the dots in bfloat16, which is what Mosaic's
one-pass float32 dot made of them all along (2e-3 of the output against a
float64 walk, before and after). A float32 pool and wide query tiles
(prefill chunks) keep the per-head float32 tiles.

A LATENT pool (MLA's absorbed decode: one row a token, read as key and as
value; `latent_paged_attention`) is walked by the same visit (ISSUE 48): its
page [page, W] is the [C, D] matrix of the as-stored form at K = 1 already,
and the kernel body is told that the value pool IS the key pool (`shared`):
one copy and one semaphore a page, the score dot and `p @ kbuf` on the one
tile. A visit is bounded
in bytes as well as in rows (`_visit_pages`), so six 128-row pages of 640
bfloat16 lanes are one. One thing more is the latent walk's own (ISSUE
50): the caller states how many leading lanes of a row are VALUES (MLA's
kv_lora_rank, 512 of the 640: the rest is the rope part and the pad, which
only the score reads), and `p @ kbuf` runs over those lanes alone, in whole
lane tiles (`value_lanes`), into an accumulator and an output that wide.

Shapes (matching the XLA reference):
- q rows     [B, K, QR, Dk] f32, 1/sqrt(D) pre-applied; QR = G query rows
  per kv head (G·T for the multi-query verify chunk).
- k/v pool   [P, page, K, Dk|Dv] in the cache storage dtype (bf16/fp8: read
  as stored, or cast to f32 on read in the per-head form), or a
  stacked.StackedLayer of the whole [L, P, page, K, D] pool: what the kernel
  is handed is ALWAYS the stack plus a layer index (a plain pool rides as a
  free [1, ...] view at layer 0), the index is one more scalar-prefetch
  operand, and the page DMAs read `pool[layer, table[b, j]]`. The engine's
  pool is stacked over layers and a custom call's operand is a buffer, so
  a per-layer slice in front of the kernel was a copy of the layer's whole
  pool, every layer of every step, to let the kernel DMA a few pages of it
  (ISSUE 27; docs/PAGED_ATTENTION.md).
- table      [B, MP] int32 page ids (scalar-prefetch: the DMA descriptors
  are computed from it before the body runs).
- limits     [B] int32 — rows with global index >= limits[b] are masked;
  the page walk is bounded by ceil(limits[b]/page).
- qpos       [B, QR] int32 query positions (sliding-window distance);
  shipped to the kernel as [B, QR, 1] so a slot's block spans the array's
  whole last two dims (Mosaic's block rule) and lands row-per-sublane,
  the orientation the [QR, page] masks broadcast from.
- sliding    [1] int32 — traced per-layer flag (gemma-2 alternates
  sliding/global layers inside a scanned stack, so it cannot be static).

Returns online-softmax partials (acc, m, l) — f32, exactly the reference's
contract — which the existing _merge_partials/_merge_partials_mq fold with
the block-local window and current token. Keeping the merge in XLA keeps
ONE numeric tail for both paths, so the reference doubles as the kernel's
oracle (tests/test_paged_flash.py runs this kernel in interpret mode on
CPU against it).

The m/l outputs are padded to 128 lanes (STAT_LANES) and sliced by the
wrapper: a 1-wide lane dimension is a legal VMEM scratch shape but a
pathological output tiling on real hardware.

The same kernel also serves CHUNKED RAGGED PREFILL (docs/CHUNKED_PREFILL.md):
paged_prefill_partials_mq tiles a prefill chunk's T·G query rows so the
online-softmax running state fits VMEM, and models/llama.prefill_chunk_paged
folds the partials with the in-chunk causal window and scatters the chunk's
fresh K/V straight into the slot's pages — no dense-bucket intermediate.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30
STAT_LANES = 128

# Query rows (K·QR, every head's) up to which a narrow pool's page goes to
# the MXU as stored (`_flat_rows`): the MXU's own row count. Past it the
# wrong-head columns cost more rows streamed than the per-head tiles cost
# to cut out, and the [rows, page·K] score array outgrows its registers.
FLAT_MAX_ROWS = 128
# (token, head) rows of K and V an as-stored visit scores at once
# (`_visit_pages`): the largest visit of which a ring of RING_MAX buffers
# still fits RING_VMEM_BYTES at 128-wide bfloat16 heads (3 MB / (4 x 512 B)),
# and under two pages of an 8-KV-head pool, so 8 heads and more walk a page
# a visit as they did. Measured (PERF.md §6 PR 41, a 32-layer scan at the
# tp = 4 cell's shape, us a layer at 1,024 / 1,536 / 2,048 rows): K = 2
# 51.1 / 36.3 / 39.2 beside 73.1 at a page a visit, K = 4 74.2 / 69.5 / 70.9
# beside 88.9; contexts of 1,500-3,000 tokens K = 2 127.4 / 123.4 / 118.6
# beside 251.5.
VISIT_ROWS = 1536
# ... and the bytes a visit lands in VMEM at most: VISIT_ROWS rows of a wide
# row (MLA's latent one, 1,280 B, key and value at once) would be 1.9 MB a
# visit and a ring of two. Above VISIT_ROWS x 512 B, so that at 128-wide 16-
# and 8-bit heads the rows decide as they did; six 128-row latent pages.
# Measured (PERF.md §6 PR 48, a 7-layer scan at the Kimi-Linear cell's shape,
# us a layer at 1 / 2 / 4 / 6 / 12 pages a visit, every visit's dots over all
# its columns): contexts of 300-800 tokens 167.5 / 125.6 / 103.5 / 97.3 /
# 100.3 beside 205.6 for the kernel before and 70.8 for the copies alone, of
# 2,000-4,000 771.0 / 510.1 / 381.3 / 361.7 / 355.6 beside 848.5 and 321.0;
# with a last visit's dots over its live pages alone 4 / 6 / 8 / 12 pages
# read 105.8 / 90.9 / 88.0 / 87.7 and 381.8 / 358.3 / 357.9 / 354.0.
VISIT_BYTES = 1024 * 1024
# VMEM the ring of visit buffers may take, and its depth bounds (`_ring_depth`).
RING_VMEM_BYTES = 3 * 1024 * 1024
RING_MAX = 4


def _flat_rows(k_dtype, v_dtype, num_kv: int, qr: int) -> bool:
    """Does a page go to the MXU as it is stored? A `[page, K, D]` tile of a
    16- or 8-bit pool IS the `[page·K, D]` matrix whose row n·K + h is token
    n's head h, in HBM and in VMEM, as long as a token's K heads fill whole
    32-bit words of a sublane (XLA:TPU then takes the reshape as a bitcast;
    compiled for a v5e at K = 2, 8, 16 in bfloat16 and K = 8, 16 in fp8,
    while fp8 at K = 2 was a copy of the pool). ONE 16-bit head a token (a
    multi-query pool, as a latent one) has no head axis to interleave: its
    `[page, 1, D]` page is the `[page, D]` matrix itself, and it is the
    per-head form that Mosaic refuses there (a slice of one head out of a
    tile of two: PR 55, ai21-jamba2-3b's pool, compiled for a v5e with no
    copy of the pool). A float32 pool, any other odd head count and wide
    query tiles (prefill chunks) keep the per-head tiles."""
    size = jnp.dtype(k_dtype).itemsize
    return (size < 4 and jnp.dtype(v_dtype).itemsize == size
            and ((num_kv, size) == (1, 2) or (num_kv * size) % 4 == 0)
            and num_kv * qr <= FLAT_MAX_ROWS)


def _visit_pages(page: int, num_kv: int, width: int, row_bytes: int, *,
                 flat: bool, swin: int = 0) -> int:
    """Consecutive table columns of a slot that ONE visit of the page walk
    lands side by side and scores with one dot a pool (ISSUE 41): as many
    whole pages as fit VISIT_ROWS (token, head) rows and VISIT_BYTES at
    `row_bytes` a row in VMEM (K and V; a latent pool's one row), never
    more than the table has columns. A visit pays a fixed chain (score dot
    -> max -> exp -> sum -> `p @ V` -> rescale, each waiting on the one
    before: some 0.3 us whether it holds 256 rows or 2,048) and nothing
    hides it, so at 2 KV heads a chip (tp = 4), where a 128-row page is 256
    rows, the chain was paid once a 128 KB: sized in rows, a visit is the
    same work whatever the caller's head count or page size. 128-row pages:
    K = 2 gives 6, K = 4 gives 3, K = 8 and 16 give 1, the walk as it was,
    traced as it was (512 B a row there, so the rows decide); a latent pool
    of 1,280 B rows gives 6 by its bytes. One page a visit too for the
    per-head form (not `flat`: a float32 pool, wide query tiles) and under
    `swin`, whose walk skips the cold middle, so consecutive visits are not
    consecutive columns (no cell runs it; it stays out of the slots' one
    stream of visits as well, `_ragged_paged_kernel`)."""
    if not flat or swin:
        return 1
    rows = min(VISIT_ROWS, VISIT_BYTES // row_bytes)
    return max(1, min(rows // (page * num_kv), width))


def _ring_depth(visit_bytes: int) -> int:
    """Visit buffers in the DMA ring: what RING_VMEM_BYTES holds of one
    visit's K and V (`_visit_pages` pages of each), between the old double
    buffer and RING_MAX (a visit that is shorter than its DMA wants two in
    flight behind the one being scored; a fourth measured nothing more,
    PERF.md §6 PR 32)."""
    return max(2, min(RING_MAX, RING_VMEM_BYTES // max(1, visit_bytes)))


def use_pallas(impl: str = "auto") -> bool:
    """Resolve the paged-attention implementation choice.

    impl: "auto" (Pallas on TPU, XLA reference elsewhere), "pallas", or
    "xla". The LOCALAI_PAGED_KERNEL env var overrides — same escape hatch
    as LOCALAI_FLASH for the prefill kernel. "pallas" off-TPU runs in
    interpret mode (slow; tests only).
    """
    impl = os.environ.get("LOCALAI_PAGED_KERNEL", "") or impl or "auto"
    if impl == "auto":
        return jax.default_backend() == "tpu"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"paged kernel impl {impl!r}: use auto|pallas|xla")
    return impl == "pallas"


def _ragged_paged_kernel(
    *refs,  # scalar-prefetch table refs (see below), then operands/outs
    page: int,
    num_kv: int,
    softcap: float,
    window: int,
    sink: int = 0,
    swin: int = 0,
    l1_span: int = 0,
    ring: int = 2,
    flat: bool = False,
    pages: int = 1,
    shared: bool = False,
    values: int = 0,
    ring_rows: int = 0,
):
    """Kernel body. Scalar-prefetch layout depends on the table layout:

    FLAT (l1_span == 0):  table_ref [B, MP] i32
    HIER (l1_span  > 0):  l1_ref [B, ML1] i32, l0_ref [NTP, SPAN] i32 — a
        slot's page COLUMN j resolves through l0[l1[b, j // SPAN], j % SPAN]
        (ops/ptable), so one 1M-token slot ships a 64-entry directory row
        instead of an 8192-wide flat row that blows the SMEM prefetch
        budget.

    Then: limits_ref [B] i32, sliding_ref [1] i32, layer_ref [1] i32 (all
    prefetch), and the regular operands. What a page visit does with them
    comes in two forms, chosen by `_flat_rows` from the pool's dtype, K and
    QR (static):

    PER HEAD (a float32 pool, wide query tiles): q_ref [1, K, QR, Dk] f32,
        qpos_ref [1, QR, 1] i32, kvs_ref [2, K] f32 SMEM, k_hbm/v_hbm
        [L, P, page, K, D] (ANY), outputs acc/m/l [1, K, QR, ·], scratch
        kbuf/vbuf [ring, page, K, D], acc_s/m_s/l_s [K, QR, ·]. A static
        unroll over heads: each head's [page, D] tile is cut out of the
        page, upcast and scaled, and goes into a float32 dot.
    AS STORED (`flat`: a 16- or 8-bit pool, K·QR <= FLAT_MAX_ROWS): the
        page is the [C = page·K, D] matrix it is stored as (row n·K + h),
        q_ref [1, R = K·QR, Dk] f32 (row h·QR + i, the k scale folded in by
        the wrapper), qpos_ref [1, R, 1] i32, colhead_ref / colrow_ref
        [1, pages·C] i32 (h and n of a column), rowhead_ref [R, 1] i32,
        k_hbm/v_hbm [L, P, C, D] (ANY), outputs [1, R, ·], scratch kbuf/vbuf
        [ring, pages·C, D], acc_s/m_s/l_s [R, ·]. ONE dot a pool a visit:
        every head's query rows against every (n, h) row, the columns of
        the other heads masked with the dead rows, so `p @ V` over the same
        long axis is each head's own sum. Nothing is cut out, upcast or
        scaled per head; the MXU loads the same 2·K tiles a page either way.

    A VISIT is `pages` consecutive table columns of the slot
    (`_visit_pages`; 1 unless `flat`): one DMA pair a page (pages are
    scattered in the pool) into the `pages` parts of ONE ring buffer, one
    dot a pool over all of it, one max / exp / sum / rescale. Column c of
    a visit is row c // K of the visit's first page onwards, so the masks
    read as before. A slot's last visit holds 1..pages live pages: the rest
    are not fetched, and the visit's dots run over its live pages alone
    (`visit_flat`: one of `pages` traced sizes of the same step), so no
    unfetched part of a buffer is read, whatever it holds, and none is
    zeroed. The columns left out are columns the mask would have killed
    (`p` exactly 0), so every sum is the sum it was.

    The slots' visits are one STREAM: every visit of every slot starts
    `ring - 1` visits ahead of the one being scored, across slots, from
    whichever program is running then (`top_up`; the scratch is [4]: visits
    started, visits scored, and the slot and visit to start next). Only the
    cold-middle walk (`swin`) is outside it: it warms up `ring - 1` visits
    and prefetches inside its own program, and takes no scratch.

    `shared` (as stored only; MLA's latent pool, K = 1): the value pool IS
    the key pool. No v_hbm and no vbuf are passed: a page is ONE copy on
    one semaphore (sem [ring, pages]) into kbuf, which `p @ V` reads too.
    `values` (> 0: fewer than the row has) are the leading lanes of a row
    that are read as VALUES:
    `p @ V` runs over `kbuf[..., :values]` and acc is [R, values]; the lanes
    past them (MLA's rope part and the pad) only ever entered the score.

    sink/swin (windowed+sink decode, docs/LONG_CONTEXT.md): a row is
    attended iff `gpos < sink` or `q_pos - gpos < swin`. The page walk then
    SKIPS the cold middle — it visits columns [0, sink_cols) ∪ [win_lo,
    np_live) via an index remap, so a spilled slot streams only its sink
    pages + trailing window from HBM. Exact: skipped pages are fully masked
    either way. Which columns those are depends on the slot's own query
    positions, which no program before it holds: this walk alone keeps its
    copies inside its program (`_warmup`, `_prefetch`).

    ring_rows (a window layer's per-slot RING, engine/state.py): the slot's
    table lists the pages of a ring of `ring_rows` rows in which position p
    lives at row p mod ring_rows, and `limits` is the number of positions
    written so far, however many. The walk is the table's (the live-page
    count is clamped to its width as ever); what changes is the position a
    row is masked AT: row r < limit holds the last position below the limit
    that is r mod ring_rows (`masked`), so under `window` a row whose
    position has left the query's window is dead although it is still in
    the ring (the rows a decode block is about to overwrite). Keys are
    stored rotated, so no reader needs the rows' order.
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if l1_span:
        l1_ref, l0_ref = refs[0], refs[1]
        refs = refs[2:]
        table_width = l1_ref.shape[1] * l1_span
    else:
        table_ref = refs[0]
        refs = refs[1:]
        table_width = table_ref.shape[1]
    stream = not swin
    if stream:
        # SMEM [4] i32 scratch that outlives a program: the stream's state
        *refs, stream_ref = refs
    (
        limits_ref,  # scalar-prefetch [B] i32
        sliding_ref,  # scalar-prefetch [1] i32
        layer_ref,  # scalar-prefetch [1] i32 — which layer of the pools
        q_ref,  # f32, scale applied
        qpos_ref,  # i32, a query row's position
        *refs,  # the constants, the pools, the outputs, the visit buffers
        acc_s,  # VMEM scratch f32: the running (acc, m, l)
        m_s,
        l_s,
        sem,  # DMA semaphores [ring, <copies a visit>]: a page's K and V copy
    ) = refs
    # consts: per head kvs_ref; as stored colhead, colrow, rowhead. k_hbm /
    # v_hbm: pool dtype, memory_space=ANY, stacked over layers. acc / m / l:
    # out f32, m and l STAT_LANES wide. kbuf / vbuf: VMEM scratch
    # [ring, <a visit's pages>], pool dtype.
    if shared:  # one pool and one buffer: V's names are K's
        *consts, k_hbm, acc_ref, m_ref, l_ref, kbuf = refs
        v_hbm, vbuf = k_hbm, kbuf
    else:
        *consts, k_hbm, v_hbm, acc_ref, m_ref, l_ref, kbuf, vbuf = refs

    b = pl.program_id(0)
    lim = limits_ref[b]
    layer = layer_ref[0]

    def live_pages(limit):
        # A slot's own page count (ragged), clamped to the table width so a
        # bad limit can never index the table out of bounds.
        return jnp.minimum((limit + page - 1) // page, table_width)

    np_live = live_pages(lim)

    if swin:
        # Cold-middle skip: walk iteration j covers table column col(j).
        sink_cols = jnp.minimum(-(-sink // page) if sink else 0, np_live)
        qmin = jnp.min(qpos_ref[0])
        win_lo = jnp.clip((qmin - swin + 1) // page, 0, np_live)
        win_lo = jnp.maximum(win_lo, sink_cols)
        n_iter = sink_cols + np_live - win_lo
        gap = win_lo - sink_cols

        def col_of(j):
            return jnp.where(j < sink_cols, j, j + gap)
    else:
        n_iter = np_live if pages == 1 else (np_live + pages - 1) // pages

        def col_of(j):
            return j

    def page_of(row, col):  # the pool page in a slot's table column
        if l1_span:
            return l0_ref[l1_ref[row, col // l1_span], col % l1_span]
        return table_ref[row, col]

    # rows of a visit's buffer that one page fills
    part_rows = kbuf.shape[1] // pages

    def copies(pid, buf, part=0):
        """One page's K and V (`shared`: its one copy) into part `part` of a
        buffer of the ring."""
        def into(ref):
            if pages == 1:
                return ref.at[buf]
            return ref.at[buf, pl.ds(part * part_rows, part_rows)]

        per = 1 if shared else 2  # copies, and semaphores, a page
        k_copy = pltpu.make_async_copy(
            k_hbm.at[layer, pid], into(kbuf), sem.at[buf, per * part])
        if shared:
            return (k_copy,)
        return (k_copy, pltpu.make_async_copy(
            v_hbm.at[layer, pid], into(vbuf), sem.at[buf, 2 * part + 1]))

    def each_copy(act, row, j, live, buf):
        """`act` (start or wait) on the copies of visit j of slot `row` into
        buffer `buf` of the ring: the visit's first page, which the caller
        knows to be live, and of the others those below the slot's `live`
        pages."""
        for part in range(pages):
            col = col_of(j) if pages == 1 else j * pages + part

            def go(col=col, part=part):
                for dma in copies(page_of(row, col), buf, part):
                    act(dma)

            if part == 0:
                go()
            else:
                pl.when(col < live)(go)

    def start(dma):
        dma.start()

    def wait(dma):
        dma.wait()

    # The visits of ALL the slots are one STREAM, in the grid's order (its
    # programs run in order on one core and the scratch outlives them): the
    # ring holds the stream's next `ring` visits whatever slots they belong
    # to (`top_up`), so no slot's first visit waits for the slot before to
    # finish (a program was some 1.4 us beside 0.68 us a visit, PERF.md §6 PR
    # 32; at one or two visits a slot that was most of the walk, PR 54). The
    # scratch is [issued, consumed, slot, visit]: how many visits of the
    # stream were started and how many the slots before this one scored, and
    # the first visit not started yet. Visit j of this slot is number
    # consumed + j of the stream and lives in that buffer mod ring. Not
    # under swin: where that walk starts depends on the next slot's own
    # query positions, so it warms up and prefetches inside its own program.
    if stream:
        @pl.when(b == 0)
        def _first_slot():
            for i in range(4):
                stream_ref[i] = 0

        @pl.when(stream_ref[2] < b)  # nothing left to start in the slots behind
        def _catch_up():
            stream_ref[2] = b
            stream_ref[3] = 0

        base = stream_ref[1]
        stream_ref[1] = base + n_iter
        first = base % ring
    else:
        first = 0

    acc_s[...] = jnp.zeros_like(acc_s)
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)

    def top_up(g):
        """Start the stream's visits up to number g + ring - 1, the ring's
        whole depth ahead of visit g: this slot's later visits, then the
        next live slots' first ones, as far as that reaches."""
        def more(c):
            issued, slot, _ = c
            return (issued < g + ring) & (slot < pl.num_programs(0))

        def one(c):
            issued, slot, visit = c
            live = live_pages(limits_ref[slot])
            due = visit * pages < live  # or this slot has no visit left

            @pl.when(due)
            def _start():
                each_copy(start, slot, visit, live, issued % ring)

            return (issued + due.astype(jnp.int32),
                    jnp.where(due, slot, slot + 1),
                    jnp.where(due, visit + 1, 0))

        issued, slot, visit = jax.lax.while_loop(
            more, one, (stream_ref[0], stream_ref[2], stream_ref[3]))
        stream_ref[0], stream_ref[2], stream_ref[3] = issued, slot, visit

    if not stream:  # swin: the first visits ride before any is scored
        for ahead in range(ring - 1):
            @pl.when(ahead < n_iter)
            def _warmup(ahead=ahead):
                each_copy(start, b, ahead, np_live, ahead % ring)

    def masked(gpos):
        """Which of a page's rows a query row attends: gpos [1 | QR, ·]
        global row indices against the slot's limit and the windows."""
        valid = gpos < lim
        if ring_rows:  # a ring's row -> the position it holds (a power of two)
            gpos = gpos + ((lim - 1 - gpos) & ~(ring_rows - 1))
        if window:
            sl = sliding_ref[0] > 0
            dist = qpos_ref[0] - gpos  # [rows, 1] - [·, columns]
            valid = valid & (~sl | (dist < window))
        if swin:
            dist = qpos_ref[0] - gpos
            valid = valid & ((gpos < sink) | (dist < swin))
        return valid

    def visit_heads(slot, j):
        QR = q_ref.shape[2]
        (kvs_ref,) = consts
        # Global row indices covered by the table column this step visits.
        valid = masked(col_of(j) * page + jax.lax.broadcasted_iota(
            jnp.int32, (QR, page), 1))
        for kh in range(num_kv):  # static unroll — one MXU pass per kv head
            q = q_ref[0, kh]  # [QR, Dk]
            # fp8 KV dequant happens HERE, in registers on the VMEM tile the
            # DMA just landed — the pool's stored bytes never exist in HBM
            # at any wider dtype (per-head scale: ISSUE 9).
            kp = kbuf[slot, :, kh, :].astype(jnp.float32) * kvs_ref[0, kh]
            s = jax.lax.dot_general(
                q, kp, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [QR, page]
            if softcap:
                s = softcap * jnp.tanh(s / softcap)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_s[kh]  # [QR, 1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(jnp.maximum(m_prev - m_new, -80.0))
            p = jnp.exp(s - m_new)
            p = jnp.where(valid, p, 0.0)
            l_s[kh] = l_s[kh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            vp = vbuf[slot, :, kh, :].astype(jnp.float32) * kvs_ref[1, kh]
            acc_s[kh] = acc_s[kh] * alpha + jax.lax.dot_general(
                p, vp, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_s[kh] = m_new

    if flat:
        colhead_ref, colrow_ref, rowhead_ref = consts
        # Once a program: q in the MXU's dtype (what Mosaic's one-pass
        # float32 dot made of it, page after page) and whose column is whose.
        qb = q_ref[0].astype(jnp.bfloat16)  # [R, Dk]
        mine = colhead_ref[...] == rowhead_ref[...]  # [1, C] == [R, 1]

    def visit_flat(slot, j):
        if pages == 1:
            return score(slot, col_of(j) * page)
        # The dots of a last visit over its LIVE pages alone (one of `pages`
        # traced sizes): the columns of a page that was never fetched are
        # not free to score and mask (a six-page visit at K = 2 holds four
        # live pages on average and its own chain, not the wire, sets its
        # pace: PERF.md §6 PR 54; with 32 query rows a latent page's two
        # dots take as long as its copy, PR 48), and with none in the dots
        # nothing is left to zero.
        live = np_live - j * pages
        for n in range(1, pages + 1):
            pl.when((live == n) if n < pages else (live >= n))(
                functools.partial(score, slot, j * (pages * page), n))

    def tile(buf, slot, cols=None, lanes=0):
        """Columns `cols` (a `pl.ds`; None: all) of a visit's buffer as the
        MXU takes them, the leading `lanes` of each row if any are named (a
        bfloat16 page goes as it is, the cast is none; fp8 -> bfloat16 is
        exact)."""
        if lanes:
            at = (slot, slice(None) if cols is None else cols, pl.ds(0, lanes))
        else:
            at = slot if cols is None else (slot, cols)
        return buf[at].astype(jnp.bfloat16)

    def score(slot, first_row, live=pages):
        """One visit's dots and its step of the online softmax, over the
        first `live` pages of the visit's buffer."""
        if live == pages:
            cols, colrow, own = None, colrow_ref[...], mine
        else:
            cols = pl.ds(0, live * part_rows)
            colrow, own = colrow_ref[:, cols], mine[:, :live * part_rows]
        ok = own & masked(first_row + colrow)  # [R, live·C]
        s = jax.lax.dot_general(
            qb, tile(kbuf, slot, cols), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [R, live·C]: every head's rows against every (n, h) row
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_s[...]  # [R, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(jnp.maximum(m_prev - m_new, -80.0))
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_s[...] = l_s[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
            p.astype(jnp.bfloat16), tile(vbuf, slot, cols, values),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )  # p is zero off its own head: the long sum is the head's own
        m_s[...] = m_new

    def body(j, carry):
        # later visits ride the wire while this one computes
        if stream:
            top_up(base + j)
        else:
            @pl.when(j + ring - 1 < n_iter)
            def _prefetch():
                each_copy(start, b, j + ring - 1, np_live, (j + ring - 1) % ring)

        buf = (first + j) % ring
        each_copy(wait, b, j, np_live, buf)
        (visit_flat if flat else visit_heads)(buf, j)
        return carry

    jax.lax.fori_loop(0, n_iter, body, 0)

    acc_ref[0] = acc_s[...]
    m_ref[0] = jnp.broadcast_to(m_s[...], m_ref.shape[1:])
    l_ref[0] = jnp.broadcast_to(l_s[...], l_ref.shape[1:])


def value_lanes(values: int, width: int) -> int:
    """The leading lanes of a `width`-lane latent row that the value dot
    runs over when the caller reads `values` of them: whole 128-lane tiles
    (a lane slice of a VMEM tile starts and ends on one), the whole row
    where the round-up reaches it or no width is stated (0)."""
    return min(width, -(-values // STAT_LANES) * STAT_LANES) if values else width


def latent_paged_attention(q, pool, table, limits, interpret: bool = False,
                           values: int = 0):
    """Decode partials over a LATENT pool (MLA's absorbed form: one row a
    token, key and value at once), for the caller that says its pool is one
    (`paged_decode_partials(latent=True)`): the as-stored walk of
    `_paged_partials_rows` over the one pool, every query head a row of the
    one pseudo-head. q [B, H, D] at the pool's row width; pool a
    [P, page, 1, D] pool or its StackedLayer; a flat table. `values`: the
    leading lanes of a row the caller reads as values (MLA's kv_lora_rank;
    0: all of them). The scores are over the whole row either way; the sum
    of values is over Dv = `value_lanes(values, D)` lanes, and acc is that
    wide: lane i < Dv of it is what the whole row's walk gives there, the
    lanes past Dv do not exist.
    Returns (acc [B, 1, H, Dv], m [B, 1, H, 1], l [B, 1, H, 1]) f32."""
    from localai_tpu.ops import ptable as _pt

    B, H, D = q.shape
    if pool.shape[2] != 1 or pool.shape[3] != D or _pt.is_hier(table):
        raise ValueError(
            "latent_paged_attention wants a one-head pool of the query's "
            f"width and a flat page table: pool {tuple(pool.shape)}, q width "
            f"{D}, hierarchical table {_pt.is_hier(table)}")
    qr = (q.astype(jnp.float32) * (1.0 / D**0.5)).reshape(B, 1, H, D)
    return _paged_partials_rows(
        qr, jnp.broadcast_to(limits[:, None], (B, H)), pool, pool, table,
        limits, 0.0, 0, None, interpret, latent=True, values=values)


def _paged_partials_rows(
    qr: jnp.ndarray,  # [B, K, QR, Dk] f32, scale applied
    qpos_rows: jnp.ndarray,  # [B, QR] i32
    k_pool,  # [P, page, K, Dk], or a StackedLayer of [L, P, page, K, Dk]
    v_pool,  # [P, page, K, Dv], likewise
    table,  # [B, MP] i32, or hierarchical (l1 [B, ML1], l0 [NTP, SPAN])
    limits: jnp.ndarray,  # [B] i32
    softcap: float,
    window: int,
    sliding,
    interpret: bool,
    kv_scale=None,  # [2, K] f32 per-head (k, v) dequant scales, or None
    sink: int = 0,  # windowed+sink decode (docs/LONG_CONTEXT.md)
    swin: int = 0,
    ring: int | None = None,  # visit buffers in the DMA ring (tests); None: `_ring_depth`
    latent: bool = False,  # v_pool IS k_pool, [.., page, 1, D]: MLA's latent rows
    values: int = 0,  # ... of which the caller reads these leading lanes as values
    ring_rows: int = 0,  # the table's pages are a ring of this many rows
):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from localai_tpu.ops import ptable as _pt
    from localai_tpu.ops.stacked import (
        note_arith, note_value_lanes, note_visit, note_walk, stacks_of)

    B, K, QR, Dk = qr.shape
    k_pool, v_pool, layer = stacks_of(k_pool, v_pool, "layer_kv_pool")
    L, P, page = k_pool.shape[:3]
    Dv = v_pool.shape[4]
    if latent:  # the value dot over the lanes that are values (`value_lanes`)
        Dv = value_lanes(values, Dv)
        note_value_lanes(Dv < Dk)
    narrow = Dv if latent and Dv < Dk else 0  # 0: `p @ V` over V's whole rows
    # A latent page [page, 1, D] is the [C, D] matrix of the as-stored form
    # already (K = 1, every column the one head's), whatever `_flat_rows`
    # says of a pool WITH a head axis; a float32 one is rounded to bfloat16
    # on its way to the MXU, which is what Mosaic's float32 dot did with it.
    flat = latent or _flat_rows(k_pool.dtype, v_pool.dtype, K, QR)
    note_arith(native=flat and k_pool.dtype.itemsize < 4)
    sl_arr = jnp.asarray(
        sliding if sliding is not None else False
    ).reshape(1).astype(jnp.int32)
    if _pt.is_hier(table):
        l1, l0 = table
        l1_span = int(l0.shape[-1])
        width = int(l1.shape[1]) * l1_span
        tbl_args = (l1.astype(jnp.int32), l0.astype(jnp.int32))
    else:
        l1_span = 0
        width = int(table.shape[1])
        tbl_args = (table.astype(jnp.int32),)
    # what a (token, head) row lands in VMEM: K and V, or the one latent row
    row_bytes = Dk * k_pool.dtype.itemsize + (
        0 if latent else Dv * v_pool.dtype.itemsize)
    pages = _visit_pages(page, K, width, row_bytes, flat=flat, swin=int(swin))
    note_visit(multipage=pages > 1)
    note_walk(stream=not swin)
    if ring is None:
        ring = _ring_depth(pages * page * K * row_bytes)
    kernel = functools.partial(
        _ragged_paged_kernel, page=page, num_kv=K,
        softcap=float(softcap), window=int(window),
        sink=int(sink), swin=int(swin), l1_span=l1_span, ring=ring, flat=flat,
        pages=pages, shared=latent, values=narrow, ring_rows=int(ring_rows),
    )
    pools = (k_pool,) if latent else (k_pool, v_pool)
    qpos_rows = qpos_rows.astype(jnp.int32)
    if flat:
        # The page as stored: rows h·QR + i of q against the [C, D] view of
        # a page (a bitcast, `_flat_rows`), `pages` of them side by side a
        # visit. The k scale rides on q and the v scale on the finished sum:
        # a multiply by ones is never traced.
        R, C = K * QR, page * K
        if kv_scale is not None:
            qr = qr * kv_scale[0].astype(jnp.float32)[None, :, None, None]
        col = np.arange(pages * C, dtype=np.int32)
        operands = (
            qr.reshape(B, R, Dk), jnp.tile(qpos_rows, (1, K))[..., None],
            jnp.asarray(col % K)[None], jnp.asarray(col // K)[None],
            jnp.asarray(np.arange(R, dtype=np.int32) // QR)[:, None],
            *(a.reshape(L, P, C, a.shape[4]) for a in pools),
        )
        lead, zeros = (R,), (0,)
        const_specs = [
            pl.BlockSpec((1, pages * C), lambda b, *_: (0, 0)),
            pl.BlockSpec((1, pages * C), lambda b, *_: (0, 0)),
            pl.BlockSpec((R, 1), lambda b, *_: (0, 0)),
        ]
        page_shape = (pages * C,)
    else:
        kvs = (jnp.ones((2, K), jnp.float32) if kv_scale is None
               else kv_scale.astype(jnp.float32))
        operands = (qr, qpos_rows[..., None], kvs, *pools)
        lead, zeros = (K, QR), (0, 0)
        const_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]  # [2, K] scales
        page_shape = (page, K)

    def rows(n):  # one slot's block of a [B, *lead, n] array
        return pl.BlockSpec((1, *lead, n), lambda b, *_: (b, *zeros, 0))

    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tbl_args) + 3,
            grid=(B,),
            in_specs=[
                rows(Dk),
                pl.BlockSpec((1, lead[-1], 1), lambda b, *_: (b, 0, 0)),
                *const_specs,
                # the pools stay in HBM
                *(pl.BlockSpec(memory_space=pl.ANY) for _ in pools),
            ],
            out_specs=[rows(Dv), rows(STAT_LANES), rows(STAT_LANES)],
            scratch_shapes=[
                *(pltpu.VMEM((ring, *page_shape, a.shape[4]), a.dtype)
                  for a in pools),
                pltpu.VMEM((*lead, Dv), jnp.float32),
                pltpu.VMEM((*lead, 1), jnp.float32),
                pltpu.VMEM((*lead, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((ring, len(pools) * pages)),
                # the stream's state
                *([] if swin else [pltpu.SMEM((4,), jnp.int32)]),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, *lead, n), jnp.float32)
            for n in (Dv, STAT_LANES, STAT_LANES)
        ],
        # a program starts later programs' copies: in order, on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        # the name a capture's reader finds the kernel's self time by
        name=("latent_paged_attention" if latent else
              "window_attention" if ring_rows else "paged_attention"),
    )(
        *tbl_args, limits.astype(jnp.int32), sl_arr,
        jnp.asarray(layer, jnp.int32).reshape(1), *operands,
    )
    if flat:
        acc = acc.reshape(B, K, QR, Dv)
        if kv_scale is not None:
            acc = acc * kv_scale[1].astype(jnp.float32)[None, :, None, None]
        m, l = m.reshape(B, K, QR, -1), l.reshape(B, K, QR, -1)
    return acc, m[..., :1], l[..., :1]


def paged_decode_partials(
    q: jnp.ndarray,  # [B, H, D]
    k_pool,  # [P, page, K, Dk], or a StackedLayer of [L, P, page, K, Dk]
    v_pool,  # [P, page, K, Dv], likewise
    table: jnp.ndarray,  # [B, MP] int32
    limits: jnp.ndarray,  # [B] int32
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
    q_pos=None,
    interpret: bool = False,
    kv_scale=None,  # [2, K] f32 per-head (k, v) dequant scales (fp8 KV)
    sink: int = 0,  # windowed+sink decode (docs/LONG_CONTEXT.md)
    swin: int = 0,
    latent: bool = False,  # the caller's pool is MLA's latent one
    values: int = 0,  # ... whose rows' leading lanes these are read as values
    ring: int = 0,  # the table's pages are a per-slot ring of this many rows
):
    """Drop-in for attention._paged_cache_partials: returns
    (acc [B, K, G, Dv], m [B, K, G, 1], l [B, K, G, 1]) f32, scale applied.
    `latent`: the one pool is key and value (`latent_paged_attention`, which
    has none of the masks: asking for one with it is refused; with `values`
    its acc holds the value lanes alone). `ring`: the pool is a window
    layer's per-slot rings (`_ragged_paged_kernel`, ring_rows): a power of
    two of rows, a flat table, a head a row."""
    B, H, D = q.shape
    if ring and (latent or swin or ring & (ring - 1)
                 or k_pool.shape[-1] != D):
        raise ValueError(
            f"a ring of {ring} rows: a power of two, read without the latent "
            "form, the sink walk and packed heads")
    if latent:
        if kv_scale is not None or softcap or swin or (
                window and sliding is not None):
            raise ValueError("the latent paged kernel has no dequant scale, "
                             "softcap or window")
        return latent_paged_attention(q, k_pool, table, limits, interpret,
                                      values)
    Kp, Dp = k_pool.shape[-2:]  # as stored: `pack` heads a row
    pack = Dp // D
    K = Kp * pack
    G = H // K
    scale = 1.0 / (D**0.5)
    if q_pos is None:
        q_pos = limits
    if sliding is None:
        window = 0
    qr = (q.astype(jnp.float32) * scale).reshape(B, K, G, D)
    if pack == 1:
        qpos_rows = jnp.broadcast_to(q_pos[:, None], (B, G))
        return _paged_partials_rows(
            qr, qpos_rows, k_pool, v_pool, table, limits,
            softcap, window, sliding, interpret, kv_scale=kv_scale,
            sink=sink, swin=swin, ring_rows=ring,
        )
    # Narrow heads, `pack` of them a row of the pool (ArchConfig.cache_pack:
    # row i of a token holds heads pack·i .. pack·i + pack - 1 side by side,
    # a 128-lane row where one 64-wide head is none and Mosaic refuses the
    # page as stored). The walk is the wide-head walk over Kp row-heads: the
    # pack·G query rows of a row-head carry q in their own head's lanes and
    # zeros in the others, so a row's score is its own head's and `p @ V`
    # holds its head's sum in the same lanes, which are cut out here. The
    # kernel and its bytes are those of Kp heads of width pack·D.
    if kv_scale is not None:
        raise NotImplementedError("several heads a row: no dequant scales")
    Dv = v_pool.shape[-1] // pack
    own = jnp.eye(pack, dtype=qr.dtype)  # [head j of the row, lanes' part]
    qp = jnp.einsum("bkjgd,ji->bkjgid", qr.reshape(B, Kp, pack, G, D), own)
    acc, m, l = _paged_partials_rows(
        qp.reshape(B, Kp, pack * G, Dp),
        jnp.broadcast_to(q_pos[:, None], (B, pack * G)), k_pool, v_pool,
        table, limits, softcap, window, sliding, interpret, sink=sink,
        swin=swin,
    )
    acc = jnp.einsum("bkjgid,ji->bkjgd",
                     acc.reshape(B, Kp, pack, G, pack, Dv), own)
    return (acc.reshape(B, K, G, Dv), m.reshape(B, K, G, 1),
            l.reshape(B, K, G, 1))


def paged_decode_partials_mq(
    q: jnp.ndarray,  # [B, T, H, D]
    k_pool,  # a pool or its StackedLayer, as in paged_decode_partials
    v_pool,
    table: jnp.ndarray,
    limits: jnp.ndarray,
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
    q_pos=None,  # [B, T]
    interpret: bool = False,
    kv_scale=None,  # [2, K] f32 per-head (k, v) dequant scales (fp8 KV)
    sink: int = 0,  # windowed+sink decode (docs/LONG_CONTEXT.md)
    swin: int = 0,
):
    """Drop-in for attention._paged_cache_partials_mq (speculative verify
    chunk): one page walk shared by all T queries. Returns
    (acc [B, K, G, T, Dv], m [B, K, G, T, 1], l [B, K, G, T, 1])."""
    B, T, H, D = q.shape
    K = k_pool.shape[2]
    G = H // K
    Dv = v_pool.shape[3]
    scale = 1.0 / (D**0.5)
    if q_pos is None:
        q_pos = jnp.broadcast_to(limits[:, None], (B, T))
    if sliding is None:
        window = 0
    # Row r = t*G + g — all T queries fold into one kernel launch.
    qr = (
        (q.astype(jnp.float32) * scale)
        .reshape(B, T, K, G, D)
        .transpose(0, 2, 1, 3, 4)
        .reshape(B, K, T * G, D)
    )
    qpos_rows = jnp.repeat(q_pos, G, axis=1)  # [B, T*G]
    acc, m, l = _paged_partials_rows(
        qr, qpos_rows, k_pool, v_pool, table, limits,
        softcap, window, sliding, interpret, kv_scale=kv_scale,
        sink=sink, swin=swin,
    )
    acc = acc.reshape(B, K, T, G, Dv).transpose(0, 1, 3, 2, 4)
    m = m.reshape(B, K, T, G, 1).transpose(0, 1, 3, 2, 4)
    l = l.reshape(B, K, T, G, 1).transpose(0, 1, 3, 2, 4)
    return acc, m, l


# Query rows the ragged kernel may hold in VMEM at once. The kernel keeps
# every query row's running (acc, m, l) in VMEM scratch for the whole page
# walk — at 8 kv heads × Dv 128 that is ~4 KB of f32 per row, so a 512-token
# prefill chunk with G=4 query rows per kv head (2048 rows ≈ 8 MB of acc
# alone, plus the q tile) blows the 16 MB scoped-VMEM budget. Prefill chunks
# therefore tile the token axis; each tile re-streams the prefix pages —
# the same O(T/tile) prefix re-read the dense flash kernel pays per q block.
PREFILL_MAX_QROWS = 512


def paged_prefill_partials_mq(
    q: jnp.ndarray,  # [B, T, H, D] — T = prefill-chunk tokens
    k_pool,  # a pool or its StackedLayer, as in paged_decode_partials
    v_pool,
    table: jnp.ndarray,
    limits: jnp.ndarray,  # [B] — rows already resident (the chunk's offset)
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
    q_pos=None,  # [B, T] global positions of the chunk tokens
    interpret: bool = False,
    max_qrows: int = PREFILL_MAX_QROWS,
    kv_scale=None,  # [2, K] f32 per-head (k, v) dequant scales (fp8 KV)
    sink: int = 0,  # windowed+sink prefix walk (docs/LONG_CONTEXT.md)
    swin: int = 0,
):
    """`paged_decode_partials_mq` for prefill-chunk query counts: the T·G
    query-row axis is tiled to `max_qrows` per kernel launch so the chunked
    ragged prefill (models/llama.prefill_chunk_paged) rides the same
    scalar-prefetch page-table kernel as decode at any chunk size. Tiles
    are a static unroll (T and the tile are both static under jit); partials
    concatenate back along T — each token's (acc, m, l) is independent, so
    tiling is exact."""
    B, T, H, D = q.shape
    K = k_pool.shape[2]
    G = H // K
    if q_pos is None:
        q_pos = jnp.broadcast_to(limits[:, None], (B, T))
    tq = max(1, max_qrows // max(G, 1))  # tokens per tile
    if T <= tq:
        return paged_decode_partials_mq(
            q, k_pool, v_pool, table, limits, softcap=softcap, window=window,
            sliding=sliding, q_pos=q_pos, interpret=interpret,
            kv_scale=kv_scale, sink=sink, swin=swin,
        )
    parts = []
    for lo in range(0, T, tq):
        hi = min(lo + tq, T)
        parts.append(paged_decode_partials_mq(
            q[:, lo:hi], k_pool, v_pool, table, limits, softcap=softcap,
            window=window, sliding=sliding, q_pos=q_pos[:, lo:hi],
            interpret=interpret, kv_scale=kv_scale, sink=sink, swin=swin,
        ))
    return tuple(
        jnp.concatenate([p[i] for p in parts], axis=3) for i in range(3)
    )
