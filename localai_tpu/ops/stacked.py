"""A stacked operand and its layer index: one convention for every operand a
Pallas kernel reads out of a per-layer stack (ROADMAP D11).

What a layer reads lives stacked over layers ([L, ...]: the weights, the
paged K/V pool), and a pallas_call's operand has to be a buffer: a slice in
front of the custom call is a copy of that layer's whole operand, every
layer of every step (the int8 weights were a third of the decode step,
ISSUE 25; the K/V pool a quarter to a third, ISSUE 27). So
llama._scan_stack does not slice such an operand. It hands the layer body a
StackedLayer, the whole stack and the layer index, and the consumer either

- gives both to its kernel, which takes the index as a scalar-prefetch
  operand and reads its layer in place (ops/quant_matmul `_qmm_call`,
  ops/paged_flash `_paged_partials_rows`), or
- slices at its own call site (`layer_slice`) in front of the XLA form,
  where the slice fuses into the dot's or the gather's operand.

The choice is static per call site; `SiteCounts` tallies it per traced
program, by kernel.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading

import jax


class StackedLayer:
    """One layer of an operand that is still stacked over layers. `stack`
    keeps its leading [L] axis: a dict of quantized leaves, or one array
    (the paged K/V pool). `layer` is the (traced int32) index meant, or None
    on an operand a caller only marks for llama._scan_stack to bind.

    A dict stack reads as the dict it holds (keys, `in`, `[]`, `dict(w)`:
    the leaves keep their [L] axis); an array stack reads as ONE layer of it
    (`shape`, `dtype`, `ndim`), so code that only asks a pool for its page
    size or head count takes either."""

    __slots__ = ("stack", "layer")

    def __init__(self, stack, layer=None):
        self.stack = stack.stack if isinstance(stack, StackedLayer) else stack
        self.layer = layer

    def keys(self):
        return self.stack.keys()

    def __getitem__(self, key):
        return self.stack[key]

    def __contains__(self, key):
        return isinstance(self.stack, dict) and key in self.stack

    @property
    def shape(self):
        return self.stack.shape[1:]

    @property
    def ndim(self):
        return self.stack.ndim - 1

    @property
    def dtype(self):
        return self.stack.dtype


def layer_of(w):
    """The layer index a StackedLayer carries; None for anything else."""
    return w.layer if isinstance(w, StackedLayer) else None


def layer_slice(w, scope: str = "layer_weights"):
    """The plain per-layer operand of a StackedLayer (anything else as it
    is): the one place a layer is sliced out of its stack, under a named
    scope a profile can show. In front of an XLA dot or gather the slice
    fuses into the operand load."""
    if not isinstance(w, StackedLayer):
        return w

    def take(a):
        return jax.lax.dynamic_index_in_dim(
            a, w.layer, 0, keepdims=False, allow_negative_indices=False)

    with jax.named_scope(scope):
        if isinstance(w.stack, dict):
            return {k: take(v) for k, v in w.stack.items()}
        return take(w.stack)


def shared_layer(a, b):
    """The layer index two StackedLayers have in common (the same traced
    value), else None."""
    layer = layer_of(a)
    return layer if layer is not None and layer_of(b) is layer else None


def stacks_of(a, b, scope: str):
    """(a's stack, b's stack, layer) for a kernel that reads the two with
    one index: the stacks of two StackedLayers of the same layer as they
    are; anything else as a free [1, ...] view of the per-layer operand
    (sliced under `scope` if need be) and layer 0."""
    layer = shared_layer(a, b)
    if layer is not None:
        return a.stack, b.stack, layer
    return layer_slice(a, scope)[None], layer_slice(b, scope)[None], 0


# What a site can hand its kernel, per kernel family: the quantized layer
# matmuls keep the short names they were first counted under.
_KEYS = {
    "quant_matmul": ("stacked", "sliced"),
    # an expert matmul over expert-sorted rows that took the grouped kernel
    # on the stack (`note_grouped`); it counts under neither of the above
    "quant_matmul_grouped": ("grouped",),
    # not a stack's fate either: the weight block the rule gave a Pallas
    # dequant-matmul call (`note_blocks`)
    "quant_matmul_blocks": ("wholerow", "narrowed"),
    "paged_attention": ("paged_attention_stacked", "paged_attention_sliced"),
    # not a stack's fate but the same kind of static choice: the arithmetic
    # of a paged-attention kernel call (`note_arith`)
    "paged_attention_arith": ("paged_attention_native", "paged_attention_f32"),
    # nor this: what a visit of a paged-attention kernel's page walk holds
    # (`note_visit`)
    "paged_attention_visit": ("paged_attention_multipage",
                              "paged_attention_onepage"),
    # nor this: how a paged-attention kernel's page walk keeps the wire busy
    # across the grid's programs (`note_walk`)
    "paged_attention_walk": ("paged_attention_stream",
                             "paged_attention_prefetch"),
    # nor this: the lanes a latent paged-attention call's value dot runs
    # over (`note_value_lanes`)
    "paged_attention_values": ("paged_attention_value_lanes",
                               "paged_attention_value_row"),
    # nor this: how a decode block's window reached one page pool
    # (`note_pool_write`)
    "pool_write": ("pool_write_inplace", "pool_write_scatter"),
    # nor this: which form an SSD layer's decode update took (`note_ssd`)
    "ssd_decode": ("ssd_decode_pallas", "ssd_decode_xla"),
    # nor this: which form an S6 layer's decode update took (`note_s6`)
    "s6_decode": ("s6_decode_pallas", "s6_decode_xla"),
}
_ALL_KEYS = tuple(k for ks in _KEYS.values() for k in ks)


class SiteCounts:
    """How many call sites of a stack-reading kernel a program's trace held,
    by what the site handed on: "stacked" (the Pallas kernel took the whole
    stack and the layer index) or "sliced" (the layer was sliced out first,
    for the XLA form or an unstacked kernel call). Quantized layer matmuls
    count under `stacked` / `sliced`, or, an expert matmul over
    expert-sorted rows that took the grouped kernel on the stack, under
    `grouped` (`note_grouped`); paged attention under
    `paged_attention_stacked` / `paged_attention_sliced`; beside them the
    arithmetic of each Pallas paged-attention call, `paged_attention_native`
    / `paged_attention_f32` (`note_arith`) and what a visit of its page walk
    holds, `paged_attention_multipage` / `paged_attention_onepage`
    (`note_visit`) and how its walk crosses a slot boundary,
    `paged_attention_stream` / `paged_attention_prefetch` (`note_walk`),
    and, a latent call alone, the lanes of its value dot,
    `paged_attention_value_lanes` / `paged_attention_value_row`
    (`note_value_lanes`), and the weight block of each Pallas dequant-matmul call,
    `wholerow` / `narrowed` (`note_blocks`); and how a decode block's window
    reached each page pool, `pool_write_inplace` / `pool_write_scatter`
    (`note_pool_write`); and the form each SSD layer's decode update took,
    `ssd_decode_pallas` / `ssd_decode_xla` (`note_ssd`), and an S6 layer's,
    `s6_decode_pallas` / `s6_decode_xla` (`note_s6`).
    The choice is static, so it is counted where it is made, once per trace. An engine
    owns one and traces its programs under `tracing(<program>)`."""

    def __init__(self):
        self._lock = threading.Lock()
        self.by_program: dict[str, dict[str, int]] = {}

    @contextlib.contextmanager
    def tracing(self, program: str):
        tally = {"traces": 1, **dict.fromkeys(_ALL_KEYS, 0)}
        token = _TALLY.set(tally)
        try:
            yield
        finally:
            _TALLY.reset(token)
            with self._lock:
                have = self.by_program.setdefault(program, dict.fromkeys(tally, 0))
                for k, v in tally.items():
                    have[k] += v

    def totals(self) -> dict[str, int]:
        with self._lock:
            progs = list(self.by_program.values())
        return {k: sum(p[k] for p in progs) for k in _ALL_KEYS}


_TALLY: contextvars.ContextVar = contextvars.ContextVar(
    "stacked_site_tally", default=None)


def note_site(stacked: bool, kernel: str = "quant_matmul") -> None:
    """Count one call site of `kernel` in the program being traced (no-op
    outside `SiteCounts.tracing`)."""
    tally = _TALLY.get()
    if tally is not None:
        tally[_KEYS[kernel][0 if stacked else 1]] += 1


def note_grouped() -> None:
    """Count one expert matmul of the program being traced that took the
    grouped Pallas kernel (ops/quant_matmul `grouped_moe_mm`): sorted rows
    against the quantized stack in place, no slice and no dequantized copy.
    The XLA `ragged_dot` form counts as `sliced` (llama._expert_stack)."""
    note_site(True, kernel="quant_matmul_grouped")


def note_blocks(wholerow: bool) -> None:
    """Count one Pallas dequant-matmul call of the program being traced by
    the weight block its rule chose (ops/quant_matmul `_blocks`):
    `wholerow`, a grid step's block spans the weight's whole out axis (one
    contiguous run of HBM), or `narrowed`, a full-width step did not fit
    the VMEM budget and the block is a column strip. The XLA form counts
    under neither."""
    note_site(wholerow, kernel="quant_matmul_blocks")


def note_arith(native: bool) -> None:
    """Count one Pallas paged-attention kernel call of the program being
    traced by what its dots are fed (ops/paged_flash): `native`, the page
    went to the MXU in the pool's own 16- or 8-bit dtype as it is stored
    (key `paged_attention_native`: a GQA pool's `[page·K, D]` tile, a
    latent pool's `[page, W]` one), or each head's tile was upcast to
    float32 first (`paged_attention_f32`: a float32 pool, a wide query
    tile). The XLA walk counts under neither."""
    note_site(native, kernel="paged_attention_arith")


def note_visit(multipage: bool) -> None:
    """Count one Pallas paged-attention kernel call of the program being
    traced by what a visit of its page walk holds (ops/paged_flash
    `_visit_pages`): `multipage`, several consecutive pages of the slot
    landed side by side and scored by one dot a pool (a narrow pool of
    whose pages two or more fit VISIT_ROWS (token, head) rows and
    VISIT_BYTES: 2 or 4 KV heads a chip at 128-row pages, a latent pool;
    key `paged_attention_multipage`), or one page a visit
    (`paged_attention_onepage`: 8 KV heads and more, the per-head form, the
    cold-middle walk). The XLA walk counts under neither."""
    note_site(multipage, kernel="paged_attention_visit")


def note_walk(stream: bool) -> None:
    """Count one Pallas paged-attention kernel call of the program being
    traced by how its page walk keeps the wire busy across the grid's
    programs (ops/paged_flash `_ragged_paged_kernel`): `stream`, the visits
    of all the slots are one stream and the ring of visit buffers holds its
    next visits whatever slots they belong to (every walk but one; key
    `paged_attention_stream`), or each slot warms up and prefetches its own
    visits inside its own program (`paged_attention_prefetch`: the
    cold-middle walk under `swin`, whose start depends on the slot's own
    query positions). The XLA walk counts under neither."""
    note_site(stream, kernel="paged_attention_walk")


def note_value_lanes(lanes: bool) -> None:
    """Count one LATENT Pallas paged-attention call of the program being
    traced (ops/paged_flash `latent_paged_attention`) by the lanes its value
    dot `p @ V` runs over: `lanes`, the leading lanes the caller reads as
    values (MLA's kv_lora_rank in whole lane tiles, 512 of a 640-lane row;
    key `paged_attention_value_lanes`), or the whole row
    (`paged_attention_value_row`: no width stated, or one whose round-up to
    lane tiles is the row). A GQA call counts under neither."""
    note_site(lanes, kernel="paged_attention_values")


def note_pool_write(inplace: bool) -> None:
    """Count one page pool (K or V) a decode block of the program being
    traced wrote its window into (ops/attention `write_window`), by how:
    `inplace`, the Pallas `pool_write` kernel copied the window's rows into
    the donated pool by DMA (the paged reader is the Pallas kernel and a
    token's row of the pool is narrower than the native tile: 2 or 4 KV
    heads a chip, four packed rows; key `pool_write_inplace`), or XLA's
    scatter did (`pool_write_scatter`: 8 rows and more a token, where the
    scatter runs in the layout the pool is stored in; the latent pool's one
    row, which no DMA can slice; every pool under the XLA walk)."""
    note_site(inplace, kernel="pool_write")


def note_ssd(pallas: bool) -> None:
    """Count one SSD (Mamba-2) decode update of the program being traced
    (ops/ssd `ssd_decode`) by its form: `pallas`, the kernel read and wrote
    its layer's rows of the stacked state in place (key `ssd_decode_pallas`),
    or the XLA step sliced the layer out and put it back
    (`ssd_decode_xla`: off the TPU, or where a caller names it)."""
    note_site(pallas, kernel="ssd_decode")


def note_s6(pallas: bool) -> None:
    """Count one S6 (Mamba-1) decode update of the program being traced
    (ops/s6 `s6_decode`) by its form, as `note_ssd` does: `s6_decode_pallas`
    or `s6_decode_xla`."""
    note_site(pallas, kernel="s6_decode")
