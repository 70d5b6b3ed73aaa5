"""The named scopes the engine's programs are written under: one vocabulary.

Every device op of every engine program carries, in its `op_name` metadata,
the `jax.named_scope`s it was traced under (`jit(decode_block)/control/while/
body/layer/while/body/attention/proj/dot_general`). A scope is compile-time
metadata: no instruction, no run-time cost. A profiler capture holds the
`op_name` of every op it timed (the op's `tf_op` stat), so device time can be
read by program and scope with nothing emitted at run time
(benchmark/reducers/scope_share.py; docs/OBSERVABILITY.md section 5).

`SCOPES` are the leaves: an op belongs to the leaf its path ENDS in, once
everything that is no word of the vocabulary (`jit(..)`, `while`, `body`,
`cond`, `branch_*`, `closed_call`, `shard_map`, `vmap(..)`, the trailing
primitive) is dropped; scopes nest, and an enclosing one is the owner of what
nothing inside it claims (`layer` around the layer scan: norms, residual adds,
the loop itself; `control` around a block's step scan). An op whose path
holds one of `SLICES` is a per-layer slice out of a stacked array, whatever
leaf it was cut for; a path that ends in no leaf is unscoped, and the
partition test (tests/test_scopes.py) refuses it.

The benchmark's reader holds the rule (`scope_share.leaf_of`) and its own
copy of both tuples (it takes nothing from the program but the system under
test); a unit test pins them equal, as `LOOP_PHASES` is pinned between
engine/runtime.py and observe/journal.py.
"""

from __future__ import annotations

import jax

SCOPES = (
    "embed",         # token embedding lookup (and its scale)
    "lm_head",       # final norm, the last-position gather, the output head
    "sample",        # everything after the logits: penalties, bias, grammar
    #                  mask, top-k/top-p, the draw, logprobs, rng, the
    #                  per-slot sampling state (counts, bias rows, keys)
    "control",       # the engine's own glue inside a program: the packed
    #                  host control unpacked, positions advanced, per-slot
    #                  token/position rows written, routing sums, the step loop
    "attention/proj",         # q/k/v, the MLA pair, KDA's q/k/v/g/beta/gate
    #                           projections and its short conv, q/k norms,
    #                           the gated short conv's [b | c | z] and b ⊙ z,
    #                           SSD's [z | xBC | dt], its conv and softplus,
    #                           S6's [x | z], conv, [dt | B | C], their norms
    "attention/rope",         # the rotation (GQA; MLA rotates inside proj)
    "attention/mix",          # the token mixer: paged_attention,
    #                           latent_paged_attention, flash_prefill, the XLA
    #                           softmax and the block-window merge, kda_decode,
    #                           the chunkwise KDA prefill, the gated short
    #                           conv's taps and gate, ssd_decode and the
    #                           chunkwise SSD prefill, s6_decode and the
    #                           selective scan over a prompt
    "attention/cache_write",  # K/V rows into pool, window or dense cache;
    #                           recurrent state and conv rows into their slots
    "attention/out",          # gate, un-latent, per-head norm, output projection
    "mlp/router",    # router logits, top-k, the held-share mapping, counters
    "mlp/experts",   # the stacked kernel, or sort + ragged_dot + combine
    "mlp/shared",    # the always-on shared expert
    "mlp/dense",     # a dense SwiGLU
    "layer",         # what is left of a layer: norms, residual adds, the scan
)

# Per-layer slices out of stacked arrays; they nest inside a leaf and are
# read as "slices" wherever they occur in a path.
SLICES = ("layer_weights", "layer_kv_pool", "layer_conv_rows", "layer_state")


# The gated short convolution (LFM2), the operator whole: its two matmuls,
# u = b * z, the rows read and written, the taps and the gate, written AROUND
# their leaves (`conv_mix/attention/proj/...`), the name a reader of a capture
# tells the conv layers' operator from the cache layers' by. Around and not
# inside one leaf: XLA names a fusion after any op in it, and on the chip the
# taps and the gate read `attention/out`. No leaf of its own: `SCOPES` is
# pinned to the benchmark's reader, which drops the word like any other it
# does not know and books each op to its leaf.
CONV_MIX = "conv_mix"

# The SSD (Mamba-2) layer, the operator whole, by the same rule: its two
# matmuls, the conv with its bias and silu, the rows read and written, the
# `ssd_decode` kernel (or the chunkwise prefill) and the gated norm.
SSD_MIX = "ssd_mix"

# The S6 (Mamba-1, `jamba`) layer, the operator whole, by the same rule: its
# four matmuls, the conv with its bias and silu, the three inner norms and
# the softplus, the rows read and written, the `s6_decode` kernel (or the
# prefill's scan, `s6_prefill`) and the gate.
S6_MIX = "s6_mix"

# A latent pool's block write (MLA's one 16-bit row a token, staged through
# VMEM by `ops/pool_write.latent_pool_write`), by the same rule: written
# around the kernel INSIDE `attention/cache_write`, which books it; the word
# is what tells a reader this write from the zero-width V pool's scatter and
# from a recurrent state's rows beside it.
LATENT_WRITE = "latent_write"

# A sliding-window attention layer (Laguna's `sliding_attention`), the layer
# whole, by the same rule: written around the one decoder layer body where it
# runs a window layer, so a reader tells the window layers' projections,
# reader (`window_attention`) and MLP from the full layers' beside them.
WINDOW_MIX = "window_mix"

# The window layers' rows into their per-slot ring (a decode block's at its
# end, a prompt's last `sliding_window` at admission): inside
# `attention/cache_write`, which books it; the word tells it from the paged
# pool's write beside it.
RING_WRITE = "ring_write"


def scope(leaf: str):
    """`jax.named_scope(leaf)` for a leaf of `SCOPES` (context manager or
    decorator), refusing a name the vocabulary does not hold."""
    if leaf not in SCOPES:
        raise ValueError(f"{leaf!r} is not in observe.scopes.SCOPES")
    return jax.named_scope(leaf)

