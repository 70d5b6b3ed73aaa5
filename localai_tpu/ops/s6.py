"""Mamba-1's selective scan (S6, arXiv:2312.00752) as the `jamba` mixer runs
it: the recurrent decode update as a Pallas kernel over the stacked per-slot
state, and the prefill's recurrence in plain XLA.

Per channel c of the inner width E and state n of N, with h in R^{N x E}
(float32), a step dt in R^E, B, C in R^N shared by all channels:

    h_t[n, c] = exp(dt_t[c] A[c, n]) h_{t-1}[n, c] + dt_t[c] x_t[c] B_t[n]
    y_t[c]    = sum_n h_t[n, c] C_t[n] + D[c] x_t[c]

dt > 0 (softplus of a rank-R projection plus a bias), A = -exp(A_log) < 0, D
the skip. EVERY element of the state has its own decay and needs its own
`exp`: there are no heads and no groups, and neither Mamba-2's scalar decay a
head (`ops/ssd.py`, which is what gives SSD its matmul form) nor KDA's vector
a head (`ops/kda.py`) computes it.

The state of every S6 layer and every slot lives in ONE array [Lm, slots, N,
E] that the engine carries through its programs (engine/state.py), 320 KiB a
slot and layer at 16 x 5120. The states lie on the sublanes (two float32
tiles at N = 16) and the channels on the lanes, so dt, dt x and y are
lane-dense rows that broadcast along sublanes, B and C are N scalars a slot
that broadcast along lanes as a column, the decay is one `exp` a vreg element
with no relayout, and the read-out is a sum over the sublanes. `s6_decode`
hands the whole stack and the layer index to the kernel (ops/stacked.py's
convention, as `ssd_decode`: the index a scalar-prefetch operand, the output
aliased onto the input), so no per-layer slice of the state is ever made. A
grid step takes SLOT_BLOCK slots, 2.5 MiB of state in and out at the
published widths.

The prefill has no matmul form: it is the recurrence itself, a `lax.scan`
over the prompt's positions whose body is the decode step on [B, N, E], the
decay computed inside it (the [T, N, E] operands of an associative scan are
320 KiB a prompt token and layer and are never made).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.ops.kda import use_pallas
from localai_tpu.ops.stacked import note_s6

# Slots a grid step of the decode kernel takes: the row operands (dt, dt x,
# y: [slots, E] float32) then come in whole sublane tiles of eight slots.
SLOT_BLOCK = 8

# Steps of the prefill's scan XLA sees as one loop iteration.
PREFILL_UNROLL = 4


# --------------------------------------------------------------------------- #
# One token: the recurrence itself (the XLA form of the decode update, the
# kernel's oracle, and the scan body of the prefill).
# --------------------------------------------------------------------------- #


def s6_step(h, x, dt, At, Bm, Cm, D):
    """h [..., N, E] f32; x, dt [..., E]; At [N, E] (A transposed); Bm, Cm
    [..., N]; D [E]. Returns (y [..., E] f32, h_new)."""
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    h = (jnp.exp(dt[..., None, :] * At.astype(f32)) * h
         + (dt * x)[..., None, :] * Bm.astype(f32)[..., :, None])
    y = jnp.sum(h * Cm.astype(f32)[..., :, None], axis=-2)
    return y + D.astype(f32) * x, h


# --------------------------------------------------------------------------- #
# Decode: the stacked state, updated in place.
# --------------------------------------------------------------------------- #


def _s6_decode_kernel(layer_ref, dt_ref, dx_ref, b_ref, c_ref, at_ref, h_ref,
                      y_ref, h_out_ref):
    """A block of slots' rows of one layer: every element of a slot's [N, E]
    state decays by its own exp(dt[c] A[n, c]), takes dt x (x) B and is read
    by C, a sum over the N sublanes."""
    del layer_ref  # consumed by the index maps
    at = at_ref[...]  # [N, E]
    for s in range(h_ref.shape[1]):  # static unroll: 80 vregs of state a slot
        h = (jnp.exp(dt_ref[s:s + 1, :] * at) * h_ref[0, s]
             + dx_ref[s:s + 1, :] * b_ref[s])
        h_out_ref[0, s] = h
        y_ref[s:s + 1, :] = jnp.sum(h * c_ref[s], axis=0, keepdims=True)


def slot_block(B: int) -> int:
    """Slots a grid step takes: SLOT_BLOCK where it divides the batch, a
    smaller batch whole; 0 where neither (the XLA step serves)."""
    if B % SLOT_BLOCK == 0:
        return SLOT_BLOCK
    return B if B < SLOT_BLOCK else 0


def _s6_decode_pallas(state, layer, x, dt, At, Bm, Cm, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    _, B, N, E = state.shape
    sb = slot_block(B)
    dt = dt.astype(f32)
    row = pl.BlockSpec((sb, E), lambda b, layer: (b, 0))
    col = pl.BlockSpec((sb, N, 1), lambda b, layer: (b, 0, 0))
    srow = pl.BlockSpec((1, sb, N, E), lambda b, layer: (layer[0], b, 0, 0))
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, 8 * sb * N * E * 4))
    return pl.pallas_call(
        _s6_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // sb,),
            in_specs=[row, row, col, col,
                      pl.BlockSpec((N, E), lambda b, layer: (0, 0)), srow],
            out_specs=[row, srow],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, E), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name="s6_decode",
        **kw,
    )(jnp.asarray(layer, jnp.int32).reshape(1), dt, dt * x.astype(f32),
      Bm.astype(f32)[..., None], Cm.astype(f32)[..., None], At.astype(f32),
      state)


def s6_decode(state, layer, x, dt, At, Bm, Cm, D, impl: str = "auto"):
    """One decode step of one S6 layer for every slot.

    state: the stacked [Lm, B, N, E] f32 state; `layer` its (traced) index.
    x, dt [B, E] (dt after softplus); At [N, E]; Bm, Cm [B, N]; D [E].
    Returns (y [B, E] f32, state): the kernel writes the layer's rows in
    place (donate the state); the XLA form slices the layer out and puts it
    back, a copy each way, and is the oracle, the off-TPU path and what a
    batch that is neither under nor a multiple of SLOT_BLOCK takes. The skip
    term D x is added here, outside the kernel, either way."""
    pallas = use_pallas(impl) and slot_block(state.shape[1]) > 0
    note_s6(pallas)
    if pallas:
        y, state = _s6_decode_pallas(
            state, layer, x, dt, At, Bm, Cm,
            interpret=jax.default_backend() != "tpu")
        return y + D.astype(jnp.float32) * x.astype(jnp.float32), state
    with jax.named_scope("layer_state"):
        h = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y, h = s6_step(h, x, dt, At, Bm, Cm, D)
    return y, jax.lax.dynamic_update_index_in_dim(state, h, layer, 0)


# --------------------------------------------------------------------------- #
# Prefill: the recurrence over a prompt.
# --------------------------------------------------------------------------- #


@jax.named_scope("s6_prefill")
def s6_prefill(x, dt, At, Bm, Cm, D, valid, h0=None):
    """The selective scan from `h0` (zeros) over right-padded prompts.

    x, dt [B, T, E] (dt after softplus); At [N, E]; Bm, Cm [B, T, N]; D [E];
    valid [B, T] bool (rows past a prompt's length neither decay nor write
    the state: their step is 0, so the state returned is the one after the
    last valid token). Returns (y [B, T, E] f32, h [B, N, E]): `s6_step`
    token by token, so equal to the decode step by construction.

    What it holds a prompt token is its rows ([T, E] and [T, N] operands, y);
    the [N, E] state and its decay exist once a prompt, inside the loop."""
    f32 = jnp.float32
    B, T, E = x.shape
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    if h0 is None:
        h0 = jnp.zeros((B, At.shape[0], E), f32)

    def body(h, xs):
        y, h = s6_step(h, *xs[:2], At, *xs[2:], D)
        return h, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm))
    h, y = jax.lax.scan(body, h0, xs, unroll=min(PREFILL_UNROLL, T))
    return jnp.moveaxis(y, 0, 1), h
