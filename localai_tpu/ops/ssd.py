"""Mamba-2's state-space duality layer (SSD, arXiv:2405.21060): the recurrent
decode update as a Pallas kernel over the stacked per-slot state, and the
chunkwise prefill in plain XLA.

Per head h, with S in R^{P x N} (float32; P the head width, N the state
width), a SCALAR decay a head and B, C in R^N shared by the heads of a group:

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h] (x) B_t
    y_t[h] = S_t[h] C_t + D[h] x_t[h]

dt > 0 is the step (softplus of the projection plus a bias), A = -exp(A_log)
< 0, D the skip. The decay is one number a head where KDA's is a vector a
channel, and the update an outer product with no correction by the state:
`ops/kda.py`'s kernel computes neither.

The state of every SSD layer and every slot lives in ONE array
[Lm, slots, H, P, N] that the engine carries through its programs
(engine/state.py), 4 MiB a slot and layer at 128 heads of 64 x 128. A decode
step reads and writes each slot's row of its layer once: `ssd_decode` hands
the whole stack and the layer index to the kernel (ops/stacked.py's
convention, as `kda_decode`: the index a scalar-prefetch operand, the output
aliased onto the input), so no per-layer slice of the state is ever made:
one layer's rows are 134 MB at 32 slots.

The kernel is a stream of 2 MiB blocks in and out, and the update (a few VPU
ops a vreg) hides under the copies. The read-out y = S C is done by the MXU,
which the kernel otherwise leaves idle: C against the head's state contracted
over d_state, a float32 dot at `Precision.HIGHEST` (six bfloat16 passes; 2e-7
of the largest |y| against a float64 read-out, the XLA step's own error). As
`jnp.sum(S * C, axis=1)` it was 1,024 lane reductions a slot and layer on the
XLU and a one-lane store a head, and did not hide: 0.447 ms a layer's call at
the published shape on a v5e where the MXU form takes 0.409, the same
pipeline with NO read-out 0.409 and XLA's in-place fusion of the update
alone 0.409 (PERF.md section 6 "PR 52"). Mosaic takes the dot at every shape
the call's BlockSpecs admit (a state narrower than a lane tile too), so
there is one read-out.

Prefill runs the chunkwise form: within a chunk of `chunk` tokens the
quadratic (attention-like) form, between chunks one sequential pass over the
chunk states. A head's decay is a scalar, so every factored product is the
exponential of a SUM of log-decays over a span of tokens, <= 0: nothing
overflows and no sub-blocking is needed (KDA's per-channel decays need it).
The chunk length changes no result but rounding.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from localai_tpu.ops.kda import use_pallas
from localai_tpu.ops.stacked import note_ssd

# Tokens a chunk of the prefill holds at most. A model's published chunk
# (Granite-4.0-H: `mamba_chunk_size` 256) is cut to this: an exact
# sub-blocking that changes no result but rounding. At 128 a prompt token's
# rows of the [chunk, chunk, heads] temporaries and its share of the chunk
# states together move the fewest bytes (0.33 MB at 128 heads of 64 x 128;
# 0.56 MB at 256), and a chunk is the v5e MXU's own 128 rows.
CHUNK = 128

# Heads a grid step of the decode kernel takes: 2 MiB of state in and out at
# 64 x 128, double-buffered 8 MiB of VMEM.
HEAD_BLOCK = 64


# --------------------------------------------------------------------------- #
# One token: the recurrence itself (the XLA form of the decode update, the
# kernel's oracle, and the scan body of `ssd_recurrent`).
# --------------------------------------------------------------------------- #


def _per_head(a, H: int):
    """[..., G, N] -> [..., H, N]: a group's vector for each of its heads."""
    return jnp.repeat(a, H // a.shape[-2], axis=-2)


def ssd_step(S, x, dt, A, Bm, Cm, D):
    """S [..., H, P, N] f32; x [..., H, P]; dt [..., H]; A, D [H]; Bm, Cm
    [..., G, N]. Returns (y [..., H, P] f32, S_new)."""
    f32 = jnp.float32
    H = S.shape[-3]
    x, dt = x.astype(f32), dt.astype(f32)
    Bh, Ch = _per_head(Bm.astype(f32), H), _per_head(Cm.astype(f32), H)
    S = (S * jnp.exp(dt * A.astype(f32))[..., None, None]
         + (dt[..., None] * x)[..., :, None] * Bh[..., None, :])
    y = jnp.einsum("...hpn,...hn->...hp", S, Ch) + D.astype(f32)[:, None] * x
    return y, S


def ssd_recurrent(x, dt, A, Bm, Cm, D, S0=None):
    """Token-by-token SSD over x [B, T, H, P], dt [B, T, H], Bm, Cm
    [B, T, G, N] (the oracle of the chunkwise form). Returns
    (y [B, T, H, P] f32, S_T [B, H, P, N])."""
    B, T, H, P = x.shape
    if S0 is None:
        S0 = jnp.zeros((B, H, P, Bm.shape[-1]), jnp.float32)

    def body(S, xs):
        x_t, dt_t, b_t, c_t = xs
        y, S = ssd_step(S, x_t, dt_t, A, b_t, c_t, D)
        return S, y

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (x, dt, Bm, Cm))
    S, y = jax.lax.scan(body, S0, xs)
    return jnp.moveaxis(y, 0, 1), S


# --------------------------------------------------------------------------- #
# Decode: the stacked state, updated in place.
# --------------------------------------------------------------------------- #


def _ssd_decode_kernel(layer_ref, dxT_ref, da_ref, b_ref, c_ref, s_ref,
                       yT_ref, s_out_ref):
    """One slot's row of one layer, a block of heads: every head's [P, N]
    state decays by its scalar, takes the outer product dt x (x) B and is
    read by C. d_state lies on the lanes: B and C arrive as rows that
    broadcast along sublanes, dt x transposed ([P, heads]: a head's vector is
    a lane slice that broadcasts along lanes as a column), the decay as a row
    of its own value.

    The read-out y[h] = S[h] C runs on the MXU: C, a sublane tile of its row,
    against the head's state as it was just written, contracted over d_state
    (the state is the transposed right-hand side, which the MXU loads as
    stored), float32 in and out at `Precision.HIGHEST`. A head's y comes back
    as a ROW (P on the lanes, the same on all 8 sublanes); eight heads' rows
    make a tile by a select a head, and the block's [heads, P] tiles are
    transposed once a grid step into the [P, heads] block the call stores."""
    del layer_ref  # consumed by the index maps
    hb, P, N = s_ref.shape[2:]
    b_row = b_ref[0, 0]  # [1, N]
    c_rows = jnp.broadcast_to(c_ref[0, 0], (8, N))
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, P), 0)
    tiles = [jnp.zeros((8, P), jnp.float32) for _ in range(-(-hb // 8))]
    for h in range(hb):  # static unroll: 8 vregs of state a head at 64 x 128
        S = s_ref[0, 0, h] * da_ref[0, h:h + 1, :]
        S = S + dxT_ref[0, 0, :, h:h + 1] * b_row
        s_out_ref[0, 0, h] = S
        y = jax.lax.dot_general(  # [8, N] x [P, N]^T: y[h] on every sublane
            c_rows, S, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        tiles[h // 8] = jnp.where(sub == h % 8, y, tiles[h // 8])
    yT_ref[0, 0] = jnp.transpose(jnp.concatenate(tiles, axis=0))[:, :hb]


def head_block(H: int, G: int) -> int:
    """Heads a grid step takes: the most, up to HEAD_BLOCK, that divide a
    group's heads (a block then reads one B and one C)."""
    per = H // G
    return next(b for b in range(min(per, HEAD_BLOCK), 0, -1) if per % b == 0)


def _ssd_decode_pallas(state, layer, x, dt, A, Bm, Cm, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    _, B, H, P, N = state.shape
    G = Bm.shape[-2]
    hb = head_block(H, G)
    nb = H // hb
    dt = dt.astype(f32)
    da = jnp.broadcast_to(jnp.exp(dt * A.astype(f32))[..., None], (B, H, N))
    # dt x, a block's heads on the lanes: [B, nb, P, hb]
    dxT = jnp.swapaxes((dt[..., None] * x.astype(f32)).reshape(B, nb, hb, P),
                       -1, -2)
    Bm, Cm = (a.astype(f32).reshape(B, G, 1, N) for a in (Bm, Cm))

    def group(b, k, layer):  # the group of head block k
        return (b, (k * hb) // (H // G), 0, 0)

    col = pl.BlockSpec((1, 1, P, hb), lambda b, k, layer: (b, k, 0, 0))
    row = pl.BlockSpec((1, 1, 1, N), group)
    srow = pl.BlockSpec((1, 1, hb, P, N),
                        lambda b, k, layer: (layer[0], b, k, 0, 0))
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(32 << 20, 6 * hb * P * N * 4))
    yT, state = pl.pallas_call(
        _ssd_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nb),
            in_specs=[col,
                      pl.BlockSpec((1, hb, N), lambda b, k, layer: (b, k, 0)),
                      row, row, srow],
            out_specs=[col, srow],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, nb, P, hb), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={5: 1},
        interpret=interpret,
        name="ssd_decode",
        **kw,
    )(jnp.asarray(layer, jnp.int32).reshape(1), dxT, da, Bm, Cm, state)
    return jnp.swapaxes(yT, -1, -2).reshape(B, H, P), state


def ssd_decode(state, layer, x, dt, A, Bm, Cm, D, impl: str = "auto"):
    """One decode step of one SSD layer for every slot.

    state: the stacked [Lm, B, H, P, N] f32 state; `layer` its (traced)
    index. x [B, H, P]; dt [B, H] (after softplus); A, D [H]; Bm, Cm
    [B, G, N]. Returns (y [B, H, P] f32, state): the kernel writes the
    layer's rows in place (donate the state); the XLA form slices the layer
    out and puts it back, a copy each way, and is the oracle and the off-TPU
    path. The skip term D x is added here, outside the kernel, either way."""
    pallas = use_pallas(impl)
    note_ssd(pallas)
    if pallas:
        y, state = _ssd_decode_pallas(
            state, layer, x, dt, A, Bm, Cm,
            interpret=jax.default_backend() != "tpu")
        return y + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32), state
    with jax.named_scope("layer_state"):
        S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    y, S = ssd_step(S, x, dt, A, Bm, Cm, D)
    return y, jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)


# --------------------------------------------------------------------------- #
# Prefill: chunkwise.
# --------------------------------------------------------------------------- #


def ssd_chunk_prefill(x, dt, A, Bm, Cm, D, valid, chunk: int = CHUNK):
    """Chunkwise SSD from a zero state over right-padded prompts.

    x [B, T, H, P]; dt [B, T, H] (after softplus); A, D [H]; Bm, Cm
    [B, T, G, N]; valid [B, T] bool (rows past a prompt's length neither
    decay nor write the state: their step is 0, so the state returned is the
    one after the last valid token). T is a multiple of min(chunk, T).
    Returns (y [B, T, H, P] f32, S [B, H, P, N]).

    The widest temporaries are the chunk's decay matrix and its product with
    C B^T, [H, C, C] float32 a chunk each, and the chunk states [H, P, N]:
    engine/state.admit_rows counts them a prompt token."""
    f32 = jnp.float32
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    J = H // G  # heads a group: head h = (g, j), so B and C are never repeated
    C = min(chunk, T)
    if T % C:
        raise ValueError(f"SSD prefill: T={T} must divide by the chunk {C}")
    n = T // C
    x = x.astype(f32)
    dt = jnp.where(valid[..., None], dt.astype(f32), 0.0)
    a = (dt * A.astype(f32)).reshape(B, n, C, G, J)  # log-decay a token, <= 0
    dx = (dt[..., None] * x).reshape(B, n, C, G, J, P)
    Bc = Bm.astype(f32).reshape(B, n, C, G, N)
    Cc = Cm.astype(f32).reshape(B, n, C, G, N)
    cum = jnp.cumsum(a, axis=2)  # inclusive, [B, n, C, G, J]
    # within a chunk: y_t += sum_{s<=t} (C_t . B_s) exp(cum_t - cum_s) dt_s x_s
    t = jnp.arange(C)
    span = cum[:, :, :, None] - cum[:, :, None, :]  # [B, n, t, s, G, J]
    span = jnp.where((t[:, None] >= t[None, :])[..., None, None], span,
                     -jnp.inf)
    cb = jnp.einsum("bctgn,bcsgn->bctsg", Cc, Bc)
    y_in = jnp.einsum("bctsgj,bcsgjp->bctgjp", cb[..., None] * jnp.exp(span),
                      dx)
    # what each chunk adds to the state by its own end
    to_end = jnp.exp(cum[:, :, -1:] - cum)  # [B, n, C, G, J]
    add = jnp.einsum("bcsgn,bcsgjp->bcgjpn", Bc, to_end[..., None] * dx)
    whole = jnp.exp(cum[:, :, -1])  # a chunk's whole decay [B, n, G, J]

    def body(S, xs):  # S [B, G, J, P, N]: the state entering the chunk
        add_c, whole_c = xs
        return S * whole_c[..., None, None] + add_c, S

    S, S_in = jax.lax.scan(
        body, jnp.zeros((B, G, J, P, N), f32),
        (jnp.moveaxis(add, 1, 0), jnp.moveaxis(whole, 1, 0)))
    y_out = jnp.einsum("bctgn,cbgjpn->bctgjp", Cc, S_in) * jnp.exp(cum)[..., None]
    y = (y_in + y_out).reshape(B, T, H, P) + D.astype(f32)[:, None] * x
    return y, S.reshape(B, H, P, N)
