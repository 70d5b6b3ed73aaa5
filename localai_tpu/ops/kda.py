"""Kimi Delta Attention (KDA, arXiv:2510.26692): the recurrent decode update
as a Pallas kernel over the stacked per-slot state, and the chunkwise
prefill in plain XLA.

Per head, with S in R^{dk x dv} (float32), a per-channel decay alpha = exp(g)
in (0, 1]^dk and a scalar beta in (0, 1), or in (0, 2) for a model that
allows negative eigenvalues (`ArchConfig.kda_neg_eigval`):

    S_t = (I - beta k k^T) Diag(alpha) S_{t-1} + beta k v^T
    o_t = S_t^T q_t

Nothing below depends on beta < 1. The decode kernel takes beta folded into
its operands. The chunkwise form solves (I + Diag(beta) A) u = rhs with A
strictly lower triangular: unit triangular whatever beta is, so the solve is
a finite substitution, not an iteration that needs a contraction. And on a
unit k the transition I - beta k k^T has the eigenvalue 1 - beta in (-1, 1),
so the state stays bounded either way.

The state of every KDA layer and every slot lives in ONE array
[Lk, slots, H, dk, dv] that the engine carries through its programs
(engine/state.py). A decode step reads and writes each slot's row of its
layer once: `kda_decode` hands the whole stack and the layer index to the
kernel (ops/stacked.py's convention; the index is a scalar-prefetch operand
and the output aliases the input), so no per-layer slice of the state is
ever made: one layer's rows are 134 MB at 64 slots x 32 heads x 128 x 128.

Prefill runs the chunkwise (WY) form: chunks of 64 tokens, all chunks'
intra-chunk terms in parallel, one sequential pass over the chunks for the
state. A chunk's unit-triangular system is solved by blocks of 16 rows
(`_solve_unit_lower`: jnp dots, nothing a Pallas body could not hold; XLA's
own triangular solve was a custom call that took a quarter of an admission,
PERF.md PR 47). Decays are per channel, so every factored product is taken
against a reference point that keeps both exponents <= 0 (sub-blocks of 16
inside a chunk): nothing overflows however strong the decay, and what
underflows is smaller than float32 can hold anyway.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 64
SUB = 16


def use_pallas(impl: str = "auto") -> bool:
    """auto: the kernel on TPU, the XLA form (its oracle) elsewhere; a test
    names one (pallas off-TPU runs interpreted)."""
    if impl == "auto":
        return jax.default_backend() == "tpu"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"kda kernel impl {impl!r}: use auto|pallas|xla")
    return impl == "pallas"


# --------------------------------------------------------------------------- #
# One token: the recurrence itself (the XLA form of the decode update, the
# kernel's oracle, and the scan body of `kda_recurrent`).
# --------------------------------------------------------------------------- #


def kda_step(S, q, k, v, g, beta):
    """S [..., H, dk, dv] f32; q, k, g [..., H, dk]; v [..., H, dv];
    beta [..., H]. Returns (o [..., H, dv] f32, S_new)."""
    f32 = jnp.float32
    S = S * jnp.exp(g.astype(f32))[..., :, None]
    kS = jnp.einsum("...hk,...hkv->...hv", k.astype(f32), S)
    u = beta.astype(f32)[..., None] * (v.astype(f32) - kS)
    S = S + k.astype(f32)[..., :, None] * u[..., None, :]
    return jnp.einsum("...hk,...hkv->...hv", q.astype(f32), S), S


def kda_recurrent(q, k, v, g, beta, S0=None):
    """Token-by-token KDA over [B, T, H, d] (the oracle of the chunkwise
    form). Returns (o [B, T, H, dv] f32, S_T [B, H, dk, dv])."""
    B, T, H, dk = q.shape
    if S0 is None:
        S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)

    def body(S, xs):
        o, S = kda_step(S, *xs)
        return S, o

    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    S, o = jax.lax.scan(body, S0, xs)
    return jnp.moveaxis(o, 0, 1), S


# --------------------------------------------------------------------------- #
# Decode: the stacked state, updated in place.
# --------------------------------------------------------------------------- #


def _kda_decode_kernel(layer_ref, qT_ref, kT_ref, gT_ref, bv_ref, bb_ref,
                       s_ref, o_ref, s_out_ref):
    """One slot's row of one layer: every head's [dk, dv] state decays,
    takes its delta-rule update and is read by the query. q, k, g arrive
    transposed ([dk, H]: a head's vector is a lane slice that broadcasts
    along lanes as a column), beta folded into `bv` = beta v and `bb` =
    beta broadcast over dv."""
    del layer_ref  # consumed by the index maps
    H = s_ref.shape[2]
    for h in range(H):  # static unroll: 16 vregs of state a head
        S = s_ref[0, 0, h] * jnp.exp(gT_ref[0, :, h:h + 1])
        kc = kT_ref[0, :, h:h + 1]
        kS = jnp.sum(S * kc, axis=0, keepdims=True)  # [1, dv]
        u = bv_ref[0, h:h + 1, :] - bb_ref[0, h:h + 1, :] * kS
        S = S + kc * u
        s_out_ref[0, 0, h] = S
        o_ref[0, h:h + 1, :] = jnp.sum(
            S * qT_ref[0, :, h:h + 1], axis=0, keepdims=True)


def _kda_decode_pallas(state, layer, q, k, v, g, beta, interpret: bool):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    _, B, H, dk, dv = state.shape
    qT, kT, gT = (jnp.swapaxes(a.astype(f32), -1, -2) for a in (q, k, g))
    bb = jnp.broadcast_to(beta.astype(f32)[..., None], (B, H, dv))
    bv = bb * v.astype(f32)

    def vec(shape):
        return pl.BlockSpec((1,) + shape, lambda b, layer: (b, 0, 0))

    srow = pl.BlockSpec((1, 1, H, dk, dv),
                        lambda b, layer: (layer[0], b, 0, 0, 0))
    kw = {}
    if not interpret:
        # a slot's row in and out, double-buffered: 8 MB at 32 x 128 x 128
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=max(32 << 20, 6 * H * dk * dv * 4))
    o, state = pl.pallas_call(
        _kda_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B,),
            in_specs=[vec((dk, H)), vec((dk, H)), vec((dk, H)),
                      vec((H, dv)), vec((H, dv)), srow],
            out_specs=[vec((H, dv)), srow],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H, dv), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={6: 1},
        interpret=interpret,
        name="kda_decode",
        **kw,
    )(jnp.asarray(layer, jnp.int32).reshape(1), qT, kT, gT, bv, bb, state)
    return o, state


def kda_decode(state, layer, q, k, v, g, beta, impl: str = "auto"):
    """One decode step of one KDA layer for every slot.

    state: the stacked [Lk, B, H, dk, dv] f32 state; `layer` its (traced)
    index. q, k, g [B, H, dk]; v [B, H, dv]; beta [B, H]. Returns
    (o [B, H, dv] f32, state): the kernel writes the layer's rows in place
    (donate the state); the XLA form slices the layer out and puts it back,
    a copy each way, and is the oracle and the off-TPU path."""
    if use_pallas(impl):
        return _kda_decode_pallas(state, layer, q, k, v, g, beta,
                                  interpret=jax.default_backend() != "tpu")
    with jax.named_scope("layer_state"):
        S = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
    o, S = kda_step(S, q, k, v, g, beta)
    return o, jax.lax.dynamic_update_index_in_dim(state, S, layer, 0)


# --------------------------------------------------------------------------- #
# Prefill: chunkwise.
# --------------------------------------------------------------------------- #


def _pair_decay_products(a, b, G, *, strict: bool):
    """M[t, s] = sum_c a_t[c] b_s[c] exp(G_t[c] - G_s[c]) for s < t (s <= t
    unless `strict`), zero elsewhere, within each chunk.

    a, b, G: [..., C, d] with G the inclusive cumulative log-decay of the
    chunk (non-increasing along C). Sub-blocks of SUB rows: a block pair
    (i > j) factors through the decay at the start of block i, so both
    exponents are <= 0; the diagonal blocks take the exponent of the
    difference, pair by pair."""
    *lead, C, d = a.shape
    n = C // SUB
    ab, bb_, Gb = (x.reshape(*lead, n, SUB, d) for x in (a, b, G))
    # diagonal blocks: [.., n, SUB(t), SUB(s), d]
    t = jnp.arange(SUB)
    keep = (t[:, None] > t[None, :]) if strict else (t[:, None] >= t[None, :])
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]
    diff = jnp.where(keep[:, :, None], diff, -jnp.inf)
    diag = jnp.einsum("...tc,...sc,...tsc->...ts", ab, bb_, jnp.exp(diff))
    # off-diagonal: reference = G at the last row of block i-1 (0 for i = 0)
    ref = jnp.concatenate(
        [jnp.zeros_like(Gb[..., :1, 0, :]), Gb[..., :-1, SUB - 1, :]], axis=-2)
    a_ref = ab * jnp.exp(Gb - ref[..., :, None, :])  # [.., n, SUB, d], <= 1
    # b against every later block's reference: [.., n(i), C(s), d]
    expo = ref[..., :, None, :] - G[..., None, :, :]
    blk_of_s = jnp.arange(C) // SUB
    earlier = blk_of_s[None, :] < jnp.arange(n)[:, None]  # [n(i), C(s)]
    expo = jnp.where(earlier[:, :, None], expo, -jnp.inf)
    b_ref = b[..., None, :, :] * jnp.exp(expo)
    off = jnp.einsum("...itc,...isc->...its", a_ref, b_ref)  # [.., n, SUB, C]
    off = off.reshape(*lead, C, C)
    # place the diagonal blocks
    eye = jnp.eye(n, dtype=a.dtype)
    full_diag = jnp.einsum("...its,ij->...itjs", diag, eye).reshape(
        *lead, C, C)
    return off + full_diag


def _solve_unit_lower(N, rhs):
    """X with (I + N) X = rhs; N [..., C, C] strictly lower triangular,
    rhs [..., C, d], C a multiple of SUB, float32 in and out.

    By blocks of SUB rows. First every diagonal block's inverse D at once,
    by substitution, D[t] = e_t - sum_{s<t} N[t, s] D[s]: SUB - 1 dependent
    steps, each an elementwise float32 product summed over the earlier
    rows (no dot). Then block forward substitution,
    X_i = D_i (R_i - sum_{j<i} N_ij X_j): C / SUB dependent steps of two
    batched dots at HIGHEST. The chunk's inverse is never formed.

    Not the closed product (I - N)(I + N^2)(I + N^4)(I + N^8), exact as it
    is for a nilpotent block: the powers hold N's path sums, thousands where
    beta nears 2 on near-parallel keys, and cancel to an inverse of order
    one; that loses 1e-3 of the solution in float32 where the substitution
    loses 4e-7, as the row-by-row solve it replaced did
    (tests/test_kimi_linear.py holds this form to that one's error). Which
    arrangement of the same arithmetic XLA:TPU lays out well was measured,
    not reasoned (PERF.md PR 47): this one; the same rows held with the blocks
    on the minor axis ran more than twice as long."""
    *lead, C, d = rhs.shape
    n = C // SUB
    hi = jax.lax.Precision.HIGHEST
    Nb = N.reshape(*lead, n, SUB, n, SUB)
    Nd = jnp.stack([Nb[..., i, :, i, :] for i in range(n)], axis=-3)
    eye = jnp.eye(SUB, dtype=N.dtype)
    rows = [jnp.broadcast_to(eye[0], Nd.shape[:-1])]
    for t in range(1, SUB):
        rows.append(eye[t] - jnp.sum(
            Nd[..., t, :t, None] * jnp.stack(rows, axis=-2), axis=-2))
    D = jnp.stack(rows, axis=-2)  # [..., n, SUB, SUB]
    R = rhs.reshape(*lead, n, SUB, d)
    X = []
    for i in range(n):
        r = R[..., i, :, :]
        if i:
            r = r - jnp.matmul(N[..., i * SUB:(i + 1) * SUB, :i * SUB],
                               jnp.concatenate(X, axis=-2), precision=hi)
        X.append(jnp.matmul(D[..., i, :, :], r, precision=hi))
    return jnp.concatenate(X, axis=-2)


@functools.partial(jax.jit, static_argnames=("chunk",))
def kda_chunk_prefill(q, k, v, g, beta, valid, chunk: int = CHUNK):
    """Chunkwise KDA from a zero state over right-padded prompts.

    q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]; valid [B, T]
    bool (rows past a prompt's length neither decay nor write the state, so
    the state returned is the one after the last valid token). T is a
    multiple of `chunk`. Returns (o [B, T, H, dv] f32, S [B, H, dk, dv]).

    The widest temporaries (the diagonal blocks' pairwise exponents,
    [SUB, SUB, dk] a sub-block) are T x H x SUB x dk float32 a request, 64 MB
    at 256 tokens of 32 heads, all requests at once: the engine bounds the
    rows of an admission program (engine/state.admit_rows). Running the
    requests in turn inside the program (`lax.map`) did not come back on the
    chip at 4 x 512 rows (PERF.md, PR 31) and is not done."""
    f32 = jnp.float32
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, T)
    if T % C or C % SUB:
        raise ValueError(f"KDA prefill: T={T} must divide by the chunk {C} "
                         f"and the chunk by {SUB}")
    N = T // C
    live = valid[..., None]
    g = jnp.where(live[..., None], g.astype(f32), 0.0)
    beta = jnp.where(live, beta.astype(f32), 0.0)

    def chunks(x):  # [B, T, H, d] -> [B, H, N, C, d]
        return jnp.moveaxis(x.astype(f32), 2, 1).reshape(
            B, H, N, C, x.shape[-1])

    qc, kc, vc, gc = chunks(q), chunks(k), chunks(v), chunks(g)
    bc = jnp.moveaxis(beta, 2, 1).reshape(B, H, N, C)
    G = jnp.cumsum(gc, axis=-2)  # inclusive, <= 0
    Gend = G[..., -1:, :]
    A = _pair_decay_products(kc, kc, G, strict=True)  # [B,H,N,C,C]
    Aqk = _pair_decay_products(qc, kc, G, strict=False)
    # (I + Diag(beta) A) u = beta (v - K+ S0): unit lower triangular
    kplus = kc * jnp.exp(G)  # k_t decayed from the chunk's start
    rhs = jnp.concatenate([bc[..., None] * vc, bc[..., None] * kplus], -1)
    sol = _solve_unit_lower(bc[..., :, None] * A, rhs)
    U0, W = sol[..., :dv], sol[..., dv:]  # u = U0 - W S0
    qplus = qc * jnp.exp(G)
    kend = kc * jnp.exp(Gend - G)  # k_s decayed to the chunk's end

    def body(S, xs):  # S [B, H, dk, dv]
        U0_, W_, qp, Aq, ke, ge = xs
        u = U0_ - jnp.einsum("bhck,bhkv->bhcv", W_, S)
        o = (jnp.einsum("bhck,bhkv->bhcv", qp, S)
             + jnp.einsum("bhcs,bhsv->bhcv", Aq, u))
        S = S * jnp.exp(ge)[..., 0, :, None] + jnp.einsum(
            "bhck,bhcv->bhkv", ke, u)
        return S, o

    xs = tuple(jnp.moveaxis(a, 2, 0) for a in (U0, W, qplus, Aqk, kend, Gend))
    S, o = jax.lax.scan(body, jnp.zeros((B, H, dk, dv), f32), xs)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, T, dv)
    return jnp.moveaxis(o, 1, 2), S
