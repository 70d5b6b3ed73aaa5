"""Ragged per-slot LoRA delta kernel for multi-tenant decode (ISSUE 10).

One engine serves many tenants: shared (possibly int8/int4-quantized) base
weights plus per-tenant LoRA adapters applied UNMERGED beside each base
matmul — y = W·x + B·(A·x) with the rank-r factors of every device-resident
adapter stacked along a leading adapter axis. Each decode row (engine slot)
carries an adapter id, so one batch freely mixes tenants; id 0 is the
all-zero null adapter, making adapter-less rows bit-exact no-ops.

The Pallas kernel is the segmented/ragged shape the paged-attention walk
already uses (ops/paged_flash): the per-row adapter ids ride as a
scalar-prefetch operand and each grid step's BlockSpec index map gathers
THAT row's A/B factor blocks out of the stacked HBM tensors — a grouped
matmul over ragged segments, with the grid pipeline double-buffering the
factor DMAs exactly like quant_matmul streams weight tiles. Decode rows are
bounded by max_slots, so x, the rank-r intermediate, and the out tile all
sit in VMEM; consecutive rows of the same tenant revisit the same factor
block without a fresh DMA.

The XLA gather path below (`lora_delta_xla`) stays the numeric oracle,
dispatched behind EngineConfig.lora_kernel exactly like paged_kernel /
quant_kernel ("auto" = Pallas on TPU; tests run the kernel in interpret
mode on CPU against the oracle).

Sharding (tp>1): pallas_call is opaque to GSPMD, so the kernel runs under
shard_map with the factor partitioning matching the base weight's role —
column-parallel targets (wq/wk/wv/w_gate/w_up) replicate A and shard B on
the out axis; row-parallel targets (wo/w_down) shard A on the in axis
(their x arrives "tp"-sharded) and psum the partial deltas inside the
declared boundary below, the same ICI boundary the base matmul already
pays at the o/down projection.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# The ONLY function here allowed to issue cross-chip collectives: the
# row-parallel shard_map closure psums its partial B·(A·x) deltas over
# "tp" (lint: sharding-consistency C3).
COLLECTIVE_BOUNDARY = ("_sharded_lora_delta",)

# Rows above which the kernel disengages (prefill-scale deltas are
# compute-bound and ride the XLA path, which GSPMD shards by propagation).
LORA_PALLAS_MAX_ROWS = 256

# Base-weight role per LoRA target key: decides the tp partitioning of the
# stacked factors (parallel/sharding._layer_specs assigns the same roles to
# the base weights themselves).
LORA_PART = {
    "wq": "col", "wk": "col", "wv": "col",
    "w_gate": "col", "w_up": "col",
    "wo": "row", "w_down": "row",
}


def use_pallas_lora(impl: str = "auto") -> bool:
    """Resolve the LoRA-delta kernel choice. impl: "auto" (Pallas on TPU,
    XLA gather elsewhere), "pallas", or "xla". LOCALAI_LORA_KERNEL env var
    overrides — same escape hatch as LOCALAI_QUANT_KERNEL."""
    impl = os.environ.get("LOCALAI_LORA_KERNEL", "") or impl or "auto"
    if impl == "auto":
        return jax.default_backend() == "tpu"
    if impl not in ("pallas", "xla"):
        raise ValueError(f"lora kernel impl {impl!r}: use auto|pallas|xla")
    return impl == "pallas"


def lora_factor_specs(part: str):
    """PartitionSpecs for one target's stacked factors
    a [L, NA, in, R] / b [L, NA, R, out] under a tp mesh (see module
    docstring: col shards b's out axis, row shards a's in axis)."""
    from jax.sharding import PartitionSpec as P

    if part == "row":
        return {"a": P(None, None, "tp", None), "b": P(None, None, None, None)}
    return {"a": P(None, None, None, None), "b": P(None, None, None, "tp")}


def _tile(n: int, targets=(512, 256, 128)) -> int:
    for t in targets:
        if t <= n and n % t == 0:
            return t
    return n


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _tp_degree(mesh) -> int:
    if mesh is None:
        return 1
    return int(mesh.shape.get("tp", 1))


# --------------------------------------------------------------------------- #
# Kernel
# --------------------------------------------------------------------------- #


def _lora_kernel(ids_ref, x_ref, a_ref, b_ref, o_ref):
    """One (row, out-tile) grid step: this row's delta tile
    B[id][:, tile] · (A[id]ᵀ·x). The id-indexed factor blocks were DMA'd by
    the grid pipeline via the scalar-prefetched ids (see _lora_call); the
    rank-r intermediate lives only in registers."""
    del ids_ref  # consumed by the BlockSpec index maps, not the body
    x = x_ref[0].astype(jnp.float32)  # [1, IN]
    a = a_ref[0].astype(jnp.float32)  # [IN, R]
    b = b_ref[0].astype(jnp.float32)  # [R, bo]
    t = jax.lax.dot_general(
        x, a, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [1, R]
    y = jax.lax.dot_general(
        t, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # [1, bo]
    o_ref[0] = y.astype(o_ref.dtype)


def _lora_call(x2, a, b, ids):
    """pallas_call launch on local (possibly shard-local) shapes.

    x2 [N, IN] float; a [NA, IN, R]; b [NA, R, OUT]; ids [N] int32.
    Returns [N, OUT] in x2.dtype. Grid (N, out-tiles); the adapter ids ride
    scalar prefetch so the factor BlockSpecs gather per-row segments. x and
    the output ship as [N, 1, ·] so a one-row block spans the array's whole
    second-minor dim (Mosaic's block rule: a multiple of 8 or the full
    dim)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, k_in = x2.shape
    na, _, r = a.shape
    out = b.shape[-1]
    bo = _tile(out)
    grid = (n, out // bo)
    return pl.pallas_call(
        _lora_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, k_in), lambda i, j, ids: (i, 0, 0)),
                pl.BlockSpec((1, k_in, r), lambda i, j, ids: (ids[i], 0, 0)),
                pl.BlockSpec((1, r, bo), lambda i, j, ids: (ids[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((1, 1, bo), lambda i, j, ids: (i, 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((n, 1, out), x2.dtype),
        interpret=_interpret(),
        name="lora_matmul",
    )(ids, x2[:, None, :], a, b)[:, 0, :]


# --------------------------------------------------------------------------- #
# XLA oracle
# --------------------------------------------------------------------------- #


def lora_delta_xla(x, a, b, ids):
    """Per-row ragged delta, gather form: rows of x (leading axis) select
    their adapter's factors. x [B, ..., in]; a [NA, in, R]; b [NA, R, out];
    ids [B] int32 (0 = null adapter → exact zero). Returns [B, ..., out] in
    x.dtype, accumulated in f32 (the delta runs bf16/f32 even when the base
    matmul is int8/int4 — docs/LORA_SERVING.md)."""
    a_sel = jnp.take(a, ids, axis=0).astype(x.dtype)  # [B, in, R]
    b_sel = jnp.take(b, ids, axis=0).astype(x.dtype)  # [B, R, out]
    t = jnp.einsum(
        "b...i,bir->b...r", x, a_sel, preferred_element_type=jnp.float32
    )
    y = jnp.einsum(
        "b...r,bro->b...o", t.astype(x.dtype), b_sel,
        preferred_element_type=jnp.float32,
    )
    return y.astype(x.dtype)


# --------------------------------------------------------------------------- #
# Sharded dispatch (tp>1 — shard_map over the factors' own partitioning)
# --------------------------------------------------------------------------- #


def _sharded_lora_delta(x, a, b, ids, mesh, part: str):
    """Run the local kernel per tp shard; row-parallel partial deltas psum
    over "tp" here (the declared ICI boundary — see COLLECTIVE_BOUNDARY)."""
    from jax.sharding import PartitionSpec as P

    row = part == "row"
    fspecs = lora_factor_specs(part)
    # The engine's stacked factors carry a leading L axis the per-layer
    # slice has already consumed — drop it from the specs.
    a_spec = P(*tuple(fspecs["a"])[1:])
    b_spec = P(*tuple(fspecs["b"])[1:])
    x_spec = P(None, "tp") if row else P(None, None)
    o_spec = P(None, None) if row else P(None, "tp")

    def local(xl, al, bl, idsl):
        y = _lora_call(xl, al, bl, idsl)
        if row:
            y = jax.lax.psum(y, "tp")
        return y

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, a_spec, b_spec, P(None)),
        out_specs=o_spec,
        check_vma=False,
    )
    return fn(x, a, b, ids)


# --------------------------------------------------------------------------- #
# Dispatcher
# --------------------------------------------------------------------------- #


def _shardable(x, a, b, part: str, tp: int) -> bool:
    if part == "row":
        return x.shape[-1] % tp == 0 and a.shape[1] % tp == 0
    return b.shape[-1] % tp == 0


def lora_delta(x, factors, ids, impl: str = "auto", mesh=None,
               part: str = "col"):
    """Per-row LoRA delta y = B[id]·(A[id]·x) for one target projection.

    factors: {"a": [NA, in, R], "b": [NA, R, out]} per-layer slices of the
    engine's stacked adapter tensors; ids [B] int32 device-adapter rows
    (0 = none). Decode-shape 2-D x routes to the Pallas ragged kernel per
    `impl` ("auto" = Pallas on TPU); everything else — prefill [B, S, in],
    interpret-unfriendly shapes, non-divisible tp splits — falls back to
    the XLA gather oracle, which GSPMD partitions by propagation."""
    a, b = factors["a"], factors["b"]
    engaged = (
        use_pallas_lora(impl)
        and x.ndim == 2
        and jnp.issubdtype(x.dtype, jnp.floating)
        and 0 < x.shape[0] <= LORA_PALLAS_MAX_ROWS
    )
    if engaged:
        tp = _tp_degree(mesh)
        if tp > 1 and part in ("col", "row"):
            if _shardable(x, a, b, part, tp):
                return _sharded_lora_delta(x, a, b, ids, mesh, part)
        else:
            return _lora_call(x, a, b, ids)
    return lora_delta_xla(x, a, b, ids)
