"""Pallas flash attention (causal, GQA, length-masked) for TPU prefill.

The prefill hot op: dense attention materializes [B, H, S, S] scores in HBM
(O(S²) memory traffic); this kernel streams KV blocks through VMEM with the
online-softmax recurrence, so HBM traffic is O(S) per query block and the
matmuls hit the MXU at block size 128. Reference equivalent: llama.cpp's
flash-attn path (grpc-server.cpp params_parse `flash_attention`).

The KV axis is a GRID dimension (innermost, with the softmax running state
carried in VMEM scratch across its iterations) — NOT a whole-sequence VMEM
block with an in-kernel loop. A [S, D] KV block is 4 MB per operand at
S=32k, which double-buffered blows the 16 MB scoped-VMEM limit; per-block
tiles keep VMEM usage constant in S, so 32k+ contexts compile.

Layout: q [B, H, S, D] (head-major so a (q-block, head) grid step is one
contiguous VMEM tile), kv [B, K_heads, S, D]; GQA maps query head h to kv
head h // (H // K). Causal + per-row validity masking via the `lengths` [B]
scalar-prefetch argument.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_block_sizes(S: int) -> tuple[int, int]:
    """(block_q, block_k) for a length-S prefill. Bigger tiles at long
    context: the grid is B·H·(S/bq)·(S/bk) steps and per-step fixed cost
    dominates past ~8k (a 32k prefill at 128×128 tiles is ~1M grid steps);
    VMEM per step stays tiny (bq·D + 2·bk·D floats). Shared by the dense
    prefill dispatcher (ops/attention.prefill_attention) and the chunked
    admission path so both pick identical tiles for a given bucket."""
    return min(256, S), min(512, S)


def _flash_kernel(
    lengths_ref,  # scalar-prefetch [B]
    q_ref,  # [1, 1, BQ, D]
    k_ref,  # [1, 1, BK, D]
    v_ref,  # [1, 1, BK, D]
    o_ref,  # [1, 1, BQ, D]
    acc_ref,  # VMEM scratch [BQ, D] f32
    m_ref,  # VMEM scratch [BQ, 1] f32
    l_ref,  # VMEM scratch [BQ, 1] f32
    *,
    block_q: int,
    block_k: int,
    num_kv_blocks: int,
    scale: float,
    window: int = 0,
):
    import jax.experimental.pallas as pl

    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = lengths_ref[b]
    bq = q_ref.shape[2]

    # Causal: kv blocks entirely above this q block contribute nothing —
    # skip their (masked-to-NEG_INF) compute. Under a sliding `window` (a
    # query attends the `window` positions up to its own) so do the blocks
    # that end before the q block's first row's window begins.
    live = ki * block_k < (qi + 1) * block_q
    if window:
        live = live & ((ki + 1) * block_k > qi * block_q - window + 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # [BQ, D]
        k_blk = k_ref[0, 0].astype(jnp.float32)  # [BK, D]
        v_blk = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [BQ, BK]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
        kv_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 1)
        mask = (kv_pos <= q_pos) & (kv_pos < length)
        if window:
            mask = mask & (q_pos - kv_pos < window)
        s = jnp.where(mask, s, NEG_INF)

        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if window:  # a row whose window lies past this block: all masked
            p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        # Padding query rows (q_pos >= length) attend over the valid prefix
        # and would emit finite garbage; zero them explicitly so the output
        # contract is "padded rows are zeros" for any downstream pooling.
        q_row = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
        o = jnp.where(
            q_row < length,
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30),
            0.0,
        )
        o_ref[0, 0] = o.astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_q", "block_k", "interpret", "window")
)
def flash_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,  # [B, S, K, D]
    lengths: jnp.ndarray,  # [B] int32 valid lengths
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    window: int = 0,  # > 0: a query attends the last `window` positions only
) -> jnp.ndarray:
    """Causal GQA flash attention. Returns [B, S, H, D] in q.dtype."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    if S % block_q or S % block_k:
        raise ValueError(f"seq len {S} must be a multiple of block sizes ({block_q},{block_k})")
    scale = 1.0 / (D**0.5)

    # Head-major layout: one (b, h, q-block) grid step reads contiguous tiles.
    qh = q.transpose(0, 2, 1, 3)  # [B, H, S, D]
    kh = k.transpose(0, 2, 1, 3)  # [B, K, S, D]
    vh = v.transpose(0, 2, 1, 3)

    num_kv_blocks = S // block_k
    grid = (B, H, S // block_q, num_kv_blocks)
    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k,
        num_kv_blocks=num_kv_blocks, scale=scale, window=int(window),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps take (*grid_ids, *scalar_prefetch_refs)
                pl.BlockSpec((1, 1, block_q, D), lambda b, h, i, j, *_: (b, h, i, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, *_: (b, h // G, j, 0)),
                pl.BlockSpec((1, 1, block_k, D), lambda b, h, i, j, *_: (b, h // G, j, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, block_q, D), lambda b, h, i, j, *_: (b, h, i, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, D), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        interpret=interpret,
        name="flash_prefill",
    )(lengths.astype(jnp.int32), qh, kh, vh)
    return out.transpose(0, 2, 1, 3)  # [B, S, H, D]
