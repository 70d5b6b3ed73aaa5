"""Ring attention: sequence-parallel causal attention over the "sp" mesh axis.

The reference has no sequence/context parallelism at all (SURVEY.md §5
"Long-context: not implemented — green-field"). Here it is first-class: the
sequence axis is sharded over "sp"; each device computes attention for its
query block while KV blocks rotate around the ring via ppermute (one ICI hop
per step), accumulating with the online-softmax recurrence — so a context of
length S needs only S/n KV residency per chip and the collective traffic
rides neighbor-to-neighbor ICI links (Liu et al., Ring Attention; the
public scaling-book recipe).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30

# Declared ICI-collective boundary (lint: sharding-consistency): the ring
# rotations themselves. KV blocks ppermute neighbor-to-neighbor inside
# _local_ring's / _local_ring_chunk's shard_map bodies; no other function
# here may touch ICI.
COLLECTIVE_BOUNDARY = ("_local_ring", "_local_ring_chunk")


def _local_ring(q, k, v, lengths, *, axis: str, n_shards: int,
                softcap: float = 0.0, window: int = 0, sliding=None):
    """Per-shard body under shard_map.

    q: [B, S_l, H, D], k/v: [B, S_l, K, D] — the local sequence block.
    lengths: [B] global valid lengths (replicated). softcap/window/sliding
    are the gemma-2 semantics (softcap BEFORE masking; sliding layers only
    attend within `window` positions back).
    """
    B, S_l, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)
    my = jax.lax.axis_index(axis)

    qf = (q.astype(jnp.float32) * scale).reshape(B, S_l, K, G, D)
    q_pos = my * S_l + jnp.arange(S_l)  # [S_l] global query positions

    acc0 = jnp.zeros((B, K, G, S_l, D), jnp.float32)
    m0 = jnp.full((B, K, G, S_l, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, S_l, 1), jnp.float32)

    def step(s, carry):
        k_blk, v_blk, acc, m, l = carry
        src = (my - s) % n_shards  # global index of the block we hold now
        kv_pos = src * S_l + jnp.arange(S_l)  # [S_l]

        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qf, k_blk.astype(jnp.float32)
        )  # [B, K, G, S_q, S_kv]
        if softcap:
            scores = softcap * jnp.tanh(scores / softcap)
        causal = kv_pos[None, :] <= q_pos[:, None]  # [S_q, S_kv]
        if window and sliding is not None:
            dist = q_pos[:, None] - kv_pos[None, :]
            causal = causal & (~sliding | (dist < window))
        valid = kv_pos[None, :] < lengths[:, None]  # [B, S_kv]
        full_mask = causal[None, None, None] & valid[:, None, None, None, :]
        scores = jnp.where(full_mask, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v_blk.astype(jnp.float32)
        )

        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return k_blk, v_blk, acc_new, m_new, l_new

    _, _, acc, m, l = jax.lax.fori_loop(0, n_shards, step, (k, v, acc0, m0, l0))
    out = acc / jnp.maximum(l, 1e-30)
    # Padding query rows attend over the valid prefix (finite garbage); zero
    # them so the contract is "padded rows are zeros" (matches ops/flash.py).
    valid_q = (q_pos[None, :] < lengths[:, None])[:, None, None, :, None]
    out = jnp.where(valid_q, out, 0.0)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S_l, H, D).astype(q.dtype)


def ring_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, D] sharded on S over `axis`
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,
    lengths: jnp.ndarray,  # [B]
    mesh: Mesh,
    axis: str = "sp",
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
) -> jnp.ndarray:
    """Causal GQA attention with the sequence axis sharded over `axis`."""
    n = mesh.shape[axis]
    seq_spec = P(None, axis, None, None)
    if sliding is None:
        fn = jax.shard_map(
            partial(_local_ring, axis=axis, n_shards=n, softcap=softcap),
            mesh=mesh,
            in_specs=(seq_spec, seq_spec, seq_spec, P(None)),
            out_specs=seq_spec,
            check_vma=False,
        )
        return fn(q, k, v, lengths)
    # `sliding` is a traced bool scalar (layer alternation) — it rides as a
    # replicated operand so one shard_map serves both layer kinds.
    fn = jax.shard_map(
        lambda q_, k_, v_, l_, sl_: _local_ring(
            q_, k_, v_, l_, axis=axis, n_shards=n, softcap=softcap,
            window=window, sliding=sl_,
        ),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(None), P()),
        out_specs=seq_spec,
        check_vma=False,
    )
    return fn(q, k, v, lengths, sliding)


def _local_ring_chunk(q, k, v, offsets, lengths, kpool, vpool, table, kvs,
                      sl, *, axis: str, n_shards: int, softcap: float,
                      window: int, has_sliding: bool, sink: int, swin: int,
                      scaled: bool):
    """Per-shard body of the sequence-parallel PREFILL CHUNK (ISSUE 14).

    The chunk's token axis is sharded over `axis`: this shard holds T/n
    query tokens (q [B, T_l, H, D]) and the matching in-chunk K/V block
    (k/v [B, T_l, K, D]). Two attention sources fold into one online-softmax
    state:

    1. The slot's RESIDENT pages — walked locally for this shard's queries
       through the replicated pool + table (ops.attention's multi-query
       page walk, windowed+sink skip included). No collective: every shard
       reads its own slice of a replicated pool.
    2. The IN-CHUNK causal part — K/V blocks rotate around the ring via
       ppermute (one ICI hop per step, the _local_ring recurrence) with the
       causal/length/sink/window masks evaluated on GLOBAL positions
       (offsets[b] + chunk index).

    Returns this shard's attention rows [B, T_l, H, D] in q's dtype; fresh
    K/V still scatters into pool pages OUTSIDE the shard_map (the chunk's
    k/v are returned by the layer body as usual)."""
    from localai_tpu.ops.attention import _paged_cache_partials_mq

    B, T_l, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)
    my = jax.lax.axis_index(axis)

    qpos = offsets[:, None] + my * T_l + jnp.arange(T_l)[None, :]  # [B, T_l]
    acc0, m0, l0 = _paged_cache_partials_mq(
        q, kpool, vpool, table, offsets,
        softcap=softcap, window=window,
        sliding=sl if has_sliding else None, q_pos=qpos,
        kv_scale=kvs if scaled else None, sink=sink, swin=swin,
    )  # acc [B, K, G, T_l, D], m/l [B, K, G, T_l, 1]

    qf = (q.astype(jnp.float32) * scale).reshape(B, T_l, K, G, D)

    def step(s, carry):
        k_blk, v_blk, acc, m, l = carry
        src = (my - s) % n_shards  # global shard index of the block we hold
        idx = src * T_l + jnp.arange(T_l)  # [T_l] in-chunk indices
        kv_pos = offsets[:, None] + idx[None, :]  # [B, T_l] global positions

        scores = jnp.einsum(
            "bqkgd,bskd->bkgqs", qf, k_blk.astype(jnp.float32)
        )  # [B, K, G, T_q, T_kv]
        if softcap:
            scores = softcap * jnp.tanh(scores / softcap)
        valid = (kv_pos[:, None, :] <= qpos[:, :, None])  # causal, global
        valid = valid & (idx[None, None, :] < lengths[:, None, None])
        dist = qpos[:, :, None] - kv_pos[:, None, :]
        if window and has_sliding:
            valid = valid & (~sl | (dist < window))
        if swin:
            valid = valid & ((kv_pos[:, None, :] < sink) | (dist < swin))
        vmask = valid[:, None, None]  # [B, 1, 1, T_q, T_kv]
        scores = jnp.where(vmask, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        p = jnp.where(vmask, p, 0.0)
        alpha = jnp.exp(jnp.maximum(m - m_new, -80.0))
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "bkgqs,bskd->bkgqd", p, v_blk.astype(jnp.float32)
        )

        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        k_blk = jax.lax.ppermute(k_blk, axis, perm)
        v_blk = jax.lax.ppermute(v_blk, axis, perm)
        return k_blk, v_blk, acc_new, m_new, l_new

    _, _, acc, m, l = jax.lax.fori_loop(
        0, n_shards, step, (k, v, acc0, m0, l0)
    )
    out = acc / jnp.maximum(l, 1e-30)  # [B, K, G, T_l, D]
    # Padding query rows (in-chunk index >= lengths) carry finite garbage;
    # zero them so the contract matches prefill_chunk_paged's dense merge.
    q_idx = my * T_l + jnp.arange(T_l)
    valid_q = (q_idx[None, :] < lengths[:, None])[:, None, None, :, None]
    out = jnp.where(valid_q, out, 0.0)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T_l, H, D).astype(q.dtype)


def ring_chunk_paged_attention(
    q: jnp.ndarray,  # [B, T, H, D] chunk queries (T divisible by sp)
    k: jnp.ndarray,  # [B, T, K, D] the chunk's fresh K rows
    v: jnp.ndarray,
    offsets: jnp.ndarray,  # [B] rows already resident (chunk starts here)
    lengths: jnp.ndarray,  # [B] valid chunk lengths
    k_pool: jnp.ndarray,  # [P, page, K, D] page pool (replicated over sp)
    v_pool: jnp.ndarray,
    table,  # [B, MP] int32 page table, or hierarchical (l1, l0) pair
    mesh: Mesh,
    axis: str = "sp",
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
    sink: int = 0,
    swin: int = 0,
    kv_scale=None,  # [2, K] f32 per-head pool dequant scales (fp8 KV)
) -> jnp.ndarray:
    """Sequence-parallel attention for one direct-to-page prefill chunk
    (models/llama.prefill_chunk_paged's sp leg): chunk tokens shard over
    `axis`, each shard walks the slot's resident pages for its own queries
    while the in-chunk K/V rotates around the ring. Composes with tp>1 —
    heads additionally shard over "tp" like every other kernel path."""
    from localai_tpu.ops import ptable as _pt
    from localai_tpu.ops.stacked import layer_slice

    # The walk inside the shard_map is XLA's: a pool still stacked over
    # layers (stacked.StackedLayer) is sliced at this site, in front of it.
    k_pool = layer_slice(k_pool, "layer_kv_pool")
    v_pool = layer_slice(v_pool, "layer_kv_pool")
    n = mesh.shape[axis]
    tp = mesh.shape.get("tp", 1) > 1
    hspec = "tp" if tp else None
    seq_spec = P(None, axis, hspec, None)
    pool_spec = P(None, None, hspec, None)
    kvs = (jnp.ones((2, k_pool.shape[2]), jnp.float32) if kv_scale is None
           else kv_scale.astype(jnp.float32))
    sl_in = sliding if sliding is not None else jnp.zeros((), bool)
    tbl_spec = _pt.shard_spec(table, P(None, None), P(None, None))
    fn = jax.shard_map(
        partial(
            _local_ring_chunk, axis=axis, n_shards=n, softcap=softcap,
            window=window, has_sliding=sliding is not None, sink=sink,
            swin=swin, scaled=kv_scale is not None,
        ),
        mesh=mesh,
        in_specs=(seq_spec, seq_spec, seq_spec, P(None), P(None),
                  pool_spec, pool_spec, tbl_spec, P(None, hspec), P()),
        out_specs=seq_spec,
        check_vma=False,
    )
    return fn(q, k, v, offsets, lengths, k_pool, v_pool, table, kvs, sl_in)
