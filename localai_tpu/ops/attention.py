"""Attention ops: batched causal prefill and single-token decode against a
slot KV cache.

Design notes (TPU-first):
- Prefill attention is a dense causal softmax-attention over the bucketed
  prompt length. XLA fuses the mask+softmax chain; a Pallas flash-attention
  kernel (localai_tpu.ops.flash) can be swapped in for long buckets.
- Decode attention reads the whole slot cache [B, S_max, K, H] with a length
  mask. This is the JAX equivalent of llama.cpp's unified KV cache read in
  its slot loop (reference: backend/cpp/llama-cpp/grpc-server.cpp:679
  PredictStream -> server slots); instead of per-slot pointers we use one
  dense cache and mask, which keeps shapes static under jit.
- GQA: queries have H heads, cache has K kv-heads, H % K == 0; we reshape
  queries to [B, K, H//K, ...] and broadcast the cache.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

NEG_INF = -1e30

# Declared ICI-collective boundary (lint: sharding-consistency). The ONLY
# function in this module allowed to issue cross-chip collectives is the
# sp-axis flash-decoding combine — everything else must stay collective-free
# so the per-token path pays ICI exclusively at the o/down projections
# (GSPMD psums from the row-parallel specs in parallel/sharding.py).
COLLECTIVE_BOUNDARY = ("_sp_cache_partials",)


def softcap_scores(sc: jnp.ndarray, cap: float) -> jnp.ndarray:
    """Gemma-2 attention-logit softcapping: cap·tanh(sc/cap). Applied BEFORE
    masking (tanh of NEG_INF would be finite and corrupt the mask)."""
    return cap * jnp.tanh(sc / cap)


def _tp_degree(mesh) -> int:
    """Tensor-parallel degree of a mesh (0/1 when absent) — the gate for the
    head-sharded shard_map kernel paths (ISSUE 7)."""
    if mesh is None:
        return 1
    return int(mesh.shape.get("tp", 1))


def _head_shard_map(fn, mesh, in_specs, out_specs):
    """shard_map over the mesh's "tp" axis for per-head kernels. Pallas
    custom calls are opaque to the SPMD partitioner — under a tp-sharded
    GSPMD program XLA would all-gather their operands per call, exactly the
    per-token collective the sharded engine must not pay. Wrapping the
    kernel in shard_map hands each chip its OWN heads' q/k/v (and paged-pool
    shard) and runs the unmodified kernel on local shapes; no collective is
    introduced — the psum stays at the o-projection where GSPMD already puts
    it (row-parallel wo, parallel/sharding.py)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def prefill_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,  # [B, S, K, D]
    length_mask: jnp.ndarray | None,  # [B, S] bool
    lengths: jnp.ndarray | None = None,  # [B] int32 (enables flash path)
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,  # traced bool scalar: this layer uses the sliding window
    mesh=None,  # Mesh with tp>1 → flash kernel head-sharded under shard_map
) -> jnp.ndarray:
    """Prefill attention dispatcher: Pallas flash kernel on TPU by default
    (opt out with LOCALAI_FLASH=0), dense math otherwise. Softcapping and a
    per-layer (traced) sliding flag (gemma-2) force the dense path; a window
    every layer of the caller's kind slides under (`sliding` the static
    `np.True_`, llama._layer_sliding) is the flash kernel's own. With a tp>1 mesh the
    flash kernel runs head-sharded under shard_map (each chip computes its
    own heads; the dense-math path needs nothing — GSPMD partitions plain
    einsums over the head axis by propagation)."""
    S = q.shape[1]
    import numpy as np

    static = isinstance(sliding, (bool, np.bool_))  # no traced per-layer flag
    window = window if sliding is not None and (not static or sliding) else 0
    if (
        lengths is not None
        and not softcap
        and (not window or static)
        and os.environ.get("LOCALAI_FLASH", "1") != "0"
        and jax.default_backend() == "tpu"
        and (S & (S - 1)) == 0  # power-of-two bucket, divisible by any block
    ):
        from localai_tpu.ops.flash import flash_block_sizes, flash_prefill_attention

        bq, bk = flash_block_sizes(S)
        if _tp_degree(mesh) > 1:
            from jax.sharding import PartitionSpec as P

            fn = _head_shard_map(
                lambda qs, ks, vs, ln: flash_prefill_attention(
                    qs, ks, vs, ln, block_q=bq, block_k=bk, window=window
                ),
                mesh,
                in_specs=(P(None, None, "tp", None), P(None, None, "tp", None),
                          P(None, None, "tp", None), P(None)),
                out_specs=P(None, None, "tp", None),
            )
            return fn(q, k, v, lengths)
        return flash_prefill_attention(q, k, v, lengths, block_q=bq,
                                       block_k=bk, window=window)
    return causal_prefill_attention(q, k, v, length_mask, softcap=softcap,
                                    window=window, sliding=sliding)


def causal_prefill_attention(
    q: jnp.ndarray,  # [B, S, H, D]
    k: jnp.ndarray,  # [B, S, K, D]
    v: jnp.ndarray,  # [B, S, K, D]
    length_mask: jnp.ndarray | None = None,  # [B, S] bool, True = valid token
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
) -> jnp.ndarray:
    """Dense causal attention for prompt processing. Returns [B, S, H, D]."""
    B, S, H, D = q.shape
    K = k.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)

    qf = q.astype(jnp.float32).reshape(B, S, K, G, D)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    # scores: [B, K, G, S_q, S_k]
    scores = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf) * scale
    if softcap:
        scores = softcap_scores(scores, softcap)
    causal = jnp.tril(jnp.ones((S, S), dtype=bool))
    if window and sliding is not None:
        dist = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]  # q_pos - k_pos
        causal = causal & (~sliding | (dist < window))
    mask = causal[None, None, None, :, :]
    if length_mask is not None:
        mask = jnp.logical_and(mask, length_mask[:, None, None, None, :])
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", probs, vf)
    return out.reshape(B, S, H, D).astype(q.dtype)


def decode_attention_appended(
    q: jnp.ndarray,  # [B, H, D] query for the single new token per slot
    k_cache: jnp.ndarray,  # [B, S_max, K, D] — cache WITHOUT the current token
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, K, D] current token's key (not yet in the cache)
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [B] int32 position of the current token
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
) -> jnp.ndarray:
    """Decode attention over `cache[0:pos] ⊕ current token`. Returns [B, H, D].

    The current token's k/v ride as separate operands so the cache write can
    happen ONCE outside the per-layer scan — rewriting the full cache per
    layer per token is the dominant HBM waste in a naive decode loop (see
    models/llama.py decode_step)."""
    B, H, D = q.shape
    S = k_cache.shape[1]
    K = k_cache.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)

    qf = q.astype(jnp.float32).reshape(B, K, G, D)
    scores = jnp.einsum(
        "bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32)
    ) * scale  # [B, K, G, S]
    if softcap:
        scores = softcap_scores(scores, softcap)
    # Cache rows at/after `positions` are stale (the current row is written
    # after the layer scan); mask them and score the current token separately.
    valid = jnp.arange(S)[None, :] < positions[:, None]  # [B, S]
    if window and sliding is not None:
        dist = positions[:, None] - jnp.arange(S)[None, :]
        valid = valid & (~sliding | (dist < window))
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    cur = jnp.einsum(
        "bkgd,bkd->bkg", qf, k_new.astype(jnp.float32)
    )[..., None] * scale  # [B, K, G, 1]
    if softcap:
        cur = softcap_scores(cur, softcap)
    probs = jax.nn.softmax(jnp.concatenate([scores, cur], axis=-1), axis=-1)
    out = jnp.einsum(
        "bkgs,bskd->bkgd", probs[..., :S], v_cache.astype(jnp.float32)
    ) + probs[..., S:] * v_new.astype(jnp.float32)[:, :, None, :]
    return out.reshape(B, H, D).astype(q.dtype)


def decode_attention_windowed(
    q: jnp.ndarray,  # [B, H, D] current token's query
    k_cache: jnp.ndarray,  # [B, S, K, D] — READ-ONLY cache (pre-block rows)
    v_cache: jnp.ndarray,
    k_local: jnp.ndarray,  # [B, n, K, D] — this decode block's earlier tokens
    v_local: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, K, D] current token
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [B] current token's position
    step: jnp.ndarray,  # scalar: index of the current token within the block
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,  # traced bool scalar: this layer uses the sliding window
    sink: int = 0,  # windowed+sink decode (docs/LONG_CONTEXT.md): rows
    swin: int = 0,  # attended iff gpos < sink or q_pos - gpos < swin
) -> jnp.ndarray:
    """Decode attention over `cache[0:block_start] ⊕ local[0:step] ⊕ current`.

    Inside a fused N-step decode block the cache stays READ-ONLY (its
    in-block rows live in the local window), so the block's lax.scan carries
    only the tiny local buffer — the full cache is written ONCE per block.
    Profiling showed the carried-cache alternative costs a full cache copy
    per token (engine VERDICT-weak decode path)."""
    B, H, D = q.shape
    S = k_cache.shape[1]
    n = k_local.shape[1]
    K = k_cache.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)

    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, D)
    block_start = positions - step  # [B]
    sc = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
    if softcap:
        sc = softcap_scores(sc, softcap)
    valid_c = jnp.arange(S)[None, :] < block_start[:, None]
    if window and sliding is not None:
        # q position is `positions`; cache row s sits at position s.
        dist_c = positions[:, None] - jnp.arange(S)[None, :]
        valid_c = valid_c & (~sliding | (dist_c < window))
    if swin:
        dist_c = positions[:, None] - jnp.arange(S)[None, :]
        valid_c = valid_c & ((jnp.arange(S)[None, :] < sink) | (dist_c < swin))
    sc = jnp.where(valid_c[:, None, None, :], sc, NEG_INF)
    sl = jnp.einsum("bkgd,bnkd->bkgn", qf, k_local.astype(jnp.float32))
    if softcap:
        sl = softcap_scores(sl, softcap)
    valid_l = jnp.arange(n) < step  # [n] — same for every slot
    if window and sliding is not None:
        # local row i sits at distance step - i from the current token.
        valid_l = valid_l & (~sliding | ((step - jnp.arange(n)) < window))
    valid_l = jnp.broadcast_to(valid_l[None, :], (B, n))
    if swin:
        dist_l = (step - jnp.arange(n))[None, :]
        gpos_l = positions[:, None] - dist_l
        valid_l = valid_l & ((gpos_l < sink) | (dist_l < swin))
    sl = jnp.where(valid_l[:, None, None, :], sl, NEG_INF)
    cur = jnp.einsum("bkgd,bkd->bkg", qf, k_new.astype(jnp.float32))[..., None]
    if softcap:
        cur = softcap_scores(cur, softcap)
    probs = jax.nn.softmax(jnp.concatenate([sc, sl, cur], axis=-1), axis=-1)
    out = (
        jnp.einsum("bkgs,bskd->bkgd", probs[..., :S], v_cache.astype(jnp.float32))
        + jnp.einsum("bkgn,bnkd->bkgd", probs[..., S:S + n], v_local.astype(jnp.float32))
        + probs[..., S + n:] * v_new.astype(jnp.float32)[:, :, None, :]
    )
    return out.reshape(B, H, D).astype(q.dtype)


def _sp_cache_partials(q, k_cache, v_cache, limits, mesh,
                       softcap: float = 0.0, window: int = 0, sliding=None,
                       q_pos=None, sink: int = 0, swin: int = 0):
    """Online-softmax partial attention over an "sp"-sharded cache.

    The KV cache's sequence axis is sharded over the mesh's "sp" axis (see
    parallel/sharding.py cache_specs), so each chip holds S/sp rows and HBM
    residency — the serving-side half of the long-context story whose compute
    half is ring prefill (parallel/ring.py). Each shard computes its local
    (max, sum-exp, weighted-acc) over rows with global index < limits[b] and
    the three small partials combine with one pmax + two psums over "sp" —
    flash-decoding across chips, riding ICI.

    q: [B, H, D]; k/v_cache: [B, S, K, D] (S sp-sharded); limits: [B] row
    bound per slot. softcap/window/sliding are the gemma-2 semantics
    (softcap BEFORE masking; sliding layers mask rows further than `window`
    below the query's position `q_pos` [B]). Returns (acc [B, K, G, D],
    m [B, K, G, 1], l [B, K, G, 1]) replicated over sp, f32, with the
    1/sqrt(D) scale already applied to q.
    """
    from functools import partial

    from jax.sharding import PartitionSpec as P

    B, H, D = q.shape
    K = k_cache.shape[2]
    scale = 1.0 / (D**0.5)
    if q_pos is None:
        q_pos = limits  # plain decode: the query sits right after the rows

    def local(qb, kc, vc, lim, qp, sl):
        Bl, Hl, D_ = qb.shape
        Kl = kc.shape[2]
        G = Hl // Kl
        S_l = kc.shape[1]
        my = jax.lax.axis_index("sp")
        gpos = my * S_l + jnp.arange(S_l)  # global row indices of this shard
        qf = (qb.astype(jnp.float32) * scale).reshape(Bl, Kl, G, D_)
        sc = jnp.einsum("bkgd,bskd->bkgs", qf, kc.astype(jnp.float32))
        if softcap:
            sc = softcap_scores(sc, softcap)
        valid = gpos[None, :] < lim[:, None]
        if window and sliding is not None:
            dist = qp[:, None] - gpos[None, :]
            valid = valid & (~sl | (dist < window))
        if swin:
            dist = qp[:, None] - gpos[None, :]
            valid = valid & ((gpos[None, :] < sink) | (dist < swin))
        sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)  # exp(NEG_INF - NEG_INF) rows zeroed by valid below
        p = jnp.where(valid[:, None, None, :], p, 0.0)
        l = jnp.sum(p, axis=-1, keepdims=True)
        acc = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
        m_g = jax.lax.pmax(m, "sp")
        alpha = jnp.exp(jnp.maximum(m - m_g, -80.0))  # -inf - -inf guard
        alpha = jnp.where(l > 0, alpha, 0.0)
        l_g = jax.lax.psum(l * alpha, "sp")
        acc_g = jax.lax.psum(acc * alpha, "sp")
        return acc_g, m_g, l_g

    # The sliding flag is a traced per-layer scalar — it rides as an explicit
    # replicated operand (closure capture of tracers is not valid under
    # shard_map).
    sl_in = sliding if sliding is not None else jnp.zeros((), bool)
    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(
            P("dp", "tp", None),
            P("dp", "sp", "tp", None),
            P("dp", "sp", "tp", None),
            P("dp"),
            P("dp"),
            P(),
        ),
        out_specs=(
            P("dp", "tp", None, None),
            P("dp", "tp", None, None),
            P("dp", "tp", None, None),
        ),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, limits, q_pos, sl_in)


def _merge_partials(q, acc_g, m_g, l_g, extra_k, extra_v, extra_mask,
                    softcap: float = 0.0):
    """Merge sharded-cache partials with a small dense tail (local window
    and/or the current token). extra_k: [B, E, K, D]; extra_mask: [B, E] or
    [E]. Returns [B, H, Dv] in q's dtype, Dv the width of the partials and
    of extra_v (q's, or a latent walk's value lanes)."""
    B, H, D = q.shape
    K = extra_k.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)
    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, D)
    se = jnp.einsum("bkgd,bekd->bkge", qf, extra_k.astype(jnp.float32))
    if softcap:
        se = softcap_scores(se, softcap)
    if extra_mask.ndim == 1:
        extra_mask = extra_mask[None, :]
    se = jnp.where(extra_mask[:, None, None, :], se, NEG_INF)
    m_e = jnp.max(se, axis=-1, keepdims=True)
    m_tot = jnp.maximum(m_g, m_e)
    p_e = jnp.exp(se - m_tot)
    p_e = jnp.where(extra_mask[:, None, None, :], p_e, 0.0)
    w_c = jnp.exp(jnp.maximum(m_g - m_tot, -80.0))
    w_c = jnp.where(l_g > 0, w_c, 0.0)
    num = acc_g * w_c + jnp.einsum("bkge,bekd->bkgd", p_e, extra_v.astype(jnp.float32))
    den = l_g * w_c + jnp.sum(p_e, axis=-1, keepdims=True)
    out = num / jnp.maximum(den, 1e-30)
    return out.reshape(B, H, -1).astype(q.dtype)


def decode_attention_appended_sp(
    q: jnp.ndarray,  # [B, H, D]
    k_cache: jnp.ndarray,  # [B, S, K, D] — sequence axis sharded over "sp"
    v_cache: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, K, D]
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [B]
    mesh,
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
) -> jnp.ndarray:
    """`decode_attention_appended` for an sp-sharded cache (see
    _sp_cache_partials). The current token is merged host-of-shard-map side
    since it is replicated over sp."""
    acc_g, m_g, l_g = _sp_cache_partials(
        q, k_cache, v_cache, positions, mesh,
        softcap=softcap, window=window, sliding=sliding, q_pos=positions,
    )
    ones = jnp.ones((q.shape[0], 1), bool)
    return _merge_partials(q, acc_g, m_g, l_g, k_new[:, None], v_new[:, None],
                           ones, softcap=softcap)


def decode_attention_windowed_sp(
    q: jnp.ndarray,  # [B, H, D]
    k_cache: jnp.ndarray,  # [B, S, K, D] — sequence axis sharded over "sp"
    v_cache: jnp.ndarray,
    k_local: jnp.ndarray,  # [B, n, K, D] block-local window (replicated)
    v_local: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, K, D]
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [B]
    step: jnp.ndarray,  # scalar
    mesh,
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
    sink: int = 0,  # windowed+sink decode (docs/LONG_CONTEXT.md)
    swin: int = 0,
) -> jnp.ndarray:
    """`decode_attention_windowed` for an sp-sharded cache: sharded partials
    over cache[0:block_start], dense merge of the block-local window and the
    current token (both tiny and replicated)."""
    n = k_local.shape[1]
    acc_g, m_g, l_g = _sp_cache_partials(
        q, k_cache, v_cache, positions - step, mesh,
        softcap=softcap, window=window, sliding=sliding, q_pos=positions,
        sink=sink, swin=swin,
    )
    # f32 concat: the block-local window may live in the cache's storage
    # dtype (fp8 KV) while the current token is model-dtype.
    ek = jnp.concatenate([k_local.astype(jnp.float32),
                          k_new[:, None].astype(jnp.float32)], axis=1)
    ev = jnp.concatenate([v_local.astype(jnp.float32),
                          v_new[:, None].astype(jnp.float32)], axis=1)
    mask = jnp.concatenate(
        [jnp.arange(n) < step, jnp.ones((1,), bool)], axis=0
    )  # [n+1] — same for every slot
    if window and sliding is not None:
        # Local row i sits `step - i` behind the query; the current token is
        # distance 0. (The window bound never trips for these in practice —
        # n << window — but the mask keeps the semantics exact.)
        dist = jnp.concatenate([step - jnp.arange(n), jnp.zeros((1,), jnp.int32)])
        mask = mask & (~sliding | (dist < window))
    if swin:
        dist = jnp.concatenate(
            [step - jnp.arange(n), jnp.zeros((1,), jnp.int32)]
        )[None, :]
        gpos = positions[:, None] - dist
        mask = mask[None, :] & ((gpos < sink) | (dist < swin))
    return _merge_partials(q, acc_g, m_g, l_g, ek, ev, mask, softcap=softcap)


def decode_attention(
    q: jnp.ndarray,  # [B, H, D] query for the single new token per slot
    k_cache: jnp.ndarray,  # [B, S_max, K, D]
    v_cache: jnp.ndarray,  # [B, S_max, K, D]
    cache_len: jnp.ndarray,  # [B] int32: number of valid cache entries (incl. current token)
) -> jnp.ndarray:
    """Single-step attention against the slot cache. Returns [B, H, D]."""
    B, H, D = q.shape
    S = k_cache.shape[1]
    K = k_cache.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)

    qf = q.astype(jnp.float32).reshape(B, K, G, D)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)

    scores = jnp.einsum("bkgd,bskd->bkgs", qf, kf) * scale  # [B, K, G, S]
    valid = jnp.arange(S)[None, :] < cache_len[:, None]  # [B, S]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vf)
    return out.reshape(B, H, D).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Paged KV cache (vLLM-style page pool, XLA-native flash-decoding over pages)
# --------------------------------------------------------------------------- #


def _sink_window_cols(limits, q_min, page, MP, sink, swin):
    """Per-slot walk plan for windowed+sink attention (ISSUE 14): page
    columns outside `[0, ceil(sink/page)) ∪ [win_lo, np_live)` can never be
    attended (a row is live iff `gpos < sink` or `q_pos - gpos < swin`, and
    q_pos only grows), so the walk skips them entirely — the whole point of
    spilling cold middle pages to the host tier. Returns (sink_cols [B],
    win_lo [B], n_cols [B]): column j of the walk maps to table column
    `j < sink_cols ? j : j + (win_lo - sink_cols)`.

    Skipping is EXACT, not approximate: a skipped page's scores would be
    NEG_INF under the mask, contributing zero to (acc, l) and leaving m
    unchanged — identical online-softmax state either way."""
    sink_pages = -(-sink // page) if sink else 0
    np_live = jnp.minimum((limits + page - 1) // page, MP)
    sink_cols = jnp.minimum(sink_pages, np_live)
    win_lo = jnp.clip((q_min - swin + 1) // page, 0, np_live)
    win_lo = jnp.maximum(win_lo, sink_cols)
    return sink_cols, win_lo, sink_cols + np_live - win_lo


def _paged_cache_partials(q, k_pool, v_pool, table, limits,
                          softcap: float = 0.0, window: int = 0, sliding=None,
                          q_pos=None, kv_scale=None, sink: int = 0,
                          swin: int = 0, ring: int = 0):
    """Online-softmax partials over a paged cache — the static-shape TPU
    answer to ragged/paged KV (SURVEY §7; reference: llama.cpp's per-slot
    contiguous cache, vLLM's PagedAttention): HBM holds one shared page pool
    [P, page, K, D] and each slot attends only the pages its table lists.
    A fori_loop walks the table PAGE_CHUNK columns at a time, gathering a
    [B, CH·page, K, D] tile per step — the dense [B, S] view never
    materializes, and the trip count is bounded by the LONGEST live context
    in the batch (ceil(max(limits)/page/CH)), so per-step bandwidth scales
    with what is actually resident, not max_seq.

    q: [B, H, D]; k/v_pool: [P, page, K, D]; table: [B, MP] int32 page ids,
    or the hierarchical (l1, l0) pair (ops/ptable — a 1M-token slot's table
    resolves through an L1 directory instead of one giant row);
    limits: [B] — rows with global index >= limits[b] are masked.
    softcap/window/sliding: gemma-2 semantics (softcap BEFORE masking;
    sliding layers mask rows further than `window` below `q_pos` [B]).
    sink/swin: engine-level windowed+sink decode (docs/LONG_CONTEXT.md) —
    a row is attended iff `gpos < sink` or `q_pos - gpos < swin`; the walk
    additionally SKIPS page columns that are entirely masked (cold middle
    pages — possibly spilled off-device), per slot.
    kv_scale: optional [2, K] f32 per-head (k, v) dequant scales for a
    scaled fp8 pool (ISSUE 9) — applied to the gathered tile right at the
    convert, so XLA fuses cast+scale into the einsum's operand load and the
    dequantized copy never round-trips HBM (mirrors the in-register dequant
    the Pallas kernel does on its VMEM tile).
    ring: the table's pages are a per-slot ring of this many rows (a window
    layer's, engine/state.py): position p lives at row p mod ring, `limits`
    counts the positions written so far, and a row is masked at the position
    it holds (paged_flash._ragged_paged_kernel, ring_rows).
    Returns (acc [B, K, G, D], m [B, K, G, 1], l [B, K, G, 1]) f32, scale
    applied.
    """
    from localai_tpu.ops import ptable as _pt

    B, H, D = q.shape
    page = k_pool.shape[1]
    K = k_pool.shape[2]
    G = H // K
    MP = _pt.width(table)
    scale = 1.0 / (D**0.5)
    qf = (q.astype(jnp.float32) * scale).reshape(B, K, G, D)
    if q_pos is None:
        q_pos = limits

    # Pages walk in chunks of PAGE_CHUNK columns per loop step. One page per
    # step is latency-bound at long context — each iteration is a tiny
    # gather + einsum serialized through the running softmax state, and a
    # 32k context is 256 sequential iterations PER LAYER (measured ~2 tok/s
    # at 32k bs1). Chunking turns that into 32 steps of MXU-sized work.
    CH = min(8, MP)
    if swin:
        sink_cols, win_lo, n_cols = _sink_window_cols(
            limits, q_pos, page, MP, sink, swin
        )

    def body(p, carry):
        m, l, acc = carry
        j = p * CH + jnp.arange(CH)  # [CH] walk columns this step
        if swin:
            # Cold-middle skip: remap walk column → table column per slot.
            cols = jnp.where(j[None, :] < sink_cols[:, None], j[None, :],
                             j[None, :] + (win_lo - sink_cols)[:, None])
            col_ok = j[None, :] < n_cols[:, None]  # [B, CH]
        else:
            cols = jnp.broadcast_to(j[None, :], (B, CH))
            col_ok = jnp.broadcast_to((j < MP)[None, :], (B, CH))
        pids = _pt.gather_cols(table, jnp.minimum(cols, MP - 1))  # [B, CH]
        kp = k_pool[pids].astype(jnp.float32)  # [B, CH, page, K, D]
        vp = v_pool[pids].astype(jnp.float32)
        if kv_scale is not None:  # in-register fp8 dequant (fused into cast)
            kp = kp * kv_scale[0][None, None, None, :, None]
            vp = vp * kv_scale[1][None, None, None, :, None]
        kp = kp.reshape(B, CH * page, K, D)
        vp = vp.reshape(B, CH * page, K, D)
        sc = jnp.einsum("bkgd,bskd->bkgs", qf, kp)
        if softcap:
            sc = softcap_scores(sc, softcap)
        # global rows covered by this chunk (clamped duplicate columns are
        # masked out via col_ok, never double-counted)
        gpos = (cols[:, :, None] * page
                + jnp.arange(page)[None, None, :]).reshape(B, -1)
        valid = (gpos < limits[:, None]) & jnp.repeat(col_ok, page, axis=1)
        if ring:  # a ring's row -> the position it holds
            gpos = gpos + (limits[:, None] - 1 - gpos) // ring * ring
        if window and sliding is not None:
            dist = q_pos[:, None] - gpos
            valid = valid & (~sliding | (dist < window))
        if swin:
            valid = valid & ((gpos < sink) | ((q_pos[:, None] - gpos) < swin))
        sc = jnp.where(valid[:, None, None, :], sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(jnp.maximum(m - m_new, -80.0))
        pr = jnp.exp(sc - m_new)
        pr = jnp.where(valid[:, None, None, :], pr, 0.0)
        l = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgs,bskd->bkgd", pr, vp)
        return m_new, l, acc

    m0 = jnp.full((B, K, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, 1), jnp.float32)
    a0 = jnp.zeros((B, K, G, D), jnp.float32)
    if swin:
        p_hi = jnp.max(n_cols).astype(jnp.int32)
    else:
        p_hi = jnp.minimum(
            (jnp.max(limits) + page - 1) // page, MP
        ).astype(jnp.int32)
    ch_hi = (p_hi + CH - 1) // CH
    m, l, acc = jax.lax.fori_loop(0, ch_hi, body, (m0, l0, a0))
    return acc, m, l


def _paged_pallas_sharded(kernel_fn, mesh, q, k_pool, v_pool, table, limits,
                          q_pos, sliding, mq: bool, kv_scale=None):
    """Run a Pallas paged-partials kernel head-sharded over the mesh's "tp"
    axis (ISSUE 7): q splits on its head axis, the pool on its kv-head axis
    (the layout the engine stores it in — pages live on the head shard that
    owns them), the page table/limits replicate (they are host-built i32
    control state, KBs), and the partials come back head-sharded for the
    (GSPMD-handled) o-projection psum. The kernel body is unchanged — it
    just sees K/tp kv heads. `sliding` is a traced per-layer scalar, so it
    rides as an explicit replicated operand (closure capture of tracers is
    not valid under shard_map). So does the layer index: the pool crosses
    the boundary stacked over layers (a plain pool as a [1, ...] view at
    layer 0), its layer axis whole on every shard, and the shard body
    re-wraps its local stack."""
    from jax.sharding import PartitionSpec as P

    from localai_tpu.ops import ptable as _pt
    from localai_tpu.ops.stacked import StackedLayer, stacks_of

    K = k_pool.shape[2]
    k_pool, v_pool, layer = stacks_of(k_pool, v_pool, "layer_kv_pool")
    sl_in = sliding if sliding is not None else jnp.zeros((), bool)
    # kv scales ride sharded on their head axis like the pool itself; ones
    # when the pool is unscaled (the kernel's multiply is exact identity).
    kvs = (jnp.ones((2, K), jnp.float32) if kv_scale is None
           else kv_scale.astype(jnp.float32))

    def local(qs, kp, vp, tbl, lim, qp, sl, sc, li):
        return kernel_fn(qs, StackedLayer(kp, li), StackedLayer(vp, li),
                         tbl, lim, q_pos=qp,
                         sliding=sl if sliding is not None else None,
                         kv_scale=sc)

    q_spec = P(None, None, "tp", None) if mq else P(None, "tp", None)
    qp_spec = P(None, None) if mq else P(None)
    # Flat tables are one replicated [B, MP] operand; the hierarchical pair
    # replicates both levels (host-built i32 control state, KBs).
    tbl_spec = _pt.shard_spec(table, P(None, None), P(None, None))
    out_specs = tuple(
        P(None, "tp", *([None] * (3 if mq else 2))) for _ in range(3)
    )
    pool_spec = P(None, None, None, "tp", None)  # [L, P, page, K, D]
    fn = _head_shard_map(
        local, mesh,
        in_specs=(q_spec, pool_spec, pool_spec,
                  tbl_spec, P(None), qp_spec, P(), P(None, "tp"), P()),
        out_specs=out_specs,
    )
    return fn(q, k_pool, v_pool, table, limits, q_pos, sl_in, kvs,
              jnp.asarray(layer, jnp.int32))


def write_window(pool, win, pid, off, impl: str = "auto", mesh=None):
    """A decode block's window into ONE page pool (K or V): row (b, r) of
    `win` [L, B, n, K, D], already in the pool's dtype, lands at
    `pool[:, pid[b, r], off[b, r]]` (`llama.write_block_to_pool` resolves
    the table). The rule is on what this sees and nowhere else (ISSUE 44):

    - the engine's paged reader is the Pallas kernel (`impl`, resolved as
      `paged_partials` resolves it) AND a token's row of one chip's part of
      the pool is narrower than the native tile and whole sublane words
      (`pool_write.in_place_rows`: 2 to 7 rows of D at 16 bits): the
      `pool_write` kernel copies the window's
      rows into the donated pool by DMA, in place; under a tp mesh inside
      `shard_map` with the pool spec `_paged_pallas_sharded` gives the
      reader, indices replicated. XLA stores such a pool tiled `T(K,128)`
      and ran the scatter in another layout, a copy of the whole pool each
      way, K and V, every block;
    - the Pallas reader AND a latent pool's one 16-bit row a token
      (`pool_write.staged_rows`, ISSUE 49): half a sublane word, which no
      DMA slices, so `latent_pool_write` reads the one or two 16-row tile
      groups a slot's rows lie in into VMEM, replaces the rows and writes
      the groups back, in place. Its ops carry `scopes.LATENT_WRITE`. The
      scatter it replaces relaid the pool like the narrow K/V pools';
    - otherwise XLA's scatter, which at 8 rows and more runs in the layout
      the pool is stored in.

    Counted per traced program (`stacked.note_pool_write`): the two kernels
    are `pool_write_inplace`, the scatter `pool_write_scatter`."""
    import functools

    from jax.sharding import PartitionSpec as P

    from localai_tpu.observe.scopes import LATENT_WRITE
    from localai_tpu.ops.paged_flash import use_pallas
    from localai_tpu.ops.pool_write import (
        in_place_rows, latent_pool_write, pool_write, staged_rows)
    from localai_tpu.ops.stacked import note_pool_write

    tp = _tp_degree(mesh)
    local = (*pool.shape[:3], pool.shape[3] // tp, pool.shape[4])
    pallas = use_pallas(impl)
    staged = pallas and tp == 1 and staged_rows(local, pool.dtype, win.shape[2])
    inplace = pallas and in_place_rows(local, pool.dtype)
    note_pool_write(inplace or staged)
    interpret = jax.default_backend() != "tpu"
    if staged:
        with jax.named_scope(LATENT_WRITE):
            return latent_pool_write(pool, win, pid, off, interpret=interpret)
    if not inplace:
        return pool.at[:, pid, off].set(win)
    kernel = functools.partial(pool_write, interpret=interpret)
    if tp > 1:
        pool_spec = P(None, None, None, "tp", None)  # [L, P, page, K, D]
        kernel = _head_shard_map(
            kernel, mesh,
            in_specs=(pool_spec, pool_spec, P(None, None), P(None, None)),
            out_specs=pool_spec)
    return kernel(pool, win, pid, off)


def _paged_pools(k_pool, v_pool, pallas: bool):
    """The pools as a paged dispatcher hands them on, the choice counted as
    one paged-attention call site (stacked.SiteCounts). The Pallas kernel
    takes a pool still stacked over layers (stacked.StackedLayer) as it is
    and reads its layer's pages in place; the XLA walk gets the layer
    sliced out here, where the slice fuses into the walk's page gather."""
    from localai_tpu.ops.stacked import layer_slice, note_site, shared_layer

    note_site(pallas and shared_layer(k_pool, v_pool) is not None,
              kernel="paged_attention")
    if pallas:
        return k_pool, v_pool
    return (layer_slice(k_pool, "layer_kv_pool"),
            layer_slice(v_pool, "layer_kv_pool"))


def paged_partials(q, k_pool, v_pool, table, limits, softcap: float = 0.0,
                   window: int = 0, sliding=None, q_pos=None,
                   impl: str = "auto", mesh=None, kv_scale=None,
                   sink: int = 0, swin: int = 0, latent: bool = False,
                   values: int = 0, ring: int = 0):
    """Paged online-softmax partials, dispatched: the fused Pallas ragged
    paged-attention kernel (ops/paged_flash — pages stream HBM→VMEM once,
    walk bounded per slot) or the XLA gather walk below (reference path and
    numeric oracle). Off-TPU the kernel runs in interpret mode, so CPU tier-1
    tests exercise the same kernel code that compiles for TPU. With a tp>1
    mesh the Pallas kernel runs head-sharded under shard_map (the XLA walk
    needs nothing — its gathers/einsums partition over the kv-head axis by
    GSPMD propagation, no collectives). sink/swin: windowed+sink mask +
    cold-page skip (ISSUE 14), identical semantics in both backends.
    k_pool/v_pool: one layer's [P, page, K, D] pool, or a StackedLayer of the
    whole [L, P, page, K, D] pool (`_paged_pools`). `latent`: the caller's
    pool holds MLA's latent rows laid out for the latent kernel (llama's
    decode step says so from `cfg.latent_pad`, and with `values` how many
    leading lanes of a row it reads as values: the kernel's acc then holds
    those lanes alone, in whole lane tiles, `paged_flash.value_lanes`); the
    XLA walk reads such a pool as any other, whole rows. A pool whose rows
    are wider than q's heads holds several heads a row
    (`ArchConfig.cache_pack`: [P, page, K/p, p·D]): the kernel walks it as
    stored (`paged_decode_partials`), the XLA walk a reshape of it. `ring`:
    the pool is a window layer's per-slot rings of that many rows and
    `limits` the positions written so far (`_paged_cache_partials`), tp = 1."""
    import functools

    from localai_tpu.ops.paged_flash import paged_decode_partials, use_pallas

    pallas = use_pallas(impl)
    k_pool, v_pool = _paged_pools(k_pool, v_pool, pallas)
    packed = not latent and k_pool.shape[-1] != q.shape[-1]
    if packed and _tp_degree(mesh) > 1:
        raise NotImplementedError(
            "a pool of several heads a row (ArchConfig.cache_pack) is read "
            "at tp = 1")
    if packed and not pallas:  # the XLA walk reads a head a row
        k_pool, v_pool = (a.reshape(*a.shape[:2], -1, q.shape[-1])
                          for a in (k_pool, v_pool))
    if pallas:
        interp = jax.default_backend() != "tpu"
        if latent and _tp_degree(mesh) > 1:
            raise NotImplementedError("a latent pool has one head: tp = 1")
        if ring and _tp_degree(mesh) > 1:
            raise NotImplementedError("a window layer's ring is read at tp = 1")
        if _tp_degree(mesh) > 1:
            return _paged_pallas_sharded(
                functools.partial(paged_decode_partials, softcap=softcap,
                                  window=window, interpret=interp,
                                  sink=sink, swin=swin),
                mesh, q, k_pool, v_pool, table, limits,
                limits if q_pos is None else q_pos, sliding, mq=False,
                kv_scale=kv_scale,
            )
        return paged_decode_partials(
            q, k_pool, v_pool, table, limits, softcap=softcap, window=window,
            sliding=sliding, q_pos=q_pos, interpret=interp, kv_scale=kv_scale,
            sink=sink, swin=swin, latent=latent, values=values, ring=ring,
        )
    return _paged_cache_partials(
        q, k_pool, v_pool, table, limits,
        softcap=softcap, window=window, sliding=sliding, q_pos=q_pos,
        kv_scale=kv_scale, sink=sink, swin=swin, ring=ring,
    )


def paged_partials_mq(q, k_pool, v_pool, table, limits, softcap: float = 0.0,
                      window: int = 0, sliding=None, q_pos=None,
                      impl: str = "auto", mesh=None, kv_scale=None,
                      sink: int = 0, swin: int = 0):
    """Multi-query `paged_partials` (speculative verify chunk) — same
    dispatch."""
    import functools

    from localai_tpu.ops.paged_flash import (
        paged_decode_partials_mq,
        use_pallas,
    )

    pallas = use_pallas(impl)
    k_pool, v_pool = _paged_pools(k_pool, v_pool, pallas)
    if pallas:
        interp = jax.default_backend() != "tpu"
        if _tp_degree(mesh) > 1:
            T = q.shape[1]
            qp = (jnp.broadcast_to(limits[:, None], (q.shape[0], T))
                  if q_pos is None else q_pos)
            return _paged_pallas_sharded(
                functools.partial(paged_decode_partials_mq, softcap=softcap,
                                  window=window, interpret=interp,
                                  sink=sink, swin=swin),
                mesh, q, k_pool, v_pool, table, limits, qp, sliding, mq=True,
                kv_scale=kv_scale,
            )
        return paged_decode_partials_mq(
            q, k_pool, v_pool, table, limits, softcap=softcap, window=window,
            sliding=sliding, q_pos=q_pos, interpret=interp, kv_scale=kv_scale,
            sink=sink, swin=swin,
        )
    return _paged_cache_partials_mq(
        q, k_pool, v_pool, table, limits,
        softcap=softcap, window=window, sliding=sliding, q_pos=q_pos,
        kv_scale=kv_scale, sink=sink, swin=swin,
    )


def paged_prefill_partials(q, k_pool, v_pool, table, limits,
                           softcap: float = 0.0, window: int = 0,
                           sliding=None, q_pos=None, impl: str = "auto",
                           mesh=None, kv_scale=None, sink: int = 0,
                           swin: int = 0):
    """Paged partials for a PREFILL CHUNK (models/llama.prefill_chunk_paged):
    q [B, T, H, D] covers a whole chunk, limits[b] is the rows already
    resident (the chunk's start offset). Same dispatch as paged_partials_mq,
    but the Pallas side tiles the chunk's query rows so any chunk size fits
    the kernel's VMEM running state (ops/paged_flash.paged_prefill_partials_mq).
    With a tp>1 mesh the tiled kernel runs head-sharded under shard_map.
    sink/swin bound the prefix walk to the sink pages + trailing window —
    what makes a 512k-token chunked prefill linear instead of quadratic."""
    import functools

    from localai_tpu.ops.paged_flash import (
        paged_prefill_partials_mq,
        use_pallas,
    )

    pallas = use_pallas(impl)
    k_pool, v_pool = _paged_pools(k_pool, v_pool, pallas)
    if pallas:
        interp = jax.default_backend() != "tpu"
        if _tp_degree(mesh) > 1:
            T = q.shape[1]
            qp = (jnp.broadcast_to(limits[:, None], (q.shape[0], T))
                  if q_pos is None else q_pos)
            return _paged_pallas_sharded(
                functools.partial(paged_prefill_partials_mq, softcap=softcap,
                                  window=window, interpret=interp,
                                  sink=sink, swin=swin),
                mesh, q, k_pool, v_pool, table, limits, qp, sliding, mq=True,
                kv_scale=kv_scale,
            )
        return paged_prefill_partials_mq(
            q, k_pool, v_pool, table, limits, softcap=softcap, window=window,
            sliding=sliding, q_pos=q_pos, interpret=interp, kv_scale=kv_scale,
            sink=sink, swin=swin,
        )
    return _paged_cache_partials_mq(
        q, k_pool, v_pool, table, limits,
        softcap=softcap, window=window, sliding=sliding, q_pos=q_pos,
        kv_scale=kv_scale, sink=sink, swin=swin,
    )


def decode_attention_windowed_paged(
    q: jnp.ndarray,  # [B, H, D]
    k_pool,  # [P, page, K, D] shared page pool, or its StackedLayer
    v_pool,
    table: jnp.ndarray,  # [B, MP] int32 page ids per slot
    k_local: jnp.ndarray,  # [B, n, K, D] block-local window
    v_local: jnp.ndarray,
    k_new: jnp.ndarray,  # [B, K, D]
    v_new: jnp.ndarray,
    positions: jnp.ndarray,  # [B]
    step: jnp.ndarray,  # scalar
    softcap: float = 0.0,
    window: int = 0,
    sliding=None,
    impl: str = "auto",
    mesh=None,  # Mesh with tp>1 → Pallas kernel head-sharded (shard_map)
    kv_scale=None,  # [2, K] f32 per-head (k, v) dequant scales (fp8 KV)
    sink: int = 0,  # windowed+sink decode (docs/LONG_CONTEXT.md): rows
    swin: int = 0,  # attended iff gpos < sink or q_pos - gpos < swin
    latent: bool = False,  # the pool is MLA's latent one (`paged_partials`)
    values: int = 0,  # ... and these leading lanes of a row are read as values
    ring: int = 0,  # the pool is per-slot rings of this many rows (a window
    # layer's): rows [0, block_start) are those still in the ring
) -> jnp.ndarray:
    """`decode_attention_windowed` over a paged pool: paged partials for
    rows [0, block_start), dense merge of the (tiny) local window + current
    token. Where the latent kernel summed the value lanes alone the merge
    runs on those lanes, and so does the result: [B, H, <value lanes>]."""
    n = k_local.shape[1]
    # a window of several heads a row (`ArchConfig.cache_pack`), a head a row
    k_local, v_local = (a.reshape(*a.shape[:2], *new.shape[1:])
                        for a, new in ((k_local, k_new), (v_local, v_new)))
    acc, m, l = paged_partials(
        q, k_pool, v_pool, table, positions - step,
        softcap=softcap, window=window, sliding=sliding, q_pos=positions,
        impl=impl, mesh=mesh, kv_scale=kv_scale, sink=sink, swin=swin,
        latent=latent, values=values, ring=ring,
    )
    # f32 concat: the block-local window may live in the cache's storage
    # dtype (fp8 KV) while the current token is model-dtype.
    ek = jnp.concatenate([k_local.astype(jnp.float32),
                          k_new[:, None].astype(jnp.float32)], axis=1)
    if latent:  # the rows are key AND value: the window's value lanes too
        ev = ek[..., :acc.shape[-1]]
    else:
        ev = jnp.concatenate([v_local.astype(jnp.float32),
                              v_new[:, None].astype(jnp.float32)], axis=1)
    mask = jnp.concatenate([jnp.arange(n) < step, jnp.ones((1,), bool)], axis=0)
    if window and sliding is not None:
        dist = jnp.concatenate([step - jnp.arange(n), jnp.zeros((1,), jnp.int32)])
        mask = mask & (~sliding | (dist < window))
    mask = jnp.broadcast_to(mask[None, :], (q.shape[0], n + 1))
    if swin:
        # Exact mask on the local rows too: row i sits at global position
        # block_start + i = positions - step + i, distance step - i.
        dist = jnp.concatenate(
            [step - jnp.arange(n), jnp.zeros((1,), jnp.int32)]
        )[None, :]
        gpos = positions[:, None] - dist
        mask = mask & ((gpos < sink) | (dist < swin))
    return _merge_partials(q, acc, m, l, ek, ev, mask, softcap=softcap)


def _paged_cache_partials_mq(q, k_pool, v_pool, table, limits,
                             softcap: float = 0.0, window: int = 0,
                             sliding=None, q_pos=None, kv_scale=None,
                             sink: int = 0, swin: int = 0):
    """Multi-query `_paged_cache_partials` for the speculative verify chunk
    and the chunked-prefill prefix walk: q [B, T, H, D], one page walk
    shared by all T queries. limits [B] bounds the cache prefix every query
    may see (the chunk's in-window causal part is merged separately).
    table is flat [B, MP] or the hierarchical (l1, l0) pair; sink/swin add
    the windowed+sink mask AND the per-slot cold-page skip (see
    _paged_cache_partials — the skip is bounded by the SMALLEST query
    position in the chunk, so every query's window stays covered). Returns
    (acc [B, K, G, T, D], m [B, K, G, T, 1], l [B, K, G, T, 1])."""
    from localai_tpu.ops import ptable as _pt

    B, T, H, D = q.shape
    page = k_pool.shape[1]
    K = k_pool.shape[2]
    G = H // K
    MP = _pt.width(table)
    scale = 1.0 / (D**0.5)
    qf = (q.astype(jnp.float32) * scale).reshape(B, T, K, G, D)
    if swin:
        sink_cols, win_lo, n_cols = _sink_window_cols(
            limits, jnp.min(q_pos, axis=1), page, MP, sink, swin
        )

    def body(p, carry):
        m, l, acc = carry
        if swin:
            col = jnp.where(p < sink_cols, p, p + (win_lo - sink_cols))  # [B]
            col_ok = p < n_cols  # [B]
        else:
            col = jnp.broadcast_to(p, (B,))
            col_ok = jnp.ones((B,), bool)
        pids = _pt.gather_cols(
            table, jnp.minimum(col, MP - 1)[:, None]
        )[:, 0]  # [B]
        kp = k_pool[pids].astype(jnp.float32)  # [B, page, K, D]
        vp = v_pool[pids].astype(jnp.float32)
        if kv_scale is not None:  # in-register fp8 dequant (fused into cast)
            kp = kp * kv_scale[0][None, None, :, None]
            vp = vp * kv_scale[1][None, None, :, None]
        sc = jnp.einsum("btkgd,bskd->bkgts", qf, kp)  # [B, K, G, T, page]
        if softcap:
            sc = softcap_scores(sc, softcap)
        gpos = col[:, None] * page + jnp.arange(page)[None, :]  # [B, page]
        valid = (gpos < limits[:, None]) & col_ok[:, None]  # [B, page]
        valid = valid[:, None, :]  # [B, 1, page]
        if window and sliding is not None:
            dist = q_pos[:, :, None] - gpos[:, None, :]  # [B, T, page]
            valid = valid & (~sliding | (dist < window))
        if swin:
            dist = q_pos[:, :, None] - gpos[:, None, :]  # [B, T, page]
            valid = valid & ((gpos[:, None, :] < sink) | (dist < swin))
        vmask = valid[:, None, None]  # [B, 1, 1, T|1, page]
        sc = jnp.where(vmask, sc, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(jnp.maximum(m - m_new, -80.0))
        pr = jnp.exp(sc - m_new)
        pr = jnp.where(vmask, pr, 0.0)
        l = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgts,bskd->bkgtd", pr, vp)
        return m_new, l, acc

    m0 = jnp.full((B, K, G, T, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, K, G, T, 1), jnp.float32)
    a0 = jnp.zeros((B, K, G, T, D), jnp.float32)
    if swin:
        p_hi = jnp.max(n_cols).astype(jnp.int32)
    else:
        p_hi = jnp.minimum(
            (jnp.max(limits) + page - 1) // page, MP
        ).astype(jnp.int32)
    m, l, acc = jax.lax.fori_loop(0, p_hi, body, (m0, l0, a0))
    return acc, m, l


def prefix_window_attention(
    q: jnp.ndarray,  # [B, T, H, D] the window's queries
    k_prefix: jnp.ndarray,  # [B, S, K, D] dense cached rows
    v_prefix: jnp.ndarray,
    k_win: jnp.ndarray,  # [B, T, K, D] the window's own fresh rows
    v_win: jnp.ndarray,
    prefix_mask: jnp.ndarray,  # [B, 1 | T, S] bool: cached rows a query sees
    window_mask: jnp.ndarray,  # [1 | B, T, T] bool: in-window (causal) rows
    softcap: float = 0.0,
    latent: bool = False,  # MLA absorbed form: K = 1, the v operands unread
) -> jnp.ndarray:
    """One softmax over `cached prefix ⊕ the window's own rows`, dense: the
    verify chunk (models/llama.decode_chunk) and the cached-prefix admission
    (prefill_tail) against a slot cache. Its paged twin is
    `paged_partials_mq` / `paged_prefill_partials` + `_merge_partials_mq`
    below: same masks, the prefix walked page by page. What a query may see
    is the caller's: both masks come in whole. Returns [B, T, H, D].

    `latent` is MLA's absorbed form, kept as a second form of the contraction
    (the softmax is shared): the one latent pseudo-head serves every query
    head as key AND value, so the head axis is contracted without a kv-head
    axis and the scale divides. It IS the general form at K = 1 with the
    latent as both operands (the paged twin runs it so), but folded in, the
    MLA programs change (tiny-mla on XLA:CPU: 94 more instructions in
    decode_chunk, the whole cache's f32 convert hoisted out of the layer
    loop), and ISSUE 28 changes no program."""
    B, T, H, D = q.shape
    S, K = k_prefix.shape[1], k_prefix.shape[2]
    f32 = jnp.float32
    if latent:
        qf = q.astype(f32) / D**0.5
        kp, kw = k_prefix[..., 0, :].astype(f32), k_win[..., 0, :].astype(f32)
        vp, vw = kp, kw
        score, mix, lift = "bthd,bsd->bhts", "bhts,bsd->bthd", (slice(None), None)
    else:
        qf = (q.astype(f32) * D**-0.5).reshape(B, T, K, H // K, D)
        kp, kw = k_prefix.astype(f32), k_win.astype(f32)
        vp, vw = v_prefix.astype(f32), v_win.astype(f32)
        score, mix = "btkgd,bskd->bkgts", "bkgts,bskd->btkgd"
        lift = (slice(None), None, None)
    sc = jnp.einsum(score, qf, kp)  # [B, (K, G | H), T, S]
    sw = jnp.einsum(score, qf, kw)  # the same over the window's T rows
    if softcap:
        sc, sw = softcap_scores(sc, softcap), softcap_scores(sw, softcap)
    sc = jnp.where(prefix_mask[lift], sc, NEG_INF)
    sw = jnp.where(window_mask[lift], sw, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([sc, sw], axis=-1), axis=-1)
    out = (jnp.einsum(mix, probs[..., :S], vp)
           + jnp.einsum(mix, probs[..., S:], vw))
    return out.reshape(B, T, H, D).astype(q.dtype)


def _merge_partials_mq(q, acc_g, m_g, l_g, extra_k, extra_v, extra_mask,
                       softcap: float = 0.0):
    """Multi-query `_merge_partials`: q [B, T, H, D], partials [..., T, ...],
    extra_k/v [B, E, K, D], extra_mask [B, T, E]. Returns [B, T, H, D]."""
    B, T, H, D = q.shape
    K = extra_k.shape[2]
    G = H // K
    scale = 1.0 / (D**0.5)
    qf = (q.astype(jnp.float32) * scale).reshape(B, T, K, G, D)
    se = jnp.einsum("btkgd,bekd->bkgte", qf, extra_k.astype(jnp.float32))
    if softcap:
        se = softcap_scores(se, softcap)
    emask = extra_mask[:, None, None]  # [B, 1, 1, T, E]
    se = jnp.where(emask, se, NEG_INF)
    m_e = jnp.max(se, axis=-1, keepdims=True)
    m_tot = jnp.maximum(m_g, m_e)
    p_e = jnp.exp(se - m_tot)
    p_e = jnp.where(emask, p_e, 0.0)
    w_c = jnp.exp(jnp.maximum(m_g - m_tot, -80.0))
    w_c = jnp.where(l_g > 0, w_c, 0.0)
    num = acc_g * w_c + jnp.einsum("bkgte,bekd->bkgtd", p_e, extra_v.astype(jnp.float32))
    den = l_g * w_c + jnp.sum(p_e, axis=-1, keepdims=True)
    out = num / jnp.maximum(den, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, T, H, D).astype(q.dtype)
