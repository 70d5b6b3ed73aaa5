"""Fused ragged paged-attention kernel (ops/paged_flash) vs the XLA gather
walk (ops/attention._paged_cache_partials*) — the paged decode hot path.

The Pallas kernel runs in interpret mode on CPU (same kernel code that
compiles for TPU); the XLA path is the numeric oracle. Covered: ragged
per-slot prefix lengths (including idle slots at limit 0), windowed/sliding
attention, softcap, MQ/GQA/MHA head layouts, the multi-query verify-chunk
variant, the full decode_attention_windowed_paged merge, the per-head fp8
scale, hierarchical tables and the sink+window walk. The stacked pool, the
page as stored, the visit rule and the engine-level cases have modules of
their own (tests/paged_cases.py lists them).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops.attention import (
    _paged_cache_partials,
    _paged_cache_partials_mq,
    decode_attention_windowed_paged,
)
from localai_tpu.ops.paged_flash import (
    paged_decode_partials,
    paged_decode_partials_mq,
)
from paged_cases import PAGE, _assert_partials_close, _hier_of, _pool, _table


@pytest.mark.parametrize("H,K", [(4, 4), (4, 2), (4, 1)])
def test_partials_match_xla_ragged(H, K):
    B, D, MP, P = 3, 32, 4, 16
    q = jax.random.normal(jax.random.key(0), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(1), P, PAGE, K, D)
    table = _table(B, MP, P)
    # Ragged: partial last page, page-aligned, idle slot (0 rows live).
    limits = jnp.array([37, 64, 0], jnp.int32)

    want = _paged_cache_partials(q, k_pool, v_pool, table, limits)
    got = paged_decode_partials(q, k_pool, v_pool, table, limits,
                                interpret=True)
    _assert_partials_close(got, want)


def test_partials_match_xla_windowed_sliding():
    B, H, K, D, MP, P = 2, 4, 2, 32, 4, 12
    q = jax.random.normal(jax.random.key(2), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(3), P, PAGE, K, D)
    table = _table(B, MP, P, seed=1)
    limits = jnp.array([50, 23], jnp.int32)
    q_pos = jnp.array([52, 23], jnp.int32)

    for sliding in (jnp.asarray(True), jnp.asarray(False)):
        want = _paged_cache_partials(
            q, k_pool, v_pool, table, limits,
            softcap=30.0, window=20, sliding=sliding, q_pos=q_pos,
        )
        got = paged_decode_partials(
            q, k_pool, v_pool, table, limits,
            softcap=30.0, window=20, sliding=sliding, q_pos=q_pos,
            interpret=True,
        )
        _assert_partials_close(got, want)


def test_partials_sliding_traced_under_jit():
    """The sliding flag is a traced per-layer scalar inside scanned layer
    stacks — the kernel must accept it as an operand, not a static."""
    B, H, K, D, MP, P = 2, 4, 2, 32, 3, 8
    q = jax.random.normal(jax.random.key(4), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(5), P, PAGE, K, D)
    table = _table(B, MP, P, seed=2)
    limits = jnp.array([40, 17], jnp.int32)

    @jax.jit
    def run(sl):
        return paged_decode_partials(
            q, k_pool, v_pool, table, limits,
            window=12, sliding=sl, interpret=True,
        )

    for flag in (True, False):
        want = _paged_cache_partials(
            q, k_pool, v_pool, table, limits,
            window=12, sliding=jnp.asarray(flag),
        )
        _assert_partials_close(run(jnp.asarray(flag)), want)


@pytest.mark.parametrize("H,K", [(4, 2), (2, 2)])
def test_partials_mq_match_xla(H, K):
    B, T, D, MP, P = 2, 3, 32, 4, 12
    q = jax.random.normal(jax.random.key(6), (B, T, H, D))
    k_pool, v_pool = _pool(jax.random.key(7), P, PAGE, K, D)
    table = _table(B, MP, P, seed=3)
    limits = jnp.array([33, 48], jnp.int32)
    q_pos = limits[:, None] + jnp.arange(T)[None, :]

    want = _paged_cache_partials_mq(
        q, k_pool, v_pool, table, limits, q_pos=q_pos,
    )
    got = paged_decode_partials_mq(
        q, k_pool, v_pool, table, limits, q_pos=q_pos, interpret=True,
    )
    _assert_partials_close(got, want)


def test_partials_mq_windowed_match_xla():
    B, T, H, K, D, MP, P = 2, 2, 4, 2, 32, 4, 10
    q = jax.random.normal(jax.random.key(8), (B, T, H, D))
    k_pool, v_pool = _pool(jax.random.key(9), P, PAGE, K, D)
    table = _table(B, MP, P, seed=4)
    limits = jnp.array([44, 9], jnp.int32)
    q_pos = limits[:, None] + jnp.arange(T)[None, :]

    want = _paged_cache_partials_mq(
        q, k_pool, v_pool, table, limits,
        window=16, sliding=jnp.asarray(True), q_pos=q_pos,
    )
    got = paged_decode_partials_mq(
        q, k_pool, v_pool, table, limits,
        window=16, sliding=jnp.asarray(True), q_pos=q_pos, interpret=True,
    )
    _assert_partials_close(got, want)


def test_decode_attention_windowed_paged_end_to_end():
    """Full paged decode attention (partials + local-window/current-token
    merge): pallas impl == xla impl, bf16 inputs."""
    B, H, K, D, MP, P, n = 2, 4, 2, 32, 4, 10, 4
    ks = jax.random.split(jax.random.key(10), 6)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    k_pool = jax.random.normal(ks[1], (P, PAGE, K, D), jnp.bfloat16)
    v_pool = jax.random.normal(ks[2], (P, PAGE, K, D), jnp.bfloat16)
    k_local = jax.random.normal(ks[3], (B, n, K, D), jnp.bfloat16)
    v_local = jax.random.normal(ks[4], (B, n, K, D), jnp.bfloat16)
    k_new = jax.random.normal(ks[5], (B, K, D), jnp.bfloat16)
    v_new = k_new * 0.5
    table = _table(B, MP, P, seed=5)
    step = jnp.int32(2)
    positions = jnp.array([39, 18], jnp.int32)  # block_start = positions-step

    kw = dict(softcap=0.0, window=0, sliding=None)
    ref = decode_attention_windowed_paged(
        q, k_pool, v_pool, table, k_local, v_local, k_new, v_new,
        positions, step, impl="xla", **kw,
    )
    out = decode_attention_windowed_paged(
        q, k_pool, v_pool, table, k_local, v_local, k_new, v_new,
        positions, step, impl="pallas", **kw,
    )
    diff = np.abs(np.asarray(out, np.float32) - np.asarray(ref, np.float32))
    assert diff.max() < 2e-2, diff.max()  # bf16 inputs


def test_partials_fp8_pool():
    """fp8 KV storage reads through the kernel's astype(f32) exactly like
    the XLA gather path."""
    B, H, K, D, MP, P = 2, 4, 2, 32, 3, 8
    q = jax.random.normal(jax.random.key(11), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(12), P, PAGE, K, D)
    k8 = k_pool.astype(jnp.float8_e4m3fn)
    v8 = v_pool.astype(jnp.float8_e4m3fn)
    table = _table(B, MP, P, seed=6)
    limits = jnp.array([41, 26], jnp.int32)

    want = _paged_cache_partials(q, k8, v8, table, limits)
    got = paged_decode_partials(q, k8, v8, table, limits, interpret=True)
    _assert_partials_close(got, want, tol=1e-3)


def test_paged_prefill_partials_tiling_exact():
    """The prefill wrapper's query-row tiling (VMEM bound) must be exact:
    tiled partials == one-shot kernel partials for a chunk larger than the
    tile."""
    from localai_tpu.ops.paged_flash import (
        paged_decode_partials_mq,
        paged_prefill_partials_mq,
    )

    B, T, H, K, D, MP, P = 1, 12, 4, 2, 32, 4, 10
    q = jax.random.normal(jax.random.key(20), (B, T, H, D))
    k_pool, v_pool = _pool(jax.random.key(21), P, PAGE, K, D)
    table = _table(B, MP, P, seed=8)
    limits = jnp.array([40], jnp.int32)
    q_pos = limits[:, None] + jnp.arange(T)[None, :]

    want = paged_decode_partials_mq(
        q, k_pool, v_pool, table, limits, q_pos=q_pos, interpret=True,
    )
    got = paged_prefill_partials_mq(
        q, k_pool, v_pool, table, limits, q_pos=q_pos, interpret=True,
        max_qrows=8,  # forces 3 tiles of 4 tokens (G=2 rows per token)
    )
    _assert_partials_close(got, want)


# --------------------------------------------------------------------------- #
# fp8 KV per-head dequant scale (ISSUE 9): pool rows store value/scale,
# BOTH paged paths multiply back in-kernel — XLA walk vs Pallas kernel.
# --------------------------------------------------------------------------- #


def test_partials_fp8_kv_scale_parity():
    """Per-head (k, v) scales: the Pallas kernel's in-register dequant must
    match the XLA walk's fused cast+scale on a SCALED fp8 pool."""
    B, H, K, D, MP, P = 2, 4, 2, 32, 3, 8
    q = jax.random.normal(jax.random.key(30), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(31), P, PAGE, K, D)
    kv_scale = jnp.asarray([[2.0, 0.5], [1.5, 3.0]], jnp.float32)  # [2, K]
    # Store value/scale like the engine's write path does.
    k8 = (k_pool / kv_scale[0][None, None, :, None]).astype(jnp.float8_e4m3fn)
    v8 = (v_pool / kv_scale[1][None, None, :, None]).astype(jnp.float8_e4m3fn)
    table = _table(B, MP, P, seed=9)
    limits = jnp.array([41, 26], jnp.int32)

    want = _paged_cache_partials(q, k8, v8, table, limits, kv_scale=kv_scale)
    got = paged_decode_partials(q, k8, v8, table, limits, kv_scale=kv_scale,
                                interpret=True)
    _assert_partials_close(got, want, tol=1e-3)
    # And the mq (verify-chunk) variant.
    T = 2
    qm = jax.random.normal(jax.random.key(32), (B, T, H, D))
    q_pos = limits[:, None] + jnp.arange(T)[None, :]
    want = _paged_cache_partials_mq(qm, k8, v8, table, limits, q_pos=q_pos,
                                    kv_scale=kv_scale)
    got = paged_decode_partials_mq(qm, k8, v8, table, limits, q_pos=q_pos,
                                   kv_scale=kv_scale, interpret=True)
    _assert_partials_close(got, want, tol=1e-3)


def test_kv_scale_recovers_clipped_fp8_range():
    """The point of the scale: values past e4m3's ±448 clip without it and
    survive with it."""
    B, H, K, D, MP, P = 1, 2, 1, 32, 2, 4
    q = jax.random.normal(jax.random.key(33), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(34), P, PAGE, K, D)
    v_pool = v_pool * 600.0  # past the e4m3 max
    table = _table(B, MP, P, seed=10)
    limits = jnp.array([24], jnp.int32)
    want = _paged_cache_partials(q, k_pool, v_pool, table, limits)  # f32 truth

    scale = jnp.asarray([[1.0], [16.0]], jnp.float32)
    v8_scaled = (v_pool / scale[1][None, None, :, None]).astype(jnp.float8_e4m3fn)
    v8_clip = v_pool.astype(jnp.float8_e4m3fn)
    k8 = k_pool.astype(jnp.float8_e4m3fn)
    acc_s, _, _ = paged_decode_partials(q, k8, v8_scaled, table, limits,
                                        kv_scale=scale, interpret=True)
    acc_c, _, _ = paged_decode_partials(q, k8, v8_clip, table, limits,
                                        interpret=True)
    ref = float(jnp.abs(want[0]).max())
    err_scaled = float(jnp.abs(acc_s - want[0]).max())
    err_clip = float(jnp.abs(acc_c - want[0]).max())
    assert err_scaled < 0.15 * ref, (err_scaled, ref)
    # Unscaled storage either saturates to e4m3's NaN or clips hard.
    assert np.isnan(err_clip) or err_clip > 2 * err_scaled, (err_clip, err_scaled)


def test_windowed_paged_kv_scale_end_to_end():
    """decode_attention_windowed_paged with a scaled fp8 pool: pallas impl
    == xla impl (the local window / current token stay model-dtype and are
    merged outside the scale)."""
    B, H, K, D, MP, P, n = 2, 4, 2, 32, 4, 10, 4
    ks = jax.random.split(jax.random.key(35), 6)
    q = jax.random.normal(ks[0], (B, H, D), jnp.bfloat16)
    kv_scale = jnp.asarray([[2.0, 0.5], [1.5, 3.0]], jnp.float32)
    k_f = jax.random.normal(ks[1], (P, PAGE, K, D))
    v_f = jax.random.normal(ks[2], (P, PAGE, K, D))
    k_pool = (k_f / kv_scale[0][None, None, :, None]).astype(jnp.float8_e4m3fn)
    v_pool = (v_f / kv_scale[1][None, None, :, None]).astype(jnp.float8_e4m3fn)
    k_local = jax.random.normal(ks[3], (B, n, K, D), jnp.bfloat16)
    v_local = jax.random.normal(ks[4], (B, n, K, D), jnp.bfloat16)
    k_new = jax.random.normal(ks[5], (B, K, D), jnp.bfloat16)
    v_new = k_new * 0.5
    table = _table(B, MP, P, seed=11)
    step = jnp.int32(2)
    positions = jnp.array([39, 18], jnp.int32)

    outs = {}
    for impl in ("xla", "pallas"):
        outs[impl] = decode_attention_windowed_paged(
            q, k_pool, v_pool, table, k_local, v_local, k_new, v_new,
            positions, step, impl=impl, kv_scale=kv_scale,
        )
    diff = np.abs(np.asarray(outs["pallas"], np.float32)
                  - np.asarray(outs["xla"], np.float32))
    assert diff.max() < 2e-2, diff.max()


# ---------------------------------------------------------------------- #
# ISSUE 14 (docs/LONG_CONTEXT.md): hierarchical page tables + windowed+
# sink walk — kernel (interpret mode) vs XLA oracle, and hier vs flat.
# ---------------------------------------------------------------------- #


@pytest.mark.parametrize("span", [1, 2, 4])
def test_hier_table_matches_flat_kernel_and_xla(span):
    """The two-level table resolves to the same pages as the flat row — in
    the Pallas kernel's in-kernel L1 walk AND the XLA gather walk."""
    B, H, K, D, MP, P = 3, 4, 2, 32, 4, 16
    q = jax.random.normal(jax.random.key(10), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(11), P, PAGE, K, D)
    table = _table(B, MP, P, seed=3)
    hier = _hier_of(table, span)
    limits = jnp.array([37, 64, 0], jnp.int32)

    want = _paged_cache_partials(q, k_pool, v_pool, table, limits)
    got_x = _paged_cache_partials(q, k_pool, v_pool, hier, limits)
    _assert_partials_close(got_x, want)
    got_k = paged_decode_partials(q, k_pool, v_pool, hier, limits,
                                  interpret=True)
    _assert_partials_close(got_k, want)


def test_sink_window_walk_matches_xla_and_masks_exactly():
    """Windowed+sink decode (sink/swin): the kernel's two-segment skip walk
    equals the XLA per-slot remapped walk, and both equal a brute-force
    mask over the full walk — the skip is exact, not approximate."""
    B, H, K, D, MP, P = 2, 4, 2, 32, 8, 20
    page = PAGE
    q = jax.random.normal(jax.random.key(12), (B, H, D))
    k_pool, v_pool = _pool(jax.random.key(13), P, page, K, D)
    table = _table(B, MP, P, seed=4)
    limits = jnp.array([8 * page, 5 * page + 3], jnp.int32)
    q_pos = limits
    sink, swin = 20, 40  # sink ends mid-page; window spans ~3 pages

    # Brute force: full walk + explicit mask via a one-off reference.
    def brute():
        import numpy as _np
        out = []
        qn = _np.asarray(q, _np.float32) * (1.0 / D**0.5)
        for b in range(B):
            rows_k, rows_v, keep = [], [], []
            for g in range(int(limits[b])):
                pid = int(_np.asarray(table)[b, g // page])
                rk = _np.asarray(k_pool, _np.float32)[pid, g % page]
                rv = _np.asarray(v_pool, _np.float32)[pid, g % page]
                rows_k.append(rk)
                rows_v.append(rv)
                keep.append(g < sink or (int(q_pos[b]) - g) < swin)
            rows_k = _np.stack(rows_k)  # [S, K, D]
            rows_v = _np.stack(rows_v)
            keep = _np.asarray(keep)
            G = H // K
            qb = qn[b].reshape(K, G, D)
            sc = _np.einsum("kgd,skd->kgs", qb, rows_k)
            sc[:, :, ~keep] = -1e30
            m = sc.max(axis=-1, keepdims=True)
            p = _np.exp(sc - m)
            p[:, :, ~keep] = 0.0
            l = p.sum(axis=-1, keepdims=True)
            acc = _np.einsum("kgs,skd->kgd", p, rows_v)
            out.append((acc, m, l))
        acc = _np.stack([o[0] for o in out])
        m = _np.stack([o[1] for o in out])
        l = _np.stack([o[2] for o in out])
        return acc, m, l

    want = brute()
    got_x = _paged_cache_partials(q, k_pool, v_pool, table, limits,
                                  q_pos=q_pos, sink=sink, swin=swin)
    _assert_partials_close(got_x, want, tol=5e-4)
    got_k = paged_decode_partials(q, k_pool, v_pool, table, limits,
                                  q_pos=q_pos, sink=sink, swin=swin,
                                  interpret=True)
    _assert_partials_close(got_k, want, tol=5e-4)
    # Hier + sink/window composed, kernel side.
    hier = _hier_of(table, 2)
    got_h = paged_decode_partials(q, k_pool, v_pool, hier, limits,
                                  q_pos=q_pos, sink=sink, swin=swin,
                                  interpret=True)
    _assert_partials_close(got_h, want, tol=5e-4)


def test_sink_window_mq_prefill_walk_matches_xla():
    """The multi-query (prefill-chunk) walk under sink/swin: kernel ==
    XLA oracle, skip bounded by the smallest query position."""
    B, T, H, K, D, MP, P = 2, 4, 4, 2, 32, 8, 20
    q = jax.random.normal(jax.random.key(14), (B, T, H, D))
    k_pool, v_pool = _pool(jax.random.key(15), P, PAGE, K, D)
    table = _table(B, MP, P, seed=5)
    limits = jnp.array([7 * PAGE, 4 * PAGE], jnp.int32)
    q_pos = limits[:, None] + jnp.arange(T)[None, :]
    sink, swin = PAGE, 3 * PAGE

    want = _paged_cache_partials_mq(q, k_pool, v_pool, table, limits,
                                    q_pos=q_pos, sink=sink, swin=swin)
    got = paged_decode_partials_mq(q, k_pool, v_pool, table, limits,
                                   q_pos=q_pos, sink=sink, swin=swin,
                                   interpret=True)
    _assert_partials_close(got, want)
    hier = _hier_of(table, 4)
    got_h = paged_decode_partials_mq(q, k_pool, v_pool, hier, limits,
                                     q_pos=q_pos, sink=sink, swin=swin,
                                     interpret=True)
    _assert_partials_close(got_h, want)
