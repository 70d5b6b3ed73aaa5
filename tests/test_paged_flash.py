"""Fused ragged paged-attention kernel (ops/paged_flash) vs the XLA gather
walk (ops/attention._paged_cache_partials*) — the paged decode hot path.

The Pallas kernel runs in interpret mode on CPU (same kernel code that
compiles for TPU); the XLA path is the numeric oracle. Covered: ragged
per-slot prefix lengths (including idle slots at limit 0), windowed/sliding
attention, softcap, MQ/GQA/MHA head layouts, the multi-query verify-chunk
variant, and the full decode_attention_windowed_paged merge.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops.attention import (
    _merge_partials,
    _paged_cache_partials,
    _paged_cache_partials_mq,
    decode_attention_windowed_paged,
)
from localai_tpu.ops.paged_flash import (
    paged_decode_partials,
    paged_decode_partials_mq,
)

PAGE = 16


def _pool(key, P, page, K, D, dtype=jnp.float32):
    kk, kv = jax.random.split(key)
    k_pool = jax.random.normal(kk, (P, page, K, D), dtype)
    v_pool = jax.random.normal(kv, (P, page, K, D), dtype)
    return k_pool, v_pool


def _table(B, MP, P, seed=0):
    rng = np.random.default_rng(seed)
    # Distinct pages per slot row (pages are exclusive in the engine).
    ids = rng.permutation(P)[: B * MP].reshape(B, MP)
    return jnp.asarray(ids, jnp.int32)


def _assert_partials_close(got, want, tol=2e-4):
    for g, w, name in zip(got, want, ("acc", "m", "l")):
        assert g.shape == w.shape, (name, g.shape, w.shape)
        diff = np.abs(np.asarray(g) - np.asarray(w))
        assert diff.max() < tol, (name, diff.max())


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


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_prefill_chunk_paged_matches_single_shot(impl):
    """Chunked direct-to-page prefill (models/llama.prefill_chunk_paged) ==
    single-shot prefill + write_prefill_to_pool: same last-position logits
    and the same KV rows land in the pool — for both the XLA walk and the
    Pallas kernel (interpret mode on CPU)."""
    import os

    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import (
        init_params,
        paged_cache_zeros,
        prefill,
        prefill_chunk_paged,
        write_prefill_to_pool,
    )

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    page, MP, P = 16, 4, 12
    plen, chunk = 50, 32
    ids = [(j * 7) % 250 + 1 for j in range(plen)]
    Sb = 64  # single-shot bucket

    # Reference: one dense-bucket prefill scattered into pages.
    toks = jnp.zeros((1, Sb), jnp.int32).at[0, :plen].set(jnp.asarray(ids))
    ref_logits, ref_ks, ref_vs = prefill(
        cfg, params, toks, jnp.asarray([plen], jnp.int32)
    )
    table = _table(1, MP, P, seed=7)
    pool_ref = paged_cache_zeros(cfg, P, page)
    pool_ref = write_prefill_to_pool(pool_ref, table[0], ref_ks, ref_vs, 0)

    # Chunked: two ragged chunks (32 + 18) written directly to pages.
    os.environ.pop("LOCALAI_PAGED_KERNEL", None)
    pool = paged_cache_zeros(cfg, P, page)
    logits = None
    for lo in range(0, plen, chunk):
        seg = ids[lo: lo + chunk]
        tb = chunk if len(seg) == chunk else 32  # bucket the ragged tail
        ctoks = jnp.zeros((1, tb), jnp.int32).at[0, : len(seg)].set(
            jnp.asarray(seg)
        )
        logits, pool = prefill_chunk_paged(
            cfg, params, ctoks, jnp.asarray([len(seg)], jnp.int32),
            jnp.asarray([lo], jnp.int32), pool, table, paged_impl=impl,
        )

    assert jnp.allclose(logits, ref_logits, atol=5e-2), float(
        jnp.abs(logits - ref_logits).max()
    )
    # Only rows the prompt actually wrote are comparable (padding rows
    # differ by construction): gather the live rows through the table.
    live = np.arange(plen)
    pids = np.asarray(table[0])[live // page]
    got_k = np.asarray(pool.k[:, pids, live % page], np.float32)
    want_k = np.asarray(pool_ref.k[:, pids, live % page], np.float32)
    got_v = np.asarray(pool.v[:, pids, live % page], np.float32)
    want_v = np.asarray(pool_ref.v[:, pids, live % page], np.float32)
    assert np.abs(got_k - want_k).max() < 2e-2
    assert np.abs(got_v - want_v).max() < 2e-2


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


def test_engine_paged_pallas_matches_xla_greedy():
    """End-to-end: a paged engine forced onto the Pallas kernel (interpret
    mode on CPU) decodes the same greedy tokens as the XLA reference."""
    from localai_tpu.engine.engine import Engine, EngineConfig
    from localai_tpu.engine.tokenizer import ByteTokenizer
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    prompt = list(range(1, 20))
    texts = {}
    for impl in ("xla", "pallas"):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(
                max_slots=2, max_seq=256, kv_pages=6, kv_page_size=64,
                paged_kernel=impl,
            ),
        )
        try:
            text, ev = eng.generate(prompt, max_new_tokens=8, ignore_eos=True)
            assert ev.kind == "done"
            texts[impl] = text
        finally:
            eng.stop()
    assert texts["pallas"] == texts["xla"]


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


def test_engine_fp8_kv_scale_paged_pallas_matches_xla():
    """End-to-end: a paged fp8 engine with kv_scale=2.0 — write paths store
    value/scale, both attention kernels dequantize in-kernel — decodes the
    same greedy tokens under pallas and xla paged kernels."""
    from localai_tpu.engine.engine import Engine, EngineConfig
    from localai_tpu.engine.tokenizer import ByteTokenizer
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    prompt = list(range(1, 20))
    texts = {}
    for impl in ("xla", "pallas"):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(
                max_slots=2, max_seq=256, kv_pages=6, kv_page_size=64,
                paged_kernel=impl, kv_cache_dtype="fp8", kv_scale=2.0,
            ),
        )
        try:
            text, ev = eng.generate(prompt, max_new_tokens=8, ignore_eos=True)
            assert ev.kind == "done"
            texts[impl] = text
        finally:
            eng.stop()
    assert texts["pallas"] == texts["xla"]


def test_engine_kv_scale_validation():
    from localai_tpu.engine.engine import Engine, EngineConfig
    from localai_tpu.engine.tokenizer import ByteTokenizer
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    tok = ByteTokenizer(cfg.vocab_size)
    # Scale without an fp8 paged pool is a config error, not a silent no-op.
    with pytest.raises(ValueError):
        Engine(cfg, params, tok,
               engine_cfg=EngineConfig(max_slots=1, max_seq=64, kv_scale=2.0))
    with pytest.raises(ValueError):
        Engine(cfg, params, tok,
               engine_cfg=EngineConfig(max_slots=1, max_seq=64, kv_pages=4,
                                       kv_page_size=32, kv_scale=2.0))
    with pytest.raises(ValueError):
        Engine(cfg, params, tok,
               engine_cfg=EngineConfig(max_slots=1, max_seq=64,
                                       kv_cache_dtype="fp8", kv_scale=-1.0))


def test_mla_paged_decode_numerics_tiny_mla():
    """MLA paged decode on the tiny-mla (DeepSeek-V3-shaped) config: the
    latent pool walks the same paged kernels (K=1 pseudo-head) — Pallas ==
    XLA greedy tokens (the dense engine agrees too; verified out-of-band,
    left out of tier-1 for the extra compile it costs)."""
    from localai_tpu.engine.engine import Engine, EngineConfig
    from localai_tpu.engine.tokenizer import ByteTokenizer
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny-mla")
    params = init_params(cfg, jax.random.key(0))
    prompt = list(range(1, 24))
    texts = {}
    for name, ecfg in (
        ("paged-xla", EngineConfig(max_slots=2, max_seq=256, kv_pages=8,
                                   kv_page_size=32, paged_kernel="xla")),
        ("paged-pallas", EngineConfig(max_slots=2, max_seq=256, kv_pages=8,
                                      kv_page_size=32, paged_kernel="pallas")),
    ):
        eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                     engine_cfg=ecfg)
        try:
            text, ev = eng.generate(prompt, max_new_tokens=8, ignore_eos=True)
            assert ev.kind == "done"
            texts[name] = text
        finally:
            eng.stop()
    assert texts["paged-pallas"] == texts["paged-xla"]


@pytest.mark.slow
def test_spec_decode_composes_with_fp8_kv_scale():
    """Speculative decoding under a SCALED fp8 paged pool: the verify
    chunk's paged partials and pool writes thread the per-head scale —
    pallas == xla greedy tokens with a draft in the loop."""
    from localai_tpu.engine.engine import Engine, EngineConfig
    from localai_tpu.engine.tokenizer import ByteTokenizer
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    dparams = init_params(cfg, jax.random.key(1))
    prompt = list(range(1, 18))
    texts = {}
    for impl in ("xla", "pallas"):
        eng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            draft_cfg=cfg, draft_params=dparams, n_draft=3,
            engine_cfg=EngineConfig(
                max_slots=2, max_seq=256, kv_pages=6, kv_page_size=64,
                paged_kernel=impl, kv_cache_dtype="fp8", kv_scale=2.0,
            ),
        )
        try:
            text, ev = eng.generate(prompt, max_new_tokens=8, ignore_eos=True)
            assert ev.kind == "done"
            texts[impl] = text
        finally:
            eng.stop()
    assert texts["pallas"] == texts["xla"]


# ---------------------------------------------------------------------- #
# ISSUE 14 (docs/LONG_CONTEXT.md): hierarchical page tables + windowed+
# sink walk — kernel (interpret mode) vs XLA oracle, and hier vs flat.
# ---------------------------------------------------------------------- #

def _hier_of(table, span):
    """Split a flat [B, MP] table into the (l1, l0) pair: chunk c of slot b
    becomes its own table page (worst case — no sharing)."""
    B, MP = table.shape
    ml1 = -(-MP // span)
    flat = np.asarray(table)
    l0 = [np.zeros((span,), np.int32)]  # row 0 = scratch-ish, unused
    l1 = np.zeros((B, ml1), np.int32)
    for b in range(B):
        for c in range(ml1):
            row = np.zeros((span,), np.int32)
            chunk = flat[b, c * span: (c + 1) * span]
            row[: len(chunk)] = chunk
            l1[b, c] = len(l0)
            l0.append(row)
    return jnp.asarray(l1), jnp.asarray(np.stack(l0), jnp.int32)


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


# ---------------------------------------------------------------------- #
# ISSUE 27: the kernel reads its layer's pages straight out of the pool
# that is still stacked over layers (stack + layer index, ops/stacked.py)
# — bit-identical to the call on the sliced layer.
# ---------------------------------------------------------------------- #

_STACK_L = 3


def _stacked_case(variant):
    """(pools [L, P, page, K, D], table, limits, kwargs) for one variant."""
    B, K, D, MP, P = 2, 2, 32, 4, 10
    kk, kv = jax.random.split(jax.random.key(40))
    k5 = jax.random.normal(kk, (_STACK_L, P, PAGE, K, D))
    v5 = jax.random.normal(kv, (_STACK_L, P, PAGE, K, D))
    table = _table(B, MP, P, seed=11)
    limits = jnp.array([3 * PAGE + 5, 2 * PAGE], jnp.int32)
    kw = {}
    if variant == "hier":
        table = _hier_of(table, 2)
    elif variant == "fp8_scale":
        kw["kv_scale"] = jnp.asarray([[2.0, 0.5], [1.5, 3.0]], jnp.float32)
        k5 = (k5 / kw["kv_scale"][0][:, None]).astype(jnp.float8_e4m3fn)
        v5 = (v5 / kw["kv_scale"][1][:, None]).astype(jnp.float8_e4m3fn)
    elif variant == "sliding":
        kw.update(window=PAGE + 3, sliding=jnp.asarray(True))
    elif variant == "sink_window":
        kw.update(sink=PAGE // 2, swin=PAGE + 5)
    return k5, v5, table, limits, kw


def _stacked_wrapper(name):
    from localai_tpu.ops.paged_flash import paged_prefill_partials_mq

    B, T, H, D = 2, 6, 4, 32
    if name == "decode":
        return paged_decode_partials, jax.random.normal(
            jax.random.key(41), (B, H, D)), {}
    q = jax.random.normal(jax.random.key(42), (B, T, H, D))
    if name == "mq":
        return paged_decode_partials_mq, q, {}
    # three tiles of two tokens: every tile re-reads the same stack
    return paged_prefill_partials_mq, q, {"max_qrows": 4}


@pytest.mark.parametrize("variant", ["flat", "hier", "fp8_scale", "sliding",
                                     "sink_window"])
@pytest.mark.parametrize("layer", [0, _STACK_L // 2, _STACK_L - 1])
@pytest.mark.parametrize("wrapper", ["decode", "mq", "prefill"])
def test_stacked_pool_bit_identical_to_sliced(wrapper, layer, variant):
    from localai_tpu.ops.stacked import StackedLayer

    fn, q, extra = _stacked_wrapper(wrapper)
    k5, v5, table, limits, kw = _stacked_case(variant)
    if q.ndim == 4:
        kw["q_pos"] = limits[:, None] + jnp.arange(q.shape[1])[None, :]
    want = fn(q, k5[layer], v5[layer], table, limits, interpret=True,
              **extra, **kw)
    li = jnp.int32(layer)
    kp = StackedLayer(k5, li)
    assert kp.shape == k5.shape[1:] and kp.dtype == k5.dtype and kp.ndim == 4
    got = fn(q, kp, StackedLayer(v5, li), table, limits, interpret=True,
             **extra, **kw)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _scan_partials(cfg, params, h, pools, table, limits, q):
    """paged_partials(impl=pallas) under llama._scan_layers with the pools
    marked to ride stacked: per-layer acc, the layer numbers the body got."""
    from localai_tpu.models import llama
    from localai_tpu.ops.attention import paged_partials
    from localai_tpu.ops.stacked import StackedLayer

    seen = []

    def layer(h, xs):
        lp, li, kc, vc, plain = xs
        assert isinstance(kc, StackedLayer) and kc.layer is vc.layer
        seen.append((kc.stack.shape, vc.stack.shape, plain.shape))
        acc, _, _ = paged_partials(q, kc, vc, table, limits, impl="pallas")
        return h, (acc, li, kc.layer, plain)

    extras = llama._paged_pool(llama.KVCache(*pools)) + (
        jnp.arange(pools[0].shape[0], dtype=jnp.float32),)
    _, out = llama._scan_layers(cfg, params, h, layer, extras)
    return out, seen


def test_scan_stack_hands_the_pool_on_with_a_traced_index():
    """A marked pool reaches the body unsliced with the scan's counter, a
    plain extra beside it sliced as ever; the kernel under the scan equals
    the per-layer calls on slices bit for bit."""
    import types

    k5, v5, table, limits, _ = _stacked_case("flat")
    q = jax.random.normal(jax.random.key(43), (2, 4, 32))
    cfg = types.SimpleNamespace(num_layers=_STACK_L, first_k_dense=0)
    params = {"layers": {"w": jnp.zeros((_STACK_L, 1))}}
    run = jax.jit(lambda k, v: _scan_partials(
        cfg, params, jnp.zeros(()), (k, v), table, limits, q)[0])
    acc, li, lk, plain = run(k5, v5)
    assert li.tolist() == lk.tolist() == plain.tolist() == [0, 1, 2]
    for l in range(_STACK_L):
        want = paged_decode_partials(q, k5[l], v5[l], table, limits,
                                     interpret=True)[0]
        np.testing.assert_array_equal(np.asarray(acc[l]), np.asarray(want))


def test_two_stack_model_gets_the_global_layer_and_no_cut_of_the_pool():
    """first_k_dense > 0 (DeepSeek layout): both stacks' scans read the
    WHOLE pool at the model's layer number — no `[:kd]` / `[kd:]` cut, which
    for a stacked pool would be a copy of most of it once a step."""
    import types

    k5, v5, table, limits, _ = _stacked_case("flat")
    q = jax.random.normal(jax.random.key(44), (2, 4, 32))
    cfg = types.SimpleNamespace(num_layers=_STACK_L, first_k_dense=1)
    params = {"dense_layers": {"w": jnp.zeros((1, 1))},
              "layers": {"w": jnp.zeros((_STACK_L - 1, 1))}}
    shapes = []

    def fn(k, v):
        out, seen = _scan_partials(cfg, params, jnp.zeros(()), (k, v), table,
                                   limits, q)
        shapes.extend(seen)
        return out

    jaxpr = jax.make_jaxpr(fn)(k5, v5)
    # one trace a stack; each saw all L layers of both pools, one row of the rest
    assert shapes == [(k5.shape, v5.shape, ())] * 2
    assert not [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "slice"]
    acc, li, lk, plain = jax.jit(fn)(k5, v5)
    assert li.tolist() == lk.tolist() == plain.tolist() == [0, 1, 2]
    for l in range(_STACK_L):
        want = paged_decode_partials(q, k5[l], v5[l], table, limits,
                                     interpret=True)[0]
        np.testing.assert_array_equal(np.asarray(acc[l]), np.asarray(want))


@pytest.mark.multichip
def test_stacked_pool_sharded_tp2(multichip):
    """tp=2 shard_map with the pool still stacked: the layer axis stays
    whole on every shard, the index is replicated; all three dispatchers
    equal the sharded call on the sliced layer bit for bit."""
    if multichip is True:
        return  # verdict delivered by the subprocess re-run
    from localai_tpu.ops import attention as A
    from localai_tpu.ops.stacked import StackedLayer
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = build_mesh(MeshPlan(tp=2))
    k5, v5, table, limits, _ = _stacked_case("flat")
    kvs = jnp.asarray([[2.0, 0.5], [1.5, 3.0]], jnp.float32)
    layer = _STACK_L - 1
    with mesh:
        for name, fn in (("decode", A.paged_partials),
                         ("mq", A.paged_partials_mq),
                         ("prefill", A.paged_prefill_partials)):
            _, q, _ = _stacked_wrapper(name)
            kw = {"kv_scale": kvs, "impl": "pallas", "mesh": mesh}
            if q.ndim == 4:
                kw["q_pos"] = limits[:, None] + jnp.arange(q.shape[1])[None, :]
            stacked, sliced = jax.jit(lambda q, k, v, i, fn=fn, kw=kw: (
                fn(q, StackedLayer(k, i), StackedLayer(v, i), table, limits, **kw),
                fn(q, k[layer], v[layer], table, limits, **kw),
            ))(q, k5, v5, jnp.int32(layer))
            for g, w in zip(stacked, sliced):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("impl,kv_heads,page", [
    ("pallas", 2, 64), ("xla", 2, 64), ("pallas", 2, 128), ("pallas", 8, 128)])
def test_engine_gauges_count_paged_attention_sites(impl, kv_heads, page):
    """After a paged request Engine.metrics() says what every traced
    paged-attention site handed on: the Pallas kernel takes the stacked pool
    (0 sliced), the XLA walk slices at its own site (0 stacked)."""
    import dataclasses

    from localai_tpu.engine.engine import Engine, EngineConfig
    from localai_tpu.engine.tokenizer import ByteTokenizer
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    cfg = get_arch("tiny")
    if kv_heads != cfg.num_kv_heads:  # eight heads of 8 in place of 4 of 16
        cfg = dataclasses.replace(cfg, num_heads=kv_heads,
                                  num_kv_heads=kv_heads)
    eng = Engine(
        cfg, init_params(cfg, jax.random.key(0)), ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=2, max_seq=256,
                                kv_pages=6 * 64 // page, kv_page_size=page,
                                paged_kernel=impl),
    )
    try:
        _, ev = eng.generate(list(range(1, 20)), max_new_tokens=4,
                             ignore_eos=True)
        assert ev.kind == "done"
        by_program, metrics = dict(eng.quant_sites.by_program), eng.metrics()
    finally:
        eng.stop()
    mine, other = (("stacked", "sliced") if impl == "pallas"
                   else ("sliced", "stacked"))
    block = by_program["decode_block"]
    assert block[f"paged_attention_{mine}"] == block["traces"] > 0
    assert block[f"paged_attention_{other}"] == 0
    assert metrics[f"paged_attention_{mine}_sites"] == sum(
        p[f"paged_attention_{mine}"] for p in by_program.values())
    assert metrics[f"paged_attention_{other}_sites"] == 0
    assert "quant_matmul_stacked_sites" not in metrics  # nothing quantized
    # beside them, what the kernel's dots were fed (ISSUE 32): the engine's
    # pool is bfloat16, so every Pallas site hands the page on as stored
    # and none upcasts; the XLA walk counts under neither
    assert eng.cache.k.dtype == jnp.bfloat16
    assert block["paged_attention_native"] == (
        block["traces"] if impl == "pallas" else 0)
    assert block["paged_attention_f32"] == 0
    assert metrics["paged_attention_native_sites"] == sum(
        p["paged_attention_native"] for p in by_program.values())
    assert metrics["paged_attention_f32_sites"] == 0
    # and what a visit of the walk held (ISSUE 41): at 2 KV heads a page is
    # 128 or 256 (token, head) rows of the 1,536 a visit takes, so the
    # kernel lands several side by side (the slot's four or two columns:
    # what one chip of tp = 4 runs); at 8 heads a 128-row page is a visit,
    # the one-chip cells' walk
    mine, other = (("multipage", "onepage") if kv_heads == 2
                   else ("onepage", "multipage"))
    assert block[f"paged_attention_{mine}"] == (
        block["traces"] if impl == "pallas" else 0)
    assert block[f"paged_attention_{other}"] == 0
    for key in (mine, other):
        assert metrics[f"paged_attention_{key}_sites"] == sum(
            p[f"paged_attention_{key}"] for p in by_program.values())
    # and how the block's window reached the two pools (ISSUE 44): 16- and
    # 8-wide heads are no whole lane tile, which no DMA slices, so these
    # pools keep XLA's scatter under either reader (the kernel's engine
    # test, at 128-wide heads, is tests/test_pool_write.py)
    assert block["pool_write_scatter"] == 2 * block["traces"]
    assert block["pool_write_inplace"] == 0
    assert metrics["pool_write_scatter_sites"] == block["pool_write_scatter"]
    assert metrics["pool_write_inplace_sites"] == 0


# ---------------------------------------------------------------------- #
# ISSUE 32: a 16- or 8-bit pool's page goes to the MXU as it is stored
# (one dot a pool a page over the [page·K, D] view, the other heads'
# columns masked), q and p in bfloat16 as the chip's one-pass float32 dot
# has always made them; a float32 pool keeps the per-head float32 tiles bit
# for bit; the DMAs run a ring of page buffers.
# ---------------------------------------------------------------------- #


def _bf16_round(x):
    """float64 -> the nearest bfloat16, as float64."""
    return np.asarray(
        jnp.asarray(np.asarray(x, np.float32)).astype(jnp.bfloat16).astype(
            jnp.float32), np.float64)


def _f64_walk(qr, qpos_rows, k_pool, v_pool, table, limits, *, kv_scale=None,
              softcap=0.0, window=0, sliding=False, sink=0, swin=0, pages=1):
    """The page walk in float64 numpy, a visit of `pages` consecutive table
    columns at a time (ISSUE 41; a slot's last visit holds what is left),
    rounding to bfloat16 exactly what the kernel hands the MXU in bfloat16:
    q (scale and k scale applied in float32 first, as the wrapper does) and
    each visit's p against the running max. It reads the listed pages
    only. qr [B, K, QR, D] float32 with 1/sqrt(D) in it; returns
    (acc, m, l) as the kernel's [B, K, QR, ·]."""
    qr = np.asarray(qr, np.float32)
    if kv_scale is not None:
        qr = qr * np.asarray(kv_scale[0], np.float32)[None, :, None, None]
    q = _bf16_round(qr)
    k = np.asarray(jnp.asarray(k_pool).astype(jnp.float32), np.float64)
    v = np.asarray(jnp.asarray(v_pool).astype(jnp.float32), np.float64)
    table, limits = np.asarray(table), np.asarray(limits)
    qpos_rows = np.asarray(qpos_rows)
    B, K, QR, _ = q.shape
    page = k.shape[1]
    acc = np.zeros((B, K, QR, v.shape[-1]))
    neg = float(np.float32(-1e30))  # the kernel's sentinel, as float32 holds it
    m = np.full((B, K, QR, 1), neg)
    l = np.zeros((B, K, QR, 1))
    for b in range(B):
        live = min(-(-int(limits[b]) // page), table.shape[1])
        for j in range(0, live, pages):
            pids = table[b, j:min(j + pages, live)]
            rows = len(pids) * page
            gpos = j * page + np.arange(rows)[None, :]  # [1, rows]
            ok = np.broadcast_to(gpos < limits[b], (QR, rows))
            dist = qpos_rows[b][:, None] - gpos
            if window and sliding:
                ok = ok & (dist < window)
            if swin:
                ok = ok & ((gpos < sink) | (dist < swin))
            kk = k[pids].reshape(rows, *k.shape[2:])
            vv = v[pids].reshape(rows, *v.shape[2:])
            s = np.einsum("kqd,nkd->kqn", q[b], kk)
            if softcap:
                s = softcap * np.tanh(s / softcap)
            s = np.where(ok[None], s, neg)
            m_new = np.maximum(m[b], s.max(-1, keepdims=True))
            alpha = np.exp(np.maximum(m[b] - m_new, -80.0))
            p = np.where(ok[None], np.exp(s - m_new), 0.0)
            l[b] = l[b] * alpha + p.sum(-1, keepdims=True)
            acc[b] = acc[b] * alpha + np.einsum(
                "kqn,nkd->kqd", _bf16_round(p), vv)
            m[b] = m_new
    if kv_scale is not None:
        acc = acc * np.asarray(kv_scale[1], np.float64)[None, :, None, None]
    return acc, m, l


def _assert_float32_grade(got, want, flips=0.01):
    """Float32-grade agreement (the module's 2e-4) with the rounded walk. A
    p that lands on a bfloat16 rounding boundary may round the other way in
    float32 than in float64 (one bfloat16 ulp of that p, 2^-8): such
    entries are rare, bounded, and only in acc."""
    for g, w, name in zip(got, want, ("acc", "m", "l")):
        assert g.shape == w.shape, (name, g.shape, w.shape)
        diff = np.abs(np.asarray(g, np.float64) - w)
        if name == "acc":
            assert diff.max() < 8e-3, (name, diff.max())
            assert (diff > 2e-4).mean() <= flips, (name, (diff > 2e-4).mean())
        else:
            assert diff.max() < 2e-4 * max(1.0, np.abs(w[w > -1e29]).max(
                initial=1.0)), (name, diff.max())


def _native_case(wrapper, pool, variant):
    """(fn, q, pools, table, limits, kwargs) of one narrow-pool case."""
    from localai_tpu.ops.paged_flash import paged_prefill_partials_mq

    B, G, D, MP, P, T = 3, 2, 32, 5, 18, 6
    # a token's K heads fill whole 32-bit words (`_flat_rows`): 2 x 16 bits,
    # 4 x 8 bits; fp8 at K = 2 keeps the per-head tiles (the tests above)
    K = 4 if pool == "fp8_scale" else 2
    H = K * G
    k4, v4 = _pool(jax.random.key(50), P, PAGE, K, D)
    table = _table(B, MP, P, seed=13)
    limits = jnp.array([4 * PAGE + 5, 0, 2 * PAGE], jnp.int32)
    kw = {}
    if pool == "fp8_scale":
        kw["kv_scale"] = jnp.asarray(
            [[2.0, 0.5, 1.25, 0.75], [1.5, 3.0, 0.5, 1.0]], jnp.float32)
        k4 = (k4 / kw["kv_scale"][0][:, None]).astype(jnp.float8_e4m3fn)
        v4 = (v4 / kw["kv_scale"][1][:, None]).astype(jnp.float8_e4m3fn)
    else:
        k4, v4 = k4.astype(jnp.bfloat16), v4.astype(jnp.bfloat16)
    if variant == "hier":
        kw["table"] = _hier_of(table, 2)
    elif variant == "sliding":
        kw.update(window=PAGE + 3, sliding=jnp.asarray(True))
    elif variant == "sink_window":
        kw.update(sink=PAGE // 2, swin=PAGE + 5)
    elif variant == "softcap":
        kw["softcap"] = 2.5
    if wrapper == "decode":
        fn, q = paged_decode_partials, jax.random.normal(
            jax.random.key(51), (B, H, D))
    else:
        q = jax.random.normal(jax.random.key(52), (B, T, H, D))
        kw["q_pos"] = limits[:, None] + jnp.arange(T)[None, :]
        fn = paged_decode_partials_mq
        if wrapper == "prefill":  # three tiles of two tokens
            fn = paged_prefill_partials_mq
            kw["max_qrows"] = 2 * G
    return fn, q, k4, v4, table, limits, kw


@pytest.mark.parametrize("variant", ["flat", "hier", "sliding", "sink_window",
                                     "softcap"])
@pytest.mark.parametrize("pool", ["bfloat16", "fp8_scale"])
@pytest.mark.parametrize("wrapper", ["decode", "mq", "prefill"])
def test_narrow_pool_page_as_stored_matches_float64_walk(wrapper, pool,
                                                         variant):
    fn, q, k4, v4, table, limits, kw = _native_case(wrapper, pool, variant)
    # 16-row pages of 2 or 4 heads: a visit is the table's five columns, all
    # of a slot's walk (one page under the cold-middle skip)
    pages = 1 if variant == "sink_window" else table.shape[1]
    _check_against_float64_walk(fn, q, k4, v4, table, limits, kw, pages)


def _check_against_float64_walk(fn, q, k4, v4, table, limits, kw, pages,
                                flips=0.01):
    """A narrow-pool wrapper call against `_f64_walk` at `pages` a visit,
    which has to be what `_visit_pages` gives the call."""
    from localai_tpu.ops.paged_flash import _flat_rows, _visit_pages

    kw = dict(kw)
    B, K, D = q.shape[0], k4.shape[2], q.shape[-1]
    G = q.shape[-2] // K
    assert _flat_rows(k4.dtype, v4.dtype, K, G * (1 if q.ndim == 3 else 2))
    assert pages == _visit_pages(k4.shape[1], K, table.shape[1], flat=True,
                                 swin=kw.get("swin", 0))
    got = fn(q, k4, v4, kw.pop("table", table), limits, interpret=True, **kw)
    # the walk's rows, as the wrappers lay them out: r = t·G + g
    qf = np.asarray(q, np.float32) * np.float32(1.0 / D**0.5)
    if q.ndim == 3:
        qr = qf.reshape(B, K, G, D)
        qpos_rows = np.broadcast_to(np.asarray(limits)[:, None], (B, G))
    else:
        T = q.shape[1]
        qr = qf.reshape(B, T, K, G, D).transpose(0, 2, 1, 3, 4).reshape(
            B, K, T * G, D)
        qpos_rows = np.repeat(np.asarray(kw["q_pos"]), G, axis=1)
    walk = {k: kw[k] for k in ("kv_scale", "softcap", "window", "sink", "swin")
            if k in kw}
    acc, m, l = _f64_walk(qr, qpos_rows, k4, v4, table, limits,
                          sliding="sliding" in kw, pages=pages, **walk)
    if q.ndim == 4:
        back = lambda a: a.reshape(B, K, q.shape[1], G, -1).transpose(
            0, 1, 3, 2, 4)
        acc, m, l = back(acc), back(m), back(l)
    _assert_float32_grade(got, (acc, m, l), flips)
    return got


# ---------------------------------------------------------------------- #
# ISSUE 41: a visit of the as-stored walk is as many consecutive pages of
# the slot as fit VISIT_ROWS (token, head) rows: one dot a pool over all of
# them, one rescale, a last visit of 1..n live pages whose unfetched part
# may hold anything.
# ---------------------------------------------------------------------- #


def _multipage_case(n, wrapper, variant):
    """(fn, q, pools, table, limits, kwargs) at `n` pages a visit: 128-row
    pages at K = 8 / 4 / 2 give 1 / 3 / 6 (fp8 needs four heads a word:
    64-row pages for n = 6), 192-row pages at K = 4 / 2 give 2 / 4. Slots
    of 1, n - 1, n, n + 1 and 2n + 1 pages, ending inside a page and on a
    page's last row, with idle slots between live ones (the handoff skips
    them) and an idle first one."""
    fp8 = variant == "fp8_scale"
    K, page = {1: (8, 128), 2: (4, 192), 3: (4, 128), 4: (2, 192),
               6: (4, 64) if fp8 else (2, 128)}[n]
    G, D, T = 2, 32, 3
    MP = 2 * n + 1
    lengths = [0, 1, 0, max(n - 1, 1), n, 0, n + 1, MP]  # live pages a slot
    ends = [0, 5, 0, page, page - 1, 0, page, 7]  # rows of the last one
    limits = jnp.array([max(c - 1, 0) * page + e
                        for c, e in zip(lengths, ends)], jnp.int32)
    B, P = len(lengths), len(lengths) * MP + 1
    k4, v4 = _pool(jax.random.key(70 + n), P, page, K, D)
    table = _table(B, MP, P, seed=20 + n)
    kw = {}
    if fp8:
        scales = [2.0, 0.5, 1.25, 0.75, 1.5, 3.0, 0.5, 1.0]
        kw["kv_scale"] = jnp.asarray([scales[:K], scales[::-1][:K]],
                                     jnp.float32)
        k4 = (k4 / kw["kv_scale"][0][:, None]).astype(jnp.float8_e4m3fn)
        v4 = (v4 / kw["kv_scale"][1][:, None]).astype(jnp.float8_e4m3fn)
    else:
        k4, v4 = k4.astype(jnp.bfloat16), v4.astype(jnp.bfloat16)
    if variant == "nan_unlisted":
        # every page no live slot lists holds NaN: the columns behind a
        # slot's last live page, the pool's free pages
        listed = np.zeros(P, bool)
        for b, c in enumerate(lengths):
            listed[np.asarray(table)[b, :c]] = True
        poison = jnp.asarray(~listed)[:, None, None, None]
        k4 = jnp.where(poison, jnp.nan, k4).astype(k4.dtype)
        v4 = jnp.where(poison, jnp.nan, v4).astype(v4.dtype)
    elif variant == "hier":
        kw["table"] = _hier_of(table, 3)
    elif variant == "sliding":
        kw.update(window=page + page // 2 + 3, sliding=jnp.asarray(True))
    elif variant == "softcap":
        kw["softcap"] = 2.5
    if wrapper == "decode":
        fn, q = paged_decode_partials, jax.random.normal(
            jax.random.key(80 + n), (B, K * G, D))
    else:
        fn, q = paged_decode_partials_mq, jax.random.normal(
            jax.random.key(90 + n), (B, T, K * G, D))
        kw["q_pos"] = limits[:, None] + jnp.arange(T)[None, :]
    return fn, q, k4, v4, table, limits, kw


@pytest.mark.parametrize("variant", ["flat", "hier", "sliding", "fp8_scale",
                                     "softcap", "nan_unlisted"])
@pytest.mark.parametrize("wrapper", ["decode", "mq"])
@pytest.mark.parametrize("n", [1, 3, 6])
def test_multipage_visit_matches_float64_walk(n, wrapper, variant):
    """n pages a visit (K = 8 / 4 / 2 at 128-row pages) against the float64
    walk that takes the same visits, over tails of every length; under
    `nan_unlisted` every page the walk must not read is NaN, the stale and
    the never-written part of a ring buffer included (the interpreter
    hands out NaN scratch)."""
    fn, q, k4, v4, table, limits, kw = _multipage_case(n, wrapper, variant)
    # a sum over a thousand rows meets a rounding boundary of some p more
    # often than one over eighty (3% of acc's entries under the softcap,
    # whose p are all near 1)
    got = _check_against_float64_walk(fn, q, k4, v4, table, limits, kw, n,
                                      flips=0.05)
    assert all(np.isfinite(np.asarray(g)).all() for g in got)


@pytest.mark.parametrize("variant", ["flat", "nan_unlisted"])
@pytest.mark.parametrize("n", [2, 4])
def test_multipage_visit_of_192_row_pages(n, variant):
    """The visits between: 192-row pages at K = 4 / 2 are 2 / 4 a visit."""
    fn, q, k4, v4, table, limits, kw = _multipage_case(n, "decode", variant)
    got = _check_against_float64_walk(fn, q, k4, v4, table, limits, kw, n,
                                      flips=0.05)
    assert all(np.isfinite(np.asarray(g)).all() for g in got)


@pytest.mark.parametrize("n", [3, 6])
def test_multipage_visit_matches_xla_walk(n):
    """The same call against the XLA page walk (float32 throughout, a chunk
    of pages at a time): bfloat16-grade agreement of the settled output."""
    fn, q, k4, v4, table, limits, kw = _multipage_case(n, "decode", "flat")
    got = fn(q, k4, v4, table, limits, interpret=True)
    want = _paged_cache_partials(q, k4, v4, table, limits)
    live = np.asarray(limits) > 0
    for g, w in ((got[0] / jnp.maximum(got[2], 1e-30),
                  want[0] / jnp.maximum(want[2], 1e-30)), (got[1], want[1])):
        np.testing.assert_allclose(np.asarray(g)[live], np.asarray(w)[live],
                                   atol=1e-2, rtol=1e-2)


@pytest.mark.parametrize("page,K,width,flat,swin,want", [
    (128, 8, 32, True, 0, 1),  # mistral int8, Solar-Open2's cache layers
    (128, 16, 32, True, 0, 1),  # OLMoE
    (128, 4, 32, True, 0, 3),
    (128, 2, 32, True, 0, 6),  # one chip of tp = 4
    (128, 1, 32, True, 0, 12),  # one chip of tp = 8
    (192, 4, 32, True, 0, 2),
    (192, 2, 32, True, 0, 4),
    (64, 8, 64, True, 0, 3),
    (64, 2, 64, True, 0, 12),
    (16, 2, 5, True, 0, 5),  # never more than the table has columns
    (256, 8, 16, True, 0, 1),
    (128, 2, 32, False, 0, 1),  # the per-head form
    (128, 2, 32, True, 512, 1),  # the cold-middle walk
])
def test_visit_rule_sizes_a_visit_in_rows(page, K, width, flat, swin, want):
    """`page · K` -> pages a visit, and what the ring then holds: at most
    VISIT_ROWS rows a visit wherever it is more than a page, and at 128-wide
    bfloat16 heads never more than RING_VMEM_BYTES (a visit of VISIT_ROWS
    rows is exactly what RING_MAX buffers of it fill)."""
    from localai_tpu.ops.paged_flash import (
        RING_VMEM_BYTES, VISIT_ROWS, _ring_depth, _visit_pages)

    n = _visit_pages(page, K, width, flat=flat, swin=swin)
    assert n == want
    assert n == 1 or n * page * K <= VISIT_ROWS
    visit_bytes = n * page * K * (128 + 128) * 2
    assert _ring_depth(visit_bytes) * visit_bytes <= RING_VMEM_BYTES
    assert _ring_depth(VISIT_ROWS * (128 + 128) * 2) == 4


def _parent_rows(qr, k_pool, v_pool, table, limits):
    """PR 31's `_ragged_paged_kernel` arithmetic, frozen (flat table, no
    window, no scales): the per-head float32 tiles, a double buffer."""
    import functools

    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, K, QR, D = qr.shape
    page = k_pool.shape[1]

    def kernel(table_ref, limits_ref, q_ref, k_hbm, v_hbm, acc_ref, m_ref,
               l_ref, kbuf, vbuf, acc_s, m_s, l_s, sem):
        b = pl.program_id(0)
        lim = limits_ref[b]
        n_iter = jnp.minimum((lim + page - 1) // page, table_ref.shape[1])

        def dma(hbm, buf, slot, j, which):
            return pltpu.make_async_copy(
                hbm.at[table_ref[b, j]], buf.at[slot], sem.at[slot, which])

        acc_s[...] = jnp.zeros_like(acc_s)
        m_s[...] = jnp.full_like(m_s, -1e30)
        l_s[...] = jnp.zeros_like(l_s)

        @pl.when(n_iter > 0)
        def _warmup():
            dma(k_hbm, kbuf, 0, 0, 0).start()
            dma(v_hbm, vbuf, 0, 0, 1).start()

        def body(j, carry):
            slot = j % 2

            @pl.when(j + 1 < n_iter)
            def _prefetch():
                dma(k_hbm, kbuf, (j + 1) % 2, j + 1, 0).start()
                dma(v_hbm, vbuf, (j + 1) % 2, j + 1, 1).start()

            dma(k_hbm, kbuf, slot, j, 0).wait()
            dma(v_hbm, vbuf, slot, j, 1).wait()
            gpos = j * page + jax.lax.broadcasted_iota(
                jnp.int32, (QR, page), 1)
            valid = gpos < lim
            for kh in range(K):
                kp = kbuf[slot, :, kh, :].astype(jnp.float32) * 1.0
                s = jax.lax.dot_general(
                    q_ref[0, kh], kp, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = jnp.where(valid, s, -1e30)
                m_prev = m_s[kh]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(jnp.maximum(m_prev - m_new, -80.0))
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_s[kh] = l_s[kh] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                vp = vbuf[slot, :, kh, :].astype(jnp.float32) * 1.0
                acc_s[kh] = acc_s[kh] * alpha + jax.lax.dot_general(
                    p, vp, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                m_s[kh] = m_new
            return carry

        jax.lax.fori_loop(0, n_iter, body, 0)
        acc_ref[0] = acc_s[...]
        m_ref[0] = jnp.broadcast_to(m_s[...], m_ref.shape[1:])
        l_ref[0] = jnp.broadcast_to(l_s[...], l_ref.shape[1:])

    blk = lambda n: pl.BlockSpec((1, K, QR, n), lambda b, *_: (b, 0, 0, 0))
    acc, m, l = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B,),
            in_specs=[blk(D), pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=[blk(D), blk(128), blk(128)],
            scratch_shapes=[
                pltpu.VMEM((2, page, K, D), k_pool.dtype),
                pltpu.VMEM((2, page, K, D), v_pool.dtype),
                pltpu.VMEM((K, QR, D), jnp.float32),
                pltpu.VMEM((K, QR, 1), jnp.float32),
                pltpu.VMEM((K, QR, 1), jnp.float32),
                pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=[jax.ShapeDtypeStruct((B, K, QR, n), jnp.float32)
                   for n in (D, 128, 128)],
        interpret=True,
    )(table, limits, qr, k_pool, v_pool)
    return acc, m[..., :1], l[..., :1]


@pytest.mark.parametrize("H,K", [(4, 2), (4, 4), (4, 1)])
def test_float32_pool_keeps_the_parents_numbers_bit_for_bit(H, K):
    """A float32 pool is not handed on as stored: same tiles, same float32
    dots, same order as before the change, whatever the ring's depth."""
    from localai_tpu.ops.paged_flash import _flat_rows, _paged_partials_rows

    B, D, MP, P = 5, 32, 5, 26
    k4, v4 = _pool(jax.random.key(60), P, PAGE, K, D)
    assert not _flat_rows(k4.dtype, v4.dtype, K, H // K)
    table = _table(B, MP, P, seed=14)
    # a slot's first page is started by the slot before it: handed on, an
    # idle slot in the way (hands nothing on, is handed nothing), a last one
    limits = jnp.array([4 * PAGE + 5, PAGE, 0, 2 * PAGE + 1, 3], jnp.int32)
    qr = (jax.random.normal(jax.random.key(61), (B, H, D))
          * (1.0 / D**0.5)).reshape(B, K, H // K, D)
    want = _parent_rows(qr, k4, v4, table, limits)
    qpos = jnp.broadcast_to(limits[:, None], (B, H // K))
    for ring in (None, 2, 3):
        got = _paged_partials_rows(qr, qpos, k4, v4, table, limits, 0.0, 0,
                                   None, True, ring=ring)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("ring", [3, 4])
def test_ring_depth_gives_the_double_buffers_numbers_bit_for_bit(ring, dtype):
    """Slots of 0, 1, ring and ring + 1 pages (and a partial page): how far
    ahead the DMAs run changes no number."""
    from localai_tpu.ops.paged_flash import _paged_partials_rows, _ring_depth

    B, K, G, D, MP, P = 6, 2, 2, 32, 6, 38
    k4, v4 = _pool(jax.random.key(62), P, PAGE, K, D, jnp.dtype(dtype))
    table = _table(B, MP, P, seed=15)
    limits = jnp.array([0, PAGE, ring * PAGE, 0, (ring + 1) * PAGE,
                        (ring - 1) * PAGE + 3], jnp.int32)
    qr = jax.random.normal(jax.random.key(63), (B, K, G, D)) * (1.0 / D**0.5)
    qpos = jnp.broadcast_to(limits[:, None], (B, G))
    run = lambda n: _paged_partials_rows(qr, qpos, k4, v4, table, limits,
                                         0.0, 0, None, True, ring=n)
    for g, w in zip(run(ring), run(2)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # what the kernel picks itself: the cells' pages, a huge page, a tiny one
    assert [_ring_depth(n) for n in (512 << 10, 1 << 20, 128 << 10, 8 << 20,
                                     1)] == [4, 3, 4, 2, 4]


@pytest.mark.parametrize("dtype,key,K,page,visit", [
    ("bfloat16", "paged_attention_native", 2, PAGE, "multipage"),
    ("float32", "paged_attention_f32", 2, PAGE, "onepage"),
    ("bfloat16", "paged_attention_native", 2, 128, "multipage"),
    ("bfloat16", "paged_attention_native", 8, 128, "onepage")])
def test_site_counts_tell_the_kernels_arithmetic(dtype, key, K, page, visit):
    """What a traced kernel call fed its dots is counted with the site
    (ops/stacked.SiteCounts): a narrow pool native, a float32 pool f32, the
    XLA walk neither. Beside it what a visit held (ISSUE 41): K = 2 several
    pages, K = 8 at 128-row pages and the per-head form one."""
    from localai_tpu.ops.attention import paged_partials
    from localai_tpu.ops.stacked import SiteCounts

    k4, v4 = _pool(jax.random.key(64), 8, page, K, 32, jnp.dtype(dtype))
    table = _table(2, 3, 8, seed=16)
    limits = jnp.array([page + 4, 2 * page + 8], jnp.int32)
    q = jax.random.normal(jax.random.key(65), (2, 2 * K, 32))
    other = ({"paged_attention_native", "paged_attention_f32"} - {key}).pop()
    for impl, n in (("pallas", 1), ("xla", 0)):
        sites = SiteCounts()
        with sites.tracing("decode_block"):
            jax.make_jaxpr(lambda q: paged_partials(
                q, k4, v4, table, limits, impl=impl))(q)
        tally = sites.by_program["decode_block"]
        assert (tally[key], tally[other]) == (n, 0)
        assert tally["paged_attention_sliced"] == 1  # a plain pool
        assert tally[f"paged_attention_{visit}"] == n
        assert tally["paged_attention_multipage"] + tally[
            "paged_attention_onepage"] == n
