"""Engines and model entry points on the paged-attention kernel (interpret
mode on CPU) against the XLA walk: chunked direct-to-page prefill, greedy
tokens, the fp8 pool's per-head scale, MLA's latent pool, and what
`Engine.metrics()` says of every traced paged-attention site.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paged_cases import _table


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
    # and how the walk crosses a slot boundary (ISSUE 54): every kernel site
    # of every program is one stream of visits, none prefetches on its own
    assert block["paged_attention_stream"] == (
        block["traces"] if impl == "pallas" else 0)
    assert metrics["paged_attention_stream_sites"] == sum(
        p["paged_attention_stream"] for p in by_program.values())
    assert metrics["paged_attention_prefetch_sites"] == 0
    # and how the block's window reached the two pools (ISSUE 44): 16- and
    # 8-wide heads are no whole lane tile, which no DMA slices, so these
    # pools keep XLA's scatter under either reader (the kernel's engine
    # test, at 128-wide heads, is tests/test_pool_write.py)
    assert block["pool_write_scatter"] == 2 * block["traces"]
    assert block["pool_write_inplace"] == 0
    assert metrics["pool_write_scatter_sites"] == block["pool_write_scatter"]
    assert metrics["pool_write_inplace_sites"] == 0
