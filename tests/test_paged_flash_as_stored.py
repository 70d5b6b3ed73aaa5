"""A 16- or 8-bit pool's page goes to the MXU as it is stored (ISSUE 32): one
dot a pool a page over the [page·K, D] view, the other heads' columns
masked, q and p in bfloat16 as the chip's one-pass float32 dot has always
made them; a float32 pool keeps the per-head float32 tiles bit for bit; the
DMAs run a ring of page buffers. The kernel in interpret mode against the
page walk in float64 (tests/paged_cases.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops.paged_flash import (
    paged_decode_partials,
    paged_decode_partials_mq,
)
from paged_cases import (
    PAGE,
    _check_against_float64_walk,
    _hier_of,
    _pool,
    _table,
)


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
    _check_against_float64_walk(("as_stored", wrapper, pool, variant), fn, q,
                                k4, v4, table, limits, kw, pages)


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
    dots, same order as before the change, whatever the ring's depth and
    whichever slot's program started a visit's copies (the stream)."""
    from localai_tpu.ops.paged_flash import _flat_rows, _paged_partials_rows

    B, D, MP, P = 9, 32, 5, 46
    k4, v4 = _pool(jax.random.key(60), P, PAGE, K, D)
    assert not _flat_rows(k4.dtype, v4.dtype, K, H // K)
    table = _table(B, MP, P, seed=14)
    # a slot's visits are started from the programs of the slots before it:
    # a run of idle slots in the way, a run of one-token ones, a last one
    limits = jnp.array([4 * PAGE + 5, PAGE, 0, 0, 2 * PAGE + 1, 1, 1, 0, 3],
                       jnp.int32)
    qr = (jax.random.normal(jax.random.key(61), (B, H, D))
          * (1.0 / D**0.5)).reshape(B, K, H // K, D)
    want = _parent_rows(qr, k4, v4, table, limits)
    qpos = jnp.broadcast_to(limits[:, None], (B, H // K))
    for ring in (None, 2, 3, 4):
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
    pages, K = 8 at 128-row pages and the per-head form one; and how the
    walk crosses a slot boundary (ISSUE 54): one stream of visits, but for
    the cold-middle walk, which prefetches inside its own program."""
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
        assert (tally["paged_attention_stream"],
                tally["paged_attention_prefetch"]) == (n, 0)
        with sites.tracing("cold_middle"):
            jax.make_jaxpr(lambda q: paged_partials(
                q, k4, v4, table, limits, impl=impl, sink=page // 2,
                swin=page + 5))(q)
        tally = sites.by_program["cold_middle"]
        assert (tally["paged_attention_stream"],
                tally["paged_attention_prefetch"]) == (0, n)
        assert tally["paged_attention_onepage"] == n


def test_the_latent_walk_counts_what_it_does():
    """The tiny Kimi-Linear preset's decode step over a bfloat16 latent pool
    (ISSUE 48): every `latent_paged_attention` call of the trace hands its
    pages to the MXU as stored, several a visit, out of the stacked pool; no
    float32 site is left. The XLA walk counts under neither."""
    from localai_tpu.models import llama as L
    from localai_tpu.models.config import get_arch
    from localai_tpu.ops.stacked import SiteCounts

    cfg = get_arch("tiny-kimi-linear")
    params = jax.eval_shape(lambda: L.init_params(cfg, jax.random.key(0)))
    B, n, kl = 2, 4, len(cfg.recurrent_layers)
    pool = L.paged_cache_zeros(cfg, 5, 16, dtype=jnp.bfloat16)
    lk = jnp.zeros((cfg.cache_layers, B, n, 1, cfg.cache_k_dim), jnp.float32)
    for impl, kernel in (("pallas", True), ("xla", False)):
        sites = SiteCounts()
        with sites.tracing("decode_block"):
            text = str(jax.make_jaxpr(lambda p, st, cv: L.decode_step_windowed(
                cfg, p, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
                pool, lk, lk[..., :0], jnp.int32(0),
                ptable=jnp.zeros((B, 4), jnp.int32), paged_impl=impl,
                recurrent=(st, cv), kda_impl="xla"))(
                    params, jnp.zeros((kl, B, 4, 16, 16), jnp.float32),
                    jnp.zeros((kl, B, 3, 3 * 64), jnp.float32)))
        tally = sites.by_program["decode_block"]
        calls = text.count("name=latent_paged_attention")
        assert (calls > 0) == kernel and "name=paged_attention" not in text
        assert tally["paged_attention_f32"] == 0
        assert tally["paged_attention_native"] == calls
        assert tally["paged_attention_multipage"] == calls
        assert tally["paged_attention_onepage"] == 0
        assert (tally["paged_attention_stream"],
                tally["paged_attention_prefetch"]) == (calls, 0)
        assert tally["paged_attention_stacked"] == calls
        # the value dot of each: kv_lora_rank 32 under a 64-wide row rounds
        # up to the row (ISSUE 50), so no tiny preset's kernel is narrower
        assert tally["paged_attention_value_row"] == calls
        assert tally["paged_attention_value_lanes"] == 0


@pytest.mark.parametrize("values,key", [
    (512, "paged_attention_value_lanes"), (600, "paged_attention_value_row"),
    (0, "paged_attention_value_row")])
def test_a_latent_call_counts_its_value_dots_lanes(values, key):
    """A latent call at the cells' row (640 lanes) counts under `value_lanes`
    where the stated width leaves whole lane tiles unread (kv_lora_rank 512)
    and under `value_row` where it does not or none is stated; a GQA call
    and the XLA walk under neither (ISSUE 50)."""
    from localai_tpu.ops.attention import paged_partials
    from localai_tpu.ops.stacked import SiteCounts

    pool = jnp.zeros((8, 128, 1, 640), jnp.bfloat16)
    table, limits = _table(2, 3, 8, seed=17), jnp.array([5, 300], jnp.int32)
    q = jnp.zeros((2, 20, 640), jnp.bfloat16)
    other = ({"paged_attention_value_lanes", "paged_attention_value_row"}
             - {key}).pop()
    for impl, n in (("pallas", 1), ("xla", 0)):
        sites = SiteCounts()
        with sites.tracing("decode_block"):
            acc, _, _ = jax.eval_shape(lambda q: paged_partials(
                q, pool, pool, table, limits, impl=impl, latent=True,
                values=values), q)
        tally = sites.by_program["decode_block"]
        assert (tally[key], tally[other]) == (n, 0)
        assert acc.shape[-1] == (512 if n and values == 512 else 640)
    k4, v4 = _pool(jax.random.key(66), 8, PAGE, 2, 32, jnp.bfloat16)
    sites = SiteCounts()
    with sites.tracing("decode_block"):
        jax.eval_shape(lambda q: paged_partials(
            q, k4, v4, table, limits, impl="pallas"), jnp.zeros((2, 4, 32)))
    tally = sites.by_program["decode_block"]
    assert tally["paged_attention_native"] == 1
    assert (tally[key], tally[other]) == (0, 0)
