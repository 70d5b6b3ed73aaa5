"""A visit of the as-stored walk is as many consecutive pages of the slot as
fit VISIT_ROWS (token, head) rows (ISSUE 41): one dot a pool over all of
them, one rescale, a last visit of 1..n live pages whose unfetched part may
hold anything; the visits of all the slots are one stream through the ring
of visit buffers (ISSUEs 50, 54). The kernel in interpret mode against the
float64 walk that takes the same visits (tests/paged_cases.py), and the rule
as a table.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.ops.attention import _paged_cache_partials
from localai_tpu.ops.paged_flash import (
    latent_paged_attention,
    paged_decode_partials,
    paged_decode_partials_mq,
    value_lanes,
)
from paged_cases import (
    _assert_float32_grade,
    _check_against_float64_walk,
    _f64_walk,
    _hier_of,
    _one_compile,
    _pool,
    _table,
)


def _multipage_case(n, wrapper, variant):
    """(fn, q, pools, table, limits, kwargs) at `n` pages a visit: 128-row
    pages at K = 8 / 4 / 2 give 1 / 3 / 6 (fp8 needs four heads a word:
    64-row pages for n = 6), 192-row pages at K = 4 / 2 give 2 / 4. Slots
    of 1, n - 1, n, n + 1 and 2n + 1 pages, ending inside a page and on a
    page's last row, with idle slots between live ones (the stream steps
    over them) and an idle first one."""
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


def _program(n, wrapper, variant):
    """The key a case's compiled call is kept under (`_one_compile`):
    `nan_unlisted` is `flat`'s program on another pool."""
    return ("visit", n, wrapper, "flat" if variant == "nan_unlisted" else variant)


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
    got = _check_against_float64_walk(
        _program(n, wrapper, variant), fn, q, k4, v4, table, limits, kw, n,
        flips=0.05)
    assert all(np.isfinite(np.asarray(g)).all() for g in got)


@pytest.mark.parametrize("variant", ["flat", "nan_unlisted"])
@pytest.mark.parametrize("n", [2, 4])
def test_multipage_visit_of_192_row_pages(n, variant):
    """The visits between: 192-row pages at K = 4 / 2 are 2 / 4 a visit."""
    fn, q, k4, v4, table, limits, kw = _multipage_case(n, "decode", variant)
    got = _check_against_float64_walk(
        _program(n, "decode", variant), fn, q, k4, v4, table, limits, kw, n,
        flips=0.05)
    assert all(np.isfinite(np.asarray(g)).all() for g in got)


@pytest.mark.parametrize("n", [3, 6])
def test_multipage_visit_matches_xla_walk(n):
    """The same call against the XLA page walk (float32 throughout, a chunk
    of pages at a time): bfloat16-grade agreement of the settled output."""
    fn, q, k4, v4, table, limits, kw = _multipage_case(n, "decode", "flat")
    got = _one_compile(_program(n, "decode", "flat"), fn, kw)(
        q, k4, v4, table, limits)
    want = _paged_cache_partials(q, k4, v4, table, limits)
    live = np.asarray(limits) > 0
    for g, w in ((got[0] / jnp.maximum(got[2], 1e-30),
                  want[0] / jnp.maximum(want[2], 1e-30)), (got[1], want[1])):
        np.testing.assert_allclose(np.asarray(g)[live], np.asarray(w)[live],
                                   atol=1e-2, rtol=1e-2)


# The latent visit at the two latent cells' page and row (128 rows of 640
# bfloat16 lanes, six pages a visit by the byte bound; GLM-4.7-Flash's 20
# query rows a slot and Kimi-Linear's 32) over a table of thirteen columns.
_LATENT = dict(page=128, W=640, MP=13, visit=6)


def _latent_case(heads, last):
    """(q, pool, table, limits): slots whose LAST visit holds `last` live
    pages, as a slot's only visit and behind one and two full ones, ending
    inside a page, on a page's last row and on the next page's first; an
    idle slot and a one-token slot between them (the stream steps over the
    one and crosses the other)."""
    page, W, MP, visit = (_LATENT[k] for k in ("page", "W", "MP", "visit"))
    full = visit * page
    limits = [(last - 1) * page + 5, 0, full + last * page, 1,
              full + (last - 1) * page + 1,
              min(2 * full + (last - 1) * page + 77, MP * page)]
    B = len(limits)
    pool = jax.random.normal(jax.random.key(150), (B * MP + 1, page, 1, W),
                             jnp.bfloat16)
    q = jax.random.normal(jax.random.key(151 + heads), (B, heads, W),
                          jnp.bfloat16)
    return q, pool, _table(B, MP, B * MP + 1, seed=30), jnp.array(
        limits, jnp.int32)


def _latent_call(key, values):
    """One compile a (heads, values): the cases differ in their limits."""
    return _one_compile(("latent", key, values), lambda q, k, v, t, l, **kw: (
        latent_paged_attention(q, k, t, l, values=values, **kw)), {})


@pytest.mark.parametrize("last", [1, 2, 3, 4, 5, 6])
@pytest.mark.parametrize("heads", [20, 32])
def test_latent_visit_matches_float64_walk(heads, last):
    """The latent walk with the value dot on the value lanes (512 of 640,
    ISSUE 50) against the float64 walk that takes the same visits: every
    live size of a last visit, alone in its slot and behind one and two full
    visits, every visit started by whichever slot is being scored when it
    comes `ring - 1` ahead in the one stream of the slots' visits. acc holds
    the value lanes alone."""
    page, W, MP, visit = (_LATENT[k] for k in ("page", "W", "MP", "visit"))
    q, pool, table, limits = _latent_case(heads, last)
    B = q.shape[0]
    got = _latent_call(heads, 512)(q, pool, pool, table, limits)
    assert got[0].shape == (B, 1, heads, 512)
    qr = (np.asarray(q, np.float32) * np.float32(1.0 / W**0.5))[:, None]
    acc, m, l = _f64_walk(
        qr, np.broadcast_to(np.asarray(limits)[:, None], (B, heads)), pool,
        pool, table, limits, pages=visit)
    _assert_float32_grade(got, (acc[..., :512], m, l), flips=0.05)
    idle = np.asarray(limits) == 0
    assert (np.asarray(got[0])[idle] == 0).all()
    assert (np.asarray(got[2])[idle] == 0).all()


@pytest.mark.parametrize("values,lanes", [(512, 512), (500, 512), (128, 128),
                                          (513, 640), (600, 640), (0, 640)])
def test_latent_value_lanes_are_the_whole_rows_lanes(values, lanes):
    """(a) of ISSUE 50 moves no lane anyone reads: the value dot on
    `value_lanes(values, W)` leading lanes gives, bit for bit, those lanes
    of the walk whose value dot runs over the whole row, with the same
    (m, l); a width whose round-up to lane tiles reaches the row is the
    whole row's kernel, the parent's."""
    W = _LATENT["W"]
    assert value_lanes(values, W) == lanes
    q, pool, table, limits = _latent_case(20, 3)
    whole = _latent_call(20, 0)(q, pool, pool, table, limits)
    got = _latent_call(20, values if lanes < W else 0)(
        q, pool, pool, table, limits)
    assert got[0].shape[-1] == lanes and whole[0].shape[-1] == W
    # (the CPU's matmul sums a 128-wide output's products in another order
    # than a 640-wide one's: float32's last bits there, nothing on the MXU)
    same = (np.testing.assert_array_equal if lanes >= 512 else
            functools.partial(np.testing.assert_allclose, rtol=0, atol=4e-6))
    same(np.asarray(got[0]), np.asarray(whole[0])[..., :lanes])
    for g, w in zip(got[1:], whole[1:]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if lanes == W:  # no narrower kernel is traced: the same program text
        text = lambda v: str(jax.make_jaxpr(lambda q: latent_paged_attention(
            q, pool, table, limits, interpret=True, values=v))(q))
        assert text(values) == text(0)


# The slots' visits are ONE STREAM (ISSUEs 50, 54): every walk but the
# cold-middle one starts visit g + ring - 1 of the stream while visit g is
# scored, whatever slot it belongs to. What a case holds: the walk's form
# (K row-heads a token, the page, query rows a head, the pool's dtype) and
# what rides on it.
_STREAM = {
    "K2_six_pages": dict(K=2, page=128, QR=4),  # one chip of tp = 4
    "K4_three_pages": dict(K=4, page=128, QR=4),  # LFM2's packed rows
    "K8_a_page": dict(K=8, page=128, QR=4),  # mistral int8
    "K16_a_page": dict(K=16, page=128, QR=1),  # OLMoE
    "ring_rows": dict(K=8, page=128, QR=8, ring_rows=512),  # Laguna's ring
    "fp8_scale_window_softcap": dict(K=4, page=64, QR=2, fp8=True,
                                     window=150, softcap=2.5),
    "mq_16_rows": dict(K=2, page=128, QR=16, window=200, verify=4),
    "f32_per_head": dict(K=2, page=16, QR=2, dtype=jnp.float32),
    "hier": dict(K=2, page=128, QR=2, span=3),
}


def _stream_case(name):
    """(call, walk, limits): `call(ring)` runs `_paged_partials_rows` at a
    ring of `ring` visit buffers, `walk()` the float64 walk of the same
    visits. The slots: a run of idle ones first, every live size 1..n
    of a last visit (alone in its slot and behind a full visit, ending inside
    a page and on a page's last row), runs of one-token slots and of idle
    ones between live ones, a slot of more visits than the ring is deep, an
    idle last but one. Every page no slot lists holds NaN, and so does a
    ring buffer nobody wrote (the interpreter's scratch)."""
    from localai_tpu.ops.paged_flash import (
        _flat_rows, _paged_partials_rows, _visit_pages)

    c = dict(_STREAM[name])
    K, page, QR, D = c["K"], c["page"], c["QR"], 32
    dtype = jnp.float8_e4m3fn if c.get("fp8") else c.get("dtype", jnp.bfloat16)
    rr = c.get("ring_rows", 0)
    flat = _flat_rows(dtype, dtype, K, QR)
    assert flat == (name != "f32_per_head")
    MP = rr // page if rr else 13
    n = _visit_pages(page, K, MP, 2 * D * jnp.dtype(dtype).itemsize, flat=flat)
    assert n == {"K2_six_pages": 6, "K4_three_pages": 3, "hier": 6,
                 "fp8_scale_window_softcap": 6, "mq_16_rows": 6}.get(name, 1)
    if rr:  # a ring's limit is the positions written, however many
        limits = [0, 0, 5, 1, 1, rr, rr + 1, 0, 700, 2000, 0, 0, 3 * page, 1,
                  rr - 1, 0, 300]
    else:
        spec = [(0, 0), (0, 0)]  # live pages, rows of the last one
        for s in range(1, n + 1):
            spec += [(n + s, page if s % 2 else 7), (s, 5 if s % 2 else page)]
            if s == (n + 1) // 2:
                spec += [(1, 1), (1, 1), (1, 1)]
        spec += [(1, 1), (0, 0), (0, 0), (0, 0), (1, 1), (MP, 3), (0, 0),
                 (2, page - 1)]
        limits = [max(p - 1, 0) * page + e for p, e in spec]
    B = len(limits)
    P = B * MP + 1
    limits = jnp.array(limits, jnp.int32)
    k4, v4 = _pool(jax.random.key(170), P, page, K, D)
    table = _table(B, MP, P, seed=40)
    kw = {}
    if c.get("fp8"):
        kw["kv_scale"] = jnp.asarray(
            [[2.0, 0.5, 1.25, 0.75], [1.5, 3.0, 0.5, 1.0]], jnp.float32)
        k4, v4 = (k4 / kw["kv_scale"][0][:, None],
                  v4 / kw["kv_scale"][1][:, None])
    k4, v4 = k4.astype(dtype), v4.astype(dtype)
    listed = np.zeros(P, bool)
    for b, lim in enumerate(np.asarray(limits)):
        listed[np.asarray(table)[b, :min(-(-int(lim) // page), MP)]] = True
    poison = jnp.asarray(~listed)[:, None, None, None]
    k4 = jnp.where(poison, jnp.nan, k4.astype(jnp.float32)).astype(dtype)
    v4 = jnp.where(poison, jnp.nan, v4.astype(jnp.float32)).astype(dtype)
    assert bool(jnp.isnan(k4.astype(jnp.float32)).any())
    qr = jax.random.normal(jax.random.key(171), (B, K, QR, D)) * (1.0 / D**0.5)
    T = c.get("verify", 1)  # a verify chunk's rows: r = t·G + g
    qpos = (limits[:, None] + 2 + jnp.arange(QR)[None, :] // (QR // T))
    window = rr or c.get("window", 0)
    softcap = c.get("softcap", 0.0)
    tbl = _hier_of(table, c["span"]) if "span" in c else table

    def call(ring):
        return jax.jit(lambda qr, k4, v4: _paged_partials_rows(
            qr, qpos, k4, v4, tbl, limits, softcap, window,
            jnp.asarray(True) if window else None, True, ring=ring,
            ring_rows=rr, **kw))(qr, k4, v4)

    def walk():
        return _f64_walk(qr, qpos, k4, v4, table, limits, softcap=softcap,
                         window=window, sliding=bool(window), pages=n,
                         ring_rows=rr, **kw,
                         **({} if flat else {"mxu": lambda x: np.asarray(
                             x, np.float64)}))

    return call, walk, limits


_STREAM_GOT = {}  # (name, ring) -> the kernel's (acc, m, l): the depth-4 case reads depth 2's


@pytest.mark.parametrize("ring", [2, 4])
@pytest.mark.parametrize("name", list(_STREAM))
def test_stream_of_visits_matches_float64_walk(name, ring):
    """Every K/V walk that is not the cold-middle one, as one stream of
    visits across slots (ISSUE 54), at a ring of two and of four visit
    buffers against the float64 walk that takes the same visits: K 2 / 4 /
    8 / 16, a ring's table, fp8 with scales under a window and a softcap,
    a verify chunk's 16 query rows, the per-head float32 form, a two-level
    table. No unlisted page is read (they hold NaN), an idle slot's rows are
    zero, and the depth of the ring moves no bit."""
    call, walk, limits = _stream_case(name)
    got = _STREAM_GOT[name, ring] = tuple(np.asarray(g) for g in call(ring))
    assert all(np.isfinite(g).all() for g in got)
    _assert_float32_grade(got, walk(), flips=0.05)
    idle = np.asarray(limits) == 0
    assert (got[0][idle] == 0).all() and (got[2][idle] == 0).all()
    if ring == 4:
        for g, w in zip(got, _STREAM_GOT.get((name, 2)) or call(2)):
            np.testing.assert_array_equal(g, np.asarray(w))


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

    n = _visit_pages(page, K, width, (128 + 128) * 2, flat=flat, swin=swin)
    assert n == want
    assert n == 1 or n * page * K <= VISIT_ROWS
    visit_bytes = n * page * K * (128 + 128) * 2
    assert _ring_depth(visit_bytes) * visit_bytes <= RING_VMEM_BYTES
    assert _ring_depth(VISIT_ROWS * (128 + 128) * 2) == 4



@pytest.mark.parametrize("cell,K,row_bytes,want,ring", [
    ("mistral-7b-int8", 8, 512, 1, 4),
    ("mistral-7b-bf16-tp4", 2, 512, 6, 4),
    ("olmoe-1b-7b-int8", 16, 512, 1, 3),
    ("solar-open2-250b-int8-ep8", 8, 512, 1, 4),
    ("lfm2-8b-a1b-int8", 4, 512, 3, 4),  # 8 heads of 64, two a row
    ("granite-4.0-h-small-int8-ep8", 8, 512, 1, 4),
    # the latent pool: one 640-wide bfloat16 row a token, key and value
    ("kimi-linear-48b-a3b-int8-ep8", 1, 1280, 6, 3),
])
def test_every_cells_visit_is_what_it_was(cell, K, row_bytes, want, ring):
    """The byte bound (ISSUE 48) sizes the latent visit and no other cell's:
    at 512 B of K and V a row VISIT_BYTES is more than VISIT_ROWS rows, so
    the rule in rows decides as it did (the GQA cells' figures are PR 41's
    and PR 42's). K row-heads a chip and the bytes a row lands are read from the
    cell's configuration (`benchmark/configs/<cell>.json`), so a cell that
    changes its pool shows up here."""
    import json
    import pathlib

    from localai_tpu.models.config import get_arch
    from localai_tpu.ops.paged_flash import (
        VISIT_BYTES, VISIT_ROWS, _ring_depth, _visit_pages)

    y = json.loads((pathlib.Path(__file__).parent.parent / "benchmark"
                    / "configs" / f"{cell}.json").read_text())["yaml"]
    cfg = get_arch(y["model"])
    page, width = y["kv_page_size"], y["context_size"] // y["kv_page_size"]
    assert (page, width) == (128, 32)
    assert K == (1 if cfg.is_mla else cfg.num_kv_heads // cfg.cache_pack
                 // int(y.get("tensor_parallel") or 1))
    assert row_bytes == (cfg.cache_k_dim + cfg.cache_v_dim) * 2  # bfloat16
    assert VISIT_BYTES >= VISIT_ROWS * 512
    n = _visit_pages(page, K, width, row_bytes, flat=True)
    assert n == want
    if row_bytes == 512:  # the rule before the byte bound
        assert n == max(1, VISIT_ROWS // (page * K))
    assert _ring_depth(n * page * K * row_bytes) == ring
