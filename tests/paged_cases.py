"""What the paged-attention test modules share (not collected): pools and
tables drawn from a seed, the two-level form of a table, the page walk in
float64 numpy and the comparisons against it.

The modules, by what is under test: `test_paged_flash.py` (the kernel's walk
against the XLA gather walk), `test_paged_flash_stacked.py` (the pool still
stacked over layers), `test_paged_flash_as_stored.py` (a narrow pool's page
handed on as stored, the ring of page buffers), `test_paged_flash_visit.py`
(how many pages a visit holds, the slots' visits as one stream),
`test_paged_flash_engine.py` (engines and model entry points on the kernel).
"""

import jax
import jax.numpy as jnp
import numpy as np

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


def _bf16_round(x):
    """float64 -> the nearest bfloat16, as float64."""
    return np.asarray(
        jnp.asarray(np.asarray(x, np.float32)).astype(jnp.bfloat16).astype(
            jnp.float32), np.float64)


def _f64_walk(qr, qpos_rows, k_pool, v_pool, table, limits, *, kv_scale=None,
              softcap=0.0, window=0, sliding=False, sink=0, swin=0, pages=1,
              ring_rows=0, mxu=_bf16_round):
    """The page walk in float64 numpy, a visit of `pages` consecutive table
    columns at a time (ISSUE 41; a slot's last visit holds what is left),
    rounding to bfloat16 exactly what the kernel hands the MXU in bfloat16:
    q (scale and k scale applied in float32 first, as the wrapper does) and
    each visit's p against the running max (`mxu`; the identity for the
    per-head float32 form, whose dots are float32). It reads the listed pages
    only. `ring_rows`: the table's pages are a ring of that many rows and a
    row is masked at the position it holds. qr [B, K, QR, D] float32 with
    1/sqrt(D) in it; returns (acc, m, l) as the kernel's [B, K, QR, ·]."""
    qr = np.asarray(qr, np.float32)
    if kv_scale is not None:
        qr = qr * np.asarray(kv_scale[0], np.float32)[None, :, None, None]
    q = mxu(qr)
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
            if ring_rows:  # the last position below the limit in that row
                gpos = gpos + ((int(limits[b]) - 1 - gpos) & ~(ring_rows - 1))
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
            acc[b] = acc[b] * alpha + np.einsum("kqn,nkd->kqd", mxu(p), vv)
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


_COMPILED = {}


def _one_compile(key, fn, kw):
    """`fn(q, k, v, table, limits, interpret=True, **kw)` under one `jax.jit`,
    kept under `key`. Tracing the interpreted kernel is most of what such a
    call costs here, so cases that differ in their data alone (a pool with
    NaN in the pages nobody lists) name the same key and share the program."""
    if key not in _COMPILED:
        _COMPILED[key] = jax.jit(lambda q, k, v, table, limits: fn(
            q, k, v, table, limits, interpret=True, **kw))
    return _COMPILED[key]


def _check_against_float64_walk(key, fn, q, k4, v4, table, limits, kw, pages,
                                flips=0.01):
    """A narrow-pool wrapper call (compiled once a `key`) against `_f64_walk`
    at `pages` a visit, which has to be what `_visit_pages` gives the call."""
    from localai_tpu.ops.paged_flash import _flat_rows, _visit_pages

    kw = dict(kw)
    B, K, D = q.shape[0], k4.shape[2], q.shape[-1]
    G = q.shape[-2] // K
    assert _flat_rows(k4.dtype, v4.dtype, K, G * (1 if q.ndim == 3 else 2))
    assert pages == _visit_pages(
        k4.shape[1], K, table.shape[1],
        (k4.shape[-1] + v4.shape[-1]) * k4.dtype.itemsize, flat=True,
        swin=kw.get("swin", 0))
    tbl = kw.pop("table", table)
    got = _one_compile(key, fn, kw)(q, k4, v4, tbl, limits)
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
