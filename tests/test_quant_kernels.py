"""The fused Pallas dequant-matmul kernels (ops/quant_matmul, ISSUE 9) in
interpret mode on the CPU against the XLA dequant oracle in models/quant.py:
the three weight forms, the block rule (ISSUE 36), the stack handed on with
a layer index (ISSUE 27) and what a decode step's trace counts of it, the
grouped expert kernel (ISSUE 38), tp = 2 shards. Quantization itself, the
engines and the configuration are tests/test_quant.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.models.quant import matmul, quantize_params, quantize_tensor, unembed_matmul


# --------------------------------------------------------------------------- #
# Fused Pallas dequant-matmul kernels (ISSUE 9, ops/quant_matmul) — interpret
# mode on CPU against the XLA dequant oracle in models/quant.py.
# --------------------------------------------------------------------------- #


def _grouped_int8(w, group=32):
    from localai_tpu.models.quant import GROUP_SIZE  # noqa: F401 — doc anchor

    g = w.shape[0] // group
    wg = w.reshape(g, group, w.shape[1])
    s = jnp.maximum(jnp.max(jnp.abs(wg), axis=1, keepdims=True) / 127.0, 1e-9)
    q = jnp.clip(jnp.round(wg / s), -127, 127).astype(jnp.int8)
    return {"gq": q, "gs": s}


@pytest.mark.parametrize("form", ["flat_int8", "grouped_int8", "packed_int4"])
def test_pallas_matmul_matches_xla_oracle(form):
    """Interpret-mode parity: the fused dequant-matmul kernel vs the XLA
    dequant path, for every weight representation."""
    from localai_tpu.models.quant import quantize_tensor_g4

    w = jax.random.normal(jax.random.key(0), (64, 96), jnp.float32) * 0.1
    if form == "flat_int8":
        q = quantize_tensor(w)
    elif form == "grouped_int8":
        q = _grouped_int8(w)
    else:
        q = quantize_tensor_g4(w)
    x = jax.random.normal(jax.random.key(1), (5, 64), jnp.float32)
    want = matmul(x, q, impl="xla")
    got = matmul(x, q, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_pallas_matmul_under_jit_and_scan():
    """The kernel must trace cleanly inside jit + lax.scan (the layer-stack
    shape every engine program uses)."""
    from localai_tpu.models.quant import quantize_tensor_g4

    L = 3
    w = jax.random.normal(jax.random.key(2), (L, 64, 64), jnp.float32) * 0.1
    q = jax.vmap(quantize_tensor_g4)(w)
    x = jax.random.normal(jax.random.key(3), (4, 64), jnp.float32)

    def run(impl):
        @jax.jit
        def fn(x, q):
            def body(h, lp):
                return matmul(h, lp, impl=impl), None

            return jax.lax.scan(body, x, q)[0]

        return fn(x, q)

    want = run("xla")
    got = run("pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("sub", ["...d,edf->...ef", "...ef,efd->...ed"])
@pytest.mark.parametrize("form", ["flat", "int4"])
def test_pallas_moe_mm_matches_xla_oracle(sub, form):
    from localai_tpu.models.llama import _moe_mm
    from localai_tpu.models.quant import quantize_tensor_g4

    E = 4
    qfn = quantize_tensor if form == "flat" else quantize_tensor_g4
    if sub == "...d,edf->...ef":
        wm = jax.random.normal(jax.random.key(4), (E, 64, 48), jnp.float32) * 0.1
        x = jax.random.normal(jax.random.key(5), (3, 64), jnp.float32)
    else:
        wm = jax.random.normal(jax.random.key(6), (E, 64, 48), jnp.float32) * 0.1
        x = jax.random.normal(jax.random.key(7), (3, E, 64), jnp.float32)
    q = jax.vmap(qfn)(wm)
    want = _moe_mm(x, q, sub, impl="xla")
    got = _moe_mm(x, q, sub, impl="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_pallas_unembed_matches_xla_oracle():
    V, D = 512, 64
    w = jax.random.normal(jax.random.key(8), (V, D), jnp.float32) * 0.1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0, 1e-9)
    q = {"q": jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8), "s": s}
    h = jax.random.normal(jax.random.key(9), (3, D), jnp.float32)
    want = unembed_matmul(h, q, impl="xla")
    got = unembed_matmul(h, q, impl="pallas")
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_pallas_disengages_at_prefill_rows():
    """Row counts past the decode threshold must fall back to the XLA path
    (the fused kernel's VMEM-resident layout is decode-shape only) — same
    numbers, no error."""
    from localai_tpu.models.quant import quantize_tensor_g4
    from localai_tpu.ops.quant_matmul import QUANT_PALLAS_MAX_ROWS, dispatch_matmul

    w = jax.random.normal(jax.random.key(10), (64, 64), jnp.float32) * 0.1
    q = quantize_tensor_g4(w)
    big = jax.random.normal(
        jax.random.key(11), (QUANT_PALLAS_MAX_ROWS + 1, 64), jnp.float32
    )
    assert dispatch_matmul(big, q, impl="pallas") is None
    np.testing.assert_allclose(
        np.asarray(matmul(big, q, impl="pallas")),
        np.asarray(matmul(big, q, impl="xla")),
        rtol=1e-5, atol=1e-5,
    )


def _quantize_form(w, form):
    from localai_tpu.models.quant import quantize_tensor_g4

    if form == "flat_int8":
        return quantize_tensor(w)
    if form == "grouped_int8":
        lead = w.shape[:-2]
        q = jax.vmap(_grouped_int8)(w.reshape(-1, *w.shape[-2:]))
        return {k: v.reshape(*lead, *v.shape[1:]) for k, v in q.items()}
    return quantize_tensor_g4(w)


def _layer(q, l):
    return {k: v[l] for k, v in q.items()}


_SHARED_X, _PER_EXPERT_X = "...d,edf->...ef", "...ef,efd->...ed"


# --------------------------------------------------------------------------- #
# The block rule (ISSUE 36): a grid step's weight block in bytes and whole rows
# --------------------------------------------------------------------------- #

# (rows, in, out, group size, packed, whole rows expected)
_RULE_SHAPES = {
    "mistral_gate_up": (32, 4096, 14336, 0, False, True),
    "mistral_down": (32, 14336, 4096, 0, False, True),
    "mistral_kv": (32, 4096, 1024, 0, False, True),
    "olmoe_up": (32, 2048, 1024, 0, False, True),
    "olmoe_down": (32, 1024, 2048, 0, False, True),
    "kimi_up": (64, 2304, 1024, 0, False, True),
    "kimi_down": (64, 1024, 2304, 0, False, True),
    "kimi_proj": (64, 2304, 4096, 0, False, True),
    "solar_up": (64, 4096, 1280, 0, False, True),
    "solar_down": (64, 1280, 4096, 0, False, True),
    "solar_q": (64, 4096, 8192, 0, False, True),
    "verify_256_rows": (256, 4096, 14336, 0, False, False),
    "verify_256_rows_down": (256, 14336, 4096, 0, False, True),
    "tp_local_3584": (32, 4096, 3584, 0, False, True),
    "tp_local_3584_down": (32, 3584, 4096, 0, False, True),
    # 8 groups of 128 are 1,024 rows: at 4,096 wide a 4 MB block, twice
    "grouped_128": (32, 14336, 4096, 128, False, False),
    "grouped_32_moe": (64, 2304, 1024, 32, False, True),
    # 8 groups of 128 are 1,024 rows: at 14,336 wide a 7 MB block, twice
    "int4_128": (32, 4096, 14336, 128, True, False),
    "int4_32": (32, 4096, 14336, 32, True, True),
    "int4_256_rows": (256, 4096, 14336, 32, True, False),
    "one_row": (1, 4096, 14336, 0, False, True),
    "tiny": (5, 64, 96, 0, False, True),
    "tiny_int4": (5, 64, 96, 32, True, True),
    "odd_288_384": (5, 288, 384, 0, False, True),
    "odd_grouped": (5, 288, 384, 32, False, True),
}


@pytest.mark.parametrize("shape", list(_RULE_SHAPES))
def test_block_rule_divides_aligns_fits_and_prefers_whole_rows(monkeypatch, shape):
    """`_blocks` as a pure function: the block divides both axes, meets the
    lane / sublane / group alignment Mosaic asks of every BlockSpec, fits
    the budget it counted, stays within the byte target unless one legal
    chunk of rows is already more, and is whole-row wherever whole rows
    fit (narrowed, to a lane multiple, only at 256 rows x 14,336)."""
    from localai_tpu.ops import quant_matmul as QM

    n, kin, out, gs, packed, wholerow = _RULE_SHAPES[shape]
    b = QM._blocks(n, kin, out, gs=gs, packed=packed, zeros=packed)
    assert kin % b.kc == 0 and out % b.bo == 0
    assert b.kc % b.sk == 0 and b.bo % b.so == 0
    assert b.kc == kin or b.kc % 128 == 0  # x's lane tile, int8 sublanes
    assert b.bo == out or b.bo % 128 == 0
    assert b.sk == b.kc or b.sk % 128 == 0
    assert b.so == b.bo or b.so % 128 == 0
    assert b.xk in (kin, b.kc)
    if gs:
        assert b.kc % gs == 0 and b.sk % gs == 0 and b.gc == b.kc // gs
        assert b.kc == kin or b.gc % 8 == 0  # the scale block's sublane tile
        assert b.sk == b.kc or (b.sk // gs) % 8 == 0
    else:
        assert b.gc == 1
    assert QM._held(n, kin, b.kc, b.bo, gs=gs, packed=packed,
                    zeros=packed) <= QM.VMEM_BUDGET
    assert (b.bo == out) == wholerow
    block_bytes = b.kc * b.bo // (2 if packed else 1)
    smaller = [kc for kc in range(128, b.kc, 128)
               if kin % kc == 0 and (not gs or (kc // gs) % 8 == 0 and kc % gs == 0)]
    assert block_bytes <= QM.BLOCK_BYTES or not smaller
    # with room for everything the rule never narrows
    monkeypatch.setattr(QM, "VMEM_BUDGET", 1 << 40)
    assert QM._blocks(n, kin, out, gs=gs, packed=packed, zeros=packed).bo == out


def test_kernels_ask_for_no_scoped_vmem_of_their_own():
    """Both kernels run under Mosaic's default scoped VMEM (16 MiB on the
    v5e) and the rule's budget stays inside it. A raised limit changes what
    XLA does around the call: with 48 and with 32 MiB asked for,
    kimi-linear's admission did not come back on the chip."""
    from localai_tpu.ops import quant_matmul as QM

    assert QM.VMEM_BUDGET < 16 << 20
    x = jax.ShapeDtypeStruct((4, 256), jnp.bfloat16)
    w = {"q": jax.ShapeDtypeStruct((256, 384), jnp.int8),
         "s": jax.ShapeDtypeStruct((1, 384), jnp.float32)}
    head = {"q": jax.ShapeDtypeStruct((384, 256), jnp.int8),
            "s": jax.ShapeDtypeStruct((384, 1), jnp.float32)}
    jaxpr = jax.make_jaxpr(lambda x, w, h: (
        QM.dispatch_matmul(x, w, impl="pallas"),
        QM.dispatch_unembed(x, h, impl="pallas")))(x, w, head)
    calls = (_pallas_calls(jaxpr.jaxpr, "int8_matmul")
             + _pallas_calls(jaxpr.jaxpr, "int8_unembed"))
    assert len(calls) == 2
    for eqn in calls:
        params = eqn.params["compiler_params"]
        limit = getattr(params.get("mosaic_tpu"), "vmem_limit_bytes", None)
        assert limit is None


@pytest.mark.parametrize("shape", [(32, 32000, 4096), (32, 50304, 2048),
                                   (64, 163840, 2304), (64, 24576, 4096),
                                   (3, 512, 64)])
def test_unembed_block_rule_takes_whole_rows_of_the_head(shape):
    from localai_tpu.ops import quant_matmul as QM

    n, v, d = shape
    bv, kc, sv = QM._unembed_blocks(n, v, d)
    assert kc == d  # whole rows of [V, D]: one contiguous run a block
    assert v % bv == 0 and bv % sv == 0
    assert bv == v or bv % 128 == 0
    assert sv == bv or sv % 128 == 0
    assert bv * kc <= QM.BLOCK_BYTES or bv == 128


def _odd_case(form, shape, kin=288, out=384, L=2, E=2):
    """Small analogues of the cells' odd widths: out = 384 = 3 x 128 lanes
    (2304, 1280, 3584 are 18, 10, 28), in = 288 = 9 x 32 sublanes."""
    moe = shape != "plain"
    w = jax.random.normal(
        jax.random.key(30), (L, E, kin, out) if moe else (L, kin, out)) * 0.1
    x = jax.random.normal(
        jax.random.key(31), (5, E, kin) if shape == "moe_per_expert_x" else (5, kin))
    sub = _PER_EXPERT_X if shape == "moe_per_expert_x" else _SHARED_X
    q = _quantize_form(w, form)

    def mm(w, impl):
        from localai_tpu.models.llama import _moe_mm
        return _moe_mm(x, w, sub, impl=impl) if moe else matmul(x, w, impl=impl)

    return q, mm, L


# How the rule is bent to reach each branch of the kernel at a small size:
# the module's constants are what `_blocks` reads when it is called.
_RULE_BENDS = {
    "as_is": {},
    # a 128 x 128 sub-tile: the rolled walk inside the step, both axes
    "sub_tile_walk": {"TILE_ELEMS": 128 * 128},
    # 128 rows a step: several k-chunks, x resident whole and indexed by k
    "k_chunks": {"BLOCK_BYTES": 128 * 384},
}


@pytest.mark.parametrize("bend,form,shape,kin", [
    ("as_is", "flat_int8", "plain", 288),
    ("as_is", "grouped_int8", "plain", 288),
    ("as_is", "packed_int4", "plain", 288),
    ("as_is", "flat_int8", "moe_shared_x", 288),
    ("as_is", "flat_int8", "moe_per_expert_x", 288),
    ("sub_tile_walk", "flat_int8", "moe_shared_x", 288),
    ("sub_tile_walk", "flat_int8", "plain", 768),
    ("sub_tile_walk", "grouped_int8", "plain", 768),
    ("sub_tile_walk", "packed_int4", "moe_per_expert_x", 768),
    ("k_chunks", "flat_int8", "plain", 768),
    ("k_chunks", "grouped_int8", "plain", 768),
    ("k_chunks", "packed_int4", "moe_per_expert_x", 768),
])
def test_block_rule_kernels_match_xla_at_odd_widths(monkeypatch, bend, form,
                                                    shape, kin):
    """Interpret-mode agreement with the XLA oracle where an axis is no
    power of two, stacked at the first and the last layer, with the rule
    as it is and bent so that the in-kernel sub-tile walk and the k-chunk
    walk over a resident x both run."""
    from localai_tpu.models.quant import StackedLayer
    from localai_tpu.ops import quant_matmul as QM

    for name, value in _RULE_BENDS[bend].items():
        monkeypatch.setattr(QM, name, value)
    q, mm, L = _odd_case(form, shape, kin=kin)
    gs = 0 if form == "flat_int8" else 32
    b = QM._blocks(5, kin, 384, gs=gs, packed=form == "packed_int4")
    if bend == "sub_tile_walk":
        assert (b.kc // b.sk) * (b.bo // b.so) > 1
    if bend == "k_chunks":
        assert kin // b.kc > 1 and b.xk == kin
    for l in (0, L - 1):
        got = mm(StackedLayer(q, jnp.int32(l)), "pallas")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(mm(_layer(q, l), "xla")),
            rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("form", ["flat_int8", "packed_int4"])
def test_block_rule_narrows_where_whole_rows_do_not_fit(monkeypatch, form):
    """A budget that cannot hold a full-width step: the block becomes a
    lane-multiple column strip, x a chunk a step, the site counts as
    narrowed, and the numbers are still the oracle's."""
    from localai_tpu.ops import quant_matmul as QM
    from localai_tpu.ops.stacked import SiteCounts

    gs, packed = (0, False) if form == "flat_int8" else (32, True)
    call = dict(gs=gs, packed=packed, zeros=packed, x_bytes=4, out_bytes=4)
    for budget in range(64 << 10, 8 << 20, 32 << 10):
        monkeypatch.setattr(QM, "VMEM_BUDGET", budget)
        b = QM._blocks(5, 768, 384, **call)
        if QM._held(5, 768, b.kc, b.bo, **call) <= budget:
            break
    assert b.bo == 128 and b.kc < 768
    if form == "flat_int8":  # float32 rows past a quarter of the budget
        assert b.xk == b.kc
    q, mm, L = _odd_case(form, "plain", kin=768)
    sites = SiteCounts()
    with sites.tracing("call"):
        got = mm(_layer(q, 1), "pallas")
    assert sites.by_program["call"]["narrowed"] == 1
    assert sites.by_program["call"]["wholerow"] == 0
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(mm(_layer(q, 1), "xla")),
        rtol=2e-4, atol=2e-4)


def test_unembed_kernel_walks_its_block_in_row_sub_tiles(monkeypatch):
    """Kimi-Linear's head in small: V = 1280 = 10 x 128 rows of D = 288; a
    small tile makes the kernel convert the block 128 rows at a time."""
    from localai_tpu.ops import quant_matmul as QM

    V, D = 1280, 288
    w = jax.random.normal(jax.random.key(40), (V, D), jnp.float32) * 0.1
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=-1, keepdims=True) / 127.0, 1e-9)
    q = {"q": jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8), "s": s}
    h = jax.random.normal(jax.random.key(41), (3, D), jnp.float32)
    want = unembed_matmul(h, q, impl="xla")
    for tile in (QM.TILE_ELEMS, 128 * D):
        monkeypatch.setattr(QM, "TILE_ELEMS", tile)
        bv, kc, sv = QM._unembed_blocks(3, V, D)
        assert (bv, kc) == (1280, D) and sv == (1280 if tile > V * D else 128)
        np.testing.assert_allclose(
            np.asarray(unembed_matmul(h, q, impl="pallas")), np.asarray(want),
            rtol=1e-4, atol=1e-4)


def test_a_256_row_call_at_14336_wide_counts_as_narrowed():
    """The row limit at mistral's ffn width: a full-width accumulator is
    14.7 MB, so the rule narrows `out` and the site says so (traced only)."""
    from localai_tpu.ops.quant_matmul import dispatch_matmul
    from localai_tpu.ops.stacked import SiteCounts

    x = jax.ShapeDtypeStruct((256, 4096), jnp.bfloat16)
    w = {"q": jax.ShapeDtypeStruct((4096, 14336), jnp.int8),
         "s": jax.ShapeDtypeStruct((1, 14336), jnp.float32)}
    sites = SiteCounts()
    with sites.tracing("verify"):
        y = jax.eval_shape(lambda x, w: dispatch_matmul(x, w, impl="pallas"), x, w)
    assert y.shape == (256, 14336)
    tally = sites.by_program["verify"]
    assert (tally["narrowed"], tally["wholerow"]) == (1, 0)
    with sites.tracing("decode"):
        jax.eval_shape(lambda x, w: dispatch_matmul(x, w, impl="pallas"),
                       jax.ShapeDtypeStruct((32, 4096), jnp.bfloat16), w)
    tally = sites.by_program["decode"]
    assert (tally["narrowed"], tally["wholerow"]) == (0, 1)


@pytest.mark.parametrize("shape", ["plain", "moe_shared_x", "moe_per_expert_x"])
@pytest.mark.parametrize("form", ["flat_int8", "grouped_int8", "packed_int4"])
def test_stacked_kernel_is_bit_identical_to_sliced(form, shape):
    """The kernel reading layer l out of the stacked weights (scalar-prefetch
    index) runs the sliced call's blocks in its order: equal bit for bit at
    the first, a middle and the last layer, and close to the XLA oracle."""
    from localai_tpu.models.llama import _moe_mm
    from localai_tpu.models.quant import StackedLayer

    L, E = 4, 3
    moe = shape != "plain"
    w = jax.random.normal(
        jax.random.key(20), (L, E, 64, 96) if moe else (L, 64, 96)) * 0.1
    q = _quantize_form(w, form)
    x = jax.random.normal(
        jax.random.key(21), (5, E, 64) if shape == "moe_per_expert_x" else (5, 64))
    sub = _PER_EXPERT_X if shape == "moe_per_expert_x" else _SHARED_X

    def mm(w, impl):
        return _moe_mm(x, w, sub, impl=impl) if moe else matmul(x, w, impl=impl)

    for l in (0, 2, L - 1):
        stacked = mm(StackedLayer(q, jnp.int32(l)), "pallas")
        np.testing.assert_array_equal(
            np.asarray(stacked), np.asarray(mm(_layer(q, l), "pallas")))
        np.testing.assert_allclose(
            np.asarray(stacked), np.asarray(mm(_layer(q, l), "xla")),
            rtol=2e-4, atol=2e-4)
        # ... and sliced at the use site, the view is the plain layer
        np.testing.assert_array_equal(
            np.asarray(mm(StackedLayer(q, jnp.int32(l)), "xla")),
            np.asarray(mm(_layer(q, l), "xla")))


@pytest.mark.parametrize("form", ["flat_int8", "packed_int4"])
def test_stacked_kernel_under_scan_with_a_traced_index(form):
    """llama._scan_stack hands the body the stack and its loop counter; the
    quantized leaves reach the kernel unsliced, the plain ones sliced."""
    from localai_tpu.models.llama import _scan_stack
    from localai_tpu.models.quant import StackedLayer

    L = 3
    w = jax.random.normal(jax.random.key(22), (L, 64, 64)) * 0.1
    stack = {"w": _quantize_form(w, form), "b": jnp.arange(L, dtype=jnp.float32)}
    x = jax.random.normal(jax.random.key(23), (4, 64))

    def run(impl):
        def layer(h, xs):
            lp, i = xs
            assert isinstance(lp["w"], StackedLayer) and lp["b"].shape == ()
            return matmul(h, lp["w"], impl=impl) + lp["b"], i

        return jax.jit(lambda h, st: _scan_stack(layer, h, st, 1, L + 1, ()))(x, stack)

    (got, idx), (want, _) = run("pallas"), run("xla")
    assert idx.tolist() == [1, 2, 3]  # the body's layer number counts from lo
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# dense-cache programs hold no paged-attention site (ops/stacked.SiteCounts)
_NO_PAGED_SITES = {"paged_attention_stacked": 0, "paged_attention_sliced": 0,
                   "paged_attention_native": 0, "paged_attention_f32": 0,
                   "paged_attention_multipage": 0,
                   "paged_attention_onepage": 0,
                   "paged_attention_stream": 0,
                   "paged_attention_prefetch": 0,
                   "paged_attention_value_lanes": 0,
                   "paged_attention_value_row": 0,
                   "pool_write_inplace": 0, "pool_write_scatter": 0,
                   "ssd_decode_pallas": 0, "ssd_decode_xla": 0,
                   "s6_decode_pallas": 0, "s6_decode_xla": 0}
# the seven Pallas dequant-matmul calls of a decode step, by the rule's block
# a dense model has no expert matmul to take the grouped kernel (`grouped`)
_WHOLEROW_7 = {"wholerow": 7, "narrowed": 0, "grouped": 0}
_NO_BLOCKS = {"wholerow": 0, "narrowed": 0, "grouped": 0}


def _pallas_calls(jaxpr, name):
    """Every pallas_call equation named `name`, through all sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and (
                eqn.params["name"] == name):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found.extend(_pallas_calls(sub, name))
    return found


def _int8_decode_step(B, impl):
    import dataclasses

    from localai_tpu.models import llama

    cfg = dataclasses.replace(get_arch("tiny"), quant_kernel=impl)
    params = quantize_params(cfg, init_params(cfg, jax.random.key(0)), "int8")
    n, kv = 4, (cfg.num_kv_heads, cfg.head_dim_)
    cache = llama.KVCache(
        k=jnp.zeros((cfg.num_layers, B, 32, *kv), jnp.bfloat16),
        v=jnp.zeros((cfg.num_layers, B, 32, *kv), jnp.bfloat16))
    local = jnp.zeros((cfg.num_layers, B, n, *kv), jnp.bfloat16)
    tok = jnp.arange(B, dtype=jnp.int32) % cfg.vocab_size
    fn = lambda p, t, pos, c, lk, lv, s: llama.decode_step_windowed(  # noqa: E731
        cfg, p, t, pos, c, lk, lv, s)
    return cfg, fn, (params, tok, tok % 8, cache, local, local, jnp.int32(0))


def test_decode_step_hands_the_kernels_the_stack_and_counts_it():
    """In the decode step's jaxpr every int8_matmul takes a weight whose
    leading dimension is the layer count, none a [1, in, out] copy, and the
    site counter saw the same seven."""
    from localai_tpu.ops.stacked import SiteCounts

    cfg, fn, args = _int8_decode_step(2, "pallas")
    sites = SiteCounts()
    with sites.tracing("decode_block"):
        jaxpr = jax.make_jaxpr(fn)(*args)
    calls = _pallas_calls(jaxpr.jaxpr, "int8_matmul")
    assert len(calls) == 7  # q, k, v, o, gate, up, down: once in the layer scan
    for eqn in calls:
        weights = [v.aval for v in eqn.invars if v.aval.dtype == jnp.int8]
        assert [w.ndim for w in weights] == [3]
        assert weights[0].shape[0] == cfg.num_layers > 1
    assert sites.by_program == {
        "decode_block": {"traces": 1, "stacked": 7, "sliced": 0, **_WHOLEROW_7,
                         **_NO_PAGED_SITES}}
    assert sites.totals() == {"stacked": 7, "sliced": 0, **_WHOLEROW_7,
                              **_NO_PAGED_SITES}


def _eqns(jaxpr):
    """Every equation, through all sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _paged_decode_step(paged_impl):
    """The int8 decode step over a PAGED pool [L, P, page, K, D]."""
    from localai_tpu.models import llama

    cfg, _, (params, tok, _, _, local, _, step) = _int8_decode_step(2, "pallas")
    kv = (cfg.num_kv_heads, cfg.head_dim_)
    kk, kv_key = jax.random.split(jax.random.key(5))
    pool = llama.KVCache(
        k=jax.random.normal(kk, (cfg.num_layers, 7, 8, *kv), jnp.bfloat16),
        v=jax.random.normal(kv_key, (cfg.num_layers, 7, 8, *kv), jnp.bfloat16))
    table = jnp.array([[0, 1, 2], [3, 4, 5]], jnp.int32)
    pos = jnp.array([19, 9], jnp.int32)
    fn = lambda p, c, lk, lv: llama.decode_step_windowed(  # noqa: E731
        cfg, p, tok, pos, c, lk, lv, step, ptable=table, paged_impl=paged_impl)
    return cfg, fn, (params, pool, local, local)


def test_paged_decode_step_hands_the_kernel_the_pool_and_counts_it():
    """In the PAGED decode step's jaxpr the one paged_attention call takes
    both pools whole, all L layers of them (a bfloat16 pool in the view it
    is stored as, [L, P, page·K, D]: ISSUE 32); nothing slices a layer's
    pool out in front of it; the site counter saw 1 stacked, 0 sliced, the
    page handed on as stored, beside the seven matmuls. With the XLA walk it saw 0 / 1, the slice is there (at
    the walk's own site) and the numbers agree."""
    from localai_tpu.ops.stacked import SiteCounts

    seen = {}
    for impl in ("pallas", "xla"):
        cfg, fn, args = _paged_decode_step(impl)
        sites = SiteCounts()
        with sites.tracing("decode_block"):
            jaxpr = jax.make_jaxpr(fn)(*args)
        pool_shape = args[1].k.shape
        layer_pools = [e for e in _eqns(jaxpr.jaxpr)
                       if e.primitive.name != "pallas_call" and any(
                           v.aval.shape == pool_shape[1:] for v in e.outvars)]
        seen[impl] = (_pallas_calls(jaxpr.jaxpr, "paged_attention"),
                      layer_pools, sites.by_program["decode_block"],
                      jax.jit(fn)(*args)[0])
    calls, layer_pools, tally, got = seen["pallas"]
    assert len(calls) == 1  # once, in the layer scan
    L, P, page, K, D = pool_shape
    pools = [v.aval for v in calls[0].invars
             if v.aval.dtype == args[1].k.dtype and v.aval.ndim >= 4]
    assert args[1].k.dtype == jnp.bfloat16
    assert [p.shape for p in pools] == [(L, P, page * K, D)] * 2
    assert pool_shape[0] == cfg.num_layers > 1
    assert not layer_pools
    assert tally == {"traces": 1, "stacked": 7, "sliced": 0, **_WHOLEROW_7,
                     "paged_attention_stacked": 1, "paged_attention_sliced": 0,
                     "paged_attention_native": 1, "paged_attention_f32": 0,
                     # 8-row pages: a visit is the table's three columns
                     "paged_attention_multipage": 1,
                     "paged_attention_onepage": 0,
                     # one stream of visits over all the slots (no swin)
                     "paged_attention_stream": 1,
                     "paged_attention_prefetch": 0,
                     # a GQA pool: no latent call to state a value width
                     "paged_attention_value_lanes": 0,
                     "paged_attention_value_row": 0,
                     # a decode STEP: the block's pool write is not in it
                     "pool_write_inplace": 0, "pool_write_scatter": 0,
                   "ssd_decode_pallas": 0, "ssd_decode_xla": 0,
                   "s6_decode_pallas": 0, "s6_decode_xla": 0}
    calls, layer_pools, tally, want = seen["xla"]
    assert not calls and len(layer_pools) >= 2  # K and V, sliced at the walk
    assert tally == {"traces": 1, "stacked": 7, "sliced": 0, **_WHOLEROW_7,
                     "paged_attention_stacked": 0, "paged_attention_sliced": 1,
                     "paged_attention_native": 0, "paged_attention_f32": 0,
                     "paged_attention_multipage": 0,
                     "paged_attention_onepage": 0,
                     "paged_attention_stream": 0,
                     "paged_attention_prefetch": 0,
                     "paged_attention_value_lanes": 0,
                     "paged_attention_value_row": 0,
                     "pool_write_inplace": 0, "pool_write_scatter": 0,
                   "ssd_decode_pallas": 0, "ssd_decode_xla": 0,
                   "s6_decode_pallas": 0, "s6_decode_xla": 0}
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-2, atol=2e-2)


def test_decode_step_above_the_row_limit_slices_at_the_use_site():
    """Rows above QUANT_PALLAS_MAX_ROWS: no kernel in the jaxpr, every site
    counts as sliced, and the numbers are the XLA path's."""
    from localai_tpu.ops.quant_matmul import QUANT_PALLAS_MAX_ROWS
    from localai_tpu.ops.stacked import SiteCounts

    B = QUANT_PALLAS_MAX_ROWS + 1
    _, fn, args = _int8_decode_step(B, "pallas")
    sites = SiteCounts()
    with sites.tracing("admit"):
        jaxpr = jax.make_jaxpr(fn)(*args)
    assert not _pallas_calls(jaxpr.jaxpr, "int8_matmul")
    assert not _pallas_calls(jaxpr.jaxpr, "int8_unembed")
    assert sites.by_program["admit"] == {
        "traces": 1, "stacked": 0, "sliced": 7, **_NO_BLOCKS, **_NO_PAGED_SITES}
    _, fn_xla, _ = _int8_decode_step(B, "xla")
    got, want = jax.jit(fn)(*args)[0], jax.jit(fn_xla)(*args)[0]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------- #
# The grouped kernel (ISSUE 38): expert-sorted rows over the quantized stack
# --------------------------------------------------------------------------- #

# name: (sorted rows M, rows a group [E], row tile, form, layer of 3, bend)
_GROUPED_CASES = {
    "empty_groups": (200, [0, 90, 0, 0, 110, 0], 64, "flat_int8", 0, "as_is"),
    "a_group_over_several_tiles": (320, [10, 290, 20], 64, "flat_int8", 0, "as_is"),
    "several_groups_in_one_tile": (64, [7, 9, 1, 30, 17], 64, "flat_int8", 0, "as_is"),
    "all_rows_in_one_expert": (192, [0, 0, 192, 0], 64, "flat_int8", 0, "as_is"),
    "rows_no_multiple_of_the_tile": (150, [70, 80], 64, "flat_int8", 0, "as_is"),
    "fewer_rows_than_a_tile": (24, [5, 0, 19], 64, "flat_int8", 0, "as_is"),
    "rows_in_no_held_group": (256, [20, 0, 3, 10], 64, "flat_int8", 0, "as_is"),
    "no_row_held_at_all": (128, [0, 0, 0], 64, "flat_int8", 0, "as_is"),
    "a_layer_of_the_stack": (200, [60, 0, 140], 64, "flat_int8", 2, "as_is"),
    "two_k_chunks": (200, [60, 40, 100], 64, "flat_int8", 1, "k_chunks"),
    "two_k_chunks_rows_in_no_group": (256, [9, 0, 40], 64, "flat_int8", 1, "k_chunks"),
    "sub_tile_walk": (200, [60, 40, 100], 64, "flat_int8", 1, "sub_tile_walk"),
    "grouped_int8": (200, [60, 0, 140], 64, "grouped_int8", 1, "as_is"),
    "grouped_int8_k_chunks": (200, [60, 0, 140], 64, "grouped_int8", 2, "k_chunks"),
    "packed_int4": (200, [60, 0, 140], 64, "packed_int4", 1, "as_is"),
    "packed_int4_k_chunks": (150, [70, 80], 64, "packed_int4", 2, "k_chunks"),
}


@pytest.mark.parametrize("case", list(_GROUPED_CASES))
def test_grouped_kernel_matches_the_xla_form(monkeypatch, case):
    """Interpret mode against `lax.ragged_dot` on the layer's slice
    (llama._ragged_mm): every row of a group agrees; the rows in no group
    are left to the caller, which zeroes them (`_moe_ragged`, below)."""
    from localai_tpu.models.llama import _ragged_mm
    from localai_tpu.ops import quant_matmul as QM

    m, sizes, tm, form, layer, bend = _GROUPED_CASES[case]
    kin, out, L = (768, 384, 3)  # 384 = 3 x 128 lanes, as 1280 is 10
    for name, value in _RULE_BENDS[bend].items():
        monkeypatch.setattr(QM, name, value)
    monkeypatch.setattr(QM, "GROUP_ROWS", tm)
    gs = 0 if form == "flat_int8" else 32
    b = QM._blocks(min(tm, m), kin, out, gs=gs, packed=form == "packed_int4",
                   zeros=form == "packed_int4")
    if bend == "k_chunks":
        assert kin // b.kc > 1
    if bend == "sub_tile_walk":
        assert (b.kc // b.sk) * (b.bo // b.so) > 1
    E = len(sizes)
    w = jax.random.normal(jax.random.key(40), (L, E, kin, out)) * 0.1
    q = _quantize_form(w, form)
    xg = jax.random.normal(jax.random.key(41), (m, kin), jnp.float32)
    sz = jnp.asarray(sizes, jnp.int32)
    held = int(sum(sizes))
    assert QM.grouped_engaged(xg, q, "pallas", None, jnp.int32(layer))
    got = jax.jit(lambda xg, q, sz, l: QM.grouped_moe_mm(
        xg, q, QM.group_visits(sz, m), layer=l))(xg, q, sz, jnp.int32(layer))
    assert got.shape == (m, out)
    # the oracle lets the rows in no group ride in the last one
    group = jnp.minimum(jnp.repeat(jnp.arange(E + 1), jnp.asarray(
        sizes + [m - held]), total_repeat_length=m), E - 1)
    want = _ragged_mm(xg, _layer(q, layer), sz.at[-1].add(m - held), group)
    np.testing.assert_allclose(np.asarray(got)[:held], np.asarray(want)[:held],
                               rtol=2e-4, atol=2e-4)


def test_grouped_visits_walk_tiles_and_groups_in_sorted_order():
    """The walk as a pure function: one visit a (tile, group) pair that
    shares a row, none for an empty group or a tile in no group, padding
    visits repeat the last real one."""
    from localai_tpu.ops import quant_matmul as QM

    assert QM.GROUP_ROWS == 64
    nvis, gid, tid, off = QM.group_visits(
        jnp.asarray([0, 130, 0, 2, 60]), 640)
    assert int(nvis[0]) == 5 and gid.shape == (10 + 5 - 1,)
    assert gid.tolist()[:5] == [1, 1, 1, 3, 4]
    assert tid.tolist()[:5] == [0, 1, 2, 2, 2]  # rows 130-191 share tile 2
    assert set(zip(gid.tolist()[5:], tid.tolist()[5:])) == {(4, 2)}
    assert off.tolist() == [0, 0, 130, 130, 132, 192]
    nvis, gid, tid, _ = QM.group_visits(jnp.asarray([0, 0]), 128)
    assert int(nvis[0]) == 0 and gid.tolist() == [1, 1, 1] and tid.tolist() == [0] * 3


def test_grouped_kernel_asks_for_no_scoped_vmem_and_tiles_x():
    """x is tiled GROUP_ROWS at a time whatever the rows (the check's 2,000
    token prompt is 16,384 sorted rows), the stack rides whole with the
    layer index scalar-prefetched, and no VMEM limit is asked for."""
    from localai_tpu.ops import quant_matmul as QM

    xg = jax.ShapeDtypeStruct((16384, 256), jnp.bfloat16)
    w = {"q": jax.ShapeDtypeStruct((3, 8, 256, 384), jnp.int8),
         "s": jax.ShapeDtypeStruct((3, 8, 1, 384), jnp.float32)}
    jaxpr = jax.make_jaxpr(lambda xg, w, sz, l: QM.grouped_moe_mm(
        xg, w, QM.group_visits(sz, 16384), layer=l))(
            xg, w, jax.ShapeDtypeStruct((8,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
    (eqn,) = _pallas_calls(jaxpr.jaxpr, "int8_grouped_matmul")
    limit = getattr(eqn.params["compiler_params"].get("mosaic_tpu"),
                    "vmem_limit_bytes", None)
    assert limit is None
    int8 = [v.aval.shape for v in eqn.invars if v.aval.dtype == jnp.int8]
    assert int8 == [(3 * 8, 256, 384)]
    x_block = eqn.params["grid_mapping"].block_mappings[0].block_shape
    assert tuple(int(getattr(d, "block_size", d)) for d in x_block) == (
        1, QM.GROUP_ROWS, 256)


@pytest.mark.multichip
def test_pallas_matmul_sharded_tp2(multichip):
    """tp=2 shard_map dispatch: col (out axis), row (group axis + psum at
    the declared boundary), unembed (vocab axis), MoE — all against the
    unsharded XLA oracle."""
    if multichip is True:
        return  # verdict delivered by the subprocess re-run
    from localai_tpu.models.llama import _moe_mm
    from localai_tpu.models.quant import quantize_tensor_g4
    from localai_tpu.parallel.mesh import MeshPlan as MP_, build_mesh

    mesh = build_mesh(MP_(tp=2))
    w = jax.random.normal(jax.random.key(12), (64, 96), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.key(13), (5, 64), jnp.float32)
    q4 = quantize_tensor_g4(w)
    qf = quantize_tensor(w)
    with mesh:
        for q, part in ((q4, "col"), (q4, "row"), (qf, "col"), (qf, "row")):
            want = matmul(x, q, impl="xla")
            got = jax.jit(
                lambda x, q, part=part: matmul(x, q, impl="pallas",
                                               mesh=mesh, part=part)
            )(x, q)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=2e-4, atol=2e-4)
        # unembed (vocab-parallel)
        V, D = 512, 64
        wl = jax.random.normal(jax.random.key(14), (V, D), jnp.float32) * 0.1
        s = jnp.maximum(jnp.max(jnp.abs(wl), -1, keepdims=True) / 127.0, 1e-9)
        ql = {"q": jnp.clip(jnp.round(wl / s), -127, 127).astype(jnp.int8),
              "s": s}
        h = jax.random.normal(jax.random.key(15), (3, D), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(jax.jit(lambda h, q: unembed_matmul(
                h, q, impl="pallas", mesh=mesh))(h, ql)),
            np.asarray(unembed_matmul(h, ql, impl="xla")),
            rtol=1e-4, atol=1e-4,
        )
        # MoE, both einsum shapes
        E = 4
        wm = jax.random.normal(jax.random.key(16), (E, 64, 64), jnp.float32) * 0.1
        qm = jax.vmap(quantize_tensor_g4)(wm)
        xm = jax.random.normal(jax.random.key(17), (3, 64), jnp.float32)
        x2 = jax.random.normal(jax.random.key(18), (3, E, 64), jnp.float32)
        for xx, sub in ((xm, "...d,edf->...ef"), (x2, "...ef,efd->...ed")):
            np.testing.assert_allclose(
                np.asarray(jax.jit(lambda x, q, sub=sub: _moe_mm(
                    x, q, sub, impl="pallas", mesh=mesh))(xx, qm)),
                np.asarray(_moe_mm(xx, qm, sub, impl="xla")),
                rtol=2e-4, atol=2e-4,
            )


@pytest.mark.multichip
def test_pallas_matmul_stacked_sharded_tp2(multichip):
    """tp=2 shard_map with the weights still stacked: the layer axis stays
    whole on every shard, the index is replicated; col, row (+psum) and both
    MoE shapes equal the sharded call on the sliced layer bit for bit."""
    if multichip is True:
        return  # verdict delivered by the subprocess re-run
    from localai_tpu.models.llama import _moe_mm
    from localai_tpu.models.quant import StackedLayer
    from localai_tpu.parallel.mesh import MeshPlan as MP_, build_mesh

    mesh = build_mesh(MP_(tp=2))
    L, E, l = 3, 4, 2
    w = jax.random.normal(jax.random.key(24), (L, 64, 96), jnp.float32) * 0.1
    wm = jax.random.normal(jax.random.key(25), (L, E, 64, 64), jnp.float32) * 0.1
    x = jax.random.normal(jax.random.key(26), (5, 64), jnp.float32)
    xe = jax.random.normal(jax.random.key(27), (5, E, 64), jnp.float32)

    def both(fn, xx, q):
        run = jax.jit(lambda xx, q, i: (fn(xx, StackedLayer(q, i)),
                                        fn(xx, _layer(q, l))))
        return run(xx, q, jnp.int32(l))

    with mesh:
        for form in ("flat_int8", "packed_int4"):
            q, qm = _quantize_form(w, form), _quantize_form(wm, form)
            cases = [
                (x, q, lambda xx, ww, part=part: matmul(
                    xx, ww, impl="pallas", mesh=mesh, part=part))
                for part in ("col", "row")
            ] + [
                (xx, qm, lambda xx, ww, sub=sub: _moe_mm(
                    xx, ww, sub, impl="pallas", mesh=mesh))
                for xx, sub in ((x, _SHARED_X), (xe, _PER_EXPERT_X))
            ]
            for xx, qq, fn in cases:
                stacked, sliced = both(fn, xx, qq)
                np.testing.assert_array_equal(np.asarray(stacked),
                                              np.asarray(sliced))
