"""Granite-4.0-H (ibm-granite/granite-4.0-h-small): Mamba-2 (SSD) layers with
a per-slot float32 state beside NoPE GQA layers over the paged K/V pool, the
attention layer BEHIND a Mamba layer of its own; every layer experts routed
the "mixtral" way (softmax over the picks' logits) under an expert share,
beside a shared MLP; a tied head; four scalar multipliers.

At the `tiny-granite-h` width on the CPU: the program (`Engine.submit`,
prefill then decode through the K/V pool and the state rows, across slot
hand-ons and a preemption) against the benchmark's plain float32 reference
(`benchmark/reference/ssd_gqa_moe.py`, which shares no code with
`localai_tpu/models/`); the SSD decode step against its chunked prefill and
the kernel (interpreted) against the XLA step; the router against the
reference's; the shares against the whole layer; the three other hybrids'
programs against what they were; the layouts `_hybrid_tables` takes.
"""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import _collect, _engine, _err_against, served_engine
from benchmark.harness import check as C
from benchmark.harness import costs_ssd_gqa as COSTS
from benchmark.harness import spec as S
from benchmark.reference import ssd_gqa_moe as REF
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch
from localai_tpu.ops import ssd as SSD

# float32 activations: the program's honest distance from the float32
# reference is then rounding alone and a wrong block stands out of it. As
# served: a share of the experts (2 of 8 held).
FULL = dataclasses.replace(get_arch("tiny-granite-h"), dtype="float32")
CFG = dataclasses.replace(FULL, expert_share=(1, 4))
PUB = get_arch("granite-4.0-h-small")
TOLERANCE = 1e-4


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with a skip, a conv bias and a gated norm's weight
    that are not their init's ones and zeros, queries and keys large enough
    for the scores' scale to move the softmax, and routed experts large
    enough at 64 wide (not a hybrid's tenth) under a router that tells them
    apart, so that the gates' form weighs."""
    params = L.init_params(cfg, jax.random.key(7))
    k1, k2, k3 = jax.random.split(jax.random.key(8), 3)
    ssd = dict(params["ssd_layers"])
    ssd["ssm_D"] = 1.0 + 0.3 * jax.random.normal(k1, ssd["ssm_D"].shape)
    ssd["conv_b"] = 0.2 * jax.random.normal(k2, ssd["conv_b"].shape)
    ssd["o_norm"] = 1.0 + 0.3 * jax.random.normal(k3, ssd["o_norm"].shape)
    gqa = {**params["gqa_layers"],
           **{n: 8.0 * params["gqa_layers"][n] for n in ("wq", "wk")}}
    lay = {**params["layers"], "w_down": 10.0 * params["layers"]["w_down"],
           "w_gate": 5.0 * params["layers"]["w_gate"],
           "w_up": 5.0 * params["layers"]["w_up"],
           "router": 20.0 * params["layers"]["router"]}
    params = {**params, "ssd_layers": ssd, "gqa_layers": gqa, "layers": lay}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


_err = functools.partial(_err_against, REF.forward)


# ---- the engine against the reference ---------------------------------------- #


served = served_engine(_seeded, CFG)


def test_engine_agrees_with_the_plain_reference(served):
    eng, params = served
    for name in ("w_z", "w_xbc", "w_dt", "wo"):
        assert params["ssd_layers"][name]["q"].dtype == jnp.int8
    assert params["embed"].dtype == jnp.float32  # the tied head stays as held
    prompts = C.sample_prompts(11, CFG.vocab_size, [40, 90])
    recs = C.run_system(eng, prompts, 9)
    errs = [_err(params, CFG, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs
    m = eng.metrics()
    assert CFG.recurrent_layers == (0, 1, 2, 3, 5, 6, 7, 8)
    assert CFG.cache_layer_ids == (4, 9)
    # the row: a [heads, head width, d_state] matrix and 3 conv inputs a layer
    assert eng.cache.state.shape == (8, 2, 8, 16, 32)
    assert eng.cache.state.dtype == jnp.float32
    assert eng.cache.conv.shape == (8, 2, 3, 128 + 2 * 32)
    assert eng.cache.k.shape == (2, 41, 16, 2, 16) == eng.cache.v.shape
    assert m["recurrent_state_bytes"] == 2 * 8 * (8 * 16 * 32 * 4 + 3 * 192 * 4)
    assert "state_snapshots" not in m
    assert m["admit_rows_max"] == rstate.admit_rows(CFG)
    # off the TPU every SSD layer's update is the XLA step, and is counted
    assert m["ssd_decode_xla_sites"] > 0 and m["ssd_decode_pallas_sites"] == 0
    ev = eng.journal.snapshot()
    rows = [e for e in ev if e["event"] == "state_rows"]
    assert rows and all(e["a"] % (2 * 8) == 0 and e["b"] <= e["a"]
                        for e in rows)
    # moe_experts counts the 2 HELD experts of the 10 MoE layers
    hit = [e for e in ev if e["event"] == "moe_experts"]
    assert hit and all(e["a"] % (10 * 2) == 0 and 0 < e["b"] <= e["a"]
                       for e in hit)
    here = [e for e in ev if e["event"] == "moe_here"]
    assert here and all(0 < e["b"] < e["a"] for e in here)
    assert any(e["event"] == "moe_load" for e in ev)


def test_successor_never_sees_the_old_tenants_state_or_pages(served):
    """Six requests through two slots, every one ending on its budget, so
    every hand-on goes through `_park` with both kinds of cache live: the old
    tenant's blocks in flight still update its state and write its pages, the
    successor's admission overwrites the row and takes pages of its own.
    Each stream's log-probabilities are the reference's for ITS ids alone."""
    eng, params = served
    prompts = C.sample_prompts(13, CFG.vocab_size, [30, 45, 20, 70, 33, 52])
    before = eng.metrics()["slots_released_early"]
    handles = [eng.submit(GenRequest(
        prompt_ids=list(p), max_new_tokens=12, temperature=0.0,
        ignore_eos=True, logprobs=20)) for p in prompts]
    errs = [_err(params, CFG, p, _collect(h, 12))
            for p, h in zip(prompts, handles)]
    assert C.verdict(errs, TOLERANCE), errs
    assert eng.metrics()["slots_released_early"] - before >= 4


def test_preempted_request_recomputes_its_state_and_its_rows():
    """A pool too small for two long decodes: the younger is preempted, its
    state row and its pages dropped, and its re-admission recomputes both
    from prompt + generated. Both streams still agree with the reference."""
    new = 100
    params = _seeded()
    eng = _engine(CFG, params, kv_pages=10, kv_preempt="auto",
                  kv_page_headroom=1)
    try:
        prompts = C.sample_prompts(14, CFG.vocab_size, [40, 44])
        handles = []
        for p in prompts:  # the first strictly older: the second is the victim
            handles.append(eng.submit(GenRequest(
                prompt_ids=list(p), max_new_tokens=new, temperature=0.0,
                ignore_eos=True)))
            time.sleep(0.3)
        streams = []
        for h in handles:
            ids = [int(ev.token_id) for ev in h if ev.kind == "token"]
            assert len(ids) == new
            streams.append(ids)
        m = eng.metrics()
    finally:
        eng.stop()
    assert m["kv_preemptions"] >= 1 and m["state_restores"] >= 1
    assert m["kv_preempt_swaps"] == 0  # the rows have no swap image
    for p, ids in zip(prompts, streams):
        lp = C.reference_logprobs(REF.forward, params, CFG, p, ids, pad_to=16)
        gap = lp.max(-1) - lp[np.arange(new), ids]
        assert gap.max() <= TOLERANCE, gap.max()


def test_what_needs_a_snapshot_is_refused_by_name():
    with pytest.raises(ValueError, match="ssd layers.*chunked admission"):
        Engine(CFG, {}, ByteTokenizer(CFG.vocab_size), engine_cfg=EngineConfig(
            max_slots=2, max_seq=256, kv_pages=40, kv_page_size=16,
            prefill_chunk=64))
    with pytest.raises(ValueError, match="a dense KV cache"):
        Engine(CFG, {}, ByteTokenizer(CFG.vocab_size), engine_cfg=EngineConfig(
            max_slots=2, max_seq=256))


# ---- a wrong block fails the same comparison ----------------------------------- #


def _ssd(params, **leaves):
    return {**params, "ssd_layers": {**params["ssd_layers"], **leaves}}


def _b_c_swapped(params):
    """The conv's channels read x | C | B: W_in's xBC columns, the taps and
    the bias moved together, so it is the split alone that is wrong."""
    di, gn = CFG.mamba_d_inner, CFG.mamba_groups * CFG.mamba_d_state
    order = np.r_[0:di, di + gn:di + 2 * gn, di:di + gn]
    ssd = params["ssd_layers"]
    return _ssd(params, w_xbc=ssd["w_xbc"][..., order],
                conv_w=ssd["conv_w"][..., order],
                conv_b=ssd["conv_b"][..., order])


def _norm_then_gate(cfg, ap, y, z, dtype, mesh=None):
    """`_ssd_out` with the gate AFTER the norm (Mamba-2's other order)."""
    y = L.rms_norm(y.reshape(*y.shape[:-2], -1), ap["o_norm"], cfg.rms_eps)
    y = (y * jax.nn.silu(z.astype(jnp.float32))).astype(dtype)
    return L.matmul(y, ap["wo"], cfg.quant_kernel, mesh, "row")


WRONG = {
    # the skip term left out
    "D_dropped": (CFG, lambda p: _ssd(
        p, ssm_D=jnp.zeros_like(p["ssd_layers"]["ssm_D"]))),
    "B_and_C_swapped": (CFG, _b_c_swapped),
    # each multiplier left at 1 (the attention's: head_dim^-0.5)
    "embedding_multiplier_1": (
        dataclasses.replace(CFG, embedding_multiplier=1.0), lambda p: p),
    "residual_multiplier_1": (
        dataclasses.replace(CFG, residual_multiplier=1.0), lambda p: p),
    "logits_scaling_1": (
        dataclasses.replace(CFG, logits_scaling=1.0), lambda p: p),
    "attention_multiplier_default": (
        dataclasses.replace(CFG, query_scale=0.0), lambda p: p),
    # the gates a softmax over ALL the experts' logits, the picks' part kept
    "softmax_over_all": (dataclasses.replace(
        CFG, moe_family="deepseek", scoring_func="softmax"), lambda p: p),
    # rotated q and k in a NoPE model
    "rope": (dataclasses.replace(CFG, attn_rope=True), lambda p: p),
}


@pytest.mark.parametrize(
    "variant", ["right", "gate_after_the_norm"] + sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant, monkeypatch):
    """The admission program's logits against the reference's at the last
    prompt token, the right program and each wrong one."""
    cfg, change = WRONG.get(variant, (CFG, lambda p: p))
    if variant == "gate_after_the_norm":
        monkeypatch.setattr(L, "_ssd_out", _norm_then_gate)
    params = _seeded()
    ids = C.sample_prompts(11, CFG.vocab_size, [48])[0]
    logits, *_ = jax.jit(lambda p, t: L.prefill(
        cfg, p, t, jnp.array([48], jnp.int32)))(
            change(params), jnp.asarray([ids], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    want = REF.forward(params, CFG, ids, [47], pad_to=16)[0]
    worst = float(np.max(np.abs(got - want)))
    assert (worst <= TOLERANCE) == (variant == "right"), (variant, worst)


# ---- the SSD operator ------------------------------------------------------------ #


def _layer(j=1):
    return jax.tree.map(lambda a: a[j], _seeded()["ssd_layers"])


def test_the_decode_step_is_the_chunked_prefill_token_by_token():
    """One SSD layer over 45 tokens of two prompts (the second 20 long), the
    prefill in chunks of 32 (the tiny preset's own) with a padded tail: its outputs, the state and
    the conv rows it leaves in the slots are what 45 decode steps from an
    empty row give (the chunk length changes rounding alone)."""
    ap = _layer()
    T, H, P, N = 45, CFG.mamba_heads, CFG.mamba_head_dim, CFG.mamba_d_state
    x = jax.random.normal(jax.random.key(2), (2, T, CFG.hidden_size))
    lens = jnp.array([T, 20], jnp.int32)
    state = jnp.zeros((1, 3, H, P, N))  # three slots, one layer
    conv = jnp.zeros((1, 3, 3, CFG.mamba_conv_dim))
    slots = jnp.array([2, 0], jnp.int32)
    y, (s_after, c_after) = L._ssd_prefill_mix(
        CFG, ap, x, lens, (state, conv), 0, slots)
    rec = (state[:, :2], conv[:, :2])
    step = jax.jit(lambda xt, rec: L._ssd_decode_mix(CFG, ap, xt, rec, 0))
    for t in range(T):
        yt, rec = step(x[:, t], rec)
        np.testing.assert_allclose(yt[0], y[0, t], atol=2e-5)
        if t < 20:
            np.testing.assert_allclose(yt[1], y[1, t], atol=2e-5)
        if t == 19:  # the shorter prompt's row, as its last token left it
            np.testing.assert_allclose(rec[0][0, 1], s_after[0, 0], atol=1e-5)
            np.testing.assert_allclose(rec[1][0, 1], c_after[0, 0], atol=1e-6)
    np.testing.assert_allclose(rec[0][0, 0], s_after[0, 2], atol=1e-5)
    np.testing.assert_allclose(rec[1][0, 0], c_after[0, 2], atol=1e-6)
    assert not np.asarray(s_after[0, 1]).any()  # a slot no prompt claimed
    assert not np.asarray(c_after[0, 1]).any()


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_chunkwise_prefill_matches_the_recurrence(chunk):
    """Any chunk length, two groups, a strong decay beside a weak one."""
    ks = jax.random.split(jax.random.key(4), 6)
    B, T, H, P, N, G = 2, 64, 8, 16, 32, 2
    x = jax.random.normal(ks[0], (B, T, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 2.0)
    A = -jnp.exp(jnp.linspace(-3.0, 4.0, H))  # decays from 0.999 to e^-50
    D = jax.random.normal(ks[2], (H,))
    Bm = jax.random.normal(ks[3], (B, T, G, N))
    Cm = jax.random.normal(ks[4], (B, T, G, N))
    valid = jnp.arange(T)[None] < jnp.array([T, 37])[:, None]
    want, S = SSD.ssd_recurrent(
        x, jnp.where(valid[..., None], dt, 0.0), A, Bm, Cm, D)
    got, Sc = SSD.ssd_chunk_prefill(x, dt, A, Bm, Cm, D, valid, chunk)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got[0], want[0], atol=1e-4)
    np.testing.assert_allclose(got[1, :37], want[1, :37], atol=1e-4)
    np.testing.assert_allclose(Sc, S, atol=1e-5)


def test_one_token_by_hand():
    """S = exp(dt A) S0 + dt x (x) B and y = S C + D x, in numbers."""
    S0 = jnp.full((1, 1, 2, 2), 2.0)
    x = jnp.array([[[1.0, 3.0]]])
    dt, A, D = jnp.array([[0.5]]), jnp.array([-np.log(4.0)]), jnp.array([10.0])
    Bm, Cm = jnp.array([[[1.0, 2.0]]]), jnp.array([[[1.0, 1.0]]])
    y, S = SSD.ssd_step(S0, x, dt, A, Bm, Cm, D)
    # decay 4^-0.5 = 0.5: S = 1 + 0.5 x (x) B
    np.testing.assert_allclose(S[0, 0], [[1.5, 2.0], [2.5, 4.0]], rtol=1e-6)
    np.testing.assert_allclose(y[0, 0], [3.5 + 10.0, 6.5 + 30.0], rtol=1e-6)


# (Lm, B, H, P, N): the tiny preset's state, narrower than a lane tile, and
# a state a lane tile wide whose head block holds 16 (one group) or 8 (two)
# heads of a group: more than one tile of eight read-outs a grid step.
KERNEL_SHAPES = {"n32": (3, 3, 8, 16, 32), "n128": (3, 3, 16, 8, 128)}
# |y - float64 walk| over the largest |y|: float32 products summed over
# d_state in another order, the dot at HIGHEST (read 1e-7 to 3e-7 here).
READ_OUT_BOUND = 2e-6


@functools.lru_cache(maxsize=None)
def _kernel_case(shape, groups, layer=1):
    """One draw a (shape, groups): the inputs, the kernel's (interpreted) and
    the jitted XLA step's (y, state) after updating `layer` of the stack."""
    ks = jax.random.split(jax.random.key(5), 7)
    Lm, B, H, P, N = KERNEL_SHAPES[shape]
    state = jax.random.normal(ks[0], (Lm, B, H, P, N))
    x = jax.random.normal(ks[1], (B, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, H)))
    A = -jnp.exp(jax.random.normal(ks[3], (H,)))
    D = jax.random.normal(ks[4], (H,))
    Bm = jax.random.normal(ks[5], (B, groups, N))
    Cm = jax.random.normal(ks[6], (B, groups, N))
    args = (state, x, dt, A, Bm, Cm, D)
    got, want = (jax.jit(lambda s, i, impl=impl: SSD.ssd_decode(
        s, i, x, dt, A, Bm, Cm, D, impl=impl))(state, jnp.int32(layer))
        for impl in ("pallas", "xla"))
    return args, got, want


GROUPS = pytest.mark.parametrize("groups", [1, 2])
SHAPES = pytest.mark.parametrize("shape", sorted(KERNEL_SHAPES))


@SHAPES
@GROUPS
def test_ssd_decode_kernel_updates_its_layer_of_the_stack_in_place(
        groups, shape):
    """The Pallas kernel (interpreted here) against the XLA step: layer 1 of
    a three-layer stack, live rows and rows of garbage alike; the other
    layers' rows are not touched."""
    (state, x, dt, A, Bm, Cm, D), (got_y, got), (want_y, want) = _kernel_case(
        shape, groups)
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)
    np.testing.assert_allclose(got[1], want[1], atol=1e-6)
    np.testing.assert_array_equal(got[0], state[0])
    np.testing.assert_array_equal(got[2], state[2])
    assert SSD.head_block(128, 1) == 64 and SSD.head_block(8, 2) == 4
    assert SSD.head_block(16, 1) == 16 and SSD.head_block(16, 2) == 8
    with pytest.raises(ValueError, match="auto|pallas|xla"):
        SSD.ssd_decode(state, 1, x, dt, A, Bm, Cm, D, impl="mosaic")


@SHAPES
@GROUPS
def test_ssd_decode_kernel_reads_the_state_out_at_float32_accuracy(
        groups, shape):
    """The kernel's y (the read-out a float32 dot at HIGHEST, on the MXU
    where there is one) against a float64 `numpy` walk of `ssd_step` over
    the same inputs, and beside the XLA step's own error."""
    args, (got_y, _), (want_y, _) = _kernel_case(shape, groups)
    S, x, dt, A, Bm, Cm, D = (np.asarray(a, np.float64) for a in args)
    H = S.shape[2]
    Bh, Ch = (np.repeat(a, H // groups, axis=1) for a in (Bm, Cm))
    S = (S[1] * np.exp(dt * A)[..., None, None]
         + (dt[..., None] * x)[..., None] * Bh[:, :, None, :])
    y = np.einsum("bhpn,bhn->bhp", S, Ch) + D[:, None] * x
    err, oracle = (float(np.abs(np.asarray(g, np.float64) - y).max()
                         / np.abs(y).max()) for g in (got_y, want_y))
    assert err <= READ_OUT_BOUND, (err, oracle)
    assert err <= max(2.0 * oracle, 5e-7), (err, oracle)


@SHAPES
@GROUPS
def test_ssd_decode_kernel_writes_the_oracles_state_bit_for_bit(groups, shape):
    """The update is elementwise float32 in the same order in both forms:
    the rows the kernel writes are the (jitted) XLA step's to the last bit,
    whatever reads them out."""
    _, (_, got), (_, want) = _kernel_case(shape, groups)
    np.testing.assert_array_equal(got[1], want[1])


@pytest.mark.parametrize("layer", [0, 2])
@SHAPES
def test_ssd_decode_kernel_touches_its_own_layer_alone(shape, layer):
    """The first and the last layer of the stack as the target: every slot of
    every OTHER layer keeps its bits, every slot of the layer is updated (a
    dead slot's row as a live one's), and y is the layer's own."""
    (state, *_), (got_y, got), (want_y, want) = _kernel_case(shape, 1, layer)
    for other in range(state.shape[0]):
        if other != layer:
            np.testing.assert_array_equal(got[other], state[other])
    np.testing.assert_array_equal(got[layer], want[layer])
    assert (np.asarray(got[layer]) != np.asarray(state[layer])).any(
        axis=(1, 2, 3)).all()  # every slot's row moved
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)


def test_the_kernel_runs_inside_the_hybrid_scan():
    """One paged decode step of the whole tiny model with the SSD kernel
    (interpreted) and with the XLA step: the same logits, state and rows."""
    params = _seeded()
    B, n, page, MP = 2, 4, 16, 4
    ks = jax.random.split(jax.random.key(21), 4)
    pool = L.paged_cache_zeros(CFG, B * MP + 1, page)
    pool = pool._replace(k=jax.random.normal(ks[0], pool.k.shape),
                         v=jax.random.normal(ks[1], pool.v.shape))
    state, conv = rstate.allocate(CFG, B, jnp.float32)
    state = 0.1 * jax.random.normal(ks[2], state.shape)
    conv = 0.1 * jax.random.normal(ks[3], conv.shape)
    lk = jnp.zeros((2, B, n, 2, 16), jnp.float32)
    table = (jnp.arange(B * MP, dtype=jnp.int32) + 1).reshape(B, MP)

    def step(impl):
        return jax.jit(lambda st, cv: L.decode_step_windowed(
            CFG, params, jnp.array([5, 9]), jnp.array([37, 20]), pool, lk, lk,
            jnp.int32(0), ptable=table, recurrent=(st, cv),
            kda_impl=impl))(state, conv)

    want, got = step("xla"), step("pallas")
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)  # logits
    np.testing.assert_allclose(got[-1][0], want[-1][0], atol=1e-5)  # state
    np.testing.assert_allclose(got[-1][1], want[-1][1], atol=1e-6)  # conv rows


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_every_op_of_the_ssd_operator_is_named_ssd_mix(form):
    """`ssdgqa_ssd_mix_share` reads the word `ssd_mix` in an op's name. XLA
    names a fusion after any op in it, so EVERY equation of the operator
    carries the word, around the leaf that books it (`scope_share` drops the
    word and reads the leaf)."""
    from localai_tpu.observe.scopes import SCOPES, SSD_MIX

    ap = _layer()
    D = CFG.hidden_size
    rec = rstate.allocate(dataclasses.replace(CFG, layer_kinds=("ssd",)), 2,
                          jnp.float32)
    if form == "decode":
        jaxpr = jax.make_jaxpr(lambda x, s, c: L._ssd_decode_mix(
            CFG, ap, x, (s, c), 0))(jnp.zeros((2, D)), *rec)
    else:
        jaxpr = jax.make_jaxpr(lambda x, s, c: L._ssd_prefill_mix(
            CFG, ap, x, jnp.array([5, 3]), (s, c), 0, jnp.array([1, 0])))(
                jnp.zeros((2, 5, D)), *rec)
    leaves = set()
    for e in jaxpr.jaxpr.eqns:
        stack = str(e.source_info.name_stack)
        assert stack.split("/")[0] == SSD_MIX, (e.primitive.name, stack)
        leaves |= {leaf for leaf in SCOPES if f"/{leaf}" in stack}
    assert leaves >= {"attention/proj", "attention/mix", "attention/cache_write",
                      "attention/out"}


def test_the_row_is_a_matrix_and_three_inputs_a_layer():
    """4 MiB of float32 state a slot and layer, and 3 rows of 8448."""
    assert rstate._shapes(PUB, 32) == (
        (36, 32, 128, 64, 128), (36, 32, 3, 8448))
    assert rstate.row_bytes(PUB, "bfloat16") == 36 * (
        128 * 64 * 128 * 4 + 3 * 8448 * 2) == 152_819_712
    st, conv = rstate.allocate(CFG, 4, jnp.bfloat16)
    assert st.shape == (8, 4, 8, 16, 32) and st.dtype == jnp.float32
    assert conv.shape == (8, 4, 3, 192) and conv.dtype == jnp.bfloat16
    # the admission bound is this kind's own, from its chunks' temporaries
    # (chunks of ops/ssd.CHUNK = 128, a sub-blocking of the published 256)
    assert SSD.CHUNK == 128 and PUB.mamba_chunk == 256
    assert rstate.admit_rows(PUB) == (1 << 30) // (4 * (
        4 * 128 * 128 + 2 * 128 * 64 * 128 // 128 + 6 * 128 * 64)) == 2048
    # and the other kinds' rows and bounds are what they were
    assert rstate.admit_rows(get_arch("kimi-linear-48b-a3b")) == 2048
    assert rstate.admit_rows(get_arch("solar-open2-250b")) == 1024
    assert rstate.admit_rows(get_arch("lfm2-8b-a1b")) is None
    st, conv = rstate.allocate(get_arch("tiny-kimi-linear"), 2, jnp.bfloat16)
    assert st.shape == (5, 2, 4, 16, 16) and conv.shape == (5, 2, 3, 192)
    st, conv = rstate.allocate(get_arch("tiny-lfm2"), 2, jnp.bfloat16)
    assert st is None and conv.shape == (5, 2, 2, 64)


# ---- the router and the share ----------------------------------------------------- #


def test_router_agrees_with_the_reference():
    """The k largest LOGITS over all experts, softmax over those k."""
    cfg = dataclasses.replace(FULL, hidden_size=16)
    k1, k2 = jax.random.split(jax.random.key(3))
    lp = {"router": jax.random.normal(k1, (16, cfg.num_experts))}
    x = jax.random.normal(k2, (32, 16))
    with jax.default_matmul_precision("highest"):
        w, sel = L._moe_route(cfg, lp, x)
        g, e = REF.route(x, lp["router"], top_k=cfg.num_experts_per_token)
    np.testing.assert_array_equal(sel, e)
    np.testing.assert_allclose(w, g, rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    logits = np.asarray(x @ lp["router"], np.float64)
    top = np.sort(logits, -1)[:, ::-1][:, :3]
    want = np.exp(top) / np.exp(top).sum(-1, keepdims=True)
    np.testing.assert_allclose(w, want, rtol=1e-5)
    # and NOT the softmax over all eight, cut to the picks
    allp = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    assert np.abs(np.sort(allp, -1)[:, ::-1][:, :3] - want).max() > 0.05


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts, with the shared MLP counted once, add
    up to the uncut reference's MoE layer; program and reference."""
    params = _seeded(cfg=FULL)
    lp = {k: v[2] for k, v in params["layers"].items()}  # one MoE layer
    x = jax.random.normal(jax.random.key(3), (24, FULL.hidden_size), jnp.float32)
    kw = dict(top_k=FULL.num_experts_per_token, eps=FULL.rms_eps, res=1.0)
    with jax.default_matmul_precision("highest"):
        whole = REF.experts(x, {k: lp[k] for k in REF._MOE}, lo=0, **kw) - x
        m = REF._rms_norm(x, lp["mlp_norm"], FULL.rms_eps)
        shared = REF._swiglu(m, lp["shared_gate"], lp["shared_up"],
                             lp["shared_down"], jnp.float32, "")
        prog, ref = -3 * shared, -3 * shared  # counted once of four times
        for i in range(4):
            cfg_i = dataclasses.replace(FULL, expert_share=(i, 4))
            held = slice(cfg_i.expert_lo, cfg_i.expert_lo + cfg_i.experts_here)
            lp_i = {**lp, **{k: lp[k][held] for k in ("w_gate", "w_up", "w_down")}}
            prog = prog + L._mlp(cfg_i, lp_i, m)
            ref = ref + REF.experts(
                x, {k: lp_i[k] for k in REF._MOE}, lo=cfg_i.expert_lo, **kw) - x
    assert float(jnp.abs(whole - shared).max()) > 1e-3  # the experts weigh
    np.testing.assert_allclose(ref, whole, atol=2e-5)
    np.testing.assert_allclose(prog, whole, atol=2e-5)


# ---- the other hybrids are what they were ------------------------------------------ #


@pytest.mark.parametrize("name", [
    "tiny-kimi-linear", "tiny-solar-open2", "tiny-lfm2"])
def test_the_other_hybrids_programs_are_what_they_were(name, monkeypatch):
    """Their multipliers are 1, so `_residual`, `_embed` and `_unembed` emit
    what they emitted: the admission and a decode step traced with the plain
    add in `_residual`'s place are the same equations, and the mixers the
    table hands out are the kind's own; the embedding is drawn at the plain
    scale, bit for bit."""
    cfg = get_arch(name)
    assert (cfg.embedding_multiplier, cfg.residual_multiplier,
            cfg.logits_scaling) == (1.0, 1.0, 1.0)
    kind = L.RECURRENT[cfg.recurrent_kind]
    want = {"kda": (L._init_kda_layers, L._kda_decode_mix, L._kda_prefill_mix),
            "conv": (L._init_conv_layers, L._conv_decode_mix,
                     L._conv_prefill_mix)}[cfg.recurrent_kind]
    assert tuple(kind) == want
    params = L.init_params(cfg, jax.random.key(0))
    keys = jax.random.split(jax.random.key(0), 32)  # `init_params`' own
    drawn = [i for i in range(32) if np.array_equal(
        (jax.random.normal(keys[i], params["embed"].shape, jnp.float32)
         * 0.02).astype(params["embed"].dtype), params["embed"])]
    assert len(drawn) == 1, drawn
    rec = rstate.allocate(cfg, 2, jnp.dtype(cfg.dtype))
    tok = jnp.ones((2, 32), jnp.int32)

    def admit():
        return str(jax.make_jaxpr(lambda p, *r: L.prefill(
            cfg, p, tok, jnp.array([20, 32]),
            recurrent=(*r, jnp.arange(2))))(params, *rec))

    now = admit()
    monkeypatch.setattr(L, "_residual", lambda cfg, h, y: h + y)
    assert admit() == now


# ---- the layouts and the published preset ------------------------------------------- #


def test_hybrid_tables_take_the_published_layer_types():
    kl, beside, nd, kd, lead = L._hybrid_tables(PUB)
    assert PUB.recurrent_kind == "ssd" and PUB.recurrent_stack == "ssd_layers"
    assert PUB.cache_stack == "gqa_layers"
    assert PUB.cache_layer_ids == (5, 15, 25, 35)
    published = S.config("granite-4.0-h-small-int8-ep8")["layer_types"]
    assert tuple("gqa" if k == "attention" else "ssd" for k in published) \
        == PUB.layer_kinds
    assert len(kl) == 36 and (nd, kd, lead) == (0, 0, False)
    # every attention layer stands directly BEHIND a Mamba layer of its own
    assert {int(l): int(m) for l, m in zip(kl, beside) if m >= 0} == {
        4: 0, 14: 1, 24: 2, 34: 3}
    assert L._hybrid_tables(CFG)[1].tolist() == [-1, -1, -1, 0, -1, -1, -1, 1]
    with pytest.raises(NotImplementedError, match="mixes the recurrent kinds"):
        L._hybrid_tables(dataclasses.replace(
            CFG, layer_kinds=("ssd", "kda", "ssd", "ssd", "gqa") * 2))
    with pytest.raises(NotImplementedError, match="beside a 'ssd' layer"):
        L._hybrid_tables(dataclasses.replace(
            CFG, layer_kinds=("ssd", "ssd", "ssd", "gqa", "gqa") * 2))


def test_published_preset_and_its_held_tree():
    """The preset's tree is the published 32B-A9B, the costs file counts the
    same, and chip 0 of 8 holds what the issue's arithmetic says."""
    tree = jax.eval_shape(lambda k: L.init_params(PUB, k), jax.random.key(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert "lm_head" not in tree and "kda_layers" not in tree
    # in_proj [4096, 16768] as its three column blocks z | xBC | dt
    assert [tree["ssd_layers"][n].shape for n in ("w_z", "w_xbc", "w_dt")] == [
        (36, 4096, 8192), (36, 4096, 8448), (36, 4096, 128)]
    assert tree["ssd_layers"]["wo"].shape == (36, 8192, 4096)
    assert tree["ssd_layers"]["conv_w"].shape == (36, 4, 8448)
    assert tree["gqa_layers"]["wk"].shape == (4, 4096, 1024)
    assert tree["layers"]["router"].shape == (40, 4096, 72)
    assert tree["layers"]["shared_gate"].shape == (40, 4096, 1536)
    assert abs(size(tree) / 1e9 - 32.2) < 0.1
    arch = S.config("granite-4.0-h-small-int8-ep8")
    assert abs(COSTS.param_count(arch) - size(tree)) < 1e5  # the dt/A/D vectors
    assert abs(COSTS.active_params(arch) / 1e9 - 9.2) < 0.25
    assert PUB.query_scale ** -0.5 == arch["attention_multiplier"]
    assert (PUB.embedding_multiplier, PUB.residual_multiplier,
            PUB.logits_scaling) == (arch["embedding_multiplier"],
                                    arch["residual_multiplier"],
                                    arch["logits_scaling"])
    assert PUB.n_shared_experts * PUB.moe_inter_size \
        == arch["shared_intermediate_size"]
    held = dataclasses.replace(PUB, expert_share=(0, 8))
    assert held.experts_here == arch["num_local_experts"] == 9
    q = jax.eval_shape(lambda k: Q.init_params_quantized(held, k),
                       jax.random.key(0))
    assert q["ssd_layers"]["w_xbc"]["q"].dtype == jnp.int8
    assert q["ssd_layers"]["w_xbc"]["q"].shape == (36, 4096, 8448)
    assert q["ssd_layers"]["wo"]["q"].shape == (36, 8192, 4096)
    assert q["ssd_layers"]["conv_w"].dtype == jnp.bfloat16
    assert q["ssd_layers"]["A_log"].dtype == jnp.float32
    assert q["layers"]["w_gate"]["q"].shape == (40, 9, 4096, 768)
    assert q["layers"]["router"].shape == (40, 4096, 72)
    assert q["embed"].dtype == jnp.bfloat16  # the tied head stays as held
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(q))
    assert abs(nbytes / 1e9 - 8.9) < 0.1
    assert abs(COSTS.weight_bytes(arch, 1) / 1e9 - 8.86) < 0.05
    # 9.66 GB of state read and written a step at 32 slots, 0.15 of operands
    assert abs(32 * 2 * 36 * 128 * 64 * 128 * 4 / 1e9 - 9.66) < 0.01
    assert abs(32 * COSTS.ssd_kernel_bytes_per_row(arch) / 1e9 - 9.82) < 0.01


def test_the_synthetic_init_undoes_the_multipliers():
    """A head that divides its logits by 16 is drawn 16 times as large, and a
    residual branch that is shrunk to 0.22 beside an embedding grown 12 x 16
    times has its out-projection drawn 12 x 16 / 0.22 times as large: the
    random model is then every other family's random model (`init_gain`), in
    the float init and in the quantized one alike; a model without
    multipliers is drawn as it was."""
    g = 12.0 * 16.0 / 0.22
    assert L.init_gain(PUB, "embed", ()) == 16.0
    assert L.init_gain(PUB, "wo", (1, 2, 3)) == g == L.init_gain(
        PUB, "shared_down", (1, 2, 3))
    assert L.init_gain(PUB, "w_down", (1, 2, 3, 4)) == g * 0.1
    assert L.init_gain(PUB, "wq", (1, 2, 3)) == 1.0
    lfm2 = get_arch("lfm2-8b-a1b")
    assert [L.init_gain(lfm2, n, (1, 2, 3, 4)) for n in (
        "embed", "wo", "shared_down", "w_down")] == [1.0, 1.0, 1.0, 0.1]
    assert L.init_gain(get_arch("mistral-7b"), "w_down", (1, 2, 3)) == 1.0
    assert L.SSD_DT == (1e-3, 1e-1)
    assert not hasattr(PUB, "mamba_init_dt")
    gt = FULL.embedding_multiplier * FULL.logits_scaling / FULL.residual_multiplier
    for tree in (L.init_params(FULL, jax.random.key(0)),
                 Q.init_params_quantized(
                     dataclasses.replace(FULL, dtype="bfloat16"),
                     jax.random.key(0))):
        std = lambda a: float(jnp.std(Q.dequantize_tensor(a).astype(jnp.float32)))  # noqa: E731
        assert abs(std(tree["embed"]) / (0.02 * FULL.logits_scaling) - 1.0) < 0.05
        for stack, name in (("ssd_layers", "wo"), ("gqa_layers", "wo"),
                            ("layers", "shared_down")):
            assert abs(std(tree[stack][name]) / (0.02 * gt) - 1.0) < 0.05
        assert abs(std(tree["layers"]["w_down"]) / (0.002 * gt) - 1.0) < 0.05
        assert abs(std(tree["ssd_layers"]["w_z"]) / 0.02 - 1.0) < 0.05
        assert np.asarray(tree["ssd_layers"]["ssm_D"] == 1.0).all()
        dt = jax.nn.softplus(tree["ssd_layers"]["dt_bias"])
        assert 1e-3 * 0.99 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.01
