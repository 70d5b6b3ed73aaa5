"""AI21-Jamba2 (ai21labs/AI21-Jamba2-3B, `jamba`): Mamba-1 (S6, the selective
scan) layers with a per-slot float32 state whose every element decays by its
own exp, and dt, B and C under an RMSNorm each, beside NoPE multi-query layers
of many query heads over ONE key/value head in the paged pool, the attention
layer BEHIND a Mamba layer of its own; a dense SwiGLU in every layer; a tied
head.

At the `tiny-jamba2` width on the CPU: the program (`Engine.submit`, prefill
then decode through the K/V pool and the state rows, across decode blocks,
slot hand-ons and a preemption) against the benchmark's plain float32
reference (`benchmark/reference/s6_mqa_dense.py`, which shares no code with
`localai_tpu/models/` or `localai_tpu/ops/`); the decode step against the
prefill's scan and the scan against the recurrence written out; the kernel
(interpreted) against the XLA step; what the engine refuses, by name; wrong
blocks that fail the same comparison; the layouts `_hybrid_tables` takes and
the published tree.
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
from benchmark.harness import costs_s6_mqa as COSTS
from benchmark.harness import spec as S
from benchmark.reference import s6_mqa_dense as REF
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch
from localai_tpu.ops import paged_flash as PF
from localai_tpu.ops import s6 as S6

# float32 activations: the program's honest distance from the float32
# reference is then rounding alone and a wrong block stands out of it.
CFG = dataclasses.replace(get_arch("tiny-jamba2"), dtype="float32")
PUB = get_arch("ai21-jamba2-3b")
# float32 on both sides over the same weights: what is left is the order of
# float32 sums (read 5e-7 to 3e-6 here); every wrong block of WRONG reads
# over 1e-2.
TOLERANCE = 1e-4
# bfloat16 activations and rows against the float32 reference over the same
# (bfloat16) weights: six layers' honest rounding, read 0.01-0.03 here.
BF16_BAND = 0.08


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with a skip, a conv bias and three inner norms'
    weights that are not their init's ones and zeros (so that dropping one
    shows), a step's projection large enough to move the step, and queries
    and keys large enough for the scores to move the softmax."""
    params = L.init_params(cfg, jax.random.key(7))
    ks = jax.random.split(jax.random.key(8), 6)
    s6 = dict(params["s6_layers"])
    s6["ssm_D"] = 1.0 + 0.3 * jax.random.normal(ks[0], s6["ssm_D"].shape)
    s6["conv_b"] = (0.2 * jax.random.normal(ks[1], s6["conv_b"].shape)
                    ).astype(s6["conv_b"].dtype)
    for i, n in enumerate(("dt_norm", "b_norm", "c_norm")):
        s6[n] = (1.5 + 0.3 * jax.random.normal(ks[2 + i], s6[n].shape)
                 ).astype(s6[n].dtype)
    s6["w_dt"] = 4.0 * s6["w_dt"]
    gqa = {**params["gqa_layers"],
           **{n: 8.0 * params["gqa_layers"][n] for n in ("wq", "wk")}}
    params = {**params, "s6_layers": s6, "gqa_layers": gqa}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


_err = functools.partial(_err_against, REF.forward)


# ---- (a) the engine against the reference ------------------------------------- #


served = served_engine(_seeded, CFG)


def test_engine_agrees_with_the_plain_reference(served):
    """int8 matrices (both sides read them as data): a prompt shorter than
    the conv, one of a page and a long one, 20 new tokens each, so two whole
    8-step decode blocks and single steps behind them."""
    eng, params = served
    for name in ("w_in", "w_x", "w_dt", "wo"):
        assert params["s6_layers"][name]["q"].dtype == jnp.int8
    assert params["s6_layers"]["A_logT"].dtype == jnp.float32
    assert params["embed"].dtype == jnp.float32  # the tied head stays as held
    prompts = C.sample_prompts(11, CFG.vocab_size, [2, 16, 90])
    recs = C.run_system(eng, prompts, 20)
    errs = [_err(params, CFG, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs
    m = eng.metrics()
    assert CFG.recurrent_layers == (0, 2, 3, 5)
    assert CFG.cache_layer_ids == (1, 4)
    # the row: a [states, channels] matrix and 3 conv inputs a layer; the
    # pool of ONE K/V head
    assert eng.cache.state.shape == (4, 2, 8, 128)
    assert eng.cache.state.dtype == jnp.float32
    assert eng.cache.conv.shape == (4, 2, 3, 128)
    assert eng.cache.k.shape == (2, 41, 16, 1, 16) == eng.cache.v.shape
    assert m["recurrent_state_bytes"] == 2 * 4 * (8 * 128 * 4 + 3 * 128 * 4)
    assert "state_snapshots" not in m
    assert m["admit_rows_max"] == rstate.admit_rows(CFG)
    # off the TPU every S6 layer's update is the XLA step, and is counted
    assert m["s6_decode_xla_sites"] > 0 and m["s6_decode_pallas_sites"] == 0
    assert "ssd_decode_xla_sites" not in m
    ev = eng.journal.snapshot()
    rows = [e for e in ev if e["event"] == "state_rows"]
    assert rows and all(e["a"] % (2 * 4) == 0 and e["b"] <= e["a"]
                        for e in rows)
    (st,) = [e for e in ev if e["event"] == "s6_state"]
    (kp,) = [e for e in ev if e["event"] == "kv_pool"]
    assert (st["a"], st["b"]) == (8 * 128, rstate.row_bytes(CFG, "float32"))
    # 41 pages of 16 rows, 2 layers x (K + V) x one head of 16 float32 a token
    assert (kp["a"], kp["b"]) == (40, 41 * 16 * 2 * 2 * 16 * 4)
    assert not any(e["event"].startswith("moe_") for e in ev)  # a dense MLP


def test_bfloat16_engine_stays_inside_its_band():
    """bfloat16 weights, activations and rows against the float32 reference
    over the same weights: honest rounding over six layers and no more."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = _seeded(cfg)
    assert params["s6_layers"]["w_in"].dtype == jnp.bfloat16
    eng = _engine(cfg, params)
    try:
        assert eng.cache.state.dtype == jnp.float32  # the state stays float32
        assert eng.cache.conv.dtype == jnp.bfloat16
        prompts = C.sample_prompts(12, cfg.vocab_size, [30, 70])
        recs = C.run_system(eng, prompts, 12)
    finally:
        eng.stop()
    errs = [_err(params, cfg, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, BF16_BAND), errs
    assert max(e["chosen"] for e in errs) > TOLERANCE  # and it is rounding


# ---- (d) slots handed on, a preemption ------------------------------------------ #


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


# ---- (e) what needs the state inside a sequence is refused by name ------------- #

REFUSED = {
    "a_dense_cache": ({"kv_pages": 0}, None, "a dense KV cache"),
    "chunked_admission": ({"prefill_chunk": 64}, None, "chunked admission"),
    "a_verify_chunk": ({"spec_mode": "prompt_lookup"}, None,
                       "speculative decoding"),
    "sp": ({}, {"sp": 2}, "tp/sp/ep/dp > 1"),
    "window_and_spill": ({"kv_spill_bytes": 1 << 20}, None,
                         "windowed+sink attention and page spill"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_where_the_engine_is_built_by_name(what):
    from localai_tpu.parallel.mesh import MeshPlan

    ecfg, plan, says = REFUSED[what]
    kw = {"max_slots": 2, "max_seq": 256, "kv_pages": 40, "kv_page_size": 16,
          **ecfg}
    with pytest.raises(ValueError) as e:
        Engine(CFG, {}, ByteTokenizer(CFG.vocab_size),
               engine_cfg=EngineConfig(**kw),
               mesh_plan=MeshPlan(**plan) if plan else None)
    assert "s6 layers" in str(e.value) and says in str(e.value), e.value
    assert f"{rstate.row_bytes(CFG, 'float32')} bytes a slot" in str(e.value)
    # tp > 1 handed to the engine degrades to 1 first (`max_valid_tp`, below);
    # the rule itself names it
    with pytest.raises(ValueError, match="s6 layers.*tp/sp/ep/dp > 1"):
        rstate.refuse(CFG, EngineConfig(**{**kw, **REFUSED["sp"][0]}),
                      MeshPlan(tp=2), None, "off")


def test_forks_prefix_reuse_adapters_and_a_checkpoint_are_off_by_name(
        tmp_path):
    """A fork of a live stream is refused when asked for, prefix-span reuse
    is switched off and journalled, a runtime adapter is refused (a dense
    hybrid too), the planner gives the model one chip, and a `jamba` checkpoint's config
    keys are read while its tensors are refused."""
    import json

    from localai_tpu.engine import weights as W
    from localai_tpu.engine.engine import AdapterError
    from localai_tpu.parallel.sharding import max_valid_tp

    eng = _engine(CFG, _seeded(), prefix_cache_entries=4)
    try:
        h = eng.submit(GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=2,
                                  temperature=0.0, ignore_eos=True))
        with pytest.raises(ValueError, match="s6 layers"):
            eng.fork(h, 2)
        h.result()
        with pytest.raises(AdapterError, match="hybrid S6/GQA"):
            eng.register_adapter("a", "/nowhere")
        assert not eng._prefix_enabled
        assert eng.metrics()["prefix_reuse_off"] == 1
        ev = eng.journal.snapshot()
        assert sum(e["event"] == "prefix_reuse_off" for e in ev) == 1
    finally:
        eng.stop()
    assert max_valid_tp(CFG, 8) == 1
    hf = {k: v for k, v in S.config("ai21-jamba2-3b-int8").items()
          if not isinstance(v, (dict, list))}
    (tmp_path / "config.json").write_text(json.dumps(hf))
    arch = W.arch_from_hf_config(str(tmp_path))
    want = dataclasses.replace(PUB, name=arch.name)
    assert arch == want
    with pytest.raises(ValueError, match="`jamba` checkpoint's tensors"):
        W.load_hf_checkpoint(arch, str(tmp_path))
    (tmp_path / "config.json").write_text(json.dumps({**hf, "num_experts": 16}))
    with pytest.raises(ValueError, match="num_experts > 1"):
        W.arch_from_hf_config(str(tmp_path))


# ---- (g) a wrong block fails the same comparison --------------------------------- #


def _s6(params, **leaves):
    return {**params, "s6_layers": {**params["s6_layers"], **leaves}}


def _b_c_swapped(params):
    """x_proj's columns read r | C | B, the two norms' weights moved with
    them, so it is the split alone that is wrong."""
    R, N = CFG.mamba_dt_rank, CFG.mamba_d_state
    order = np.r_[0:R, R + N:R + 2 * N, R:R + N]
    s6 = params["s6_layers"]
    return _s6(params, w_x=s6["w_x"][..., order], b_norm=s6["c_norm"],
               c_norm=s6["b_norm"])


def _decay_a_head(params):
    """One decay for all N states of a channel (their mean): what a kernel
    with a scalar decay a head would compute."""
    A = params["s6_layers"]["A_logT"]
    mean = jnp.log(jnp.mean(jnp.exp(A), axis=-2, keepdims=True))
    return _s6(params, A_logT=jnp.broadcast_to(mean, A.shape))


WRONG = {
    # the skip term left out
    "D_dropped": (CFG, lambda p: _s6(
        p, ssm_D=jnp.zeros_like(p["s6_layers"]["ssm_D"]))),
    "B_and_C_swapped": (CFG, _b_c_swapped),
    # softplus(r W_dt) without its bias
    "dt_bias_dropped": (CFG, lambda p: _s6(
        p, dt_bias=jnp.zeros_like(p["s6_layers"]["dt_bias"]))),
    "decay_a_head_not_a_channel": (CFG, _decay_a_head),
    # rotated q and k in a NoPE model
    "rope": (dataclasses.replace(CFG, attn_rope=True), lambda p: p),
}


@pytest.mark.parametrize(
    "variant", ["right", "inner_norms_dropped"] + sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant, monkeypatch):
    """The admission program's logits against the reference's at the last
    prompt token, the right program and each wrong one."""
    cfg, change = WRONG.get(variant, (CFG, lambda p: p))
    if variant == "inner_norms_dropped":
        monkeypatch.setattr(L, "rms_norm", _norm_but_not_the_inner_ones)
    params = _seeded()
    ids = C.sample_prompts(11, CFG.vocab_size, [48])[0]
    logits, *_ = jax.jit(lambda p, t: L.prefill(
        cfg, p, t, jnp.array([48], jnp.int32)))(
            change(params), jnp.asarray([ids], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    want = REF.forward(params, CFG, ids, [47], pad_to=16)[0]
    worst = float(np.max(np.abs(got - want)))
    assert (worst <= TOLERANCE) == (variant == "right"), (variant, worst)
    if variant == "inner_norms_dropped":
        # ... and it IS plain Mamba-1's mixer: the reference with its inner
        # norms taken out (the benchmark's second control) reads the same
        plain = REF.forward(params, CFG, ids, [47], pad_to=16,
                            inner_norms=False)[0]
        assert float(np.max(np.abs(got - plain))) <= TOLERANCE


_real_rms_norm = L.rms_norm


def _norm_but_not_the_inner_ones(x, w, eps=1e-5):
    """`rms_norm` that passes a row of the inner norms' widths (dt rank 8,
    d_state 8; no other norm of the tiny model is that narrow) on whole."""
    if x.shape[-1] in (CFG.mamba_dt_rank, CFG.mamba_d_state):
        return x
    return _real_rms_norm(x, w, eps)


# ---- (b) the S6 operator: decode step, scan, recurrence ---------------------------- #


def _layer(j=1):
    return jax.tree.map(lambda a: a[j], _seeded()["s6_layers"])


def test_the_decode_step_is_the_prefill_scan_token_by_token():
    """One S6 layer over 45 tokens of two prompts (the second 20 long): the
    prefill's outputs, the state and the conv rows it leaves in the slots
    are what 45 decode steps from an empty row give."""
    ap = _layer()
    T, N, E = 45, CFG.mamba_d_state, CFG.mamba_d_inner
    x = jax.random.normal(jax.random.key(2), (2, T, CFG.hidden_size))
    lens = jnp.array([T, 20], jnp.int32)
    state = jnp.zeros((1, 3, N, E))  # three slots, one layer
    conv = jnp.zeros((1, 3, 3, E))
    slots = jnp.array([2, 0], jnp.int32)
    y, (s_after, c_after) = L._s6_prefill_mix(
        CFG, ap, x, lens, (state, conv), 0, slots)
    rec = (state[:, :2], conv[:, :2])
    step = jax.jit(lambda xt, rec: L._s6_decode_mix(CFG, ap, xt, rec, 0))
    for t in range(T):
        yt, rec = step(x[:, t], rec)
        np.testing.assert_allclose(yt[0], y[0, t], atol=2e-5)
        if t < 20:
            np.testing.assert_allclose(yt[1], y[1, t], atol=2e-5)
        if t == 19:  # the shorter prompt's row, as its last token left it
            np.testing.assert_allclose(rec[0][0, 1], s_after[0, 0], atol=1e-6)
            np.testing.assert_allclose(rec[1][0, 1], c_after[0, 0], atol=1e-6)
    np.testing.assert_allclose(rec[0][0, 0], s_after[0, 2], atol=1e-6)
    np.testing.assert_allclose(rec[1][0, 0], c_after[0, 2], atol=1e-6)
    assert not np.asarray(s_after[0, 1]).any()  # a slot no prompt claimed
    assert not np.asarray(c_after[0, 1]).any()


@pytest.mark.parametrize("T", [2, 7, 45])  # 2: shorter than the conv's 4 taps
def test_the_scan_is_the_recurrence_written_out(T):
    """`s6_prefill` against float64 loops over t, c and n, a strong decay
    beside a weak one, the second prompt cut short; and the prefill mixer
    on a prompt shorter than the conv reads zeros before its start."""
    ks = jax.random.split(jax.random.key(4), 6)
    B, N, E = 2, 4, 6
    x = jax.random.normal(ks[0], (B, T, E))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, E)) - 1.0)
    At = -jnp.exp(jax.random.normal(ks[2], (N, E)) * 1.5)
    D = jax.random.normal(ks[3], (E,))
    Bm = jax.random.normal(ks[4], (B, T, N))
    Cm = jax.random.normal(ks[5], (B, T, N))
    short = max(1, T - 3)
    valid = jnp.arange(T)[None] < jnp.array([T, short])[:, None]
    got, h = S6.s6_prefill(x, dt, At, Bm, Cm, D, valid)
    x64, dt64, A64, D64, B64, C64 = (np.asarray(a, np.float64)
                                     for a in (x, dt, At, D, Bm, Cm))
    for b, n_valid in enumerate((T, short)):
        hh = np.zeros((N, E))
        for t in range(n_valid):
            for c in range(E):
                for n in range(N):
                    hh[n, c] = (np.exp(dt64[b, t, c] * A64[n, c]) * hh[n, c]
                                + dt64[b, t, c] * x64[b, t, c] * B64[b, t, n])
            y = (hh * C64[b, t][:, None]).sum(0) + D64 * x64[b, t]
            np.testing.assert_allclose(got[b, t], y, atol=2e-5)
        np.testing.assert_allclose(h[b], hh, atol=2e-5)
    # the mixer whole on a prompt of T tokens: what the reference's layer
    # gives (zeros before the start, the conv's edge)
    ap = _layer(0)
    xs = jax.random.normal(ks[0], (1, T, CFG.hidden_size))
    y, _ = L._s6_prefill_mix(CFG, ap, xs, jnp.array([T]), None, 0, None)
    lw = {**ap, "attn_norm": jnp.ones((CFG.hidden_size,))}
    # the reference norms its input; hand it rows whose norm is themselves
    unit = xs[0] / jnp.sqrt(jnp.mean(xs[0] ** 2, -1, keepdims=True) + 1e-6)
    y_unit, _ = L._s6_prefill_mix(CFG, ap, unit[None], jnp.array([T]), None,
                                  0, None)
    want = REF.s6_layer(xs[0], lw, eps=1e-6) - xs[0]
    np.testing.assert_allclose(y_unit[0], want, atol=2e-5)
    assert y.shape == (1, T, CFG.hidden_size)


def test_one_token_by_hand():
    """h = exp(dt A) h0 + dt x (x) B and y = sum_n h C + D x, in numbers:
    two states whose decays differ, one channel."""
    h0 = jnp.full((2, 1), 2.0)
    x, dt = jnp.array([3.0]), jnp.array([0.5])
    At = jnp.array([[-np.log(4.0)], [-np.log(16.0)]])  # decays 1/2 and 1/4
    Bm, Cm, D = jnp.array([1.0, 2.0]), jnp.array([1.0, 10.0]), jnp.array([10.0])
    y, h = S6.s6_step(h0, x, dt, At, Bm, Cm, D)
    np.testing.assert_allclose(h[:, 0], [1.0 + 1.5, 0.5 + 3.0], rtol=1e-6)
    np.testing.assert_allclose(y, [2.5 + 35.0 + 30.0], rtol=1e-6)


# ---- (c) the kernel ------------------------------------------------------------------ #

# (Lm, B, N, E): the tiny preset's state with a batch under the slot block,
# and a state of two sublane tiles with two grid steps of eight slots.
KERNEL_SHAPES = {"b3_n8": (3, 3, 8, 128), "b16_n16": (3, 16, 16, 256)}
SHAPES = pytest.mark.parametrize("shape", sorted(KERNEL_SHAPES))


@functools.lru_cache(maxsize=None)
def _kernel_case(shape, layer=1):
    """One draw a shape: the inputs, the kernel's (interpreted) and the
    jitted XLA step's (y, state) after updating `layer` of the stack."""
    ks = jax.random.split(jax.random.key(5), 7)
    Lm, B, N, E = KERNEL_SHAPES[shape]
    state = jax.random.normal(ks[0], (Lm, B, N, E))
    x = jax.random.normal(ks[1], (B, E))
    dt = jax.nn.softplus(jax.random.normal(ks[2], (B, E)))
    At = -jnp.exp(jax.random.normal(ks[3], (N, E)))
    D = jax.random.normal(ks[4], (E,))
    Bm = jax.random.normal(ks[5], (B, N))
    Cm = jax.random.normal(ks[6], (B, N))
    got, want = (jax.jit(lambda s, i, impl=impl: S6.s6_decode(
        s, i, x, dt, At, Bm, Cm, D, impl=impl))(state, jnp.int32(layer))
        for impl in ("pallas", "xla"))
    return state, got, want


@pytest.mark.parametrize("layer", [0, 1, 2])
@SHAPES
def test_s6_decode_kernel_updates_its_layer_of_the_stack_in_place(shape, layer):
    """The Pallas kernel (interpreted here) against the XLA step, every
    layer of a three-layer stack as the target: every slot of every OTHER
    layer keeps its bits, every slot of the layer is updated (a dead slot's
    row as a live one's) to the oracle's bits, and y is the layer's own."""
    state, (got_y, got), (want_y, want) = _kernel_case(shape, layer)
    for other in range(state.shape[0]):
        if other != layer:
            np.testing.assert_array_equal(got[other], state[other])
    np.testing.assert_allclose(got[layer], want[layer], rtol=1e-6, atol=1e-6)
    assert (np.asarray(got[layer]) != np.asarray(state[layer])).any(
        axis=(1, 2)).all()  # every slot's row moved
    np.testing.assert_allclose(got_y, want_y, atol=2e-5)


def test_the_slot_block_and_the_impl_names():
    assert S6.SLOT_BLOCK == 8
    assert [S6.slot_block(b) for b in (1, 3, 8, 12, 64, 128)] == [
        1, 3, 8, 0, 8, 8]
    state, *_ = _kernel_case("b3_n8")
    with pytest.raises(ValueError, match="auto|pallas|xla"):
        S6.s6_decode(state, 1, *([None] * 6), impl="mosaic")


def test_the_kernel_runs_inside_the_hybrid_scan():
    """One paged decode step of the whole tiny model with the S6 kernel
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
    lk = jnp.zeros((2, B, n, 1, 16), jnp.float32)
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
def test_every_op_of_the_s6_operator_is_named_s6_mix(form):
    """`s6mqa_s6_mix_share` reads the word `s6_mix` in an op's name. XLA
    names a fusion after any op in it, so EVERY equation of the operator
    carries the word, around the leaf that books it (`scope_share` drops the
    word and reads the leaf); the prefill's scan carries `s6_prefill` too."""
    from localai_tpu.observe.scopes import S6_MIX, SCOPES

    ap = _layer()
    D = CFG.hidden_size
    rec = rstate.allocate(dataclasses.replace(CFG, layer_kinds=("s6",)), 2,
                          jnp.float32)
    if form == "decode":
        jaxpr = jax.make_jaxpr(lambda x, s, c: L._s6_decode_mix(
            CFG, ap, x, (s, c), 0))(jnp.zeros((2, D)), *rec)
    else:
        jaxpr = jax.make_jaxpr(lambda x, s, c: L._s6_prefill_mix(
            CFG, ap, x, jnp.array([5, 3]), (s, c), 0, jnp.array([1, 0])))(
                jnp.zeros((2, 5, D)), *rec)
    leaves, stacks = set(), []
    for e in jaxpr.jaxpr.eqns:
        stack = str(e.source_info.name_stack)
        assert stack.split("/")[0] == S6_MIX, (e.primitive.name, stack)
        leaves |= {leaf for leaf in SCOPES if f"/{leaf}" in stack}
        stacks.append(stack)
    assert leaves >= {"attention/proj", "attention/mix", "attention/cache_write",
                      "attention/out"}
    assert any("s6_prefill" in s for s in stacks) == (form == "prefill")


# ---- (f) the row, the layouts and the published preset -------------------------------- #


def test_the_row_is_a_matrix_of_states_by_channels_and_three_inputs():
    """320 KiB of float32 state a slot and layer, and 3 rows of 5120."""
    assert rstate._shapes(PUB, 128) == (
        (26, 128, 16, 5120), (26, 128, 3, 5120))
    assert rstate.row_bytes(PUB, "bfloat16") == 26 * (
        16 * 5120 * 4 + 3 * 5120 * 2) == 9_318_400
    assert 128 * rstate.row_bytes(PUB, "bfloat16") / 1e9 == pytest.approx(
        1.19, abs=0.005)  # ISSUE 55's recurrent_state_bytes at 128 slots
    st, conv = rstate.allocate(CFG, 4, jnp.bfloat16)
    assert st.shape == (4, 4, 8, 128) and st.dtype == jnp.float32
    assert conv.shape == (4, 4, 3, 128) and conv.dtype == jnp.bfloat16
    # the admission bound is this kind's own: a token's float32 rows, the
    # state once a prompt inside the loop
    assert rstate.admit_rows(PUB) == (1 << 30) // (
        4 * (8 * 5120 + 3 * 8192)) == 4096
    # and the other kinds' rows and bounds are what they were
    assert rstate.admit_rows(get_arch("kimi-linear-48b-a3b")) == 2048
    assert rstate.admit_rows(get_arch("solar-open2-250b")) == 1024
    assert rstate.admit_rows(get_arch("granite-4.0-h-small")) == 2048
    assert rstate.admit_rows(get_arch("laguna-xs.2")) == 3318
    assert rstate.admit_rows(get_arch("lfm2-8b-a1b")) is None
    st, conv = rstate.allocate(get_arch("tiny-granite-h"), 2, jnp.bfloat16)
    assert st.shape == (8, 2, 8, 16, 32) and conv.shape == (8, 2, 3, 192)


def test_hybrid_tables_take_the_published_period_and_offset():
    kl, beside, nd, kd, lead = L._hybrid_tables(PUB)
    assert PUB.recurrent_kind == "s6" and PUB.recurrent_stack == "s6_layers"
    assert PUB.cache_stack == "gqa_layers"
    assert PUB.cache_layer_ids == (7, 21)  # l mod 14 == 7
    arch = S.config("ai21-jamba2-3b-int8")
    assert tuple("gqa" if i % arch["attn_layer_period"]
                 == arch["attn_layer_offset"] else "s6"
                 for i in range(arch["num_hidden_layers"])) == PUB.layer_kinds
    assert len(kl) == 26 and (nd, kd, lead) == (0, 0, False)
    # each attention layer stands directly BEHIND a Mamba layer of its own
    assert {int(l): int(m) for l, m in zip(kl, beside) if m >= 0} == {
        6: 0, 20: 1}
    assert L._hybrid_tables(CFG)[0].tolist() == [0, 2, 3, 5]
    assert L._hybrid_tables(CFG)[1].tolist() == [0, -1, 1, -1]
    with pytest.raises(NotImplementedError, match="mixes the recurrent kinds"):
        L._hybrid_tables(dataclasses.replace(
            CFG, layer_kinds=("s6", "gqa", "ssd") * 2))
    with pytest.raises(NotImplementedError, match="beside a 's6' layer"):
        L._hybrid_tables(dataclasses.replace(
            CFG, layer_kinds=("s6", "gqa", "gqa") * 2))


def test_published_preset_and_its_held_tree():
    """The preset's tree is the published 3B, the costs file counts the
    same, and the chip holds what the configuration's sizing says."""
    tree = jax.eval_shape(lambda k: L.init_params(PUB, k), jax.random.key(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert "lm_head" not in tree and "ssd_layers" not in tree
    s6 = tree["s6_layers"]
    assert [s6[n].shape for n in ("w_in", "w_x", "w_dt", "wo")] == [
        (26, 2560, 10240), (26, 5120, 192), (26, 160, 5120), (26, 5120, 2560)]
    assert s6["A_logT"].shape == (26, 16, 5120) and s6["conv_w"].shape == (
        26, 4, 5120)
    assert [s6[n].shape[-1] for n in ("dt_norm", "b_norm", "c_norm")] == [
        160, 16, 16]
    gqa = tree["gqa_layers"]
    assert gqa["wq"].shape == (2, 2560, 2560) and gqa["wk"].shape == (
        2, 2560, 128) == gqa["wv"].shape
    assert tree["layers"]["w_gate"].shape == (28, 2560, 8192)
    assert "router" not in tree["layers"]
    assert abs(size(tree) / 1e9 - 3.03) < 0.005  # the card's "3B"
    arch = S.config("ai21-jamba2-3b-int8")
    assert COSTS.param_count(arch) == size(tree)
    assert (PUB.mamba_d_inner, PUB.mamba_d_state, PUB.mamba_dt_rank,
            PUB.mamba_conv) == (5120, 16, 160, 4)
    assert PUB.head_dim_ == arch["head_dim"] == 2560 // 20
    q = jax.eval_shape(lambda k: Q.init_params_quantized(PUB, k),
                       jax.random.key(0))
    for n in ("w_in", "w_x", "w_dt", "wo"):
        assert q["s6_layers"][n]["q"].dtype == jnp.int8
    assert q["s6_layers"]["conv_w"].dtype == jnp.bfloat16
    assert q["s6_layers"]["A_logT"].dtype == jnp.float32
    assert q["embed"].dtype == jnp.bfloat16  # the tied head stays as held
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(q))
    assert abs(nbytes / 1e9 - 3.21) < 0.01
    # a step at 128 slots: 2.18 GB of state read and written
    assert 128 * COSTS.s6_state_bytes_per_row(arch) == 128 * 26 * 2 * 327_680
    assert abs(128 * COSTS.s6_state_bytes_per_row(arch) / 1e9 - 2.18) < 0.01


def test_the_synthetic_init_is_mamba_ones_published_one():
    """A[c, n] = n + 1 in every channel, the step log-uniform in
    `SSD_DT` (the kinds that draw from it: "ssd" and "s6"), the skip at 1, in
    the float init and in the quantized one alike."""
    for tree in (L.init_params(CFG, jax.random.key(0)),
                 Q.init_params_quantized(
                     dataclasses.replace(CFG, dtype="bfloat16"),
                     jax.random.key(0))):
        s6 = tree["s6_layers"]
        np.testing.assert_allclose(
            jnp.exp(s6["A_logT"]),
            np.broadcast_to(np.arange(1, 9)[:, None], s6["A_logT"].shape),
            rtol=1e-6)
        assert np.asarray(s6["ssm_D"] == 1.0).all()
        dt = jax.nn.softplus(s6["dt_bias"])
        lo, hi = L.SSD_DT
        assert lo * 0.99 <= float(dt.min()) and float(dt.max()) <= hi * 1.01
        assert all(np.asarray(s6[n] == 1.0).all()
                   for n in ("dt_norm", "b_norm", "c_norm"))


# ---- (h) the other hybrids are what they were ------------------------------------------ #


@pytest.mark.parametrize("name", [
    "tiny-kimi-linear", "tiny-solar-open2", "tiny-lfm2", "tiny-granite-h"])
def test_the_other_hybrids_programs_are_what_they_were(name):
    """What this model touched of the code they share leaves them alone: the
    mixers the table hands out are the kind's own, their admission traces no
    equation under the new operator's name, the decay's draw by name is what
    it was, and the as-stored rule of the paged reader, which a ONE-head
    pool changed, gives every other head count what it gave
    (`tools/same_program.py` run on the parent tree and on this one is the
    whole proof: PERF.md section 6, PR 55)."""
    cfg = get_arch(name)
    kind = L.RECURRENT[cfg.recurrent_kind]
    want = {"kda": (L._init_kda_layers, L._kda_decode_mix, L._kda_prefill_mix),
            "conv": (L._init_conv_layers, L._conv_decode_mix,
                     L._conv_prefill_mix),
            "ssd": (L._init_ssd_layers, L._ssd_decode_mix,
                    L._ssd_prefill_mix)}[cfg.recurrent_kind]
    assert tuple(kind) == want
    params = L.init_params(cfg, jax.random.key(0))
    rec = rstate.allocate(cfg, 2, jnp.dtype(cfg.dtype))
    tok = jnp.ones((2, 32), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, *r: L.prefill(
        cfg, p, tok, jnp.array([20, 32]),
        recurrent=(*r, jnp.arange(2))))(params, *rec))
    assert "s6" not in text
    stack = params[cfg.recurrent_stack]
    assert "A_logT" not in stack
    if "A_log" in stack:  # drawn U(1, 16), as it was
        A = np.exp(np.asarray(stack["A_log"], np.float64))
        assert 1.0 <= A.min() and A.max() <= 16.0 and np.unique(A).size > 4
    # the paged reader's as-stored rule: K KV heads, G query rows a head
    bf16, f32, fp8 = jnp.bfloat16, jnp.float32, jnp.float8_e4m3fn
    for K in (2, 3, 4, 5, 6, 8, 16):
        for G in (1, 4, 6, 8):
            for dt in (bf16, fp8, f32):
                size = jnp.dtype(dt).itemsize
                old = (size < 4 and (K * size) % 4 == 0
                       and K * G <= PF.FLAT_MAX_ROWS)
                assert PF._flat_rows(dt, dt, K, G) == old, (K, G, dt)
    assert PF._flat_rows(bf16, bf16, 1, 20)  # the one-head pool, as stored
    assert not PF._flat_rows(f32, f32, 1, 20)
    assert not PF._flat_rows(bf16, bf16, 1, PF.FLAT_MAX_ROWS + 1)
