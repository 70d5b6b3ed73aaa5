"""LFM2-MoE (LiquidAI/LFM2-8B-A1B): gated short convolutions with a two-row
per-slot state beside GQA layers (per-head q/k norms, rope) over the paged K/V
pool, the attention layer LEADING its conv layer; a dense prefix of conv
layers, then sigmoid-routed experts held whole; a tied head.

At the `tiny-lfm2` width on the CPU: the program (`Engine.submit`, prefill
then decode through the K/V pool and the conv rows, across slot hand-ons and
a preemption) against the benchmark's plain float32 reference
(`benchmark/reference/conv_gqa_moe.py`, which shares no code with
`localai_tpu/models/`); the conv's decode step against its prefill form; the
router against the reference's and, for the DeepSeek-V3 family, against what
it was; the layouts `_hybrid_tables` takes and refuses.
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
from benchmark.reference import conv_gqa_moe as REF
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch

# float32 activations: the program's honest distance from the float32
# reference is then rounding alone and a wrong block stands out of it.
CFG = dataclasses.replace(get_arch("tiny-lfm2"), dtype="float32")
PUB = get_arch("lfm2-8b-a1b")
TOLERANCE = 1e-4


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with an expert bias that moves picks and q/k norms that
    are not all ones."""
    params = L.init_params(cfg, jax.random.key(7))
    k1, k2, k3 = jax.random.split(jax.random.key(8), 3)
    lay = dict(params["layers"])
    lay["router_bias"] = 0.1 * jax.random.normal(
        k1, lay["router_bias"].shape, jnp.float32)
    gqa = dict(params["gqa_layers"])
    for name, k in (("q_norm", k2), ("k_norm", k3)):
        gqa[name] = (1.0 + 0.3 * jax.random.normal(
            k, gqa[name].shape, jnp.float32)).astype(gqa[name].dtype)
    params = {**params, "layers": lay, "gqa_layers": gqa}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


_err = functools.partial(_err_against, REF.forward)


# ---- the engine against the reference ---------------------------------------- #


served = served_engine(_seeded, CFG)


def test_engine_agrees_with_the_plain_reference(served):
    eng, params = served
    assert params["conv_layers"]["w_in"]["q"].dtype == jnp.int8
    prompts = C.sample_prompts(11, CFG.vocab_size, [40, 90])
    recs = C.run_system(eng, prompts, 9)
    errs = [_err(params, CFG, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs
    m = eng.metrics()
    assert CFG.recurrent_layers == (0, 1, 3, 4, 5) and CFG.cache_layer_ids == (2,)
    # the row is the conv's two inputs a layer and nothing else
    assert eng.cache.state is None
    assert eng.cache.conv.shape == (5, 2, 2, 64)
    # 64-wide heads: the pool holds the two KV heads of a token in one row
    assert CFG.cache_pack == 2 and PUB.cache_pack == 2
    assert eng.cache.k.shape == (1, 41, 16, 1, 128) == eng.cache.v.shape
    assert m["recurrent_state_bytes"] == 2 * 5 * 2 * 64 * 4
    assert "state_snapshots" not in m
    assert "admit_rows_max" not in m and "admit_splits" not in m  # KDA's bound
    ev = eng.journal.snapshot()
    rows = [e for e in ev if e["event"] == "state_rows"]
    assert rows and all(e["a"] % (2 * 5) == 0 and e["b"] <= e["a"]
                        for e in rows)
    # moe_experts counts every expert of the 4 MoE layers: held whole
    hit = [e for e in ev if e["event"] == "moe_experts"]
    assert hit and all(e["a"] % (4 * 8) == 0 and 0 < e["b"] <= e["a"]
                       for e in hit)
    assert any(e["event"] == "moe_load" for e in ev)
    assert not [e for e in ev if e["event"] == "moe_here"]  # no share


def test_successor_never_sees_the_old_tenants_rows_or_pages(served):
    """Six requests through two slots, every one ending on its budget, so
    every hand-on goes through `_park` with both kinds of cache live: the old
    tenant's blocks in flight still shift its conv rows and write its pages,
    the successor's admission overwrites the rows and takes pages of its own.
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


def test_preempted_request_recomputes_its_rows():
    """A pool too small for two long decodes: the younger is preempted, its
    conv rows and its pages dropped, and its re-admission recomputes both
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


def test_the_pallas_walk_reads_two_heads_a_row():
    """The decode step over a pool of two 64-wide heads a 128-lane row: the
    kernel (interpreted here), which walks the rows as stored with q in its
    own head's lanes, and the XLA walk over a reshape of the pool give the
    same step; the rows the layer emits are the pool's; a model of wide
    heads, or one that is no hybrid, keeps a head a row."""
    params = _seeded()
    B, n, page, MP = 2, 4, 16, 4
    ks = jax.random.split(jax.random.key(21), 3)
    pool = L.paged_cache_zeros(CFG, B * MP + 1, page)
    assert pool.k.shape == (1, B * MP + 1, page, 1, 128)
    pool = pool._replace(k=jax.random.normal(ks[0], pool.k.shape),
                         v=jax.random.normal(ks[1], pool.v.shape))
    conv = 0.1 * jax.random.normal(ks[2], (5, B, 2, CFG.hidden_size))
    lk = jnp.zeros((1, B, n, 1, 128), jnp.float32)
    table = (jnp.arange(B * MP, dtype=jnp.int32) + 1).reshape(B, MP)

    def step(impl):
        return jax.jit(lambda cv: L.decode_step_windowed(
            CFG, params, jnp.array([5, 9]), jnp.array([37, 20]), pool, lk, lk,
            jnp.int32(0), ptable=table, paged_impl=impl,
            recurrent=(None, cv)))(conv)

    want, got = step("xla"), step("pallas")
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)  # logits
    assert got[1].shape == (1, B, n, 1, 128)  # the new K rows, as the pool's
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    np.testing.assert_allclose(got[-1][1], want[-1][1], atol=1e-5)  # conv rows
    assert dataclasses.replace(CFG, head_dim=128).cache_pack == 1
    assert get_arch("llama-3.2-1b").cache_pack == 1  # 64 wide, no hybrid
    assert get_arch("tiny-solar-open2").cache_kv_heads == 2


def test_what_needs_a_snapshot_is_refused_by_name():
    with pytest.raises(ValueError, match="conv layers.*chunked admission"):
        Engine(CFG, {}, ByteTokenizer(CFG.vocab_size), engine_cfg=EngineConfig(
            max_slots=2, max_seq=256, kv_pages=40, kv_page_size=16,
            prefill_chunk=64))


# ---- a wrong block fails the same comparison ----------------------------------- #


def _swapped(params, order):
    """W_in's three D-wide parts in another order."""
    conv = dict(params["conv_layers"])
    parts = jnp.split(conv["w_in"], 3, axis=-1)
    conv["w_in"] = jnp.concatenate([parts[i] for i in order], axis=-1)
    return {**params, "conv_layers": conv}


def _taps_reversed(params):
    conv = dict(params["conv_layers"])
    conv["conv_w"] = conv["conv_w"][:, ::-1]
    return {**params, "conv_layers": conv}


WRONG = {
    # the gate c taken for the conv's input z
    "split_b_z_c": (CFG, lambda p: _swapped(p, (0, 2, 1))),
    # tap 0 on the current token
    "taps_reversed": (CFG, _taps_reversed),
    # q and k unnormed
    "no_qk_norm": (dataclasses.replace(CFG, qk_norm=False), lambda p: p),
    # DeepSeek-V3's scale on the picks
    "scaled_picks": (dataclasses.replace(CFG, routed_scaling_factor=2.5),
                     lambda p: p),
    # an untied head would read another matrix: here, the embedding reversed
    "another_head": (dataclasses.replace(CFG, tie_embeddings=False),
                     lambda p: {**p, "lm_head": p["embed"][::-1]}),
}


@pytest.mark.parametrize("variant", ["right"] + sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant):
    """The admission program's logits against the reference's at the last
    prompt token, the right program and each wrong one."""
    cfg, change = WRONG.get(variant, (CFG, lambda p: p))
    params = _seeded()
    ids = C.sample_prompts(11, CFG.vocab_size, [48])[0]
    logits, *_ = jax.jit(lambda p, t: L.prefill(
        cfg, p, t, jnp.array([48], jnp.int32)))(
            change(params), jnp.asarray([ids], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    want = REF.forward(params, CFG, ids, [47], pad_to=16)[0]
    worst = float(np.max(np.abs(got - want)))
    assert (worst <= TOLERANCE) == (variant == "right"), (variant, worst)


# ---- the conv operator ---------------------------------------------------------- #


def test_the_decode_step_is_the_prefill_form_token_by_token():
    """One conv layer over 11 tokens of two prompts (the second 7 long):
    the prefill form's outputs and the rows it leaves in the slots are what
    11 decode steps from empty rows give, bit for bit."""
    ap = jax.tree.map(lambda a: a[1], _seeded()["conv_layers"])
    x = jax.random.normal(jax.random.key(2), (2, 11, CFG.hidden_size))
    lens = jnp.array([11, 7], jnp.int32)
    rows = jnp.zeros((1, 3, 2, CFG.hidden_size))  # three slots, one layer
    slots = jnp.array([2, 0], jnp.int32)
    y, (_, after) = L._conv_prefill_mix(CFG, ap, x, lens, (None, rows), 0, slots)
    rec = (None, jnp.zeros((1, 2, 2, CFG.hidden_size)))
    for t in range(11):
        yt, rec = L._conv_decode_mix(CFG, ap, x[:, t], rec, 0)
        np.testing.assert_array_equal(yt[0], y[0, t])
        if t < 7:
            np.testing.assert_array_equal(yt[1], y[1, t])
        if t == 6:  # the shorter prompt's rows: its tokens 5 and 6
            np.testing.assert_array_equal(rec[1][0, 1], after[0, 0])
    np.testing.assert_array_equal(rec[1][0, 0], after[0, 2])
    assert not np.asarray(after[0, 1]).any()  # a slot no prompt claimed
    # by hand: b = z = c = 1 over two tokens and taps (w0, w1, w2) give
    # v_0 = w2, v_1 = w1 + w2 (nothing before the sequence's start)
    D = CFG.hidden_size
    one = {"w_in": jnp.zeros((D, 3 * D)), "wo": jnp.eye(D),
           "conv_w": jnp.array([[2.0], [3.0], [5.0]]) * jnp.ones((3, D))}
    c, window = L._conv_inputs(CFG, one, jnp.zeros((1, 2, D)),
                               jnp.zeros((1, 2, D)))
    out = L._conv_out(CFG, one, jnp.ones_like(c),
                      window.at[:, 2:].set(1.0))
    np.testing.assert_array_equal(out[0, :, 0], [5.0, 8.0])


@pytest.mark.parametrize("form", ["decode", "prefill"])
def test_every_op_of_the_conv_operator_is_named_conv_mix(form):
    """`convgqa_conv_mix_share` reads the word `conv_mix` in an op's name. XLA
    names a fusion after any op in it, so EVERY equation of the operator
    carries the word, around the leaf that books it (`scope_share` drops the
    word and reads the leaf)."""
    from localai_tpu.observe.scopes import CONV_MIX, SCOPES

    ap = jax.tree.map(lambda a: a[1], _seeded()["conv_layers"])
    D = CFG.hidden_size
    rows = jnp.zeros((1, 2, 2, D))
    if form == "decode":
        jaxpr = jax.make_jaxpr(lambda x, r: L._conv_decode_mix(
            CFG, ap, x, (None, r), 0))(jnp.zeros((2, D)), rows)
    else:
        jaxpr = jax.make_jaxpr(lambda x, r: L._conv_prefill_mix(
            CFG, ap, x, jnp.array([5, 3]), (None, r), 0, jnp.array([1, 0])))(
                jnp.zeros((2, 5, D)), rows)
    leaves = set()
    for e in jaxpr.jaxpr.eqns:
        stack = str(e.source_info.name_stack)
        assert stack.split("/")[0] == CONV_MIX, (e.primitive.name, stack)
        leaves |= {leaf for leaf in SCOPES if f"/{leaf}" in stack}
    assert leaves >= {"attention/proj", "attention/mix", "attention/cache_write",
                      "attention/out"}


def test_the_row_is_two_inputs_a_layer_and_no_matrix():
    assert rstate.row_bytes(PUB, "bfloat16") == 18 * 2 * 2048 * 2 == 147456
    st, conv = rstate.allocate(PUB, 4, jnp.bfloat16)
    assert st is None and conv.shape == (18, 4, 2, 2048)
    assert rstate.admit_rows(PUB) is None  # KDA's byte bound is not this kind's
    kimi = get_arch("tiny-kimi-linear")
    st, conv = rstate.allocate(kimi, 2, jnp.bfloat16)
    assert st.shape == (5, 2, 4, 16, 16) and conv.shape == (5, 2, 3, 192)


# ---- the router ------------------------------------------------------------------ #


@pytest.mark.parametrize("bias", [0.0, 0.2])
def test_router_agrees_with_the_reference(bias):
    """Sigmoid over all experts, the top k of score + bias, the picks' plain
    scores over their sum + 1e-6 (a sum small enough to feel it), x 1."""
    cfg = dataclasses.replace(CFG, hidden_size=16)
    k1, k2, k3 = jax.random.split(jax.random.key(3), 3)
    lp = {"router": jax.random.normal(k1, (16, cfg.num_experts)) - 4.0,
          "router_bias": bias * jax.random.normal(k2, (cfg.num_experts,))}
    x = jnp.abs(jax.random.normal(k3, (32, 16)))
    with jax.default_matmul_precision("highest"):
        w, sel = L._deepseek_route(cfg, lp, x)
        g, e = REF.route(x, lp["router"], lp["router_bias"],
                         top_k=cfg.num_experts_per_token, scaling=1.0)
    np.testing.assert_array_equal(sel, e)
    np.testing.assert_allclose(w, g, rtol=1e-6)
    s = np.asarray(jnp.take_along_axis(jax.nn.sigmoid(
        x @ lp["router"]), sel, -1), np.float64)
    assert s.sum(-1).min() < 1e-3  # where 1e-6 is a thousandth and more
    np.testing.assert_allclose(w, s / (s.sum(-1, keepdims=True) + 1e-6),
                               rtol=1e-5)
    assert float(jnp.abs(w.sum(-1) - 1.0).max()) > 1e-4


@pytest.mark.parametrize("name", [
    "tiny-mla", "tiny-kimi-linear", "tiny-solar-open2"])
def test_deepseek_v3_family_routing_is_what_it_was(name):
    """The renormalisation's epsilon now comes from the config: 1e-20 for
    every model that had it in the code, bit for bit."""
    cfg = get_arch(name)
    assert cfg.norm_topk_eps == 1e-20 and CFG.norm_topk_eps == 1e-6
    k1, k2, k3 = jax.random.split(jax.random.key(5), 3)
    E, k = cfg.num_experts, cfg.num_experts_per_token
    lp = {"router": jax.random.normal(k1, (cfg.hidden_size, E)),
          "router_bias": 0.1 * jax.random.normal(k2, (E,))}
    x = jax.random.normal(k3, (24, cfg.hidden_size))
    w, sel = L._deepseek_route(cfg, lp, x)
    plain = dataclasses.replace(cfg, norm_topk_prob=False,
                                routed_scaling_factor=1.0)
    raw, sel0 = L._deepseek_route(plain, lp, x)
    np.testing.assert_array_equal(sel, sel0)
    was = raw / (raw.sum(axis=-1, keepdims=True) + 1e-20)
    np.testing.assert_array_equal(w, was * cfg.routed_scaling_factor)


# ---- the layouts ------------------------------------------------------------------ #


def test_hybrid_tables_take_the_published_layer_types():
    kl, beside, nd, kd, lead = L._hybrid_tables(PUB)
    assert PUB.recurrent_kind == "conv" and PUB.recurrent_stack == "conv_layers"
    assert PUB.cache_layer_ids == (2, 6, 10, 14, 18, 21)
    assert len(kl) == 18 and (nd, kd, lead) == (2, 2, True)
    # every attention layer stands directly in front of a conv layer of its own
    assert {int(l): int(m) for l, m in zip(kl, beside) if m >= 0} == {
        3: 0, 7: 1, 11: 2, 15: 3, 19: 4, 22: 5}
    assert L._hybrid_tables(CFG)[1].tolist() == [-1, -1, 0, -1, -1]


@pytest.mark.parametrize("kinds,why", [
    # two recurrent kinds in one stack
    (("conv", "conv", "gqa", "kda", "conv", "conv"), "mixes the recurrent kinds"),
    # "behind": layer 1 is a dense-prefix layer and layer 2 is not in front
    # of a conv layer of its own
    (("conv", "conv", "gqa", "gqa", "conv", "conv"), "beside a 'conv' layer"),
    # an attention layer in the dense prefix
    (("conv", "gqa", "conv", "conv", "conv", "conv"), "dense-prefix"),
])
def test_hybrid_tables_refuse_by_name(kinds, why):
    with pytest.raises(NotImplementedError, match=why):
        L._hybrid_tables(dataclasses.replace(CFG, layer_kinds=kinds))


def test_published_preset_and_its_tree():
    """The preset's tree is the published 8.3 B, and what int8 holds of it."""
    tree = jax.eval_shape(lambda k: L.init_params(PUB, k), jax.random.key(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert "lm_head" not in tree and "kda_layers" not in tree
    assert size(tree["conv_layers"]) == 18 * (4 * 2048 * 2048 + 3 * 2048)
    assert size(tree["gqa_layers"]) == 6 * (
        2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 64)
    # the pool: 4 rows of two 64-wide heads a token, 12,288 B in bfloat16
    pool = jax.eval_shape(lambda: L.paged_cache_zeros(PUB, 257, 128))
    assert pool.k.shape == (6, 257, 128, 4, 128) == pool.v.shape
    assert abs(size(tree) / 1e9 - 8.34) < 0.01
    q = jax.eval_shape(lambda k: Q.init_params_quantized(PUB, k),
                       jax.random.key(0))
    assert q["conv_layers"]["w_in"]["q"].shape == (18, 2048, 6144)
    assert q["conv_layers"]["w_in"]["q"].dtype == jnp.int8
    assert q["conv_layers"]["conv_w"].dtype == jnp.bfloat16
    assert q["layers"]["w_gate"]["q"].shape == (22, 32, 2048, 1792)
    assert q["layers"]["router_bias"].dtype == jnp.float32
    assert q["embed"].dtype == jnp.bfloat16  # the tied head stays as held
