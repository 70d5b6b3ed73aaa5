"""Solar-Open2 (upstage/Solar-Open2-250B): periods of one gated NoPE GQA layer
over an ordinary paged K/V pool and three KDA layers with a per-slot
recurrent state (beta in (0, 2)), the cache layer LEADING its period; every
layer a sigmoid-routed MoE with a shared expert, of which a share is held.

At the `tiny-solar-open2` width on the CPU: the program (`Engine.submit`,
prefill then decode through the K/V pool and the recurrent state, across slot
hand-ons and a preemption) against the benchmark's plain float32 reference
(`benchmark/reference/kda_gqa_moe.py`, which shares no code with
`localai_tpu/models/`); the delta rule at beta near 2 against numbers worked
out by hand; the share test; the layouts `_hybrid_tables` takes and refuses.
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
from benchmark.reference import kda_gqa_moe as REF
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch
from localai_tpu.ops import kda as KDA

SHARE = (1, 8)  # 3 of 24 experts: a count that is no power of two
# float32 activations: the program's honest distance from the float32
# reference is then rounding alone (2e-6 at worst over the right cases below)
# and a wrong block stands out of it (2e-3 at the least, the rotation).
CFG = dataclasses.replace(get_arch("tiny-solar-open2"), expert_share=SHARE,
                          dtype="float32")
TOLERANCE = 1e-4


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with a correction bias that moves picks, a head norm
    that is not all ones and a W_beta large enough that beta passes 1."""
    params = L.init_params(cfg, jax.random.key(7))
    k1, k2 = jax.random.split(jax.random.key(8))
    lay = dict(params["layers"])
    lay["router_bias"] = 0.1 * jax.random.normal(
        k1, lay["router_bias"].shape, jnp.float32)
    kda = dict(params["kda_layers"])
    kda["o_norm"] = (1.0 + 0.3 * jax.random.normal(
        k2, kda["o_norm"].shape, jnp.float32)).astype(kda["o_norm"].dtype)
    kda["w_beta"] = kda["w_beta"] * 40.0
    params = {**params, "layers": lay, "kda_layers": kda}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


_err = functools.partial(_err_against, REF.forward)


# ---- the engine against the reference ---------------------------------------- #


served = served_engine(_seeded, CFG)


def test_engine_agrees_with_the_plain_reference(served):
    eng, params = served
    prompts = C.sample_prompts(11, CFG.vocab_size, [40, 90])
    recs = C.run_system(eng, prompts, 9)
    errs = [_err(params, CFG, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs
    m = eng.metrics()
    kl = len(CFG.recurrent_layers)
    assert kl == 6 and CFG.cache_layer_ids == (0, 4)
    assert m["recurrent_state_bytes"] == 2 * rstate.row_bytes(CFG, "float32")
    assert "state_snapshots" not in m and m["admit_splits"] == 0
    # the pool is the cache layers' ordinary keys and values
    assert eng.cache.k.shape == (2, 41, 16, 2, 16) == eng.cache.v.shape
    assert eng.cache.state.shape == (kl, 2, 4, 16, 16)
    ev = eng.journal.snapshot()
    rows = [e for e in ev if e["event"] == "state_rows"]
    assert rows and all(e["a"] % (2 * kl) == 0 and e["b"] <= e["a"]
                        for e in rows)
    here = [e for e in ev if e["event"] == "moe_here"]
    picks, landed = sum(e["a"] for e in here), sum(e["b"] for e in here)
    assert picks == m["moe_picks"] and landed == m["moe_picks_here"]
    assert 0.03 < landed / picks < 0.3  # an eighth of the experts held
    # the grouped expert kernel does not run off the TPU, so no admission
    # program reports a walk (below: where it runs)
    assert not [e for e in ev if e["event"] == "moe_admit_rows"]
    assert m["moe_admit_rows"] == 0 == m["moe_admit_rows_held"]
    # moe_experts counts the held experts: 8 MoE layers x 3 of 24
    assert all(e["a"] % (8 * 3) == 0 for e in ev if e["event"] == "moe_experts")
    assert any(e["event"] == "moe_load" for e in ev)


def test_admission_reports_what_its_grouped_kernel_walked():
    """`prefill(expert_rows=True)` where the grouped kernel engages (asked
    for by name: interpret mode here): the sorted (row, pick) pairs it was
    compiled for over the 8 MoE layers and those of an expert held here, an
    eighth; the same logits as the XLA form. Nothing to report at few rows,
    where the admission runs all-experts."""
    params = _seeded(quantize="int8")
    toks = jax.random.randint(jax.random.key(5), (2, 48), 0, CFG.vocab_size)
    lens = jnp.asarray([48, 31], jnp.int32)
    logits = {}
    for kernel in ("xla", "pallas"):
        cfg = dataclasses.replace(CFG, quant_kernel=kernel)
        logits[kernel], _, _, walked = jax.jit(
            lambda p, t, n, cfg=cfg: L.prefill(cfg, p, t, n, expert_rows=True)
        )(params, toks, lens)
        pairs = 8 * 2 * 48 * CFG.num_experts_per_token
        if kernel == "xla":
            assert walked.tolist() == [0, 0]
        else:
            assert int(walked[0]) == pairs
            assert 0.03 < int(walked[1]) / pairs < 0.3
    np.testing.assert_allclose(logits["pallas"], logits["xla"], atol=3e-2)
    cfg = dataclasses.replace(CFG, quant_kernel="pallas")
    *_, walked = L.prefill(cfg, params, toks[:, :16], lens // 3,
                           expert_rows=True)
    assert walked.tolist() == [0, 0]  # 32 rows: all-experts


def test_one_period_stage_agrees_with_the_plain_reference():
    """The benchmark cell's cut: `stage_layers` keeps one whole period (one
    cache layer, so a pool stacked over ONE layer, and three KDA layers) and
    `vocab_rows` the head's first rows; the engine against the reference."""
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.server.manager import _apply_deployment_share

    cfg = _apply_deployment_share(CFG, ModelConfig(
        name="x", stage_layers=4, vocab_rows=384))
    assert (cfg.num_layers, cfg.vocab_size, cfg.cache_layer_ids) == (4, 384, (0,))
    params = _seeded(cfg, quantize="int8")
    eng = _engine(cfg, params)
    try:
        assert eng.cache.k.shape == (1, 41, 16, 2, 16)
        assert eng.cache.state.shape == (3, 2, 4, 16, 16)
        prompts = C.sample_prompts(13, cfg.vocab_size, [30, 70])
        recs = C.run_system(eng, prompts, 9)
    finally:
        eng.stop()
    errs = [_err(params, cfg, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs


def test_the_pallas_walk_reads_its_layer_from_inside_the_hybrid_scan():
    """The cache layer's index reaches `paged_attention` as a scalar from
    inside the scan's conditional: the kernel (interpreted here) and the XLA
    walk give the same step over a pool whose two layers differ; and an
    engine on the kernel counts every site as stacked and, its pool being
    bfloat16, as native."""
    params = _seeded()
    B, n, page, MP = 2, 4, 16, 4
    kl = len(CFG.recurrent_layers)
    ks = jax.random.split(jax.random.key(21), 4)
    pool = L.paged_cache_zeros(CFG, B * MP + 1, page)
    pool = pool._replace(k=jax.random.normal(ks[0], pool.k.shape),
                         v=jax.random.normal(ks[1], pool.v.shape))
    state = 0.1 * jax.random.normal(ks[2], (kl, B, 4, 16, 16))
    conv = 0.1 * jax.random.normal(ks[3], (kl, B, 3, 3 * 64))
    lk = jnp.zeros((CFG.cache_layers, B, n, 2, 16), jnp.float32)
    table = (jnp.arange(B * MP, dtype=jnp.int32) + 1).reshape(B, MP)

    def step(impl):
        return jax.jit(lambda st, cv: L.decode_step_windowed(
            CFG, params, jnp.array([5, 9]), jnp.array([37, 20]), pool, lk, lk,
            jnp.int32(0), ptable=table, paged_impl=impl,
            recurrent=(st, cv), kda_impl=impl))(state, conv)

    want, got = step("xla"), step("pallas")
    np.testing.assert_allclose(got[0], want[0], atol=2e-4)  # logits
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)  # the new K rows
    np.testing.assert_allclose(got[-1][0], want[-1][0], atol=1e-5)  # state

    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    eng = _engine(cfg, _seeded(cfg), paged_kernel="pallas")
    try:
        _, ev = eng.generate(list(range(5, 45)), max_new_tokens=4,
                             ignore_eos=True)
        assert ev.kind == "done"
        block = dict(eng.quant_sites.by_program)["decode_block"]
        m = eng.metrics()
    finally:
        eng.stop()
    assert block["paged_attention_stacked"] == block["traces"] > 0
    assert block["paged_attention_native"] == block["traces"]
    assert block["paged_attention_sliced"] == block["paged_attention_f32"] == 0
    assert m["paged_attention_f32_sites"] == 0 < m["paged_attention_native_sites"]


def test_successor_never_sees_the_old_tenants_state_or_pages(served):
    """Six requests through two slots, every one ending on its budget, so
    every hand-on goes through `_park` with both kinds of cache live: the old
    tenant's blocks in flight still update its state row and write its pages,
    the successor's admission overwrites the row and takes pages of its own.
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


NEW = 100


def test_preempted_request_recomputes_its_state_and_its_rows():
    """A pool too small for two long decodes: the younger is preempted, its
    state row and its pages dropped, and its re-admission recomputes both
    from prompt + generated. Both streams still agree with the reference."""
    params = _seeded()
    eng = _engine(CFG, params, kv_pages=10, kv_preempt="auto",
                  kv_page_headroom=1)
    try:
        prompts = C.sample_prompts(14, CFG.vocab_size, [40, 44])
        handles = []
        for p in prompts:  # the first strictly older: the second is the victim
            handles.append(eng.submit(GenRequest(
                prompt_ids=list(p), max_new_tokens=NEW, temperature=0.0,
                ignore_eos=True)))
            time.sleep(0.3)
        streams = []
        for h in handles:
            ids = [int(ev.token_id) for ev in h if ev.kind == "token"]
            assert len(ids) == NEW
            streams.append(ids)
        m = eng.metrics()
    finally:
        eng.stop()
    assert m["kv_preemptions"] >= 1 and m["state_restores"] >= 1
    assert m["kv_preempt_swaps"] == 0  # the state has no swap image
    for p, ids in zip(prompts, streams):
        lp = C.reference_logprobs(REF.forward, params, CFG, p, ids, pad_to=16)
        gap = lp.max(-1) - lp[np.arange(NEW), ids]
        assert gap.max() <= TOLERANCE, gap.max()


def test_an_admission_group_is_cut_by_the_byte_bound(monkeypatch):
    """Eight prompts of one bucket arrive together; no admission program
    takes more rows than the bound's bytes buy at the model's own widths,
    and the cut is counted and journalled."""
    per_token = 2 * CFG.kda_heads * KDA.SUB * CFG.kda_head_dim * 4
    monkeypatch.setattr(rstate, "ADMIT_BYTES", 64 * per_token)
    assert rstate.admit_rows(CFG) == 64
    eng = _engine(CFG, _seeded(), max_slots=8, kv_pages=64)
    try:
        prompts = C.sample_prompts(15, CFG.vocab_size, [20] * 8)
        bucket = eng._bucket_for(20)
        handles = [eng.submit(GenRequest(
            prompt_ids=list(p), max_new_tokens=1, temperature=0.0,
            ignore_eos=True)) for p in prompts]
        assert all(h.result()[1].kind == "done" for h in handles)
        sizes = {key[0] for key in eng._admit_cache}
        m = eng.metrics()
        cuts = [e for e in eng.journal.snapshot()
                if e["event"] == "admit_split"]
    finally:
        eng.stop()
    assert sizes and max(sizes) == max(1, 64 // bucket) < 8, (sizes, bucket)
    assert m["admit_splits"] == len(cuts) >= 1 and m["admit_rows_max"] == 64
    assert all(e["a"] > 1 and e["b"] >= e["a"] for e in cuts)


def test_the_byte_bound_gives_each_published_model_its_rows():
    assert rstate.admit_rows(get_arch("kimi-linear-48b-a3b")) == 2048
    assert rstate.admit_rows(get_arch("solar-open2-250b")) == 1024


# ---- a wrong block fails the same comparison ----------------------------------- #


WRONG = {
    # the gate left out of the program
    "gate_off": dataclasses.replace(CFG, attn_gate=False),
    # the program rotating q and k where the model does not
    "rotated": dataclasses.replace(CFG, attn_rope=True),
    # beta held to (0, 1): no negative eigenvalue
    "beta_below_one": dataclasses.replace(CFG, kda_neg_eigval=False),
    # the program holding another share than the reference is told
    "another_share": dataclasses.replace(CFG, expert_share=(2, 8)),
}


@pytest.mark.parametrize("variant", ["right"] + sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant):
    """The admission program's logits against the reference's at the last
    prompt token, the right program and each wrong one."""
    cfg = WRONG.get(variant, CFG)
    params = _seeded()
    ids = C.sample_prompts(11, CFG.vocab_size, [48])[0]
    logits, *_ = jax.jit(lambda p, t: L.prefill(
        cfg, p, t, jnp.array([48], jnp.int32)))(
            params, jnp.asarray([ids], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    want = REF.forward(params, CFG, ids, [47], pad_to=16)[0]
    worst = float(np.max(np.abs(got - want)))
    assert (worst <= TOLERANCE) == (variant == "right"), (variant, worst)


# ---- the delta rule at beta near 2 --------------------------------------------- #


def test_two_tokens_at_beta_near_two_by_hand():
    """One head, no decay, k_1 = k_2 = e_1, q = e_1 dk^0 (a unit read):
    S_1 = b1 e1 v1^T, so o_1 = b1 v1; S_2 = (1 - b2) b1 e1 v1^T + b2 e1 v2^T,
    so o_2 = (1 - b2) b1 v1 + b2 v2. At b = 1.99 the first term's sign has
    flipped: o_2 = -1.9701 v1 + 1.99 v2. A beta held below 1 cannot give it."""
    d, b = 16, 1.99
    e1 = jnp.zeros((d,)).at[0].set(1.0)
    v1 = jnp.arange(1.0, d + 1.0)
    v2 = jnp.cos(jnp.arange(d, dtype=jnp.float32))
    q = k = jnp.broadcast_to(e1, (1, 2, 1, d))
    v = jnp.stack([v1, v2])[None, :, None, :]
    g = jnp.zeros((1, 2, 1, d))
    beta = jnp.full((1, 2, 1), b)
    want = np.stack([b * v1, (1 - b) * b * v1 + b * v2])
    assert want[1, 3] < 0 < v1[3]  # -1.9701 x 4 + 1.99 cos 3: the flip
    o, S = KDA.kda_recurrent(q, k, v, g, beta)
    np.testing.assert_allclose(o[0, :, 0], want, rtol=1e-6)
    pad = 14  # the chunkwise form takes sub-blocks of 16 rows
    qp, kp, vp, gp = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
    valid = (jnp.arange(16) < 2)[None]
    oc, Sc = jax.jit(KDA.kda_chunk_prefill)(
        qp, kp, vp, gp, jnp.pad(beta, ((0, 0), (0, pad), (0, 0))), valid)
    np.testing.assert_allclose(oc[0, :2, 0], want, rtol=1e-5)
    np.testing.assert_allclose(Sc, S, rtol=1e-5, atol=1e-6)
    # the decode kernel, from S_1, on the stacked state
    _, S1 = KDA.kda_recurrent(q[:, :1], k[:, :1], v[:, :1], g[:, :1],
                              beta[:, :1])
    for impl in ("pallas", "xla"):
        o2, st = KDA.kda_decode(S1[None], jnp.int32(0), q[:, 1], k[:, 1],
                                v[:, 1], g[:, 1], beta[:, 1], impl=impl)
        # o_2's first entry is -1.9701 + 1.99: the sum keeps an ulp of its
        # terms (2^-23), 6e-6 of what is left, and which way it rounds is the
        # backend's (a fused multiply-add or two roundings)
        np.testing.assert_allclose(o2[0, 0], want[1], rtol=1e-6, atol=2.0**-22)
        np.testing.assert_allclose(st[0], S, rtol=1e-6, atol=1e-6)


def test_the_model_asks_for_beta_up_to_two():
    """`_kda_inputs` under `kda_neg_eigval`: twice the sigmoid."""
    ap = jax.tree.map(lambda a: a[0], _seeded()["kda_layers"])
    x = jax.random.normal(jax.random.key(2), (1, 32, CFG.hidden_size))
    conv = jnp.zeros((1, CFG.kda_conv - 1, 3 * 64))
    beta = L._kda_inputs(CFG, ap, x, conv)[4]
    unit = L._kda_inputs(dataclasses.replace(CFG, kda_neg_eigval=False),
                         ap, x, conv)[4]
    np.testing.assert_allclose(beta, 2.0 * unit, rtol=1e-6)
    assert float(beta.max()) > 1.5 and float(beta.min()) > 0.0


# ---- the share test ------------------------------------------------------------ #


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The eight shares' routed parts, with the shared expert counted once,
    add up to the uncut reference's MoE layer; program and reference."""
    full = dataclasses.replace(CFG, expert_share=None)
    params = _seeded(cfg=full)
    lp = {k: v[2] for k, v in params["layers"].items()}  # one MoE layer
    x = jax.random.normal(jax.random.key(3), (24, full.hidden_size), jnp.float32)
    lw = {k: lp[k] for k in REF._MOE}
    kw = dict(top_k=full.num_experts_per_token, eps=full.rms_eps,
              scaling=full.routed_scaling_factor)
    from benchmark.reference.kda_mla_moe import _rms_norm, _swiglu

    with jax.default_matmul_precision("highest"):
        whole = REF.experts(x, lw, lo=0, **kw) - x
        m = _rms_norm(x, lp["mlp_norm"], full.rms_eps)
        shared = _swiglu(m, lp["shared_gate"], lp["shared_up"],
                         lp["shared_down"], jnp.float32, "")
        prog, ref = -7 * shared, -7 * shared  # counted once of eight times
        for i in range(8):
            cfg_i = dataclasses.replace(full, expert_share=(i, 8))
            assert cfg_i.experts_here == 3
            held = slice(cfg_i.expert_lo, cfg_i.expert_lo + cfg_i.experts_here)
            lp_i = {**lp, **{k: lp[k][held] for k in ("w_gate", "w_up", "w_down")}}
            prog = prog + L._mlp(cfg_i, lp_i, m)
            ref = ref + REF.experts(
                x, {k: lp_i[k] for k in REF._MOE}, lo=cfg_i.expert_lo, **kw) - x
    np.testing.assert_allclose(ref, whole, atol=2e-5)
    np.testing.assert_allclose(prog, whole, atol=2e-5)


# ---- the layouts the one scan takes -------------------------------------------- #


def _kinds(cfg, kinds, **kw):
    return dataclasses.replace(cfg, layer_kinds=tuple(kinds),
                               num_layers=len(kinds), **kw)


def test_hybrid_tables_take_a_cache_layer_behind_or_in_front():
    kl, beside, nd, kd, lead = L._hybrid_tables(CFG)
    assert lead and (nd, kd) == (0, 0)
    assert list(kl) == [1, 2, 3, 5, 6, 7] and list(beside) == [0, -1, -1, 1, -1, -1]
    kimi = get_arch("tiny-kimi-linear")
    kl, beside, nd, kd, lead = L._hybrid_tables(kimi)
    assert not lead and (nd, kd) == (1, 1)
    assert list(kl) == [0, 1, 2, 4, 5] and list(beside) == [-1, -1, 0, -1, 1]
    # a ragged end in front: the last period cut after its cache layer's KDA
    *_, lead = L._hybrid_tables(_kinds(CFG, ["gqa", "kda", "kda", "gqa", "kda"]))
    assert lead
    # a cache layer between two KDA layers reads as behind the first
    *_, lead = L._hybrid_tables(_kinds(CFG, ["kda", "gqa", "kda"]))
    assert not lead


REFUSED_LAYOUTS = {
    "two_cache_layers_in_a_row": ["gqa", "gqa", "kda", "kda"],
    "some_behind_some_in_front": ["gqa", "kda", "kda", "gqa"],
    "no_kda_layer": ["gqa", "gqa"],
    "the_other_models_kind": ["mla", "kda", "kda", "kda"],
    "a_kind_nobody_knows": ["gqa", "kda", "rwkv", "kda"],
}


@pytest.mark.parametrize("what", sorted(REFUSED_LAYOUTS))
def test_hybrid_tables_refuse_what_the_scan_cannot_run(what):
    with pytest.raises(NotImplementedError, match="beside a 'kda' layer"):
        L._hybrid_tables(_kinds(CFG, REFUSED_LAYOUTS[what]))


def test_hybrid_tables_take_a_dense_prefix_of_one_kind_only():
    """A cache layer may carry a dense-prefix MLP where the whole prefix is
    cache layers (they run ahead of the scan, beside no recurrent layer:
    Laguna's layer 0); a prefix that mixes the kinds is refused."""
    kimi = get_arch("tiny-kimi-linear")
    _, beside, nd, kd, lead = L._hybrid_tables(
        _kinds(kimi, ["mla", "kda", "kda", "kda"]))
    assert (beside.tolist(), nd, kd, lead) == ([-1, -1, -1], 0, 1, False)
    with pytest.raises(NotImplementedError, match="dense-prefix"):
        L._hybrid_tables(_kinds(kimi, ["kda", "mla", "kda"], first_k_dense=2))


# ---- what such a model is refused, in its own words ----------------------------- #


def test_refusal_speaks_of_this_models_rows():
    with pytest.raises(ValueError) as e:
        Engine(CFG, _seeded(), ByteTokenizer(CFG.vocab_size),
               engine_cfg=EngineConfig(max_slots=2, max_seq=128, kv_pages=0))
    said = str(e.value)
    assert "recurrent state" in said and "K/V cache rows" in said
    assert f"{rstate.row_bytes(CFG, 'float32')} bytes a slot" in said
    assert "latent" not in said


def test_gated_nope_attention_keeps_its_checkpoint_names(tmp_path):
    """A dense model with the softmax layers' attention: the gate is saved
    and loaded as `self_attn.g_proj`, the two flags as the published
    config.json spells them; a checkpoint with linear-attention layers is
    refused by name (its stacks have no loader: synthetic weights only)."""
    import json

    from localai_tpu.engine.weights import (
        arch_from_hf_config, load_hf_checkpoint, save_hf_checkpoint)

    cfg = dataclasses.replace(get_arch("tiny"), attn_rope=False, attn_gate=True)
    params = L.init_params(cfg, jax.random.key(3))
    d = str(tmp_path / "gated")
    save_hf_checkpoint(cfg, params, d)
    arch = arch_from_hf_config(d)
    assert arch.attn_gate and not arch.attn_rope
    loaded = load_hf_checkpoint(arch, d)
    np.testing.assert_allclose(
        np.asarray(loaded["layers"]["wg"], np.float32),
        np.asarray(params["layers"]["wg"], np.float32), atol=1e-2)
    toks = jnp.asarray([list(range(5, 29))], jnp.int32)
    lens = jnp.array([24], jnp.int32)
    want = L.prefill(cfg, params, toks, lens)[0]
    np.testing.assert_allclose(L.prefill(arch, loaded, toks, lens)[0], want,
                               atol=2e-2)
    plain = dataclasses.replace(cfg, attn_gate=False)
    assert float(jnp.max(jnp.abs(L.prefill(plain, params, toks, lens)[0]
                                 - want))) > 1e-3
    with open(f"{d}/config.json") as f:
        hf = json.load(f)
    with open(f"{d}/config.json", "w") as f:
        json.dump({**hf, "linear_attn_config": {"num_heads": 4}}, f)
    with pytest.raises(ValueError, match="linear-attention"):
        arch_from_hf_config(d)


# ---- the published preset and the deployment's keys ------------------------------ #


def test_published_preset_and_its_held_tree():
    """The preset's shapes against the benchmark's byte counts: the tree a
    chip holds under the deployment's cut is `costs_kda_gqa.held_params`, and
    the uncut model is the published 250B."""
    from benchmark.harness import costs_kda_gqa
    from benchmark.harness import spec as S
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.server.manager import _apply_deployment_share

    arch = S.config("solar-open2-250b-int8-ep8")
    pub = get_arch("solar-open2-250b")
    assert pub.cache_layer_ids == tuple(range(0, 48, 4)) == tuple(
        arch["gqa_layers"])
    assert abs(costs_kda_gqa.param_count(arch) / 1e9 - 250.3) < 0.05
    y = arch["yaml"]
    cfg = _apply_deployment_share(pub, ModelConfig(
        name="x", expert_share=y["expert_share"],
        stage_layers=y["stage_layers"], vocab_rows=y["vocab_rows"]))
    assert (cfg.num_layers, cfg.vocab_size, cfg.experts_here) == (
        arch["num_hidden_layers"], arch["vocab_size"],
        arch["n_routed_experts"]) == (4, 24576, 40)
    assert cfg.layer_kinds == ("gqa", "kda", "kda", "kda")
    tree = jax.eval_shape(lambda k: L.init_params(cfg, k), jax.random.key(0))
    held = costs_kda_gqa.held_params(arch)
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    norms = 4 * 2 * 4096
    assert size(tree["kda_layers"]) == held["kda_attention"]
    assert size(tree["gqa_layers"]) == held["gqa_attention"]
    assert size(tree["lm_head"]) == held["head"] == size(tree["embed"])
    moe = size(tree["layers"]) - norms
    assert moe - 4 * 320 == held["shared_router"] + held["experts_held"]
    q = jax.eval_shape(lambda k: Q.init_params_quantized(cfg, k),
                       jax.random.key(0))
    assert q["gqa_layers"]["wg"]["q"].dtype == jnp.int8
    assert q["gqa_layers"]["wg"]["q"].shape == (1, 4096, 8192)
    assert q["kda_layers"]["A_log"].dtype == jnp.float32
    assert q["layers"]["w_gate"]["q"].shape == (4, 40, 4096, 1280)
    assert q["layers"]["w_down"]["q"].shape == (4, 40, 1280, 4096)
    # the synthetic decay step: fla's own for this model, a hundredth of it
    # (the default) for Kimi-Linear, whose init must not move
    assert pub.kda_init_dt == (1e-3, 1e-1)
    assert get_arch("kimi-linear-48b-a3b").kda_init_dt == L.KDA_DT
    sp = lambda x: np.log1p(np.exp(np.asarray(x, np.float64)))  # noqa: E731
    k = jax.random.key(4)
    mine = sp(L.init_special("dt_bias", k, (4096,), pub.kda_init_dt))
    assert 1e-3 * 0.99 <= mine.min() and mine.max() <= 1e-1 * 1.01
    np.testing.assert_array_equal(
        L.init_special("dt_bias", k, (64,)),
        L.init_special("dt_bias", k, (64,), L.KDA_DT))
    assert _apply_deployment_share(pub, ModelConfig(name="x")) is pub
    # the other hybrid's one deployment key takes the same road
    kimi = _apply_deployment_share(get_arch("kimi-linear-48b-a3b"), ModelConfig(
        name="x", expert_share=[0, 8]))
    assert (kimi.expert_share, kimi.experts_here, kimi.num_layers) == (
        (0, 8), 32, 27)
    with pytest.raises(ValueError, match="vocab_rows"):
        _apply_deployment_share(pub, ModelConfig(name="x", vocab_rows=196609))
    with pytest.raises(ValueError, match="stage_layers"):
        _apply_deployment_share(pub, ModelConfig(name="x", stage_layers=49))
