"""Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B): KDA layers with a per-slot
recurrent state beside NoPE-MLA layers with paged latent rows, a sigmoid
router with a shared expert, and an expert layer that holds a share.

At the `tiny-kimi-linear` width on the CPU: the program (`Engine.submit`,
prefill then decode through the latent pool and the recurrent state, across
slot hand-ons and a preemption) against the benchmark's plain float32
reference (`benchmark/reference/kda_mla_moe.py`, which shares no code with
`localai_tpu/models/`); the kernels against their XLA oracles; the share
test; and what such a model is refused.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import _engine, served_engine
from benchmark.harness import check as C
from benchmark.reference import kda_mla_moe as REF
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch
from localai_tpu.ops import attention as A
from localai_tpu.ops import kda as KDA

SHARE = (1, 4)
# float32 activations, so that the program's honest distance from the float32
# reference is rounding alone and a wrong block stands out of it (in bfloat16
# this flat 7-layer model's honest 0.0085 hides a wrong share's 0.0096).
CFG = dataclasses.replace(get_arch("tiny-kimi-linear"), expert_share=SHARE,
                          dtype="float32")
# Log-probability units, system against the float32 reference: 1e-6 at worst
# over every right case below (int8 weights included: both sides read them);
# the wrong variants land at 3.7e-4 (MLA rotated), 1.2e-3 (another share) and
# 0.68 with the reference's best id missing at 7 of 9 positions (a state row
# the admission did not write).
TOLERANCE = 1e-4


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with a correction bias that moves picks and a head norm
    that is not all ones."""
    params = L.init_params(cfg, jax.random.key(7))
    k1, k2 = jax.random.split(jax.random.key(8))
    lay = dict(params["layers"])
    lay["router_bias"] = 0.1 * jax.random.normal(
        k1, lay["router_bias"].shape, jnp.float32)
    kda = dict(params["kda_layers"])
    kda["o_norm"] = (1.0 + 0.3 * jax.random.normal(
        k2, kda["o_norm"].shape, jnp.float32)).astype(kda["o_norm"].dtype)
    params = {**params, "layers": lay, "kda_layers": kda}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


def _worst(eng, params, cfg, prompts, new):
    recs = C.run_system(eng, prompts, new)
    return [C.compare(r, C.reference_logprobs(
        REF.forward, params, cfg, p, r["ids"], pad_to=16))
        for p, r in zip(prompts, recs)]


# ---- the engine against the reference ---------------------------------------- #


# the tests that build their own engine (preemption, the planted faults, the
# cut group) run plain weights
served = served_engine(_seeded, CFG)


def test_engine_agrees_with_the_plain_reference(served):
    eng, params = served
    prompts = C.sample_prompts(11, CFG.vocab_size, [40, 90])
    errs = _worst(eng, params, CFG, prompts, 9)
    assert C.verdict(errs, TOLERANCE), errs
    m = eng.metrics()
    kl = len(CFG.recurrent_layers)
    assert m["recurrent_state_bytes"] == 2 * kl * (
        4 * 16 * 16 * 4 + 3 * 3 * 64 * 4)
    assert "state_snapshots" not in m and m["prefix_reuse_off"] == 1
    ev = [e for e in eng.journal.snapshot()]
    rows = [e for e in ev if e["event"] == "state_rows"]
    assert rows and all(e["a"] % (2 * kl) == 0 and e["b"] <= e["a"]
                        for e in rows)
    here = [e for e in ev if e["event"] == "moe_here"]
    picks, landed = sum(e["a"] for e in here), sum(e["b"] for e in here)
    assert picks == m["moe_picks"] and landed == m["moe_picks_here"]
    assert 0.1 < landed / picks < 0.45  # a quarter of the experts held
    # An admission program's account is its grouped expert kernel's, which
    # does not run off the TPU: every program came back with a walk of no
    # rows and none was entered. One that did run (as the drain hands it on):
    assert not [e for e in ev if e["event"] == "moe_admit_rows"]
    assert m["moe_admit_rows"] == 0 == m["moe_admit_rows_held"]
    eng._count_admit_routing(np.asarray([6 * 128 * 8, 1600], np.int32))
    (adm,) = [e for e in eng.journal.snapshot()
              if e["event"] == "moe_admit_rows"]
    assert (adm["a"], adm["b"]) == (6144.0, 1600.0)
    m2 = eng.metrics()
    assert (m2["moe_admit_rows"], m2["moe_admit_rows_held"]) == (6144, 1600)
    assert sum(e["event"] == "prefix_reuse_off" for e in ev) == 1
    # moe_experts counts the held experts: 6 MoE layers x 4 of 16
    assert all(e["a"] % (6 * 4) == 0 for e in ev if e["event"] == "moe_experts")


def test_successor_never_sees_the_old_tenants_state(served):
    """Six requests through two slots, every one ending on its budget, so
    every hand-on goes through `_park`: the old tenant's blocks in flight
    still update the row, the successor's admission overwrites it. Each
    stream's log-probabilities are the reference's for ITS ids alone."""
    eng, params = served
    prompts = C.sample_prompts(13, CFG.vocab_size, [30, 45, 20, 70, 33, 52])
    before = eng.metrics()["slots_released_early"]
    handles = [eng.submit(GenRequest(
        prompt_ids=list(p), max_new_tokens=12, temperature=0.0,
        ignore_eos=True, logprobs=20)) for p in prompts]
    errs = []
    for p, h in zip(prompts, handles):
        rec = {"ids": [], "lp": [], "top": []}
        for ev in h:
            assert ev.kind != "error", ev.error
            if ev.kind == "token":
                rec["ids"].append(int(ev.token_id))
                rec["lp"].append(float(ev.logprob))
                rec["top"].append({int(i): float(v)
                                   for i, v in (ev.top_logprobs or [])})
        assert len(rec["ids"]) == 12
        errs.append(C.compare(rec, C.reference_logprobs(
            REF.forward, params, CFG, p, rec["ids"], pad_to=16)))
    assert C.verdict(errs, TOLERANCE), errs
    assert eng.metrics()["slots_released_early"] - before >= 4


NEW = 100


def test_preempted_request_recomputes_its_state():
    """A pool too small for two long decodes: the younger is preempted, its
    state row dropped, and its re-admission recomputes the row from prompt +
    generated. Both streams still agree with the reference."""
    params = _seeded()
    import time

    # Worst case is 9 pages each (144 rows); the pool holds 10, admission
    # takes 3 + 1 each, so both run and growth collides mid-decode.
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


def _admission_leaves_the_state_row(monkeypatch):
    """Planted: the admission program computes the prompt's state and does
    not write it, so the request decodes on what its slot's row held before
    (zeros here; an old tenant's state after a hand-on)."""
    real = L.prefill

    def prefill(*a, recurrent=None, **kw):
        out = real(*a, recurrent=recurrent, **kw)
        return out if recurrent is None else out[:-1] + (tuple(recurrent[:2]),)

    monkeypatch.setattr(L, "prefill", prefill)
    return CFG, CFG


WRONG = {
    # the reference told another share than the program holds
    "another_share": lambda mp: (
        CFG, dataclasses.replace(CFG, expert_share=(2, 4))),
    # the program rotating MLA's rope dims where the model does not
    "rotated_mla": lambda mp: (dataclasses.replace(CFG, mla_rope=True), CFG),
    # a stale recurrent-state row: what parking or a preemption could leave
    "stale_state_row": _admission_leaves_the_state_row,
}


@pytest.mark.parametrize("variant", sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant, monkeypatch, served):
    cfg, ref_cfg = WRONG[variant](monkeypatch)
    prompt = C.sample_prompts(11, cfg.vocab_size, [90])
    if variant == "another_share":  # the right program, the reference wrong
        eng, params = served
        rec = C.run_system(eng, prompt, 9)[0]
        # experts 8-11 in place of 4-7: other weights
        ref_params = _seeded(cfg=ref_cfg, quantize="int8")
    else:
        ref_params = params = _seeded()
        eng = _engine(cfg, params)
        try:
            rec = C.run_system(eng, prompt, 9)[0]
        finally:
            eng.stop()
    err = C.compare(rec, C.reference_logprobs(
        REF.forward, ref_params, ref_cfg, prompt[0], rec["ids"], pad_to=16))
    assert not C.verdict([err], TOLERANCE), err


# ---- the share test ------------------------------------------------------------ #


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts, with the shared expert counted once,
    add up to the uncut reference's MoE layer; program and reference."""
    full = dataclasses.replace(CFG, expert_share=None, dtype="float32")
    params = _seeded(cfg=full)
    lp = {k: v[2] for k, v in params["layers"].items()}  # one MoE layer
    x = jax.random.normal(jax.random.key(3), (24, full.hidden_size), jnp.float32)
    lw = {k: lp[k] for k in REF._MOE}
    kw = dict(top_k=full.num_experts_per_token, eps=full.rms_eps,
              scaling=full.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        whole = REF.experts(x, lw, lo=0, **kw) - x
        m = REF._rms_norm(x, lp["mlp_norm"], full.rms_eps)
        shared = REF._swiglu(m, lp["shared_gate"], lp["shared_up"],
                             lp["shared_down"], jnp.float32, "")
        prog, ref = -3 * shared, -3 * shared  # counted once of four times
        for i in range(4):
            cfg_i = dataclasses.replace(full, expert_share=(i, 4))
            held = slice(cfg_i.expert_lo, cfg_i.expert_lo + cfg_i.experts_here)
            lp_i = {**lp, **{k: lp[k][held] for k in ("w_gate", "w_up", "w_down")}}
            prog = prog + L._mlp(cfg_i, lp_i, m)
            ref = ref + REF.experts(
                x, {k: lp_i[k] for k in REF._MOE}, lo=cfg_i.expert_lo, **kw) - x
    np.testing.assert_allclose(ref, whole, atol=2e-5)
    np.testing.assert_allclose(prog, whole, atol=2e-5)


@pytest.mark.parametrize("kernel", ["auto", "pallas"])
def test_wide_rows_sort_the_held_picks_and_match_all_experts(monkeypatch, kernel):
    """Above the rule's row bound the quantized share runs the sort with the
    picks held elsewhere in no group: `ragged_dot` lets them ride in the
    last group and zeroes them ("auto" off the TPU); the grouped kernel
    ("pallas": interpret mode here) never visits their tiles, and the rows
    it did not write are zeroed before the combine. Same sum as the
    all-experts form either way."""
    from localai_tpu.ops import quant_matmul as QM

    cfg = dataclasses.replace(CFG, quant_kernel=kernel)
    params = _seeded(quantize="int8")
    lp = {k: jax.tree.map(lambda a: a[1], v)
          for k, v in params["layers"].items()}
    x = jax.random.normal(jax.random.key(4), (3, 16, cfg.hidden_size),
                          jnp.bfloat16)
    dense = L._mlp(cfg, lp, x)
    monkeypatch.setattr(L, "QUANT_PALLAS_MAX_ROWS", 8)
    monkeypatch.setattr(L, "MOE_ALL_EXPERTS_MAX_ROWS", 8)
    monkeypatch.setattr(QM, "GROUP_ROWS", 16)  # 384 sorted rows: 24 tiles
    visited = []
    real = QM.group_visits

    def spy(sizes, m):
        out = real(sizes, m)
        visited.append((int(out.nvis[0]), int(sizes.sum()), m))
        return out

    monkeypatch.setattr(L, "group_visits", spy)
    admit = []
    ragged = L._mlp(cfg, lp, x, admit=admit)
    if kernel == "pallas":
        # most sorted rows are picks held elsewhere, and their tiles are not
        # visited: at most a visit a held tile and one more a held expert;
        # one walk serves the three projections
        assert len(visited) == 1
        nvis, held, m = visited[0]
        assert admit[0].tolist() == [m, held]
        assert m == 3 * 16 * cfg.num_experts_per_token and 0 < held < m // 2
        assert nvis <= -(-held // 16) + cfg.experts_here
    else:
        assert visited == [] and admit == []
    assert np.isfinite(np.asarray(ragged, np.float32)).all()
    np.testing.assert_allclose(np.asarray(ragged, np.float32),
                               np.asarray(dense, np.float32), atol=2e-2)


# ---- the kernels against their XLA oracles ------------------------------------- #


def _kda_operands(B, T, H, d, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (B, T, H, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (B, T, H, d)))
    v = jax.random.normal(ks[2], (B, T, H, d))
    # decays from 0.998 a step down to e^-12: the factored products of the
    # chunkwise form would overflow float32 without their reference points
    g = -jnp.exp(jax.random.uniform(ks[3], (B, T, H, d), minval=-6, maxval=2.5))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def test_chunkwise_prefill_matches_the_recurrence():
    B, T, H, d = 3, 128, 2, 16
    q, k, v, g, beta = _kda_operands(B, T, H, d)
    lens = jnp.array([128, 70, 5])
    valid = jnp.arange(T)[None] < lens[:, None]
    o_ref, S_ref = KDA.kda_recurrent(
        q, k, v, jnp.where(valid[..., None, None], g, 0),
        jnp.where(valid[..., None], beta, 0))
    o, S = jax.jit(KDA.kda_chunk_prefill)(q, k, v, g, beta, valid)
    np.testing.assert_allclose(np.where(valid[..., None, None], o - o_ref, 0),
                               0, atol=1e-5)
    np.testing.assert_allclose(S, S_ref, atol=2e-5)


def _unit_lower_system(C, beta_max, keys, batch=32, d=16, dr=24, seed=0):
    """The chunk's system as the prefill builds it, with no decay between
    the tokens (the decay only shrinks N): N[t, s] = beta_t k_t . k_s below
    the diagonal. `keys` "near-duplicate" is k_t = unit(k_0 + 0.05 eps_t),
    the worst conditioning the model can produce."""
    rng = np.random.default_rng(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    k = rng.standard_normal((batch, C, d))
    if keys == "near-duplicate":
        k = rng.standard_normal((batch, 1, d)) + 0.05 * k
    k = unit(k)
    beta = rng.uniform(0.0, beta_max, (batch, C, 1))
    N = beta * np.tril(np.einsum("btc,bsc->bts", k, k), -1)
    rhs = rng.standard_normal((batch, C, dr))
    return N.astype(np.float32), rhs.astype(np.float32)


@pytest.mark.parametrize("keys", ["random-unit", "near-duplicate"])
@pytest.mark.parametrize("beta_max", [1.0, 2.0])
@pytest.mark.parametrize("C", [16, 32, 48, 64])
def test_blocked_solve_is_as_exact_as_the_row_by_row_one(C, beta_max, keys):
    """`_solve_unit_lower` against a float64 solve, held to the error that
    `jax.scipy.linalg.solve_triangular` (the form it replaced) makes on the
    same operands: the bar is that solve's, not a constant. The error is
    norm-wise over the batch, |got - want|_F / |want|_F (the blocked form
    reads 0.46-0.98 of the row-by-row one's over these cases and twelve
    seeds each; the largest single element's ratio is an extreme-value
    statistic that swings 0.3-2.2 on the same operands and holds nothing)."""
    import scipy.linalg

    N, rhs = _unit_lower_system(C, beta_max, keys)
    M = np.eye(C) + N.astype(np.float64)
    want = np.stack([scipy.linalg.solve_triangular(
        m, r, lower=True, unit_diagonal=True)
        for m, r in zip(M, rhs.astype(np.float64))])

    def rel_err(got):
        return (np.linalg.norm(np.asarray(got, np.float64) - want)
                / np.linalg.norm(want))

    rows = rel_err(jax.scipy.linalg.solve_triangular(
        jnp.asarray(M, jnp.float32), rhs, lower=True, unit_diagonal=True))
    blocked = rel_err(jax.jit(KDA._solve_unit_lower)(N, rhs))
    assert blocked <= 2 * rows, (blocked, rows)
    assert blocked < 1e-6  # float32 on a system conditioned like 1e2


def test_the_prefill_program_holds_no_triangular_solve():
    B, T, H, d = 2, 128, 2, 16
    args = _kda_operands(B, T, H, d) + (jnp.ones((B, T), bool),)
    hlo = jax.jit(KDA.kda_chunk_prefill).lower(*args).as_text(dialect="hlo")
    assert "dot(" in hlo and "triangular" not in hlo.lower()
    assert "custom-call" not in hlo and "while" in hlo  # the chunk scan alone


def test_an_admission_group_is_cut_to_the_state_modules_rows(monkeypatch):
    """Eight prompts of one bucket arrive together; no admission program
    takes more than admit_rows // bucket of them."""
    from localai_tpu.engine import state

    monkeypatch.setattr(state, "admit_rows", lambda cfg: 64)
    eng = _engine(CFG, _seeded(), max_slots=8, kv_pages=64)
    try:
        prompts = C.sample_prompts(15, CFG.vocab_size, [20] * 8)
        bucket = eng._bucket_for(20)
        handles = [eng.submit(GenRequest(
            prompt_ids=list(p), max_new_tokens=1, temperature=0.0,
            ignore_eos=True)) for p in prompts]
        assert all(h.result()[1].kind == "done" for h in handles)
        sizes = {key[0] for key in eng._admit_cache}
    finally:
        eng.stop()
    assert sizes and max(sizes) == max(1, 64 // bucket) < 8, (sizes, bucket)


def test_kda_decode_kernel_updates_its_layer_of_the_stack_in_place():
    B, H, d, Lk = 4, 4, 16, 3
    q, k, v, g, beta = (a[:, 0] for a in _kda_operands(B, 1, H, d, seed=1))
    state = jax.random.normal(jax.random.key(9), (Lk, B, H, d, d))
    want_o, want_S = KDA.kda_step(state[1], q, k, v, g, beta)
    for impl in ("pallas", "xla"):
        o, st = jax.jit(lambda s, i, impl=impl: KDA.kda_decode(
            s, i, q, k, v, g, beta, impl=impl))(state, jnp.int32(1))
        np.testing.assert_allclose(o, want_o, atol=1e-6)
        np.testing.assert_allclose(st[1], want_S, atol=1e-6)
        np.testing.assert_array_equal(st[0], state[0])
        np.testing.assert_array_equal(st[2], state[2])


# The latent walk's cases, at the cell's page and row (128 rows of 640: six
# pages a visit by `_visit_pages`' byte bound) over a table of thirteen
# columns, and the first test's tiny shape, whose whole table is one visit.
# Limits a slot; "nan": every page no live slot lists holds NaN, in every
# layer.
_LATENT_PAGE, _LATENT_W, _LATENT_MP, _V = 128, 640, 13, 6 * 128
_LATENT_CASES = {
    # ragged limits, an idle slot, a page's last row
    "ragged_tiny": dict(page=16, W=64, MP=4, limits=[0, 37, 64]),
    # more pages than one visit holds: two and three visits, a second visit
    # of one live page's first row
    "longer_than_a_visit": dict(limits=[_V + 128 + 3, 13 * 128, _V + 1]),
    # exactly a visit, and exactly two: both end on their visit's last row
    "exactly_a_visit": dict(limits=[_V, 2 * _V, _V - 1]),
    # a last visit of one, two and three live pages: the rest of its buffer
    # holds whatever it held (here NaN, from the pages of an earlier slot or
    # never-written scratch) and no dot may read it
    "last_visit_unfetched_nan": dict(
        limits=[128 + 5, _V + 1, _V + 3 * 128 - 3], nan=True),
    "one_token_nan": dict(limits=[1, 0, 11 * 128], nan=True),
    # the stream across slots: a live slot after an idle one, after a one-page one, after
    # two idle ones, and an idle last one
    "stream_after_idle_and_one_page": dict(
        limits=[0, 300, 128, 700, 0, 0, _V + 1, 0]),
    "stream_from_a_second_visit": dict(limits=[_V + 128, 64, 13 * 128, 200]),
    # the value dot over the value lanes alone (512 of 640: both latent
    # cells' kv_lora_rank; ISSUE 50), at GLM-4.7-Flash's 20 query rows a
    # slot and Kimi-Linear's 32: every live size of a last visit, as a
    # slot's only visit and behind a full one
    "value_lanes_h20_last_visits": dict(
        H=20, values=512, nan=True,
        limits=[128 - 3, 2 * 128 - 3, 3 * 128 - 3, _V + 4 * 128 - 3,
                _V + 5 * 128 - 3, _V + _V - 3, _V + 77]),
    "value_lanes_h32_last_visits": dict(
        H=32, values=512, nan=True,
        limits=[128 - 3, 2 * 128 - 3, 3 * 128 - 3, _V + 4 * 128 - 3,
                _V + 5 * 128 - 3, _V + _V - 3, _V + 77]),
    # an idle slot, a one-token slot, a page's last row and the next one's
    # first, in a first visit and in a second
    "value_lanes_h20_page_ends": dict(
        H=20, values=512,
        limits=[0, 1, 3 * 128, 3 * 128 + 1, _V + 2 * 128, _V + 2 * 128 + 1, 0]),
    # the slots' visits as one stream (ISSUE 50): the ring runs ahead across
    # runs of idle slots, one-visit slots shorter than the ring is deep, a
    # three-visit slot between them, an idle first and last slot
    "stream_across_idle_and_short_slots": dict(
        H=20, values=512, nan=True,
        limits=[0, 0, 260, 0, 300, 256, 0, 0, 2 * _V + 5, 0, 400, 0]),
    "stream_of_one_visit_slots": dict(
        H=32, values=512, nan=True,
        limits=[300, 257, 700, 256, 384, _V, 500, 333]),
    # a value width whose round-up to lane tiles is the row: the whole row's
    # kernel, and acc the row wide
    "value_width_rounds_up_to_the_row": dict(
        H=20, values=600, limits=[0, 128 + 5, _V + 1, 3 * 128]),
}
_LATENT_PROGRAMS = {}


@pytest.mark.parametrize("layer", [1, 0])
@pytest.mark.parametrize("case", list(_LATENT_CASES))
def test_latent_kernel_matches_the_xla_walk(case, layer):
    """A caller that says its [P, page, 1, W] pool is a latent one gets the
    latent walk (the as-stored visit over the one pool: several pages a
    visit, the ring, the one stream of visits), reading its layer out of the stacked
    pool; against the XLA walk at the parent's tolerance. What that kernel
    lacks is refused, not dropped."""
    from localai_tpu.ops.paged_flash import _visit_pages

    from localai_tpu.ops.paged_flash import value_lanes

    spec = _LATENT_CASES[case]
    page, W, MP = (spec.get("page", _LATENT_PAGE), spec.get("W", _LATENT_W),
                   spec.get("MP", _LATENT_MP))
    limits = jnp.array(spec["limits"], jnp.int32)
    B, H, Lm = len(spec["limits"]), spec.get("H", 4), 2
    values = spec.get("values", 0)
    Dv = value_lanes(values, W)  # the lanes the kernel's acc holds
    assert _visit_pages(page, 1, MP, 2 * W, flat=True) == min(6, MP)
    pool = jax.random.normal(jax.random.key(5), (Lm, B * MP + 1, page, 1, W),
                             jnp.bfloat16)
    table = (jnp.arange(B * MP, dtype=jnp.int32) + 1).reshape(B, MP)
    q = jax.random.normal(jax.random.key(6), (B, H, W), jnp.bfloat16)

    def partials(impl):
        # cases of one shape share a trace of the interpreted kernel
        key = (impl, B, H, page, W, MP, values)
        if key not in _LATENT_PROGRAMS:
            _LATENT_PROGRAMS[key] = jax.jit(
                lambda q, pool, table, limits, i: A.paged_partials(
                    q, Q.StackedLayer(pool, i), Q.StackedLayer(pool, i),
                    table, limits, impl=impl, latent=True, values=values))
        return _LATENT_PROGRAMS[key]

    if case == "ragged_tiny":
        with pytest.raises(ValueError, match="softcap or window"):
            A.paged_partials(q, pool[0], pool[0], table, limits,
                             impl="pallas", latent=True, softcap=30.0)
    acc0, m0, l0 = partials("xla")(q, pool, table, limits, jnp.int32(layer))
    if spec.get("nan"):
        listed = np.zeros(pool.shape[1], bool)
        for b, n in enumerate(spec["limits"]):
            listed[np.asarray(table)[b, : -(-n // page)]] = True
        pool = jnp.where(listed[None, :, None, None, None], pool, jnp.nan)
    acc, m, l = partials("pallas")(q, pool, table, limits, jnp.int32(layer))
    assert np.isfinite(np.asarray(acc)).all() and np.isfinite(np.asarray(l)).all()
    # the kernel's acc is the value lanes (in whole lane tiles) and no more,
    # the XLA walk's the whole row: the lanes anyone reads are compared
    assert acc.shape == (B, 1, H, Dv) and acc0.shape == (B, 1, H, W)
    assert (Dv < W) == (values == 512)
    acc0 = acc0[..., :Dv]
    live = np.asarray(l0) > 0
    assert live.any(axis=(1, 2, 3)).tolist() == [n > 0 for n in spec["limits"]]
    np.testing.assert_array_equal(np.asarray(l)[~live], 0)
    np.testing.assert_allclose(np.where(live, acc / np.where(live, l, 1), 0),
                               np.where(live, acc0 / np.where(live, l0, 1), 0),
                               atol=2e-3)
    # (m, l) as the merge reads the pair, m + log l: l alone is a sum of
    # exp(s - m) in the frame of its own m, and the kernel's m sits some
    # |s| · 2^-9 off the walk's (it rounds the scaled q to bfloat16, as
    # Mosaic's float32 dot did on the chip), which a one-token slot shows in
    # m and a long one in l
    lse, lse0 = (np.where(live, m_ + np.log(np.where(live, l_, 1)), 0)
                 for m_, l_ in ((m, l), (m0, l0)))
    # (a one-token slot's lse IS its score, off by q's rounding alone, some
    # 2e-3 a standard deviation at a 640-wide row: twenty rows of it reach
    # 4e-3, the parent's kernel and this one alike)
    np.testing.assert_allclose(lse, lse0, rtol=2e-3,
                               atol=5e-3 if 1 in spec["limits"] and H > 4
                               else 2e-3)


def test_decode_step_slices_no_layer_out_of_the_state():
    """With the kernel the step's program holds no op whose result is one
    layer's state; the XLA form is the one that slices (and says so)."""
    cfg = CFG
    params = _seeded()
    B, n = 2, 4
    pool = L.paged_cache_zeros(cfg, 5, 16)
    state = jnp.zeros((len(cfg.recurrent_layers), B, 4, 16, 16), jnp.float32)
    conv = jnp.zeros((len(cfg.recurrent_layers), B, 3, 3 * 64), jnp.float32)
    lk = jnp.zeros((cfg.cache_layers, B, n, 1, cfg.cache_k_dim), jnp.float32)

    def text(impl):
        return str(jax.make_jaxpr(lambda st, cv: L.decode_step_windowed(
            cfg, params, jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32),
            pool, lk, lk[..., :0], jnp.int32(0),
            ptable=jnp.zeros((B, 4), jnp.int32), paged_impl=impl,
            recurrent=(st, cv), kda_impl=impl))(state, conv))

    layer_row = "f32[1,2,4,16,16]"  # dynamic_slice of one layer of the state
    assert layer_row not in text("pallas")
    assert layer_row in text("xla")


# ---- what such a model is refused ---------------------------------------------- #


REFUSED = {
    "dense_cache": ({"kv_pages": 0}, {}, "dense KV cache"),
    "chunked_admission": ({"prefill_chunk": 64}, {}, "chunked admission"),
    "speculation": ({"spec_mode": "prompt_lookup"}, {}, "speculative"),
    "scaled_fp8_pool": ({"kv_cache_dtype": "fp8", "kv_scale": 2.0}, {},
                        "kv_scale"),
    # (tp > 1 degrades to 1 with a warning, as for any model it cannot shard)
    "expert_parallel": ({}, {"ep": 2}, "tp/sp/ep/dp"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_at_load_by_name(what):
    from localai_tpu.parallel.mesh import MeshPlan

    ecfg, plan, says = REFUSED[what]
    kw = {"max_slots": 2, "max_seq": 128, "kv_pages": 8, "kv_page_size": 16,
          **ecfg}
    with pytest.raises(ValueError) as e:
        Engine(CFG, _seeded(), ByteTokenizer(CFG.vocab_size),
               engine_cfg=EngineConfig(**kw),
               mesh_plan=MeshPlan(**plan) if plan else None)
    assert "recurrent state" in str(e.value) and says in str(e.value), e.value


def test_fork_and_runtime_lora_are_refused_and_auto_tp_is_one(served):
    from localai_tpu.engine.engine import AdapterError
    from localai_tpu.parallel.sharding import max_valid_tp

    eng, _ = served
    h = eng.submit(GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=2,
                              temperature=0.0, ignore_eos=True))
    with pytest.raises(ValueError, match="recurrent state"):
        eng.fork(h, 2)
    h.result()
    with pytest.raises(AdapterError, match="hybrid KDA/MLA"):
        eng.register_adapter("a", "/nowhere")
    assert max_valid_tp(CFG, 8) == 1


# ---- the published preset -------------------------------------------------------- #


def test_published_preset_and_its_held_tree():
    """The preset's shapes against the benchmark's byte counts: the tree a
    chip holds under the deployment's share is `costs_hybrid.held_params`."""
    from benchmark.harness import costs_hybrid
    from benchmark.harness import spec as S

    arch = S.config("kimi-linear-48b-a3b-int8-ep8")
    cfg = dataclasses.replace(get_arch("kimi-linear-48b-a3b"),
                              expert_share=tuple(arch["yaml"]["expert_share"]))
    assert cfg.layer_kinds.count("kda") == 20 and cfg.cache_layer_ids == (
        3, 7, 11, 15, 19, 23, 26)
    assert cfg.experts_here == arch["num_experts"] == 32
    tree = jax.eval_shape(lambda k: L.init_params(cfg, k), jax.random.key(0))
    held = costs_hybrid.held_params(arch)
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    norms = 27 * 2 * 2304
    assert size(tree["kda_layers"]) == held["kda_attention"]
    assert size(tree["mla_layers"]) == held["mla_attention"]
    assert size(tree["lm_head"]) == held["head"] == size(tree["embed"])
    moe = size(tree["layers"]) + size(tree["dense_layers"]) - norms
    assert moe - 26 * 256 == held["shared_router_dense"] + held["experts_held"]
    q = jax.eval_shape(lambda k: Q.init_params_quantized(cfg, k),
                       jax.random.key(0))
    assert q["kda_layers"]["wq"]["q"].dtype == jnp.int8
    assert q["kda_layers"]["A_log"].dtype == jnp.float32
    assert q["mla_layers"]["w_kb"].dtype == jnp.bfloat16
    assert q["layers"]["w_gate"]["q"].shape == (26, 32, 2304, 1024)
