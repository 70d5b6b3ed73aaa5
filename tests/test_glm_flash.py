"""GLM-4.7-Flash (zai-org/GLM-4.7-Flash, `glm4_moe_lite`): rotated latent
attention (MLA) with a q-lora bottleneck in EVERY layer through the plain
layer scan, value heads wider than the nope heads, a head count that is no
multiple of 8, one dense layer and then sigmoid-routed experts (`noaux_tc`)
beside a shared one under an expert share, padded latent rows in the paged
pool.

At the `tiny-glm-4.7-flash` width on the CPU: the program (`Engine.submit`,
prefill then decode through the paged latent pool across page and block
boundaries, slot hand-ons and a preemption) against the benchmark's plain
float32 reference (`benchmark/reference/mla_moe.py`, which shares no code
with `localai_tpu/models/`); five wrong blocks against the same comparison;
the shares against the whole layer; the latent pool's staged block write
(`ops/pool_write.latent_pool_write`, interpreted) against the scatter it
replaces; the checkpoint's names; the published tree against the benchmark's
counts.
"""

import dataclasses
import functools
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import _collect, _engine, _err_against, served_engine
from benchmark.harness import check as C
from benchmark.harness import costs_mla_moe as COSTS
from benchmark.harness import spec as S
from benchmark.reference import mla_moe as REF
from localai_tpu.engine import GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import PRESETS, get_arch
from localai_tpu.ops import attention as A
from localai_tpu.ops.pool_write import (
    GROUP_ROWS, in_place_rows, latent_pool_write, staged_rows)
from localai_tpu.ops.stacked import SiteCounts

# float32 activations: the program's honest distance from the float32
# reference is then rounding alone and a wrong block stands out of it. As
# served: a share of the experts (2 of 8 held).
FULL = dataclasses.replace(get_arch("tiny-glm-4.7-flash"), dtype="float32")
CFG = dataclasses.replace(FULL, expert_share=(1, 4))
PUB = get_arch("glm-4.7-flash")
TOLERANCE = 1e-4


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with a correction bias and a bottleneck norm that are
    not their init's zeros and ones, queries and keys large enough for the
    rotation and the scores' scale to move the softmax, and routed experts
    large enough (not the preset's tenth) under a router that tells them
    apart, so that the weights' form weighs."""
    params = L.init_params(cfg, jax.random.key(7))
    k1, k2 = jax.random.split(jax.random.key(8))
    lay = dict(params["layers"])
    lay["router_bias"] = 0.3 * jax.random.normal(k1, lay["router_bias"].shape)
    lay["router"] = 20.0 * lay["router"]
    lay["w_down"] = 100.0 * lay["w_down"]
    lay["w_gate"], lay["w_up"] = 5.0 * lay["w_gate"], 5.0 * lay["w_up"]
    lay["shared_down"] = 5.0 * lay["shared_down"]
    params = {**params, "layers": lay}
    for stack in ("layers", "dense_layers"):
        d = dict(params[stack])
        for n in ("wq_a", "wq_b", "wkv_a"):
            d[n] = 6.0 * d[n]
        d["q_norm_a"] = 1.0 + 0.3 * jax.random.normal(k2, d["q_norm_a"].shape)
        params[stack] = d
    return Q.quantize_params(cfg, params, quantize) if quantize else params


_err = functools.partial(_err_against, REF.forward)


# ---- the engine against the reference ---------------------------------------- #


served = served_engine(_seeded, CFG)


def test_preset_is_the_combination_no_other_has():
    assert CFG.is_mla and not CFG.is_hybrid and CFG.mla_rope
    assert CFG.q_lora_rank and CFG.num_heads % 8
    assert CFG.v_head_dim > CFG.qk_nope_head_dim
    # v is as wide as q and k: `_mla_full_qkv` pads nothing
    assert CFG.v_head_dim == CFG.qk_head_dim
    assert PUB.v_head_dim == PUB.qk_head_dim == 256
    assert (CFG.cache_k_dim, CFG.cache_v_dim, CFG.cache_kv_heads) == (128, 0, 1)
    assert PUB.cache_k_dim == 640 and PUB.cache_layers == 47
    assert CFG.first_k_dense == 1 and CFG.experts_here == 2


def test_engine_agrees_with_the_plain_reference(served):
    """Prefill, then 19 decoded positions: two 8-step blocks and single
    steps, across the 16-row page boundary at 48 and at 96 (prompts of 40
    and 90), through the paged latent pool."""
    eng, params = served
    for name in ("wq_a", "wq_b", "wkv_a", "wo"):
        assert params["layers"][name]["q"].dtype == jnp.int8
    assert not isinstance(params["layers"]["w_kb"], dict)
    prompts = C.sample_prompts(11, CFG.vocab_size, [40, 90])
    recs = C.run_system(eng, prompts, 19)
    errs = [_err(params, CFG, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs
    assert eng.cache.k.shape == (4, 41, 16, 1, 128)
    assert eng.cache.v.shape == (4, 41, 16, 1, 0)
    m = eng.metrics()
    assert m["admit_rows_max"] == rstate.admit_rows(CFG)
    ev = eng.journal.snapshot()
    # the plain scan's MoE layers journal their routing under a share: the 2
    # HELD experts of the 3 MoE layers, and the picks that landed here
    hit = [e for e in ev if e["event"] == "moe_experts"]
    assert hit and all(e["a"] % (3 * 2) == 0 and 0 < e["b"] <= e["a"]
                       for e in hit)
    here = [e for e in ev if e["event"] == "moe_here"]
    assert here and all(0 < e["b"] < e["a"] for e in here)
    assert any(e["event"] == "moe_load" for e in ev)
    # what the latent walk read: rows held at dispatch x steps, of the pool's
    rows = [e for e in ev if e["event"] == "latent_rows"]
    blocks = [e for e in ev if e["event"] == "decode_block"]
    assert len(rows) == len(blocks) > 0
    for r, b in zip(rows, blocks):
        assert r["b"] == b["a"] * 40 * 16 and 0 < r["a"] < r["b"]
    first = rows[0]["a"] / blocks[0]["a"]  # one slot: its prompt's rows
    assert first in (40.0, 90.0)


def test_staged_write_serves_a_bfloat16_pool():
    """The pool as the cell holds it (bfloat16 rows) under the Pallas reader:
    the block write is the staged kernel (interpreted here), counted as in
    place; the zero-width V pool keeps its empty scatter. Against the
    reference within bfloat16's rounding."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = _seeded(cfg, "int8")
    eng = _engine(cfg, params, paged_kernel="pallas")
    try:
        prompts = C.sample_prompts(12, cfg.vocab_size, [40, 90])
        recs = C.run_system(eng, prompts, 19)
        m = eng.metrics()
    finally:
        eng.stop()
    assert eng.cache.k.dtype == jnp.bfloat16
    assert m["pool_write_inplace_sites"] == m["pool_write_scatter_sites"] > 0
    errs = [_err(params, cfg, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, 0.03), errs


def test_successor_never_sees_the_old_tenants_pages(served):
    """Six requests through two slots, every one ending on its budget, so
    every hand-on goes through `_park`: the old tenant's blocks in flight
    still write its pages, the successor's admission takes pages of its
    own. Each stream's log-probabilities are the reference's for ITS ids."""
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


@pytest.mark.parametrize("policy", ["recompute", "swap"])
def test_preempted_request_keeps_its_stream(policy):
    """A pool too small for two long decodes: the younger is preempted and
    its pages dropped. Under `recompute` its re-admission computes the latent
    rows again from prompt + generated; under `swap` (what `auto` takes for
    a young slot) the rows go to the host through `pages_gather` and come
    back through `swap_in`. Both streams still agree with the reference."""
    new = 100
    params = _seeded()
    eng = _engine(CFG, params, kv_pages=10, kv_preempt=policy,
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
    assert m["kv_preemptions"] >= 1
    assert m[f"kv_preempt_{policy}s"] >= 1
    for p, ids in zip(prompts, streams):
        lp = C.reference_logprobs(REF.forward, params, CFG, p, ids, pad_to=16)
        gap = lp.max(-1) - lp[np.arange(new), ids]
        assert gap.max() <= TOLERANCE, gap.max()


# ---- a wrong block fails the same comparison ----------------------------------- #


def _rotated_nope(cfg, lp, x, positions, inv, mesh=None):
    """`_mla_full_qkv` with the rotation applied to the first rope-width
    nope dims of q and k and the rope dims left as projected."""
    H, n, rot, r = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.kv_lora_rank)
    q = L._mla_q(cfg, lp, x, mesh)
    q = jnp.concatenate([L.apply_rope(q[..., :rot], positions, inv),
                         q[..., rot:]], axis=-1)
    ckv = L.matmul(x, lp["wkv_a"], cfg.quant_kernel)
    c = L.rms_norm(ckv[..., :r], lp["kv_norm"], cfg.rms_eps)
    rows = L._latent_pad(cfg, jnp.concatenate(
        [c[..., None, :], ckv[..., None, r:]], axis=-1))
    k_nope = jnp.einsum("btr,hnr->bthn", c, lp["w_kb"]).astype(x.dtype)
    k_nope = jnp.concatenate([L.apply_rope(k_nope[..., :rot], positions, inv),
                              k_nope[..., rot:]], axis=-1)
    k_pe = jnp.broadcast_to(ckv[..., None, r:], (*x.shape[:2], H, rot))
    k = jnp.concatenate([k_nope, k_pe.astype(x.dtype)], axis=-1)
    v = jnp.einsum("btr,hvr->bthv", c, lp["w_vb"]).astype(x.dtype)
    return q, k, v, rows


def _no_bottleneck_norm(cfg, lp, x, mesh=None):
    """`_mla_q` without the RMSNorm between W_qa and W_qb."""
    ql = L.matmul(x, lp["wq_a"], cfg.quant_kernel)
    q = L.matmul(ql, lp["wq_b"], cfg.quant_kernel, mesh, "col")
    return q.reshape(*x.shape[:-1], cfg.num_heads, cfg.qk_head_dim)


def _biased_weights(cfg, lp, x):
    """`_deepseek_route` whose weights are the BIASED scores of the picks."""
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32),
                        lp["router"].astype(jnp.float32))
    choice = jax.nn.sigmoid(logits) + lp["router_bias"]
    w, sel = jax.lax.top_k(choice, cfg.num_experts_per_token)
    w = w / (w.sum(-1, keepdims=True) + cfg.norm_topk_eps)
    return w * cfg.routed_scaling_factor, sel


def _shared_per_share(params):
    """The shared expert counted per share: each of the four shares adds a
    quarter of it, so that their sum holds it once."""
    lay = params["layers"]
    return {**params, "layers": {**lay, "shared_down": 0.25 * lay["shared_down"]}}


WRONG = {
    "rotation_off": dict(cfg=dataclasses.replace(CFG, mla_rope=False)),
    "rotation_on_the_nope_dims": dict(patch=("_mla_full_qkv", _rotated_nope)),
    "bottleneck_norm_left_out": dict(patch=("_mla_q", _no_bottleneck_norm)),
    "weights_from_the_biased_scores": dict(
        patch=("_deepseek_route", _biased_weights)),
    "shared_expert_counted_per_share": dict(change=_shared_per_share),
}


@pytest.mark.parametrize("variant", ["right"] + sorted(WRONG))
def test_a_wrong_block_fails_the_same_comparison(variant, monkeypatch):
    """The admission program's logits against the reference's at the last
    prompt token, the right program and each wrong one."""
    spec = WRONG.get(variant, {})
    if "patch" in spec:
        monkeypatch.setattr(L, *spec["patch"])
    cfg = spec.get("cfg", CFG)
    params = _seeded()
    ids = C.sample_prompts(11, CFG.vocab_size, [48])[0]
    logits, *_ = jax.jit(lambda p, t: L.prefill(
        cfg, p, t, jnp.array([48], jnp.int32)))(
            spec.get("change", lambda p: p)(params),
            jnp.asarray([ids], jnp.int32))
    got = np.asarray(jax.nn.log_softmax(logits[0]))
    want = REF.forward(params, CFG, ids, [47], pad_to=16)[0]
    worst = float(np.max(np.abs(got - want)))
    assert (worst <= TOLERANCE) == (variant == "right"), (variant, worst)


def test_absorbed_decode_agrees_with_the_full_rank_prefill():
    """The two forms the system runs: 48 tokens prefilled at once (plain),
    and the 48th decoded against the 47 rows before it (absorbed, through a
    dense latent cache) give the same logits."""
    params = _seeded()
    ids = jnp.asarray([C.sample_prompts(5, CFG.vocab_size, [48])[0]], jnp.int32)
    full, *_ = L.prefill(CFG, params, ids, jnp.array([48], jnp.int32))
    _, ks, vs = L.prefill(CFG, params, ids[:, :47], jnp.array([47], jnp.int32))
    cache = L.write_prefill_to_cache(
        L.KVCache.zeros(CFG, 1, 64), ks, vs, jnp.int32(0))
    step, _ = L.decode_step(CFG, params, ids[:, 47], jnp.array([47], jnp.int32),
                            cache)
    np.testing.assert_allclose(jax.nn.log_softmax(step[0]),
                               jax.nn.log_softmax(full[0]), atol=TOLERANCE)


# ---- the shares add up ------------------------------------------------------------ #


def test_the_shares_add_up_to_the_uncut_layer():
    """The four shares' routed parts, with the shared expert counted once,
    add up to the uncut reference's MoE layer; program and reference."""
    params = _seeded(cfg=FULL)
    lp = {k: v[1] for k, v in params["layers"].items()}  # one MoE layer
    x = jax.random.normal(jax.random.key(3), (24, FULL.hidden_size), jnp.float32)
    kw = dict(top_k=FULL.num_experts_per_token, eps=FULL.rms_eps,
              scaling=FULL.routed_scaling_factor)
    with jax.default_matmul_precision("highest"):
        whole = REF.experts(x, {k: lp[k] for k in REF._MOE}, lo=0, **kw) - x
        m = REF._rms_norm(x, lp["mlp_norm"], FULL.rms_eps)
        from benchmark.reference.kda_mla_moe import _swiglu
        shared = _swiglu(m, lp["shared_gate"], lp["shared_up"],
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


# ---- the latent pool's block write ------------------------------------------------ #

LW, PAGE, MP, SCRATCH = 128, 32, 3, 12  # 12 live pages, then the idle slots'


def _bits(a):
    return np.asarray(a).view(np.uint16)


def _write_case(n, starts, seed=0):
    rng = np.random.default_rng(seed)
    B = len(starts)
    pool = jnp.asarray(rng.standard_normal((3, SCRATCH + 1, PAGE, 1, LW)),
                       jnp.bfloat16)
    win = jnp.asarray(rng.standard_normal((3, B, n, 1, LW)) * 3, jnp.bfloat16)
    table = rng.permutation(SCRATCH)[:4 * MP].reshape(4, MP).astype(np.int32)
    table = np.concatenate([table] * -(-B // 4))[:B]  # slots share pages in
    # turn: the cases below never send two live rows to one address
    start = np.asarray(starts, np.int32)
    row = np.minimum(start[:, None] + np.arange(n)[None], MP * PAGE - 1)
    return pool, win, table, start, row


def _scattered(pool, win, pid, off):
    return pool.at[:, jnp.asarray(pid), jnp.asarray(off)].set(win)


@pytest.mark.parametrize("n", [16, 4, 1])
def test_staged_write_is_the_scatter_at_every_offset_of_a_group(n):
    """A slot's window starting at each of a tile group's 16 offsets (at
    n = 16 every start but 0 spills into the next group; 25-31 of a 32-row
    page straddle two pages), one slot at a time over four slots' pages:
    the staged kernel against the scatter, bit for bit."""
    starts = [PAGE - 7 + o if o >= 9 else o for o in range(GROUP_ROWS)]
    starts += [2 * PAGE - 1, MP * PAGE - n]  # a page's last row; the table's end
    pool, win, table, start, row = _write_case(n, starts)
    got = want = pool
    for lo in range(0, len(starts), 4):  # four slots with pages of their own
        sl = slice(lo, lo + 4)
        pid = table[sl][np.arange(len(row[sl]))[:, None], row[sl] // PAGE]
        off = row[sl] % PAGE
        got = latent_pool_write(got, win[:, sl], jnp.asarray(pid),
                                jnp.asarray(off), interpret=True)
        want = _scattered(want, win[:, sl], pid, off)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    assert (_bits(got) != _bits(pool)).any()


def test_staged_write_of_an_idle_slot_and_of_rows_past_the_table():
    """Through `write_block_to_pool` under the Pallas reader, beside the XLA
    walk's scatter: a straddle, an idle slot (SCRATCH entries, its position
    at the last row: every row clamps) and a live slot whose last rows pass
    its table. Every row of a live page that the scatter writes inside the
    table is the kernel's too; rows clamped to the table's last row are
    dropped by the kernel (never read); nothing else changes."""
    n = 16
    pool, win, table, start, row = _write_case(
        n, [PAGE - 5, MP * PAGE - 1, MP * PAGE - 6, 40], seed=1)
    table[1] = SCRATCH

    def write(impl):
        sites = SiteCounts()
        with sites.tracing("write"):
            out = jax.jit(lambda p, t: L.write_block_to_pool(
                L.KVCache(p, p[..., :0]), t, win, win[..., :0],
                jnp.asarray(start), paged_impl=impl))(pool, jnp.asarray(table))
        return out.k, sites.by_program["write"]

    got, tally = write("pallas")
    want, tally_x = write("xla")
    assert (tally["pool_write_inplace"], tally["pool_write_scatter"]) == (1, 1)
    assert (tally_x["pool_write_inplace"], tally_x["pool_write_scatter"]) == (0, 2)
    g, w, before = _bits(got), _bits(want), _bits(pool)
    same = np.ones(g.shape[1:3], bool)
    same[SCRATCH] = False  # the idle slot's page: garbage either way
    last = (int(table[2, -1]), PAGE - 1)  # slot 2's clamp address
    same[last] = False
    np.testing.assert_array_equal(g[:, same], w[:, same])
    # slot 2's row at the table's last address is its own sixth row, not one
    # of the ten that clamped onto it
    np.testing.assert_array_equal(g[:, last[0], last[1]], _bits(win)[:, 2, 5])
    pid = table[np.arange(4)[:, None], row // PAGE]
    for b in (0, 2, 3):
        for r in range(n):
            same[pid[b, r], row[b, r] % PAGE] = False
    np.testing.assert_array_equal(g[:, same], before[:, same])


@pytest.mark.parametrize("shape,dtype,n,staged", [
    ((47, 769, 128, 1, 640), "bfloat16", 16, True),
    ((7, 513, 128, 1, 640), "bfloat16", 4, True),
    ((7, 513, 128, 1, 640), "bfloat16", 1, True),
    ((7, 513, 128, 1, 640), "bfloat16", 32, False),  # more than two groups
    ((7, 513, 128, 1, 0), "bfloat16", 16, False),  # MLA's V pool
    ((7, 513, 128, 1, 576), "bfloat16", 16, False),  # part of a lane tile
    ((7, 513, 128, 1, 640), "float32", 16, False),  # whole words: the DMA's
    ((7, 513, 128, 1, 640), "float8_e4m3fn", 16, False),  # not compiled
    ((7, 513, 8, 1, 640), "bfloat16", 4, False),  # a page under a group
    ((7, 513, 128, 2, 128), "bfloat16", 16, False),  # the DMA's
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_staged_rule_is_on_the_latent_row(shape, dtype, n, staged):
    assert staged_rows(shape, dtype, n) is staged
    assert not (staged and in_place_rows(shape, dtype))
    if shape[-1] == 0:
        assert not in_place_rows(shape, "float32")


def test_write_window_names_the_latent_write():
    """The staged kernel's op carries `latent_write` inside the leaf that
    books it, which is what `mlamoe_latent_write_share` reads."""
    from localai_tpu.observe.scopes import LATENT_WRITE

    pool = jax.ShapeDtypeStruct((2, 5, 16, 1, 128), jnp.bfloat16)
    win = jax.ShapeDtypeStruct((2, 3, 4, 1, 128), jnp.bfloat16)
    idx = jax.ShapeDtypeStruct((3, 4), jnp.int32)
    table = jax.ShapeDtypeStruct((3, 2), jnp.int32)
    text = jax.jit(lambda p, t, w, s: L.write_block_to_pool(
        L.KVCache(p, p[..., :0]), t, w, w[..., :0], s,
        paged_impl="pallas")).trace(
            pool, table, win, jax.ShapeDtypeStruct((3,), jnp.int32)).jaxpr
    names = [str(e.source_info.name_stack) for e in _eqns(text)
             if e.params.get("name") == "latent_pool_write"]
    assert f"attention/cache_write/{LATENT_WRITE}" in names
    jaxpr = jax.make_jaxpr(lambda p, w, a, b: A.write_window(
        p, w, a, b, impl="xla"))(pool, win, idx, idx)
    assert "pallas_call" not in str(jaxpr)


def _eqns(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for sub in jax.core.jaxprs_in_params(e.params):
            yield from _eqns(sub)


# ---- the checkpoint's names --------------------------------------------------------- #


def test_checkpoint_names_and_the_mtp_block_is_skipped(tmp_path):
    """A `glm4_moe_lite` checkpoint (the V3 block's tensor names) with an MTP
    block at `model.layers.<num_hidden_layers>.*`: the config is read to the
    preset's fields, the language model's tensors land where the synthetic
    tree has them, and no name of the MTP block is ever asked for."""
    from safetensors.numpy import load_file, save_file

    from localai_tpu.engine import weights as W

    cfg = dataclasses.replace(FULL, name="glm")
    params = L.init_params(cfg, jax.random.key(2))
    W.save_hf_checkpoint(cfg, params, str(tmp_path))
    path = os.path.join(tmp_path, "model.safetensors")
    tensors = load_file(path)
    mtp = f"model.layers.{cfg.num_layers}."
    extra = {mtp + k.split(".", 3)[3]: np.full_like(v, np.nan)
             for k, v in tensors.items() if k.startswith("model.layers.1.")}
    extra.update({mtp + "eh_proj.weight": np.full((64, 128), np.nan, np.float32),
                  mtp + "enorm.weight": np.full((64,), np.nan, np.float32),
                  mtp + "shared_head.norm.weight": np.full((64,), np.nan, np.float32)})
    save_file({**tensors, **extra}, path)
    with open(os.path.join(tmp_path, "config.json")) as f:
        hf = json.load(f)
    hf.update({"model_type": "glm4_moe_lite", "topk_method": "noaux_tc",
               "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
               "attention_bias": False, "_name_or_path": "glm"})
    del hf["rope_interleave"], hf["head_dim"]

    def read():
        with open(os.path.join(tmp_path, "config.json"), "w") as f:
            json.dump(hf, f)
        return W.arch_from_hf_config(str(tmp_path))

    # the published config.json states no pairing, and none is guessed
    with pytest.raises(ValueError, match="rope_interleave"):
        read()
    hf["rope_interleave"] = False
    arch = read()
    for name in ("vocab_size", "hidden_size", "intermediate_size", "num_layers",
                 "num_heads", "rope_theta", "rms_eps", "tie_embeddings",
                 "moe_family", "num_experts", "num_experts_per_token",
                 "first_k_dense", "n_shared_experts", "moe_intermediate_size",
                 "routed_scaling_factor", "scoring_func", "router_bias",
                 "norm_topk_prob", "n_group", "topk_group", "kv_lora_rank",
                 "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                 "v_head_dim", "mla_rope", "max_position"):
        assert getattr(arch, name) == getattr(cfg, name), name
    assert arch.scoring_func == "sigmoid" and arch.router_bias
    assert not arch.rope_interleave and arch.head_dim == cfg.qk_rope_head_dim
    asked = []
    real = W._ShardReader.get

    def get(self, name):
        asked.append(name)
        return real(self, name)

    W._ShardReader.get = get
    try:
        loaded = W.load_hf_checkpoint(
            dataclasses.replace(arch, dtype="float32"), str(tmp_path))
    finally:
        W._ShardReader.get = real
    assert asked and not any(n.startswith(mtp) for n in asked)
    flat = dict(jax.tree_util.tree_leaves_with_path(loaded))
    for path_, leaf in jax.tree_util.tree_leaves_with_path(params):
        got = np.asarray(flat[path_], np.float32)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, np.asarray(leaf, np.float32),
                                   atol=1e-6, err_msg=str(path_))


# ---- the published preset ----------------------------------------------------------- #


def test_published_preset_and_its_held_tree():
    """The preset's tree is the published 30B-A3B (without its MTP block),
    the costs file counts the same, and chip 0 of 8 holds what the issue's
    arithmetic says; nothing is allocated."""
    tree = jax.eval_shape(lambda k: L.init_params(PUB, k), jax.random.key(0))
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    lay = tree["layers"]
    assert lay["wq_a"].shape == (46, 2048, 768)
    assert lay["wq_b"].shape == (46, 768, 20 * 256)
    assert lay["wkv_a"].shape == (46, 2048, 576)
    assert lay["w_kb"].shape == (46, 20, 192, 512)
    assert lay["w_vb"].shape == (46, 20, 256, 512)
    assert lay["wo"].shape == (46, 20 * 256, 2048)
    assert lay["router"].shape == (46, 2048, 64)
    assert lay["w_gate"].shape == (46, 64, 2048, 1536)
    assert tree["dense_layers"]["w_gate"].shape == (1, 2048, 10240)
    assert tree["lm_head"].shape == tree["embed"].shape == (154880, 2048)
    arch = S.config("glm-4.7-flash-int8-ep8")
    # the correction bias (46 x 64) is no matrix of the costs file's
    assert abs(COSTS.param_count(arch) - (size(tree) - 46 * 64)) == 0
    assert abs(COSTS.param_count(arch) / 1e9 - 29.9) < 0.05
    assert abs(COSTS.active_params(arch) / 1e9 - 3.6) < 0.05
    for key, want in (("hidden_size", PUB.hidden_size),
                      ("num_hidden_layers", PUB.num_layers),
                      ("num_attention_heads", PUB.num_heads),
                      ("q_lora_rank", PUB.q_lora_rank),
                      ("kv_lora_rank", PUB.kv_lora_rank),
                      ("qk_nope_head_dim", PUB.qk_nope_head_dim),
                      ("qk_rope_head_dim", PUB.qk_rope_head_dim),
                      ("v_head_dim", PUB.v_head_dim),
                      ("moe_intermediate_size", PUB.moe_inter_size),
                      ("intermediate_size", PUB.intermediate_size),
                      ("num_experts_per_tok", PUB.num_experts_per_token),
                      ("n_shared_experts", PUB.n_shared_experts),
                      ("first_k_dense_replace", PUB.first_k_dense),
                      ("routed_scaling_factor", PUB.routed_scaling_factor),
                      ("rope_theta", PUB.rope_theta),
                      ("vocab_size", PUB.vocab_size),
                      ("max_position_embeddings", PUB.max_position)):
        assert arch[key] == want, key
    assert arch["published"]["n_routed_experts"] == PUB.num_experts == 64
    assert arch["reduced"] == ["n_routed_experts"]
    assert arch["assumed"]["latent_row_values"] == PUB.cache_k_dim
    held = dataclasses.replace(PUB, expert_share=tuple(arch["yaml"]["expert_share"]))
    assert held.experts_here == arch["n_routed_experts"] == 8
    q = jax.eval_shape(lambda k: Q.init_params_quantized(held, k),
                       jax.random.key(0))
    for name in ("wq_a", "wq_b", "wkv_a", "wo", "shared_down"):
        assert q["layers"][name]["q"].dtype == jnp.int8
        assert q["layers"][name]["s"].shape[-2] == 1  # a scale a channel
    assert q["layers"]["wq_a"]["q"].shape == (46, 2048, 768)
    assert q["layers"]["wkv_a"]["q"].shape == (46, 2048, 576)
    assert q["layers"]["w_kb"].dtype == q["layers"]["w_vb"].dtype == jnp.bfloat16
    assert q["layers"]["w_gate"]["q"].shape == (46, 8, 2048, 1536)
    assert q["layers"]["router"].shape == (46, 2048, 64)
    assert q["lm_head"]["q"].dtype == jnp.int8 and q["embed"].dtype == jnp.bfloat16
    nbytes = sum(int(np.prod(a.shape)) * a.dtype.itemsize
                 for a in jax.tree.leaves(q))
    assert abs(nbytes / 1e9 - 6.2) < 0.1
    h = COSTS.held_params(arch)
    assert size({k: v for k, v in lay.items() if k in (
        "wq_a", "wq_b", "wkv_a", "wo", "w_kb", "w_vb", "kv_norm", "q_norm_a")}
    ) + size({k: v for k, v in tree["dense_layers"].items() if k in (
        "wq_a", "wq_b", "wkv_a", "wo", "w_kb", "w_vb", "kv_norm", "q_norm_a")}
    ) == h["mla_attention"]
    assert h["experts_held"] == 46 * 8 * 3 * 2048 * 1536
    assert abs(COSTS.weight_bytes(arch, 1) / 1e9 - 5.54) < 0.02
    assert COSTS.latent_bytes_per_token(arch, 2) == 60160
    # the pool as the cell holds it: 896 pages of 128 rows, 6.90 GB
    pages = arch["yaml"]["kv_pages"] * arch["yaml"]["kv_page_size"]
    assert abs(pages * COSTS.latent_bytes_per_token(arch, 2) / 1e9 - 6.90) < 0.01
    assert rstate.admit_rows(PUB) == 6864


def test_the_routed_down_projection_is_drawn_at_a_tenth():
    assert L.init_gain(PUB, "w_down", (46, 8, 1536, 2048)) == 0.1
    assert L.init_gain(PUB, "w_down", (1, 10240, 2048)) == 1.0
    assert L.init_gain(PUB, "shared_down", (46, 1536, 2048)) == 1.0


@pytest.mark.parametrize("name", sorted(PRESETS))
def test_one_field_says_what_the_routed_down_projection_is_drawn_at(name):
    """`routed_down_gain` alone decides it: every hybrid preset says the tenth
    that `is_hybrid` used to imply (their synthetic weights are the accepted
    cells'), the two GLM presets ask for it, no other preset does (a DENSE
    hybrid, AI21-Jamba2, has no routed experts to draw)."""
    arch = PRESETS[name]
    tenth = (arch.is_hybrid and arch.is_moe) or name.endswith("glm-4.7-flash")
    scalars = float(arch.embedding_multiplier * arch.logits_scaling
                    / arch.residual_multiplier)
    assert L.init_gain(arch, "w_down", (2, 8, 64, 32)) == pytest.approx(
        scalars * (0.1 if tenth else 1.0), rel=1e-12)
