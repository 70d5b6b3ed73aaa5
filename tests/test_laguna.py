"""Laguna-XS.2 (poolside/Laguna-XS.2): periods of one full-attention GQA layer
over the paged K/V pool and three sliding-window layers with another head
count whose slots hold a RING of their last `sliding_window` rows; the full
layers rotate half of each head under YaRN, the window layers the whole head
at their own base; a per-head output gate; layer 0 dense AND a cache layer,
the others a sigmoid-routed MoE with a shared expert, of which a share is held.

At the `tiny-laguna-xs.2` width on the CPU: the program (`Engine.submit`,
prefill then decode through the rings and the pool, across block boundaries,
the ring's wrap, slot hand-ons and a preemption) against the benchmark's plain
float32 reference (`benchmark/reference/swa_gqa_moe.py`, which shares no code
with `localai_tpu/models/`); the ring's reader and the windowed flash kernel
against their XLA forms; the share test; what such a model is refused.
"""

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from model_cases import _engine, _err_against, served_engine
from benchmark.harness import check as C
from benchmark.reference import swa_gqa_moe as REF
from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import state as rstate
from localai_tpu.models import llama as L
from localai_tpu.models import quant as Q
from localai_tpu.models.config import get_arch
from localai_tpu.ops import attention as A
from localai_tpu.ops import rope as R

SHARE = (1, 4)  # 4 of 16 experts
# float32 activations: the program's honest distance from the float32
# reference is then rounding alone (1e-6 at worst over the right cases below)
# and a wrong block stands out of it (5e-2 at the least: the window left out).
CFG = dataclasses.replace(get_arch("tiny-laguna-xs.2"), expert_share=SHARE,
                          dtype="float32")
W = CFG.sliding_window  # 16
TOLERANCE = 1e-4
NEW = 20  # two 8-step blocks and four single steps past the admission's token


def _seeded(cfg=CFG, quantize=""):
    """Seeded weights with a selection bias that moves picks."""
    params = L.init_params(cfg, jax.random.key(7))
    lay = dict(params["layers"])
    lay["router_bias"] = 0.1 * jax.random.normal(
        jax.random.key(8), lay["router_bias"].shape, jnp.float32)
    params = {**params, "layers": lay}
    return Q.quantize_params(cfg, params, quantize) if quantize else params


_err = functools.partial(_err_against, REF.forward)
served = served_engine(_seeded, CFG)


# ---- the engine against the reference ---------------------------------------- #


# (a) contexts of 0.5, 1, 1.5 and 6 windows: the ring part full, exactly full,
# wrapped once and many times, each then decoded across block boundaries
@pytest.mark.parametrize("prompt", [W // 2, W, W + W // 2, 6 * W])
def test_engine_agrees_with_the_plain_reference(served, prompt):
    eng, params = served
    (ids,) = C.sample_prompts(11 + prompt, CFG.vocab_size, [prompt])
    (rec,) = C.run_system(eng, [ids], NEW)
    err = _err(params, CFG, ids, rec)
    assert C.verdict([err], TOLERANCE), err
    # the control: the reference with the window taken out is another model
    lp = C.reference_logprobs(REF.forward, params, CFG, ids, rec["ids"],
                              pad_to=16, window=0)
    assert not C.verdict([C.compare(rec, lp)], 100 * TOLERANCE)


def test_two_kinds_of_rows_two_accounts(served):
    """(c) The window layers' rows are a fixed ring a slot, whatever the
    context; the KV manager's pages are the full layers' alone; the journal
    and the gauges tell the two apart."""
    eng, _ = served
    ls, lf = len(CFG.recurrent_layers), CFG.cache_layers
    assert (ls, lf, CFG.cache_layer_ids) == (6, 2, (0, 4))
    # the pool: the 2 full layers; the rings: 6 window layers x 2 slots x 1 page
    assert eng.cache.k.shape == (lf, 41, 16, 2, 16) == eng.cache.v.shape
    assert eng.cache.state.shape == (ls, 2 * 1, W, 2, 16) == eng.cache.conv.shape
    before = eng.metrics()
    short, long_ = C.sample_prompts(3, CFG.vocab_size, [5, 100])
    C.run_system(eng, [short, long_], 9)
    m = eng.metrics()
    ring_bytes = 2 * ls * W * 2 * 16 * 2 * 4  # slots x layers x rows x K x D x (k, v) x f32
    assert m["window_state_bytes"] == ring_bytes == before["window_state_bytes"]
    assert m["window_state_bytes"] == 2 * rstate.row_bytes(CFG, "float32")
    assert "recurrent_state_bytes" not in m
    # a page is the full layers' rows alone
    assert eng._page_bytes() == lf * 16 * 2 * (16 + 16) * 4
    read = m["window_rows_read"] - before["window_rows_read"]
    full = m["window_rows_full"] - before["window_rows_full"]
    # one 8-step block each, counted at the rows held at its dispatch (its
    # own rows ride in its window): the short one reads its 5 rows a step,
    # all it has; the long one its 16-row ring of 100
    assert full == 8 * 5 + 8 * 100
    assert read == 8 * 5 + 8 * W
    ev = eng.journal.snapshot()
    (ws,) = [e for e in ev if e["event"] == "window_state"]
    (kp,) = [e for e in ev if e["event"] == "kv_pool"]
    assert (ws["a"], ws["b"]) == (W, ring_bytes)
    assert (kp["a"], kp["b"]) == (40, 41 * eng._page_bytes())
    rows = [e for e in ev if e["event"] == "window_rows"]
    assert sum(e["a"] for e in rows) == m["window_rows_read"]
    assert sum(e["b"] for e in rows) == m["window_rows_full"]


def test_successor_never_sees_the_old_tenants_ring_or_pages(served):
    """Six requests through two slots, every one ending on its budget, so
    every hand-on goes through `_park` with both kinds of rows live: the old
    tenant's blocks in flight still write its ring and its pages, the
    successor's admission overwrites the ring's live rows and takes pages of
    its own. Each stream's log-probabilities are the reference's for ITS ids
    alone; among them prompts shorter than the ring after longer ones, whose
    stale rows lie past the new tenant's limit."""
    eng, params = served
    prompts = C.sample_prompts(12, CFG.vocab_size, [70, 9, 33, 5, 48, 12])
    handles = [eng.submit(GenRequest(
        prompt_ids=list(p), max_new_tokens=11, temperature=0.0,
        ignore_eos=True, logprobs=20)) for p in prompts]
    from model_cases import _collect

    recs = [_collect(h, 11) for h in handles]
    errs = [_err(params, CFG, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, TOLERANCE), errs


@pytest.mark.parametrize("policy", ["recompute", "swap"])
def test_preempted_request_keeps_its_stream(policy):
    """(d) A pool too small for two long decodes: the younger is preempted,
    its ring and its pages dropped, and its re-admission recomputes both from
    prompt + generated (a prompt of several windows: the windowed prefill and
    the ring's last rows). Asked to swap, it recomputes all the same: the
    ring has no swap image. Both streams still agree with the reference."""
    params = _seeded()
    n = 100
    eng = _engine(CFG, params, kv_pages=10, kv_preempt=policy,
                  kv_page_headroom=1)
    try:
        prompts = C.sample_prompts(14, CFG.vocab_size, [40, 44])
        handles = []
        for p in prompts:  # the first strictly older: the second is the victim
            handles.append(eng.submit(GenRequest(
                prompt_ids=list(p), max_new_tokens=n, temperature=0.0,
                ignore_eos=True)))
            time.sleep(0.3)
        streams = []
        for h in handles:
            ids = [int(ev.token_id) for ev in h if ev.kind == "token"]
            assert len(ids) == n
            streams.append(ids)
        m = eng.metrics()
    finally:
        eng.stop()
    assert m["kv_preemptions"] >= 1 and m["state_restores"] >= 1
    assert m["kv_preempt_swaps"] == 0
    for p, ids in zip(prompts, streams):
        lp = C.reference_logprobs(REF.forward, params, CFG, p, ids, pad_to=16)
        gap = lp.max(-1) - lp[np.arange(n), ids]
        assert gap.max() <= TOLERANCE, gap.max()


def test_int8_weights_stay_inside_the_band_of_bfloat16_ones():
    """(g) The served form (int8 matrices, bfloat16 activations and rows)
    against the float32 reference over the SAME int8 matrices read as data:
    inside the band honest bfloat16 compute shows (0.25 at this width, the
    rehearsal's tolerance), far outside float32's 1e-4, and the window taken
    out of the reference is further off than that band's honest readings."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    params = _seeded(cfg, quantize="int8")
    assert params["swa_layers"]["wq"]["q"].dtype == jnp.int8
    assert params["gqa_layers"]["wg_head"].dtype == jnp.bfloat16  # never rounded
    eng = _engine(cfg, params)
    try:
        prompts = C.sample_prompts(21, cfg.vocab_size, [W, 6 * W])
        recs = C.run_system(eng, prompts, 17)
    finally:
        eng.stop()
    errs = [_err(params, cfg, p, r) for p, r in zip(prompts, recs)]
    assert C.verdict(errs, 0.25), errs
    assert not C.verdict(errs, TOLERANCE)


# ---- the ring's reader and writer, the windowed prefill ------------------------ #


def _ring_case(n0, step, ring=32, page=16, K=2, G=4, D=16, seed=0):
    """One slot a context: rings whose row r holds position
    n0-1 - ((n0-1-r) mod ring), queries at n0 + step."""
    B, P = len(n0), ring // page
    ks = jax.random.split(jax.random.key(seed), 3)
    n0, step = jnp.asarray(n0, jnp.int32), jnp.asarray(step, jnp.int32)
    q = jax.random.normal(ks[0], (B, K * G, D), jnp.float32)
    kp = jax.random.normal(ks[1], (B * P, page, K, D), jnp.float32)
    vp = jax.random.normal(ks[2], (B * P, page, K, D), jnp.float32)
    table = jnp.arange(B * P, dtype=jnp.int32).reshape(B, P)  # as ring_table's
    return q, kp, vp, table, n0, step


def _ring_by_hand(q, kp, vp, n0, step, ring):
    """Softmax attention of each query over the positions of its ring that
    are inside its window, one slot at a time."""
    B, H, D = q.shape
    K = kp.shape[2]
    out = np.zeros((B, H, D))
    for b in range(B):
        rows = np.arange(ring)
        pos = (int(n0[b]) - 1) - ((int(n0[b]) - 1 - rows) % ring)
        live = (rows < int(n0[b])) & (int(n0[b] + step[b]) - pos < ring)
        if not live.any():
            continue
        k = np.asarray(kp).reshape(B, ring, K, D)[b][live]
        v = np.asarray(vp).reshape(B, ring, K, D)[b][live]
        for h in range(H):
            s = k[:, h // (H // K)] @ np.asarray(q[b, h]) / np.sqrt(D)
            p = np.exp(s - s.max())
            out[b, h] = (p / p.sum()) @ v[:, h // (H // K)]
    return out


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ring_reader_masks_a_row_at_the_position_it_holds(impl):
    """Contexts under a ring, exactly one, wrapped once and several times,
    each at a query 0, 3 and 7 steps into its block: the rows the block is
    about to replace are dead although they are still in the ring. The XLA
    walk and the kernel (interpreted here) against a walk by hand."""
    ring = 32
    n0 = [0, 1, 13, 31, 32, 33, 45, 64, 70, 127, 200, 1000]
    step = [0, 3, 7, 0, 3, 7, 0, 3, 7, 0, 3, 7]
    q, kp, vp, table, n0, step = _ring_case(n0, step, ring)
    acc, m, l = jax.jit(functools.partial(
        A.paged_partials, window=ring, sliding=np.True_, impl=impl, ring=ring))(
        q, kp, vp, table, n0, q_pos=n0 + step)
    got = np.where(l > 0, acc / np.where(l > 0, l, 1), 0).reshape(q.shape)
    want = _ring_by_hand(q, kp, vp, n0, step, ring)
    np.testing.assert_allclose(got, want, atol=2e-2 if impl == "pallas" else 1e-5)
    assert float(l[0].max()) == 0.0  # nothing written yet: nothing read


def test_ring_refuses_what_its_remap_cannot_do():
    q, kp, vp, table, n0, _ = _ring_case([5], [0], ring=32)
    from localai_tpu.ops.paged_flash import paged_decode_partials

    with pytest.raises(ValueError, match="power of two"):
        paged_decode_partials(q, kp, vp, table, n0, ring=24, interpret=True)


def test_block_rows_land_at_their_positions_mod_the_ring():
    """`write_block_to_pool(ring=True)`: a block that starts 3 rows before
    the ring's end wraps to its start; every slot writes its own pages."""
    Ls, B, n, K, D = 2, 3, 8, 2, 16
    rings = jnp.zeros((Ls, B * 1, W, K, D), jnp.float32)
    win = jax.random.normal(jax.random.key(1), (Ls, B, n, K, D), jnp.float32)
    start = jnp.asarray([0, W - 3, 5 * W + 9], jnp.int32)
    out = L.write_block_to_pool(
        L.KVCache(rings, rings), L.ring_table(CFG, B), win, win, start,
        ring=True).k
    for b in range(B):
        for t in range(n):
            np.testing.assert_array_equal(
                out[:, b, (int(start[b]) + t) % W], win[:, b, t])
    assert float(jnp.abs(out).sum()) == pytest.approx(
        float(jnp.abs(win).sum()), rel=1e-5)


@pytest.mark.parametrize("S,window", [(64, 16), (128, 48)])
def test_flash_prefill_under_a_window_is_the_dense_form(S, window):
    """The flash kernel with a static window (interpreted here) against the
    dense form under the same mask; blocks that lie wholly before a query
    block's window are skipped, rows whose window misses a visited block
    add nothing."""
    from localai_tpu.ops.flash import flash_prefill_attention

    ks = jax.random.split(jax.random.key(4), 3)
    q = jax.random.normal(ks[0], (2, S, 8, 16), jnp.float32)
    k = jax.random.normal(ks[1], (2, S, 2, 16), jnp.float32)
    v = jax.random.normal(ks[2], (2, S, 2, 16), jnp.float32)
    lens = jnp.asarray([S, S - 7], jnp.int32)
    mask = jnp.arange(S)[None, :] < lens[:, None]
    got = flash_prefill_attention(q, k, v, lens, block_q=16, block_k=16,
                                  interpret=True, window=window)
    want = A.causal_prefill_attention(q, k, v, mask, window=window,
                                      sliding=jnp.bool_(True))
    want = jnp.where(mask[:, :, None, None], want, 0)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # the dispatcher: a static flag keeps the window, none drops it
    assert A.prefill_attention(q, k, v, mask, window=window,
                               sliding=np.True_).shape == q.shape
    np.testing.assert_allclose(
        A.prefill_attention(q, k, v, mask, window=window, sliding=None),
        A.causal_prefill_attention(q, k, v, mask), atol=1e-6)


# ---- the reference against closed forms, the model's own pieces ---------------- #


def test_yarn_over_the_rotated_half_and_the_passed_lanes():
    """The published full layer: YaRN over dim 64 (low 5, high 16), the
    program's frequencies the reference's; a head's first 64 lanes rotated in
    pairs (i, i + 32), its last 64 passed; the amplitude on the rotated lanes
    of q alone (squared), the reference's on q and k."""
    pub = get_arch("laguna-xs.2")
    full = pub.kind_view("gqa")
    inv = np.asarray(R.rope_frequencies(full))
    ref = REF.yarn_inv(64, 5e5, 64.0, 4096, 64.0, 1.0)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv, ref, rtol=2e-6)
    i = np.arange(32)
    np.testing.assert_allclose(ref[:6], 5e5 ** (-2 * i[:6] / 64))  # extrapolated
    np.testing.assert_allclose(ref[16:], 5e5 ** (-2 * i[16:] / 64) / 64)
    assert R.rope_query_amp(full) == pytest.approx(1.4158883083359672 ** 2)
    win = pub.kind_view("swa")
    assert (win.num_heads, win.rope_scaling, win.rotary_dim) == (64, None, 128)
    np.testing.assert_allclose(
        R.rope_frequencies(win), 1e4 ** (-2 * np.arange(64) / 128), rtol=2e-6)
    assert R.rope_query_amp(win) == 1.0 and full.sliding_window == 0
    x = jax.random.normal(jax.random.key(2), (1, 3, 2, 128), jnp.float32)
    pos = jnp.asarray([[0, 5, 900]])
    y = R.apply_rope(x, pos, jnp.asarray(inv))
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    np.testing.assert_array_equal(y[:, 0], x[:, 0])  # position 0: no turn
    want = REF.rotate(x[0], pos[0], ref)
    np.testing.assert_allclose(y[0], want, atol=5e-4)  # float32 angles at 900
    np.testing.assert_allclose(  # a pair keeps its length
        y[0, 2, :, :32] ** 2 + y[0, 2, :, 32:64] ** 2,
        x[0, 2, :, :32] ** 2 + x[0, 2, :, 32:64] ** 2, rtol=1e-5)


def test_the_reference_window_has_its_edge_at_512_keys():
    """One window layer of the reference by hand: position i attends j iff
    0 <= i - j < window, the token's own among them."""
    T, Wn = 24, 8
    # hidden 16, two query heads over one KV head of 8: q = k = 0, v the
    # normed input's first 8 dims, W_o the identity, the gate's operand 0
    lw = {"attn_norm": jnp.ones((16,)), "wg_head": jnp.zeros((16, 2)),
          "wq": jnp.zeros((16, 16)), "wk": jnp.zeros((16, 8)),
          "wv": jnp.pad(jnp.eye(8), ((0, 8), (0, 0))), "wo": jnp.eye(16)}
    h = jax.random.normal(jax.random.key(3), (T, 16), jnp.float32)
    kw = dict(heads=2, kv_heads=1, inv=(1.0, 0.1, 0.01, 0.001), amp=1.0,
              eps=1e-6)
    out = REF.attention(h, lw, window=Wn, **kw) - h
    # q = k = 0: uniform weights over the window; gate sigmoid(0) = 1/2
    v = REF._rms_norm(h, lw["attn_norm"], 1e-6)[:, :8]
    want = np.stack([np.asarray(v[max(0, i - Wn + 1): i + 1]).mean(0)
                     for i in range(T)]) / 2
    np.testing.assert_allclose(out[:, :8], want, atol=1e-5)
    np.testing.assert_allclose(out[:, 8:], want, atol=1e-5)
    full = REF.attention(h, lw, window=0, **kw) - h
    np.testing.assert_allclose(full[:Wn], out[:Wn], atol=1e-6)
    assert float(jnp.abs(full[Wn:] - out[Wn:]).max()) > 1e-3


def test_sliding_phase_is_the_configs_and_gemma_keeps_its_own():
    """`_layer_sliding`: Gemma's presets end a period with its global layer
    (phase None = pattern - 1), a phase of 0 begins it, a window kind's view
    slides in every layer, statically."""
    li = jnp.arange(8)
    base = dataclasses.replace(get_arch("tiny"), sliding_window=4)
    assert base.sliding_phase is None
    np.testing.assert_array_equal(L._layer_sliding(base, li), li % 2 != 1)
    six = dataclasses.replace(base, sliding_pattern=6)
    np.testing.assert_array_equal(L._layer_sliding(six, li), li % 6 != 5)
    lead = dataclasses.replace(base, sliding_pattern=4, sliding_phase=0)
    np.testing.assert_array_equal(L._layer_sliding(lead, li), li % 4 != 0)
    assert L._layer_sliding(CFG.kind_view("swa"), li) is np.True_
    assert L._layer_sliding(CFG.kind_view("gqa"), li) is None
    assert L._layer_sliding(get_arch("tiny"), li) is None


def test_the_four_shares_add_up_to_the_uncut_layer():
    """(b) The four shares' routed parts, with the shared expert counted
    once, add up to the uncut reference's MoE layer; program and reference."""
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
        prog, ref = -3 * shared, -3 * shared  # counted once of four times
        for i in range(4):
            cfg_i = dataclasses.replace(full, expert_share=(i, 4))
            assert cfg_i.experts_here == 4
            held = slice(cfg_i.expert_lo, cfg_i.expert_lo + cfg_i.experts_here)
            lp_i = {**lp, **{k: lp[k][held] for k in ("w_gate", "w_up", "w_down")}}
            prog = prog + L._mlp(cfg_i, lp_i, m)
            ref = ref + REF.experts(
                x, {k: lp_i[k] for k in REF._MOE}, lo=cfg_i.expert_lo, **kw) - x
    np.testing.assert_allclose(ref, whole, atol=2e-5)
    np.testing.assert_allclose(prog, whole, atol=2e-5)


# ---- the shapes, the layout, what such a model is refused ---------------------- #


def test_per_kind_shapes_and_layer_zero_twice_over():
    """(f) `wq`, `wo` and the gate at the two head counts, each kind in its
    own stack; layer 0 the first cache layer AND the dense layer: it runs
    ahead of the scan, beside no window layer."""
    p = jax.eval_shape(lambda k: L.init_params(CFG, k), jax.random.key(0))
    shapes = lambda t: {k: v.shape for k, v in t.items()}  # noqa: E731
    assert shapes(p["gqa_layers"]) == {
        "wq": (2, 64, 6 * 16), "wk": (2, 64, 32), "wv": (2, 64, 32),
        "wo": (2, 6 * 16, 64), "wg_head": (2, 64, 6)}
    assert shapes(p["swa_layers"]) == {
        "wq": (6, 64, 8 * 16), "wk": (6, 64, 32), "wv": (6, 64, 32),
        "wo": (6, 8 * 16, 64), "wg_head": (6, 64, 8)}
    assert p["dense_layers"]["w_gate"].shape == (1, 64, 128)
    assert p["layers"]["w_gate"].shape == (7, 4, 64, 32)  # 4 of 16 held
    assert p["layers"]["router"].shape == (7, 64, 16)
    kl, beside, nd, kd, lead = L._hybrid_tables(CFG)
    # layer 0 ahead of the scan; what is left ENDS its periods with the full
    # layer (1 2 3 | 4, 5 6 7): layer 4 follows window layer 3
    assert (kl.tolist(), beside.tolist()) == (
        [1, 2, 3, 5, 6, 7], [-1, -1, 1, -1, -1, -1])
    assert (nd, kd, lead) == (0, 1, False)
    pub = get_arch("laguna-xs.2")
    kl, beside, nd, kd, lead = L._hybrid_tables(pub)
    assert len(kl) == 30 and sorted(set(beside.tolist())) == [-1] + list(range(1, 10))
    assert pub.cache_layers == 10 and (pub.ring_pages, pub.ring_page) == (4, 128)


def test_published_preset_and_its_held_tree():
    """The preset's shapes against the benchmark's byte counts: the tree a
    chip holds under the deployment's share is `costs_swa_moe.held_params`,
    the uncut model the card's 33.4 B, and the config keys give the preset."""
    import json
    import os
    import tempfile

    from benchmark.harness import costs_swa_moe as costs
    from benchmark.harness import spec as S
    from localai_tpu.engine.weights import arch_from_hf_config, load_hf_checkpoint

    arch = S.config("laguna-xs.2-int8-ep8")
    pub = get_arch("laguna-xs.2")
    assert abs(costs.param_count(arch) / 1e9 - 33.4) < 0.06
    cfg = dataclasses.replace(pub, expert_share=tuple(arch["yaml"]["expert_share"]))
    assert cfg.experts_here == arch["num_experts"] == 32
    tree = jax.eval_shape(lambda k: L.init_params(cfg, k), jax.random.key(0))
    held = costs.held_params(arch)
    size = lambda t: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(t))  # noqa: E731
    assert size(tree["gqa_layers"]) == held["full_attention"]
    assert size(tree["swa_layers"]) == held["window_attention"]
    assert size(tree["lm_head"]) == held["head"] == size(tree["embed"])
    norms = 2 * 2048
    assert size(tree["dense_layers"]) - norms == held["dense_mlp"]
    assert size(tree["layers"]) - 39 * norms - 39 * 256 == (
        held["shared_experts"] + held["routers"] + held["experts_held"])
    # a slot's window rows and a token's pool rows, as the issue reckons them
    assert rstate.row_bytes(cfg, "bfloat16") == 30 * 512 * 4096 == 62914560
    assert costs.kv_row_bytes(arch, 2) * costs.layers(arch)["full"] == 40960
    assert rstate.admit_rows(cfg) == 3318
    with tempfile.TemporaryDirectory() as d:
        keys = {k: v for k, v in arch.items() if k in (
            "model_type", "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_key_value_heads", "head_dim",
            "max_position_embeddings", "rms_norm_eps", "num_experts_per_tok",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "tie_word_embeddings", "gating", "sliding_window",
            "rope_parameters", "layer_types", "mlp_layer_types",
            "moe_routed_scaling_factor", "num_attention_heads_per_layer")}
        keys["num_experts"] = arch["published"]["num_experts"]
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(keys, f)
        read = arch_from_hf_config(d)
        assert dataclasses.replace(
            read, name=pub.name, routed_down_gain=pub.routed_down_gain) == pub
        with pytest.raises(ValueError, match="tensors are not loaded yet"):
            load_hf_checkpoint(read, d)


REFUSED = {
    "dense_cache": ({"kv_pages": 0}, {}, "dense KV cache"),
    "chunked_admission": ({"prefill_chunk": 64}, {}, "chunked admission"),
    "verify_chunk": ({"spec_mode": "prompt_lookup"}, {}, "speculative"),
    "sequence_parallel": ({}, {"sp": 2}, "tp/sp/ep/dp"),
    "expert_parallel": ({}, {"ep": 2}, "tp/sp/ep/dp"),
    "long_blocks": ({"block_sizes": (32, 1)}, {}, "decode blocks of 32"),
    "fp8_rings": ({"kv_cache_dtype": "fp8"}, {}, "8-bit cache"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_refused_at_load_by_name(what):
    """(e) What needs the ring at a point inside a sequence, or in another
    layout, is refused where the engine is built."""
    from localai_tpu.parallel.mesh import MeshPlan

    ecfg, plan, says = REFUSED[what]
    kw = {"max_slots": 2, "max_seq": 128, "kv_pages": 8, "kv_page_size": 16,
          **ecfg}
    with pytest.raises(ValueError) as e:
        Engine(CFG, _seeded(), ByteTokenizer(CFG.vocab_size),
               engine_cfg=EngineConfig(**kw),
               mesh_plan=MeshPlan(**plan) if plan else None)
    assert "window rows" in str(e.value) and says in str(e.value), e.value
    assert f"{rstate.row_bytes(CFG, 'float32')} bytes a slot" in str(e.value)


def test_the_engines_own_window_stays_refused():
    """`attention_window` / `attention_sink` are a full-attention model's."""
    with pytest.raises(ValueError, match="architectural sliding window"):
        Engine(CFG, _seeded(), ByteTokenizer(CFG.vocab_size),
               engine_cfg=EngineConfig(
                   max_slots=2, max_seq=128, kv_pages=8, kv_page_size=16,
                   attention_window=64, attention_sink=4))


def test_fork_prefix_reuse_and_tp_are_off_by_name():
    """(e) ... a fork of a live stream is refused when asked for, prefix-span
    reuse is switched off and journalled, and the planner gives such a model
    one chip (tp = 1)."""
    from localai_tpu.engine.engine import AdapterError
    from localai_tpu.parallel.sharding import max_valid_tp

    eng = _engine(CFG, _seeded(), prefix_cache_entries=4)
    try:
        h = eng.submit(GenRequest(prompt_ids=[5, 6, 7], max_new_tokens=2,
                                  temperature=0.0, ignore_eos=True))
        with pytest.raises(ValueError, match="window rows"):
            eng.fork(h, 2)
        h.result()
        with pytest.raises(AdapterError, match="hybrid SWA/GQA"):
            eng.register_adapter("a", "/nowhere")
        assert not eng._prefix_enabled
        assert eng.metrics()["prefix_reuse_off"] == 1
        ev = eng.journal.snapshot()
        assert sum(e["event"] == "prefix_reuse_off" for e in ev) == 1
    finally:
        eng.stop()
    assert max_valid_tp(CFG, 8) == 1
