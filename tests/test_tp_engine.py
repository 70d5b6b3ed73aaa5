"""Tensor-parallel serving (ISSUE 7, docs/SHARDED_SERVING.md).

The engine's tp path must be INVISIBLE to callers: on a forced 8-device CPU
mesh a tp=2 engine produces byte-identical output to tp=1 across every
serving mode — greedy and seeded sampling, dense and paged caches, chunked
prefill, prefix-cache hits, and a cluster span export→import round-trip —
while the page allocator/refcounts stay host-global and the multi-layer
plumbing (knob → plan → mesh → shard_map'd kernels) stays internal.

The Pallas kernel equivalence test runs the SAME shard_map'd kernel code
that compiles for TPU, in interpret mode, against the tp=1 XLA reference.
"""

import threading

import jax
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.parallel.mesh import MeshPlan
from localai_tpu.parallel.sharding import (
    ShardingPlanError,
    max_valid_tp,
    validate_plan,
)
from localai_tpu.testing import faults
from localai_tpu.testing.streams import assert_same_until_near_tie, stream

PAGE = 32
PROMPT = [(i * 37) % 251 + 1 for i in range(70)]  # covers 2 full KV pages
PROMPT2 = [(i * 13) % 251 + 2 for i in range(44)]
SHORT = [5, 9, 11, 250, 3, 17, 42]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _mk(tiny, tp: int, paged: bool, **kw):
    cfg, params = tiny
    defaults = dict(
        max_slots=2, max_seq=128, min_prefill_bucket=16,
        prefix_admit_async_compile=False,
    )
    if paged:
        defaults.update(kv_pages=10, kv_page_size=PAGE)
    defaults.update(kw)
    eng = Engine(
        cfg, params, ByteTokenizer(cfg.vocab_size),
        mesh_plan=MeshPlan(tp=tp) if tp > 1 else None,
        engine_cfg=EngineConfig(**defaults),
    )
    eng.start()
    return eng


def _gen_ids(eng, prompt, **kw):
    """(token ids, text) of one request — identity asserts compare the raw
    sampled ids, not just their decoded text."""
    h = eng.submit(GenRequest(prompt_ids=list(prompt), ignore_eos=True, **kw))
    ids, parts = [], []
    for ev in h:
        if ev.kind == "token":
            ids.append(ev.token_id)
            parts.append(ev.text)
        assert ev.kind != "error", ev.error
    return ids, "".join(parts)


# --------------------------------------------------------------------- #
# Plan validation: typed error + engine auto-degrade
# --------------------------------------------------------------------- #


def test_validate_plan_raises_typed_error_naming_max_tp():
    cfg = get_arch("tiny")  # 4 heads, 2 kv heads
    with pytest.raises(ShardingPlanError) as ei:
        validate_plan(cfg, tp=4)
    assert ei.value.axis == "tp"
    assert ei.value.requested == 4
    assert ei.value.max_tp == 2 == max_valid_tp(cfg, 4)
    assert "max valid tp" in str(ei.value)
    # ShardingPlanError stays a ValueError for existing except-clauses.
    assert isinstance(ei.value, ValueError)
    # ep violations carry no tp degrade target.
    moe = get_arch("tiny-moe")  # 4 experts
    with pytest.raises(ShardingPlanError) as ei:
        validate_plan(moe, tp=1, ep=3)
    assert ei.value.axis == "ep" and ei.value.max_tp == 0


@pytest.mark.multichip
def test_engine_degrades_invalid_tp_instead_of_crashing(tiny, multichip,
                                                        caplog):
    if multichip < 4:
        pytest.skip("needs >= 4 devices")
    import logging

    with caplog.at_level(logging.WARNING, logger="localai_tpu.engine"):
        # tiny has 2 kv heads: tp=4 is invalid, max_valid_tp is 2.
        eng = _mk(tiny, 1, False, tensor_parallel=4)
    try:
        assert eng.plan.tp == 2
        assert any("degrading to tp=2" in r.message for r in caplog.records)
        _, text = _gen_ids(eng, SHORT, max_new_tokens=4)
        assert text
    finally:
        eng.stop()


@pytest.mark.multichip
def test_tensor_parallel_env_auto(tiny, multichip, monkeypatch):
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    monkeypatch.setenv("LOCALAI_TENSOR_PARALLEL", "auto")
    eng = _mk(tiny, 1, False)
    try:
        # auto = all devices, degraded to the architecture's max (2 kv heads).
        assert eng.ecfg.tensor_parallel == -1
        assert eng.plan.tp == max_valid_tp(eng.cfg, multichip)
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# tp=2 output identity vs tp=1 (the acceptance bar)
# --------------------------------------------------------------------- #


@pytest.mark.multichip
@pytest.mark.parametrize("paged", [False, True], ids=["dense", "paged"])
def test_tp2_output_identical_to_tp1(tiny, multichip, paged):
    """Greedy: the same tokens (a wrong head shard, a dropped all-reduce or a
    pool shard read through the wrong table shows here, and in the logprobs
    below at the first step). Sampled: what two reduction orders can promise
    (localai_tpu/testing/streams.py): logprobs within LOGPROB_TOL while the
    context is shared, tokens identical up to a tie within it. The top-k
    request parts ways at step 7, where ranks 6 and 7 of its 8 candidates
    sit 4.0e-4 apart at tp=1 and swap at tp=2 (PERF.md §6, PR 28); an rng,
    seed or sampler-parameter fault parts them where no tie is."""
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    ref = _mk(tiny, 1, paged)
    tp2 = _mk(tiny, 2, paged)
    try:
        want = _gen_ids(ref, PROMPT, max_new_tokens=12)
        got = _gen_ids(tp2, PROMPT, max_new_tokens=12)
        assert got == want, paged
        for kw in (
            dict(max_new_tokens=12, temperature=0.8, seed=7),
            dict(max_new_tokens=12, temperature=0.9, top_k=8, min_p=0.02,
                 seed=1234),
        ):
            same = assert_same_until_near_tie(
                stream(ref, PROMPT, **kw), stream(tp2, PROMPT, **kw))
            assert same >= 4, (paged, kw, same)
        # Prefix-cache hit: the repeat admits through the cached path.
        hits0 = tp2.m_prefix_hits
        want = _gen_ids(ref, PROMPT, max_new_tokens=8)
        got = _gen_ids(tp2, PROMPT, max_new_tokens=8)
        assert got == want and tp2.m_prefix_hits == hits0 + 1
    finally:
        ref.stop()
        tp2.stop()


@pytest.mark.multichip
def test_tp2_chunked_prefill_identical_to_tp1(tiny, multichip):
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    ref = _mk(tiny, 1, True, prefill_chunk=32)
    tp2 = _mk(tiny, 2, True, prefill_chunk=32)
    try:
        for kw in (dict(max_new_tokens=10),
                   dict(max_new_tokens=10, temperature=0.7, seed=3)):
            want = _gen_ids(ref, PROMPT, **kw)
            got = _gen_ids(tp2, PROMPT, **kw)
            assert got == want, kw
        assert tp2.m_chunked_admits >= 1  # 70 tokens really chunked at C=32
    finally:
        ref.stop()
        tp2.stop()


@pytest.mark.multichip
def test_tp2_span_export_import_roundtrip_identical(tiny, multichip):
    """Cluster span transfer over a SHARDED pool: export on one tp=2
    engine, import on another, and the prefix-hit continuation must equal a
    tp=1 engine's output — the LAIKV byte-exact serialization contract
    survives the kv-head axis being split across chips."""
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    ref = _mk(tiny, 1, True)
    src = _mk(tiny, 2, True)
    dst = _mk(tiny, 2, True)
    try:
        for prompt, kw in (
            (PROMPT, dict(max_new_tokens=10)),
            ([(i * 29) % 251 + 1 for i in range(66)],
             dict(max_new_tokens=10, temperature=0.8, seed=11)),
        ):
            want = _gen_ids(ref, prompt, **kw)
            src.generate(prompt, max_new_tokens=2, ignore_eos=True)
            frame = src.export_prefix_span(prompt)
            assert frame is not None and frame[:5] == b"LAIKV"
            assert dst.import_span_bytes(frame) is True
            hits0 = dst.m_prefix_host_hits
            got = _gen_ids(dst, prompt, **kw)
            assert got == want, kw
            assert dst.m_prefix_host_hits == hits0 + 1, (
                "continuation did not serve from the imported span")
    finally:
        ref.stop()
        src.stop()
        dst.stop()


@pytest.mark.multichip
def test_tp2_pallas_kernel_matches_tp1_xla(tiny, multichip):
    """The shard_map'd ragged paged-attention Pallas kernel (interpret mode
    on CPU — the same code that compiles for TPU) under tp=2 must match the
    tp=1 XLA reference walk byte-for-byte."""
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    ref = _mk(tiny, 1, True, paged_kernel="xla")
    tp2 = _mk(tiny, 2, True, paged_kernel="pallas")
    try:
        for kw in (dict(max_new_tokens=8),
                   dict(max_new_tokens=8, temperature=0.8, seed=5)):
            assert _gen_ids(tp2, PROMPT2, **kw) == _gen_ids(ref, PROMPT2, **kw)
        # How each block's window reached the pools is counted (ISSUE 44).
        # One 16-wide head a chip is no DMA slice, so this pair scatters;
        # the pair that writes in place (two 128-wide heads a chip) is
        # tests/test_pool_write.py::test_tp2_engine_writes_in_place_and_matches_tp1
        for eng in (ref, tp2):
            m, block = eng.metrics(), eng.quant_sites.by_program["decode_block"]
            assert m["pool_write_scatter_sites"] == 2 * block["traces"] > 0
            assert m["pool_write_inplace_sites"] == 0
    finally:
        ref.stop()
        tp2.stop()


@pytest.mark.multichip
def test_head_sharded_flash_prefill_matches_dense(multichip):
    """The dense flash prefill kernel under the tp shard_map wrapper
    (interpret mode — the same wrapping prefill_attention applies on TPU)
    must match the unsharded dense reference."""
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from localai_tpu.ops.attention import (
        _head_shard_map,
        causal_prefill_attention,
    )
    from localai_tpu.ops.flash import flash_prefill_attention
    from localai_tpu.parallel.mesh import build_mesh

    rng = np.random.default_rng(0)
    B, S, H, K, D = 2, 128, 4, 2, 16
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, S, K, D)), jnp.float32)
    lengths = jnp.asarray([100, 37], jnp.int32)
    mask = jnp.arange(S)[None, :] < lengths[:, None]
    ref = causal_prefill_attention(q, k, v, mask)
    mesh = build_mesh(MeshPlan(tp=2))
    fn = _head_shard_map(
        lambda qs, ks, vs, ln: flash_prefill_attention(
            qs, ks, vs, ln, block_q=64, block_k=64, interpret=True),
        mesh,
        in_specs=(P(None, None, "tp", None), P(None, None, "tp", None),
                  P(None, None, "tp", None), P(None)),
        out_specs=P(None, None, "tp", None),
    )
    with mesh:
        out = jax.jit(fn)(q, k, v, lengths)
    # Padding rows: flash zeroes them, the dense reference emits garbage —
    # compare valid rows only.
    np.testing.assert_allclose(
        np.asarray(out)[mask], np.asarray(ref)[mask], atol=2e-5, rtol=2e-5)


# --------------------------------------------------------------------- #
# collective_dispatch fault containment (ISSUE 7 satellite)
# --------------------------------------------------------------------- #


def _drain_all(handles, timeout=120.0):
    finals = {}

    def drain(i, h):
        finals[i] = list(h)[-1]

    ts = [threading.Thread(target=drain, args=(i, h))
          for i, h in enumerate(handles)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in ts), "hung caller"
    return finals


@pytest.mark.multichip
def test_collective_dispatch_fault_contained(tiny, multichip):
    """A mid-collective dispatch fault on a sharded engine fails the
    affected requests with terminal error events and the engine keeps
    serving — never a hung caller (fixed-seed tier-1 smoke)."""
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    eng = _mk(tiny, 2, True)
    try:
        with faults.active(faults.FaultSchedule(
                seed=21, rate=1.0, sites=("collective_dispatch",),
                max_faults=1)):
            finals = _drain_all([
                eng.submit(GenRequest(prompt_ids=SHORT, max_new_tokens=6,
                                      ignore_eos=True))
                for _ in range(3)
            ])
        kinds = {ev.kind for ev in finals.values()}
        assert "error" in kinds, finals  # the injected fault surfaced
        # Containment: the engine still serves after the schedule is spent.
        _, ev = eng.generate(SHORT, max_new_tokens=4, ignore_eos=True)
        assert ev.kind == "done"
        assert not eng._pending and not eng.h_active.any()
    finally:
        eng.stop()


@pytest.mark.multichip
def test_collective_fault_loop_death_releases_global_allocator(tiny,
                                                               multichip):
    """Loop death while sharded traffic is in flight (engine_loop +
    collective_dispatch schedule): every caller gets a terminal event and
    _release_all_state leaves the GLOBAL page allocator fully accounted —
    the host-side pool is shared by every shard, so a mid-collective death
    may not strand any pages."""
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    import time

    eng = _mk(tiny, 2, True)
    try:
        # Get traffic genuinely mid-flight (slots active, pages held)
        # BEFORE arming the schedule, so the death lands with state to
        # release.
        handles = [
            eng.submit(GenRequest(prompt_ids=PROMPT2, max_new_tokens=48,
                                  ignore_eos=True))
            for _ in range(2)
        ]
        firsts = [h._q.get(timeout=60.0) for h in handles]
        assert all(ev.kind == "token" for ev in firsts)
        with faults.active(faults.FaultSchedule(
                seed=77, rate=1.0,
                sites=("engine_loop", "collective_dispatch"), max_faults=2)):
            deadline = time.monotonic() + 60.0
            while not eng.is_dead and time.monotonic() < deadline:
                time.sleep(0.005)
            finals = _drain_all(handles)
        assert all(ev.kind in ("done", "error") for ev in finals.values())
        assert eng.is_dead
        # Global allocator quiesced: every page free, no stray refcounts,
        # no slot table left behind.
        P = eng.ecfg.kv_pages
        assert sorted(eng._free_pages) == list(range(P))
        assert not np.asarray(eng._page_refs[:P]).any()
        assert all(not pages for pages in eng._slot_pages)
        assert not eng._prefix_entries and not eng._prefix_host
        assert eng._host_bytes == 0
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Sharded weight loading (engine/weights.sharded_put)
# --------------------------------------------------------------------- #


@pytest.mark.multichip
def test_sharded_put_places_checkpoint_shards(tiny, multichip, tmp_path):
    if multichip < 2:
        pytest.skip("needs >= 2 devices")
    from localai_tpu.engine.weights import (
        load_hf_checkpoint,
        save_hf_checkpoint,
        sharded_put,
    )
    from localai_tpu.parallel.mesh import build_mesh

    cfg, params = tiny
    save_hf_checkpoint(cfg, params, str(tmp_path))
    mesh = build_mesh(MeshPlan(tp=2))
    loaded = load_hf_checkpoint(cfg, str(tmp_path),
                                put=sharded_put(cfg, mesh))
    plain = load_hf_checkpoint(cfg, str(tmp_path))
    flat_s = jax.tree.leaves(loaded)
    flat_p = jax.tree.leaves(plain)
    assert len(flat_s) == len(flat_p)
    for a, b in zip(flat_s, flat_p):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32), np.asarray(b, np.float32))
    # The big projections really are sharded over tp, not replicated.
    wq = loaded["layers"]["wq"]
    assert "tp" in str(wq.sharding.spec)
    assert not wq.sharding.is_fully_replicated
    # Norms replicate.
    assert loaded["final_norm"].sharding.is_fully_replicated
