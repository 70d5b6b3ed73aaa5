"""Pipelined engine-loop runtime (ISSUE 17, docs/ENGINE_RUNTIME.md).

The contract under test: the loop decides WHEN host work runs and HOW MUCH
crosses the host→device link, never WHAT the programs compute. Every sweep
below submits a set of requests together, so that plans are staged ahead,
control state is committed by difference and admissions land between
blocks, and requires of each the bytes it gets served alone on the same
engine — dense and paged, greedy and seeded, chunked prefill, speculative
rounds, grammar-DFA. On top of that: the steady-state transfer probe (a decode
block whose control state didn't change uploads NOTHING), the budgeted
housekeeping sidecar, the admit-coalesce hold regression (hold must only
suppress dispatch, not starve chunk progress), and the `control_commit`
fault seam.
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine import runtime
from localai_tpu.functions.jsonschema import GrammarConstraint
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.observe import journal as jmod
from localai_tpu.testing import faults

PAGE = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    params = init_params(cfg, jax.random.key(0))
    return cfg, params


def _mk(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=4, max_seq=256, min_prefill_bucket=16,
                    spec_mode="off")
    defaults.update(kw)
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**defaults))
    eng.start()
    return eng


def _run_set(eng, reqs):
    """Submit all requests up front (concurrent admission) and collect
    (text, kind, finish_reason) per request, in submit order."""
    handles = [eng.submit(GenRequest(**r)) for r in reqs]
    return [h.result() for h in handles]


def _together_and_alone(eng, reqs):
    """The set submitted together, then each request with the engine to
    itself; every request must read the same either way."""
    together = _run_set(eng, reqs)
    alone = [_run_set(eng, [r])[0] for r in reqs]
    for i, ((tt, et), (ta, ea)) in enumerate(zip(together, alone)):
        assert et.kind == ea.kind == "done", (i, et, ea)
        assert tt == ta, f"request {i}: together != alone\n{tt!r}\n{ta!r}"
        assert et.finish_reason == ea.finish_reason, i
    return together


def _sweep(tiny, reqs, **cfg):
    eng = _mk(tiny, **cfg)
    try:
        _together_and_alone(eng, reqs)
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Phase-vector schema is pinned in BOTH modules (journal can't import the
# engine): they must never drift.
# --------------------------------------------------------------------- #


def test_loop_phases_pinned():
    assert runtime.LOOP_PHASES == jmod.LOOP_PHASES
    assert len(runtime.LOOP_PHASES) == 10
    assert runtime.LOOP_PHASES[-1] == "wait"


# --------------------------------------------------------------------- #
# Byte-identical sweeps: served together vs served alone
# --------------------------------------------------------------------- #


def test_served_together_matches_served_alone_dense(tiny):
    reqs = (
        # Greedy, varied prompt lengths (different prefill buckets).
        [dict(prompt_ids=list(range(65, 65 + n)), max_new_tokens=24,
              ignore_eos=True) for n in (3, 17, 40)]
        # Seeded sampling: per-slot rng chains must be unaffected by
        # admission timing / prepare-ahead reordering / who shares a block.
        + [dict(prompt_ids=[70, 71, 72], max_new_tokens=24,
                temperature=0.9, seed=1000 + i, ignore_eos=True)
           for i in range(3)]
    )
    _sweep(tiny, reqs)


def test_served_together_matches_served_alone_paged_chunked(tiny):
    # Paged KV + chunked prefill: the long prompt takes the multi-chunk
    # admission path while the short one decodes; page-table growth
    # happens at stage time, ahead of the dispatch that needs it.
    reqs = [
        dict(prompt_ids=[(65 + i) % 256 for i in range(150)],
             max_new_tokens=20, ignore_eos=True),
        dict(prompt_ids=[66, 67], max_new_tokens=20, temperature=0.8,
             seed=7, ignore_eos=True),
    ]
    _sweep(tiny, reqs, kv_pages=24, kv_page_size=PAGE,
           max_seq=512, prefill_chunk=64)


@pytest.mark.slow
def test_served_together_matches_served_alone_spec(tiny):
    # Speculative rounds never stage (the spec planner commits probe/EWMA
    # state when it runs) but the commit/ptable path still carries them —
    # outputs must not move from one serving to the next.
    base = [65, 66, 67, 68] * 6
    reqs = [dict(prompt_ids=base, max_new_tokens=24, ignore_eos=True)]
    _sweep(tiny, reqs, spec_mode="prompt_lookup", max_slots=2)


def test_served_together_matches_served_alone_grammar_dfa(tiny):
    schema = {"type": "object",
              "properties": {"a": {"type": "integer"},
                             "b": {"type": "boolean"}},
              "required": ["a", "b"]}
    reqs = [dict(prompt_ids=[10, 20, 30], max_new_tokens=120,
                 grammar=GrammarConstraint(schema))]
    eng = _mk(tiny, max_slots=2)
    try:
        # Sync table build: otherwise early tokens ride the host-walk
        # fallback or wait on the async compile, and the outputs depend on
        # admission TIMING rather than on the runtime under test.
        eng.prewarm_grammar(schema)
        (text, _ev), = _together_and_alone(eng, reqs)
    finally:
        eng.stop()
    json.loads(text)  # still valid under the schema's DFA


# --------------------------------------------------------------------- #
# One H2D control commit per block, ZERO in steady state
# --------------------------------------------------------------------- #


def test_steady_state_decode_skips_control_upload(tiny):
    # Small block size => many blocks per generation => a long steady-state
    # run where the pack/override/ptable bytes never change between blocks.
    eng = _mk(tiny, max_slots=2, block_sizes=(4, 1))
    try:
        _txt, ev = eng.generate([65, 66, 67], max_new_tokens=48,
                                ignore_eos=True)
        assert ev.kind == "done"
        c = eng._ctrl
        blocks = eng.m_loop_blocks
        assert blocks >= 10, blocks
        # Every block went through the stager...
        assert c.commits >= blocks
        # ...but only the first (and at most a couple of edge blocks around
        # admission) actually uploaded; steady-state blocks skipped.
        assert c.skips >= blocks - 4, (c.commits, c.skips, c.transfers())
        assert c.transfers() <= 4, (c.uploads, c.row_uploads)
        m = eng.metrics()
        assert m["ctrl_commit_skips"] == c.skips
        assert m["loop_blocks"] == blocks
        assert m["loop_host_ms_total"] / m["loop_blocks"] > 0.0
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Budgeted housekeeping sidecar
# --------------------------------------------------------------------- #


def test_housekeeping_budget_skips_optional_work(tiny, monkeypatch):
    eng = _mk(tiny, max_slots=2, housekeeping_budget_ms=2.0)
    try:
        calls = {"purge": 0, "deadline": 0, "saves": 0, "spill": 0}
        monkeypatch.setattr(eng, "_enforce_deadlines",
                            lambda: calls.__setitem__(
                                "deadline", calls["deadline"] + 1))
        monkeypatch.setattr(eng, "_flush_deferred_saves",
                            lambda slot_idx=None: calls.__setitem__(
                                "saves", calls["saves"] + 1))
        monkeypatch.setattr(eng, "_spill_cold_pages",
                            lambda: calls.__setitem__(
                                "spill", calls["spill"] + 1))

        def slow_purge():
            calls["purge"] += 1
            time.sleep(0.01)  # 10ms > 2ms budget

        monkeypatch.setattr(eng, "_purge_pending", slow_purge)
        eng._housekeeping(time.monotonic())
        # Lifecycle sweeps always ran; optional work was budgeted out.
        assert calls["purge"] == 1 and calls["deadline"] == 1
        assert calls["saves"] == 0 and calls["spill"] == 0

        monkeypatch.setattr(eng, "_purge_pending",
                            lambda: calls.__setitem__(
                                "purge", calls["purge"] + 1))
        eng._housekeeping(time.monotonic())
        assert calls["saves"] == 1 and calls["spill"] == 1
    finally:
        eng.stop()


def test_deadline_index_wakes_housekeeping(tiny):
    eng = _mk(tiny, max_slots=2)
    try:
        now = time.monotonic()
        # Nothing due: the heap is empty and the interval just reset.
        eng._hk_last = now
        assert not eng._hk_due(now)
        # A pushed deadline in the past makes the very next check due,
        # regardless of interval — expiry latency is heap-driven.
        eng._deadlines.push(now - 1.0)
        assert eng._hk_due(now)
        eng._housekeeping(now)  # consumes the expired entry
        eng._hk_last = time.monotonic()
        assert not eng._hk_due(time.monotonic())
    finally:
        eng.stop()


def test_deferred_prefix_save_flushes_on_finish(tiny):
    # Admission parks the span save on the sidecar; by the time the
    # request finishes, the span (or its finish-time superset) must be
    # queryable as if it had been saved at admission.
    prompt = [65 + (i % 20) for i in range(40)]
    eng = _mk(tiny, prefix_cache_entries=4, prefix_cache_min=16,
              prefix_admit_async_compile=False)
    try:
        _t, ev = eng.generate(list(prompt), max_new_tokens=4,
                              ignore_eos=True)
        assert ev.kind == "done"
        # Same prompt again: it must hit the prefix cache.
        _t, ev = eng.generate(list(prompt), max_new_tokens=4,
                              ignore_eos=True)
        assert ev.kind == "done"
        assert eng.m_prefix_hits >= 1
        assert not eng._deferred_saves  # nothing left parked
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Admit-coalesce hold: suppresses DISPATCH only (regression — the old
# loop `continue`d and starved chunk progress for the whole window)
# --------------------------------------------------------------------- #


def test_coalesce_hold_does_not_starve_chunked_prefill(tiny):
    window_ms = 2000.0
    eng = _mk(tiny, max_slots=3, max_seq=512, prefill_chunk=64,
              kv_pages=24, kv_page_size=PAGE,
              admit_coalesce_ms=window_ms)
    try:
        # Warm the chunk-mid/final and decode programs: the measured
        # window must show LOOP scheduling, not first-use XLA compiles.
        eng.generate([(65 + i) % 256 for i in range(150)], max_new_tokens=2,
                     ignore_eos=True)
        eng.generate([65, 66], max_new_tokens=4, ignore_eos=True)
        # A decodes throughout, keeping the engine "dispatchable" so the
        # hold (free slots + fresh admission) actually engages.
        ha = eng.submit(GenRequest(prompt_ids=[65, 66], max_new_tokens=512,
                                   ignore_eos=True))
        deadline = time.monotonic() + 30.0
        while not eng.h_active.any() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert eng.h_active.any()
        # B needs multi-chunk prefill; its admission re-arms the hold
        # window. Chunk progress must ride INSIDE the window.
        t0 = time.monotonic()
        # Different bytes from the warmup prompt: a prefix-cache hit would
        # shortcut the chunked admission under test.
        hb = eng.submit(GenRequest(
            prompt_ids=[(66 + i) % 256 for i in range(150)],
            max_new_tokens=2, ignore_eos=True))
        first_chunk_t = None
        deadline = time.monotonic() + 30.0
        while first_chunk_t is None and time.monotonic() < deadline:
            for rec in eng._journal.snapshot():
                if rec["event"] == "chunk" and rec["t"] >= t0:
                    first_chunk_t = rec["t"]
                    break
            time.sleep(0.01)
        assert first_chunk_t is not None, "chunked prefill never advanced"
        assert (first_chunk_t - t0) * 1000.0 < 0.75 * window_ms, (
            "chunk progress was starved for the coalesce-hold window "
            f"({(first_chunk_t - t0) * 1000.0:.0f}ms >= {window_ms}ms)")
        ha.cancel()
        hb.cancel()
        ha.result()
        hb.result()
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# control_commit fault seam
# --------------------------------------------------------------------- #


def test_control_commit_fault_contained(tiny):
    eng = _mk(tiny, max_slots=2)
    try:
        with faults.active(faults.FaultSchedule(
                seed=11, rate=1.0, sites=("control_commit",),
                max_faults=1)):
            with pytest.raises(RuntimeError, match="control_commit"):
                eng.generate([65, 66], max_new_tokens=8, ignore_eos=True)
        # Fires before any device mutation or scheduled advance: the next
        # un-faulted request must be clean.
        _t, ev = eng.generate([65, 66], max_new_tokens=8, ignore_eos=True)
        assert ev.kind == "done"
        events = {e["event"] for e in eng._journal.snapshot()}
        assert "fault_control_commit" in events
    finally:
        eng.stop()


def test_fault_site_and_journal_event_registered():
    assert "control_commit" in faults.SITES
    assert "fault_control_commit" in jmod.FAULT_EVENTS


# --------------------------------------------------------------------- #
# loop_iter phase attribution
# --------------------------------------------------------------------- #


def test_loop_iter_carries_phase_vector(tiny):
    eng = _mk(tiny, max_slots=2)
    try:
        _t, ev = eng.generate([65, 66, 67], max_new_tokens=16,
                              ignore_eos=True)
        assert ev.kind == "done"
        iters = [r for r in eng._journal.snapshot()
                 if r["event"] == "loop_iter"]
        assert iters, "no loop_iter windows journaled"
        with_phases = [r for r in iters if "phases" in r]
        assert with_phases, "loop_iter windows lost their phase vectors"
        # Zero-valued phases are elided from the snapshot; whatever is
        # present must come from the pinned schema and be positive.
        ph = with_phases[-1]["phases"]
        assert ph and set(ph) <= set(jmod.LOOP_PHASES)
        assert all(v > 0.0 for v in ph.values())
        # Host-side accounting excludes the wait phase by contract.
        m = eng.metrics()
        assert m["loop_host_ms_total"] >= 0.0
    finally:
        eng.stop()
