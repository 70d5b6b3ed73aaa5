"""Budget-covered early release of a slot index (Engine._park, ISSUE 29).

When the decode block that covers a request's token budget is dispatched,
the slot index is handed to the next request at once and the old tenant is
parked until that block comes back. The contract under test: every request
receives exactly the tokens it would have had with a slot to itself and
exactly one terminal event, on every path a parked tenant can take (budget,
stop string, EOS, cancel, deadline, a failed dispatch, loop death,
shutdown); the row account still closes and a budget-ended request loses
less than one block; the page pool stays exactly accounted with parked
tenants in it; and the paths the rule leaves alone (a host-walk grammar, a
speculative engine) never release early.
"""

from __future__ import annotations

import threading
import time

import jax
import numpy as np
import pytest

from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
from localai_tpu.engine.engine import RequestHandle, _Entry, _Slot
from localai_tpu.functions.jsonschema import GrammarConstraint
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.testing import faults

PAGE = 16
BLOCK = 16
# Mixed budgets, none a whole number of blocks past the admission's token.
BUDGETS = (5, 70, 130, 33, 64, 66, 2, 100, 18, 41)
MODES = ("dense", "paged")


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(0))


def _mk(tiny, mode, slots, tokenizer=None, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=slots, max_seq=256, kv_page_size=PAGE,
                    kv_pages=96 if mode == "paged" else 0,
                    pipeline_depth=3, block_sizes=(BLOCK,))
    defaults.update(kw)
    eng = Engine(cfg, params, tokenizer or ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**defaults))
    eng.start()
    return eng


class _Printable(ByteTokenizer):
    """One printable character a token, so a stream's text can be cut by
    token position (the byte tokenizer's ids above 255 decode to nothing)."""

    def decode(self, ids):
        return "".join(chr(33 + i % 90) for i in ids)

    def token_strings(self):
        return [chr(33 + i % 90) for i in range(self.vocab_size)]


def _req(i, budget, **kw):
    kw.setdefault("ignore_eos", True)
    return GenRequest(prompt_ids=[1 + i, 5, 9, 3 + i], max_new_tokens=budget,
                      temperature=0.0, **kw)


def _drain(handles, timeout=120.0):
    """Every event of every handle, each on its own consumer with a bound:
    a handle that never gets its terminal event fails here, not the suite's
    clock."""
    out = [None] * len(handles)

    def run(j, h):
        out[j] = list(h)

    ts = [threading.Thread(target=run, args=(j, h), daemon=True)
          for j, h in enumerate(handles)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=timeout)
    assert not any(t.is_alive() for t in ts), "a consumer never got a terminal"
    return out


def _ids(events):
    return [e.token_id for e in events if e.kind == "token"]


def _terminals(events):
    return [e for e in events if e.kind in ("done", "error")]


def _wait_quiet(eng, timeout=30.0):
    end = time.monotonic() + timeout
    while time.monotonic() < end:
        if (not eng._inflight and not eng._parked and not eng._pending
                and not any(s is not None for s in eng.slots)):
            return
        time.sleep(0.01)
    raise AssertionError("engine never went quiet")


def _pool_accounted(eng):
    """Every page is free or referenced by exactly its holders: slot tables,
    parked tenants, prefix spans (the walk's own check)."""
    if eng._paged:
        from test_paged_kv import _check_pool_invariants

        _check_pool_invariants(eng)


@pytest.fixture(scope="module", params=MODES)
def crowd(request, tiny, monkeypatch_module):
    """BUDGETS through three slots and, for reference, through a slot each:
    the events of both runs, the crowded engine's gauges and journal."""
    monkeypatch_module.setenv("LOCALAI_ALLOC_DEBUG", "1")
    mode = request.param
    solo_eng = _mk(tiny, mode, len(BUDGETS))
    try:
        solo = _drain([solo_eng.submit(_req(i, b))
                       for i, b in enumerate(BUDGETS)])
    finally:
        solo_eng.stop()
    eng = _mk(tiny, mode, 3)
    try:
        got = _drain([eng.submit(_req(i, b)) for i, b in enumerate(BUDGETS)])
        _wait_quiet(eng)
        _pool_accounted(eng)
        return dict(mode=mode, solo=solo, got=got, metrics=eng.metrics(),
                    journal=eng.journal.snapshot(),
                    preemptions=eng.m_kv_preemptions)
    finally:
        eng.stop()


@pytest.fixture(scope="module")
def monkeypatch_module():
    mp = pytest.MonkeyPatch()
    yield mp
    mp.undo()


# (a) same tokens as with a slot each, one terminal, the budget exactly
def test_streams_match_a_slot_each(crowd):
    for i, (a, b) in enumerate(zip(crowd["solo"], crowd["got"])):
        assert _ids(a) == _ids(b), f"request {i} ({BUDGETS[i]} tokens)"
        assert [e.text for e in a] == [e.text for e in b], i


def test_one_done_and_the_whole_budget(crowd):
    for i, events in enumerate(crowd["got"]):
        term = _terminals(events)
        assert len(term) == 1 and events[-1] is term[0], i
        assert term[0].kind == "done" and term[0].finish_reason == "length"
        assert term[0].completion_tokens == BUDGETS[i] == len(_ids(events))


def test_every_budget_ended_request_left_its_slot_early(crowd):
    m = crowd["metrics"]
    assert m["slots_released"] == len(BUDGETS)
    assert m["slots_released_early"] == len(BUDGETS)
    turn = [e for e in crowd["journal"] if e["event"] == "slot_turnover"]
    assert len(turn) == len(BUDGETS)
    assert all(e["a"] == 1.0 and e["b"] == 1.0 for e in turn)
    assert crowd["preemptions"] == 0


# (b) the row account closes; less than a block is lost a request
def test_row_account_closes(crowd):
    m = crowd["metrics"]
    assert m["decode_rows_dispatched"] == (
        m["decode_rows_posted"] + m["decode_rows_overshoot"]
        + m["decode_rows_empty"])
    # The admission's own token is no decode row.
    assert m["decode_rows_posted"] == sum(b - 1 for b in BUDGETS)
    rows = [e for e in crowd["journal"] if e["event"] == "decode_rows"]
    lost = [e for e in crowd["journal"] if e["event"] == "decode_rows_lost"]
    assert sum(e["a"] for e in rows) == m["decode_rows_dispatched"]
    assert sum(e["a"] for e in lost) == m["decode_rows_overshoot"]
    assert sum(e["b"] for e in lost) == m["decode_rows_empty"]


def test_a_request_loses_only_the_rest_of_its_last_block(crowd):
    """With one block size a request is live in ceil((budget - 1) / BLOCK)
    blocks and in no row after them: the loss is exact, and under a block."""
    want = sum(-(b - 1) % BLOCK for b in BUDGETS)
    assert all(-(b - 1) % BLOCK < BLOCK for b in BUDGETS)
    assert crowd["metrics"]["decode_rows_overshoot"] == want


# (c) a parked tenant that ends sooner than its budget
def _reference(tiny, mode, budget, tokenizer=None):
    eng = _mk(tiny, mode, 1, tokenizer=tokenizer)
    try:
        return _drain([eng.submit(_req(0, budget))])[0]
    finally:
        eng.stop()


def _hook_park(eng, after):
    """Run `after(slot)` on the loop thread right after a tenant is parked."""
    park = eng._park

    def hooked(i, last):
        slot = eng.slots[i]
        park(i, last)
        after(slot)

    eng._park = hooked


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("how", ("cancel", "deadline"))
def test_parked_tenant_cancelled_or_past_its_deadline(tiny, mode, how,
                                                      monkeypatch):
    monkeypatch.setenv("LOCALAI_ALLOC_DEBUG", "1")
    eng = _mk(tiny, mode, 2)

    def after(slot):
        if slot.request.max_new_tokens != 40:
            return
        if how == "cancel":
            slot.handle.cancel()
        else:
            slot.handle.deadline = time.monotonic() - 1.0

    _hook_park(eng, after)
    try:
        handles = [eng.submit(_req(0, 40)), eng.submit(_req(1, 70)),
                   eng.submit(_req(2, 30))]
        got = _drain(handles)
        _wait_quiet(eng)
        _pool_accounted(eng)
        m = eng.metrics()
    finally:
        eng.stop()
    term = _terminals(got[0])
    assert len(term) == 1 and term[0].kind == "done"
    assert term[0].finish_reason == "stop"
    assert len(_ids(got[0])) < 40
    for events, budget in ((got[1], 70), (got[2], 30)):
        assert _terminals(events)[0].completion_tokens == budget
    assert m["slots_released_early"] == 3
    if how == "deadline":
        assert m["deadline_expired"] == 1


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("how", ("stop_string", "eos"))
def test_parked_tenant_ends_in_its_last_block(tiny, mode, how, monkeypatch):
    """The request's budget is covered when its last block is dispatched, so
    it is parked; a stop string or an EOS inside that block ends it through
    the ordinary path, with the text cut where it would have been."""
    monkeypatch.setenv("LOCALAI_ALLOC_DEBUG", "1")
    budget = 2 * BLOCK + 9  # the admission's token, two blocks, 8 of a third
    first = 2 * BLOCK + 2  # a token well inside the last block
    cfg = tiny[0]
    tok = _Printable(cfg.vocab_size)
    ref = _reference(tiny, mode, budget, tokenizer=tok)
    ids = _ids(ref)
    text = "".join(e.text for e in ref)
    assert len(text) == len(ids) == budget
    if how == "stop_string":
        k = next(k for k in range(first, budget - 1)
                 if text.count(text[k:k + 2]) == 1)
        kw = dict(stop=[text[k:k + 2]])
    else:
        k = next(k for k in range(first, budget) if ids[k] not in ids[:k])
        tok.eos_ids = (ids[k],)
        kw = dict(ignore_eos=False)
    eng = _mk(tiny, mode, 1, tokenizer=tok)
    try:
        handles = [eng.submit(_req(0, budget, **kw)),
                   eng.submit(_req(0, 20, **kw))]
        got = _drain(handles)
        _wait_quiet(eng)
        _pool_accounted(eng)
        turn = {e["rid"]: e["a"] for e in eng.journal.snapshot()
                if e["event"] == "slot_turnover"}
    finally:
        eng.stop()
    term = _terminals(got[0])
    assert len(term) == 1 and term[0].finish_reason == "stop"
    assert "".join(e.text for e in got[0]) == text[:k]
    if how == "eos":
        assert _ids(got[0]) == ids[:k]
        assert term[0].completion_tokens == k
    assert turn[handles[0].rid] == 1.0  # it was parked when it ended
    assert _ids(got[1]) == ids[:20] and got[1][-1].kind == "done"
    assert len(_terminals(got[1])) == 1


@pytest.mark.parametrize("ended", ("budget", "stop_string"))
def test_chunked_successor_gets_none_of_the_old_tenants_tokens(tiny, ended):
    """A chunked admission claims the index chunks before the program that
    activates it, while blocks dispatched for the old tenant are still in
    flight: their rows belong to the old tenancy's generation, whether the
    tenant was parked (budget) or released when it ended (stop string)."""
    cfg = tiny[0]
    kw = dict(max_seq=512, prefill_chunk=32, prefix_cache_entries=0)
    old = dict(prompt_ids=[1, 5, 9, 3], max_new_tokens=120, temperature=0.0,
               ignore_eos=True)
    new = dict(prompt_ids=[x % 200 + 1 for x in range(7, 107)],
               max_new_tokens=20, temperature=0.0, ignore_eos=True)
    want = []
    for r in (old, new):
        eng = _mk(tiny, "paged", 1, tokenizer=_Printable(cfg.vocab_size), **kw)
        try:
            want.append("".join(
                e.text for e in _drain([eng.submit(GenRequest(**r))])[0]))
        finally:
            eng.stop()
    cut = len(want[0])
    if ended == "stop_string":
        cut = next(k for k in range(20, 60)
                   if want[0].count(want[0][k:k + 2]) == 1)
        old["stop"] = [want[0][cut:cut + 2]]
    eng = _mk(tiny, "paged", 1, tokenizer=_Printable(cfg.vocab_size), **kw)
    try:
        got = _drain([eng.submit(GenRequest(**old)),
                      eng.submit(GenRequest(**new))])
        m = eng.metrics()
    finally:
        eng.stop()
    assert "".join(e.text for e in got[0]) == want[0][:cut]
    assert "".join(e.text for e in got[1]) == want[1]
    assert [len(_terminals(g)) for g in got] == [1, 1]
    assert m["chunked_admissions"] == 1
    assert m["slots_released_early"] == (2 if ended == "budget" else 1)


def test_host_walk_grammar_slot_is_never_released_early(tiny, monkeypatch):
    monkeypatch.setenv("LOCALAI_GRAMMAR_DFA", "0")
    schema = {"type": "object", "properties": {"a": {"type": "integer"}},
              "required": ["a"]}
    eng = _mk(tiny, "paged", 2, block_sizes=(BLOCK, 4, 1))
    try:
        h = eng.submit(GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=12,
                                  temperature=0.0,
                                  grammar=GrammarConstraint(schema)))
        events = _drain([h])[0]
        m = eng.metrics()
    finally:
        eng.stop()
    assert len(_terminals(events)) == 1 and events[-1].kind == "done"
    assert m["slots_released"] == 1 and m["slots_released_early"] == 0


@pytest.mark.parametrize("mode", MODES)
def test_speculative_engine_never_releases_early(tiny, mode):
    eng = _mk(tiny, mode, 2, spec_mode="prompt_lookup",
              block_sizes=(BLOCK, 4, 1))
    try:
        got = _drain([eng.submit(_req(i, b)) for i, b in enumerate((20, 37, 9))])
        m = eng.metrics()
    finally:
        eng.stop()
    for events, budget in zip(got, (20, 37, 9)):
        term = _terminals(events)
        assert len(term) == 1 and term[0].completion_tokens == budget
    assert m["slots_released"] == 3 and m["slots_released_early"] == 0


# (d) failure containment and shutdown with a tenant parked
@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("how", ("fail_block", "loop_death", "shutdown"))
def test_parked_tenant_gets_its_terminal_when_things_break(tiny, mode, how,
                                                           monkeypatch):
    monkeypatch.setenv("LOCALAI_ALLOC_DEBUG", "1")
    eng = _mk(tiny, mode, 2)
    parked = threading.Event()
    sched = faults.FaultSchedule(
        seed=1, rate=1.0, max_faults=1,
        sites=["device_dispatch" if how == "fail_block" else "engine_loop"])

    def after(slot):
        if parked.is_set():
            return
        parked.set()
        if how == "shutdown":
            eng._shutdown.set()  # the loop leaves with the tenant parked
        else:
            sched.threads = frozenset({threading.get_ident()})
            faults.install(sched)

    _hook_park(eng, after)
    try:
        handles = [eng.submit(_req(0, 24)), eng.submit(_req(1, 90))]
        assert parked.wait(timeout=60)
        if how == "shutdown":
            eng.stop()
        got = _drain(handles, timeout=60)
    finally:
        faults.uninstall()
        eng.stop()
    for events in got:
        assert len(_terminals(events)) >= 1
        assert events[-1].kind in ("done", "error")
    first = _terminals(got[0])[0]
    if how == "fail_block":
        # Its last block was in flight and owed nothing to the failed
        # dispatch: the parked tenant ends as if nothing had happened.
        assert first.kind == "done" and first.completion_tokens == 24
        assert _terminals(got[1])[0].kind == "error"
    elif how == "loop_death":
        assert first.kind == "error" and "engine loop died" in first.error
    else:
        assert first.kind == "done"
    _pool_accounted(eng)
    if how != "shutdown":  # stop() posts terminals and leaves state as it is
        assert not eng._parked


# (e) the pool with parked tenants in it
def _live(i):
    return _Slot(request=GenRequest(prompt_ids=[1 + i], max_new_tokens=1),
                 handle=RequestHandle(), prompt_len=1, scheduled=1)


@pytest.mark.parametrize("l1_span", (0, 2), ids=("flat", "hier"))
def test_allocator_walk_with_parked_tenants(tiny, l1_span, monkeypatch):
    """The randomized page-refcount walk of tests/test_paged_kv.py with
    parking in it: admit-style allocs (with shared prefix pages), growth,
    park, a successor seated on the vacated index, the parked tenant's
    finish-time span pin and release, plain finishes and eviction, the whole
    pool accounted after every step. Under
    LOCALAI_ALLOC_DEBUG a leak, a double release or a claim over a held
    table raises."""
    monkeypatch.setenv("LOCALAI_ALLOC_DEBUG", "1")
    rng = np.random.default_rng(29 + l1_span)
    eng = _mk(tiny, "paged", 4, kv_pages=24, kv_l1_span=l1_span,
              max_seq=512, kv_swap_bytes=64 << 20)
    eng.stop()  # the walk drives the allocator itself, as the loop would
    B = eng.ecfg.max_slots
    last = _Entry(kind="block", toks=None, tk=None)
    serial = 0
    parks = handed = 0
    for _step in range(240):
        op = int(rng.integers(0, 6))
        free = [i for i in range(B) if eng.slots[i] is None]
        live = [i for i in range(B) if eng.slots[i] is not None]
        if op == 0 and free:  # admission, with or without a prefix hit
            i = int(rng.choice(free))
            shared = stps = None
            if eng._prefix_entries and rng.random() < 0.5:
                e = eng._prefix_entries[0]
                shared = e["pages"][: int(rng.integers(1, len(e["pages"]) + 1))]
                stps = e.get("tps")
            before = {p for s in eng._parked.values() for p in s.parked.pages}
            if eng._pages_alloc(i, int(rng.integers(1, 4)), shared=shared,
                                shared_tps=stps) is not None:
                eng.slots[i] = _live(i)
                eng.h_active[i] = True
                eng._slot_gen[i] += 1
                fresh = set(eng._slot_pages[i]) - set(shared or ())
                assert not fresh & before, "a parked tenant's page handed on"
                handed += bool(before)
        elif op == 1 and live:  # decode growth
            i = int(rng.choice(live))
            eng._pages_grow_slot(
                i, len(eng._slot_pages[i]) + int(rng.integers(1, 3)))
        elif op == 2 and live:  # budget covered: the index is handed on
            i = int(rng.choice(live))
            eng._park(i, last)
            assert eng.slots[i] is None and not eng._slot_pages[i]
            parks += 1
        elif op == 3 and eng._parked:  # its last block came back
            key = list(eng._parked)[int(rng.integers(0, len(eng._parked)))]
            slot = eng._parked[key]
            n = len(slot.parked.pages)
            if n and rng.random() < 0.7 and len(eng._prefix_entries) < 6:
                k = int(rng.integers(1, n + 1))
                serial += 1
                eng._prefix_save(key[0], np.full((k * PAGE,), serial, np.int32),
                                 k * PAGE, parked=slot.parked)
            eng._release_parked(slot)
            eng._release_parked(slot)  # a second release is a no-op
        elif op == 4 and live:  # a request that ended sooner: plain finish
            i = int(rng.choice(live))
            eng._release(i)
        elif op == 5:  # pressure eviction (spills to the host tier)
            eng._prefix_evict_for_pages(
                len(eng._free_pages) + int(rng.integers(1, 4)))
        _pool_accounted(eng)
    assert parks > 10 and handed > 3
    for slot in list(eng._parked.values()):
        eng._release_parked(slot)
    for i in range(B):
        if eng.slots[i] is not None:
            eng._release(i)
    while eng._prefix_entries:
        eng._prefix_drop(eng._prefix_entries.pop())
    _pool_accounted(eng)
    assert len(eng._free_pages) == eng.ecfg.kv_pages


def test_small_pool_makes_the_successor_wait_and_preempts_nobody(tiny,
                                                                 monkeypatch):
    """One slot, a pool that cannot hold the parked tenant's pages and the
    next request's at once: the successor is seated when the old tenant's
    last block has come back (today's timing), nobody is preempted, both
    get their tokens."""
    monkeypatch.setenv("LOCALAI_ALLOC_DEBUG", "1")
    budgets = (60, 40)
    prompts = ([7, 5, 9, 3], list(range(1, 41)))
    want = []
    for ids, b in zip(prompts, budgets):
        ref = _mk(tiny, "paged", 1)
        try:
            want.append(_ids(_drain([ref.submit(GenRequest(
                prompt_ids=ids, max_new_tokens=b, temperature=0.0,
                ignore_eos=True))])[0]))
        finally:
            ref.stop()
    eng = _mk(tiny, "paged", 1, kv_pages=6, prefix_cache_entries=0)
    try:
        got = _drain([eng.submit(GenRequest(
            prompt_ids=ids, max_new_tokens=b, temperature=0.0,
            ignore_eos=True)) for ids, b in zip(prompts, budgets)])
        _wait_quiet(eng)
        _pool_accounted(eng)
        events = [(e["event"], e["rid"]) for e in eng.journal.snapshot()
                  if e["event"] in ("admitted", "terminal")]
        m = eng.metrics()
    finally:
        eng.stop()
    assert [_ids(g) for g in got] == want
    assert m["kv_preemptions"] == 0
    assert m["slots_released_early"] == 2
    # admitted A, terminal A, admitted B, terminal B: B waited for A's pages
    assert [ev for ev, _rid in events] == [
        "admitted", "terminal", "admitted", "terminal"]
