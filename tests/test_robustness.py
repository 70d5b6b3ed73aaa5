"""Crash-only request-lifecycle robustness (ISSUE 4, docs/ROBUSTNESS.md):

- supervision: loop death → clean engine `dead` state with pool/host tier
  fully released → manager evicts + transparently reloads on the next
  request → bounded restart budget → quarantine with a typed 503-style error;
- bounded admission: QueueFullError at submit, queue timeouts, per-request
  deadlines (pending AND active), cancel-while-pending terminal events;
- deterministic fault injection (localai_tpu/testing/faults): a fixed-seed
  smoke runs in tier-1; the wide seeded sweep (ISSUE 4 acceptance: hundreds
  of schedules, zero hung callers, pool+host tier accounted at quiesce) is
  marked slow.

The reference gets all of this from its process model (watchdog.go kills a
wedged backend; the OS reclaims its memory; the next request respawns it) —
an in-process engine has to earn each property explicitly, and each one here
is pinned by a test.
"""

import os
import threading
import time

import jax
import numpy as np
import pytest
import yaml

from localai_tpu.config import ApplicationConfig
from localai_tpu.engine import (
    ByteTokenizer,
    Engine,
    EngineConfig,
    GenRequest,
    QueueFullError,
)
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.server import ModelManager
from localai_tpu.server.manager import ModelQuarantinedError
from localai_tpu.testing import faults

PAGE = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(0))


def _mk_engine(tiny, **kw):
    cfg, params = tiny
    defaults = dict(max_slots=2, max_seq=128, min_prefill_bucket=16)
    defaults.update(kw)
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(**defaults))
    eng.start()
    return eng


def _drain(handle):
    evs = list(handle)
    assert evs, "empty stream"
    assert evs[-1].kind in ("done", "error"), evs
    return evs


def _join_all(threads, timeout=120.0):
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    alive = [t.name for t in threads if t.is_alive()]
    assert not alive, f"hung request threads: {alive}"


def _assert_pool_accounted(eng):
    """ISSUE 4 acceptance: page pool + host tier fully accounted. Valid on
    a quiesced OR dead engine (a dead one released everything)."""
    if not eng._paged:
        assert eng._host_bytes == sum(
            e.get("bytes", 0) for e in eng._prefix_host
        )
        return
    P = eng.ecfg.kv_pages
    refs = np.zeros(P, np.int64)
    for pages in eng._slot_pages:
        for p in pages:
            refs[p] += 1
    for e in eng._prefix_entries:
        for p in e.get("pages", []):
            refs[p] += 1
    assert (refs == np.asarray(eng._page_refs[:P])).all(), (
        "refcount drift", refs.tolist(), eng._page_refs[:P].tolist())
    free = eng._free_pages
    assert len(set(free)) == len(free), f"duplicate free pages: {free}"
    assert all(refs[p] == 0 for p in free), "free page still referenced"
    covered = set(free) | {p for p in range(P) if refs[p] > 0}
    assert covered == set(range(P)), f"leaked pages: {set(range(P)) - covered}"
    assert eng._host_bytes == sum(
        e.get("bytes", 0) for e in eng._prefix_host
    ), "host-tier byte accounting drifted"


def _quiesce(eng, timeout=60.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if eng.is_dead:
            return
        with eng._pending_lock:
            idle = not eng._pending
        if (idle and not eng._inflight and not eng.h_active.any()
                and not eng._chunkings):
            return
        time.sleep(0.05)
    raise AssertionError("engine did not quiesce")


# --------------------------------------------------------------------- #
# Bounded admission + deadlines + cancellation
# --------------------------------------------------------------------- #


def test_queue_full_sheds_with_retry_after(tiny):
    eng = _mk_engine(tiny, max_slots=1, max_pending=2)
    try:
        blocker = eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                        max_new_tokens=10_000,
                                        ignore_eos=True))
        deadline = time.monotonic() + 30
        while not eng.h_active.any() and time.monotonic() < deadline:
            time.sleep(0.01)  # wait until the blocker holds the only slot
        held = [blocker]
        held += [eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                       max_new_tokens=10_000,
                                       ignore_eos=True))
                 for _ in range(2)]
        shed = 0
        for _ in range(4):
            try:
                held.append(eng.submit(GenRequest(prompt_ids=[7, 7],
                                                  max_new_tokens=4)))
            except QueueFullError as e:
                shed += 1
                assert e.retry_after_s >= 1.0
                assert e.limit == 2
        assert shed >= 1, "bounded queue never shed"
        assert eng.metrics()["queue_shed"] >= shed
        for h in held:
            h.cancel()
        for h in held:
            _drain(h)
    finally:
        eng.stop()


def test_queue_timeout_expires_pending(tiny):
    eng = _mk_engine(tiny, max_slots=1, queue_timeout_s=0.3)
    try:
        blocker = eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                        max_new_tokens=10_000,
                                        ignore_eos=True))
        time.sleep(0.1)
        victim = eng.submit(GenRequest(prompt_ids=[5, 5], max_new_tokens=4))
        evs = _drain(victim)
        assert evs[-1].kind == "error"
        assert "queue_timeout" in evs[-1].error or "timed out" in evs[-1].error
        assert eng.metrics()["queue_timeouts"] >= 1
        blocker.cancel()
        _drain(blocker)
    finally:
        eng.stop()


def test_deadline_expires_pending_request(tiny):
    eng = _mk_engine(tiny, max_slots=1)
    try:
        blocker = eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                        max_new_tokens=10_000,
                                        ignore_eos=True))
        time.sleep(0.1)
        victim = eng.submit(GenRequest(prompt_ids=[5, 5], max_new_tokens=4,
                                       deadline_s=0.3))
        evs = _drain(victim)
        assert evs[-1].kind == "error"
        assert "deadline" in evs[-1].error
        assert eng.metrics()["deadline_expired"] >= 1
        blocker.cancel()
        _drain(blocker)
    finally:
        eng.stop()


def test_deadline_cancels_active_slot(tiny):
    """An ACTIVE slot past its deadline is cancelled: the stream terminates
    (finish_reason stop, fewer tokens than requested) and the slot frees."""
    eng = _mk_engine(tiny, max_slots=2)
    try:
        h = eng.submit(GenRequest(prompt_ids=[1, 2, 3], max_new_tokens=50_000,
                                  ignore_eos=True, deadline_s=0.5))
        evs = _drain(h)
        final = evs[-1]
        assert final.kind == "done" and final.finish_reason == "stop"
        assert final.completion_tokens < 50_000
        # The slot must actually release so the engine serves new traffic.
        _, ev = eng.generate([9, 9], max_new_tokens=2, ignore_eos=True)
        assert ev.kind == "done"
    finally:
        eng.stop()


def test_engine_default_deadline_applies(tiny):
    """EngineConfig.deadline_s (YAML / LOCALAI_DEADLINE tier) covers
    requests that carry no per-request deadline."""
    eng = _mk_engine(tiny, max_slots=1, deadline_s=0.4)
    try:
        blocker = eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                        max_new_tokens=10_000,
                                        ignore_eos=True))
        time.sleep(0.1)
        victim = eng.submit(GenRequest(prompt_ids=[5, 5], max_new_tokens=4))
        evs = _drain(victim)
        assert evs[-1].kind in ("error", "done")
        # blocker itself also carries the default deadline → terminates too
        evs_b = _drain(blocker)
        assert evs_b[-1].kind == "done"
        assert evs_b[-1].finish_reason == "stop"
    finally:
        eng.stop()


def test_cancel_while_pending_posts_terminal_event(tiny):
    """Regression (ISSUE 4 satellite): cancelling a PENDING request on a
    saturated engine must unblock its consumer promptly — previously the
    entry sat in _pending (admission only purges the head when a slot is
    free) and result() hung until the blocker finished."""
    eng = _mk_engine(tiny, max_slots=1)
    try:
        blocker = eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                        max_new_tokens=10_000,
                                        ignore_eos=True))
        time.sleep(0.1)
        victim = eng.submit(GenRequest(prompt_ids=[5, 5], max_new_tokens=4))
        got = []

        def consume():
            got.append(_drain(victim))

        t = threading.Thread(target=consume, name="victim-consumer")
        t.start()
        time.sleep(0.05)
        victim.cancel()
        t.join(timeout=10)  # blocker still holds its slot the whole time
        assert not t.is_alive(), (
            "cancelled pending request left its consumer blocked"
        )
        assert got and got[0][-1].kind == "done"
        blocker.cancel()
        _drain(blocker)
    finally:
        eng.stop()


def test_cancel_all_terminates_pending_and_active(tiny):
    eng = _mk_engine(tiny, max_slots=1)
    try:
        handles = [eng.submit(GenRequest(prompt_ids=[1, 2, 3],
                                         max_new_tokens=10_000,
                                         ignore_eos=True))
                   for _ in range(3)]
        time.sleep(0.1)
        n = eng.cancel_all()
        # A request can sit in the admission gap (popped from pending, not
        # yet in a slot) and be missed — the watchdog calls cancel_all
        # repeatedly, so a second sweep is the contract here too.
        assert n >= 2
        time.sleep(0.2)
        eng.cancel_all()
        for h in handles:
            evs = _drain(h)
            assert evs[-1].kind in ("done", "error")
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Supervision: loop death, restart budget, quarantine
# --------------------------------------------------------------------- #


def _kill_engine(eng, timeout=30.0):
    """Deterministically kill the engine loop via the injected-fault site
    and wait for the death to be fully processed."""
    # threads= scopes the injection to THIS engine's loop: any other live
    # engine in the process would otherwise race for the single fault.
    with faults.active(faults.FaultSchedule(
            seed=0, rate=1.0, sites=("engine_loop",), max_faults=1,
            threads={eng._thread.ident})):
        eng._wake.set()
        deadline = time.monotonic() + timeout
        while not eng.is_dead and time.monotonic() < deadline:
            time.sleep(0.01)
    assert eng.is_dead, "injected engine_loop fault did not kill the loop"
    t = eng._thread
    if t is not None:
        t.join(timeout=timeout)


def test_loop_death_releases_pool_and_host_tier(tiny):
    """_loop_guard's crash-only teardown: every live/pending caller gets a
    terminal event AND the paged pool + host tier come back fully
    accounted (the manager scrapes a dead engine before evicting it)."""
    eng = _mk_engine(tiny, max_slots=2, max_seq=256, kv_pages=10,
                     kv_page_size=PAGE)
    try:
        handles = [eng.submit(GenRequest(prompt_ids=list(range(1, 30)),
                                         max_new_tokens=10_000,
                                         ignore_eos=True))
                   for _ in range(3)]
        time.sleep(0.2)  # let some admit and decode
        _kill_engine(eng)
        for h in handles:
            evs = _drain(h)
            assert evs[-1].kind == "error"
            assert "engine loop died" in evs[-1].error
        assert len(eng._free_pages) == eng.ecfg.kv_pages
        assert eng._host_bytes == 0
        assert all(not p for p in eng._slot_pages)
        _assert_pool_accounted(eng)
        assert eng.metrics()["loop_dead"] == 1.0
        # A dead engine fails new submits with an error event, immediately.
        evs = _drain(eng.submit(GenRequest(prompt_ids=[1], max_new_tokens=2)))
        assert evs[-1].kind == "error"
    finally:
        eng.stop()


def _mk_manager(tmp_path, **app_kw):
    d = tmp_path / "models"
    d.mkdir(exist_ok=True)
    (d / "m.yaml").write_text(yaml.safe_dump({
        "name": "m", "model": "tiny", "context_size": 64,
        "max_slots": 2, "max_tokens": 4,
    }))
    return ModelManager(ApplicationConfig(models_dir=str(d), **app_kw))


def test_manager_restarts_dead_engine_transparently(tmp_path):
    """Crash-only supervision: loop death → eviction → the next request
    loads a FRESH engine and serves (watchdog.go kill-and-respawn parity,
    without a process boundary)."""
    mgr = _mk_manager(tmp_path, restart_budget=3, restart_window_s=60.0,
                      quarantine_s=60.0)
    try:
        lm = mgr.get("m")
        _, ev = lm.engine.generate([65, 66], max_new_tokens=2, ignore_eos=True)
        assert ev.kind == "done"
        _kill_engine(lm.engine)
        lm2 = mgr.get("m")
        assert lm2 is not lm, "manager returned the dead engine"
        _, ev = lm2.engine.generate([65, 66], max_new_tokens=2,
                                    ignore_eos=True)
        assert ev.kind == "done"
        stats = mgr.restart_stats("m")
        assert stats["restarts_total"] == 1
        assert stats["quarantines_total"] == 0
        gauges = dict(((n, tuple(sorted(lb.items()))), v)
                      for n, lb, v in mgr.health_gauges())
        assert gauges[("localai_model_restarts", (("model", "m"),))] == 1.0
    finally:
        mgr.shutdown()


def test_manager_quarantines_after_restart_budget(tmp_path):
    """The (budget+1)-th death inside the window trips quarantine: requests
    get a typed error with a Retry-After window instead of feeding a
    reload/crash loop — and the model serves again once it expires."""
    # The refused get() has to come within the quarantine of the second
    # death being noted: 11 ms in 20 of 20 runs under six workers' load here
    # (PR 28), over the 1 s this window used to be in one driver run
    # (PR 26), so it is 3 s.
    mgr = _mk_manager(tmp_path, restart_budget=1, restart_window_s=60.0,
                      quarantine_s=3.0)
    try:
        for _ in range(2):
            lm = mgr.get("m")
            _kill_engine(lm.engine)
        with pytest.raises(ModelQuarantinedError) as exc:
            mgr.get("m")
        assert exc.value.retry_after_s > 0
        assert mgr.restart_stats("m")["quarantines_total"] == 1
        time.sleep(3.1)
        lm = mgr.get("m")  # quarantine expired — transparent reload
        _, ev = lm.engine.generate([65, 66], max_new_tokens=2,
                                   ignore_eos=True)
        assert ev.kind == "done"
    finally:
        mgr.shutdown()


def test_watchdog_reaps_dead_engine_without_traffic(tmp_path):
    """The watchdog notices a corpse between requests (frees HBM early and
    starts the restart-budget clock at the real death)."""
    mgr = _mk_manager(tmp_path, watchdog_idle_timeout_s=0.0,
                      watchdog_busy_timeout_s=3600.0,
                      watchdog_interval_s=0.2)
    try:
        lm = mgr.get("m")
        _kill_engine(lm.engine)
        deadline = time.monotonic() + 15
        while mgr.peek("m") is not None and time.monotonic() < deadline:
            time.sleep(0.05)
        assert mgr.peek("m") is None, "watchdog never reaped the dead engine"
        assert mgr.restart_stats("m")["restarts_total"] == 1
    finally:
        mgr.shutdown()


# --------------------------------------------------------------------- #
# HTTP mapping: 429 + Retry-After, 503 quarantine
# --------------------------------------------------------------------- #


def _mk_request(body):
    from localai_tpu.server.app import Request

    return Request(method="POST", path="/v1/chat/completions", params={},
                   query={}, headers={}, body=body)


def test_http_queue_full_maps_to_429_with_retry_after(tmp_path):
    from localai_tpu.server.app import ApiError
    from localai_tpu.server.openai_api import OpenAIApi

    d = tmp_path / "models"
    d.mkdir()
    (d / "m.yaml").write_text(yaml.safe_dump({
        "name": "m", "model": "tiny", "context_size": 64,
        "max_slots": 1, "max_tokens": 4, "max_pending": 1,
    }))
    mgr = ModelManager(ApplicationConfig(models_dir=str(d)))
    api = OpenAIApi(mgr)
    try:
        lm = mgr.get("m")
        held = [lm.engine.submit(GenRequest(prompt_ids=[1, 2, 3],
                                            max_new_tokens=10_000,
                                            ignore_eos=True))]
        deadline = time.monotonic() + 30
        while not lm.engine.h_active.any() and time.monotonic() < deadline:
            time.sleep(0.01)  # blocker must hold the only slot first
        held.append(lm.engine.submit(GenRequest(prompt_ids=[1, 2, 3],
                                                max_new_tokens=10_000,
                                                ignore_eos=True)))
        time.sleep(0.1)
        with pytest.raises(ApiError) as exc:
            api.chat(_mk_request({
                "model": "m", "max_tokens": 2,
                "messages": [{"role": "user", "content": "x"}],
            }))
        assert exc.value.status == 429
        assert exc.value.retry_after is not None
        resp = exc.value.to_response()
        assert int(resp.headers["Retry-After"]) >= 1
        assert lm.in_flight == 0, "shed request leaked its lease"
        for h in held:
            h.cancel()
        for h in held:
            _drain(h)
    finally:
        mgr.shutdown()


def test_http_quarantine_maps_to_503_with_retry_after(tmp_path):
    from localai_tpu.server.app import ApiError
    from localai_tpu.server.openai_api import OpenAIApi

    mgr = _mk_manager(tmp_path, restart_budget=0, restart_window_s=60.0,
                      quarantine_s=30.0)
    api = OpenAIApi(mgr)
    try:
        lm = mgr.get("m")
        _kill_engine(lm.engine)  # budget 0 → first death quarantines
        with pytest.raises(ApiError) as exc:
            api.chat(_mk_request({
                "model": "m", "max_tokens": 2,
                "messages": [{"role": "user", "content": "x"}],
            }))
        assert exc.value.status == 503
        resp = exc.value.to_response()
        assert int(resp.headers["Retry-After"]) >= 1
    finally:
        mgr.shutdown()


# --------------------------------------------------------------------- #
# Fault-injection harness
# --------------------------------------------------------------------- #


def test_fault_schedule_is_deterministic_per_site():
    a = faults.FaultSchedule(seed=42, rate=0.3)
    b = faults.FaultSchedule(seed=42, rate=0.3)
    pattern_a = [a.should_fire("device_dispatch") for _ in range(200)]
    pattern_b = [b.should_fire("device_dispatch") for _ in range(200)]
    assert pattern_a == pattern_b
    assert any(pattern_a) and not all(pattern_a)
    # Other sites draw from their own streams: interleaving calls to one
    # site must not perturb another.
    c = faults.FaultSchedule(seed=42, rate=0.3)
    pattern_c = []
    for _ in range(200):
        c.should_fire("page_alloc")
        pattern_c.append(c.should_fire("device_dispatch"))
    assert pattern_c == pattern_a


def test_fault_schedule_thread_scoping():
    """threads= makes fire() calls from other threads invisible: not
    counted, no draw consumed — a bystander loop can't eat a max_faults=1
    injection aimed at a specific engine's thread (the cluster
    replica-death test depends on exactly this)."""
    me = threading.get_ident()
    scoped = faults.FaultSchedule(seed=3, rate=1.0, sites=("page_alloc",),
                                  max_faults=1, threads={me + 1})
    assert not scoped.should_fire("page_alloc")  # wrong thread: filtered
    assert scoped.calls["page_alloc"] == 0       # ...and not counted
    hit = faults.FaultSchedule(seed=3, rate=1.0, sites=("page_alloc",),
                               max_faults=1, threads={me})
    assert hit.should_fire("page_alloc")
    assert "threads=" in repr(hit) and "threads=" not in repr(scoped.sites)

    # From a worker thread inside the scope set, the same schedule fires.
    out = []
    t = threading.Thread(
        target=lambda s: out.append(s.should_fire("page_alloc")),
        args=(faults.FaultSchedule(seed=3, rate=1.0, sites=("page_alloc",),
                                   max_faults=1, threads=None),),
        name="fault-scope-probe")
    t.start(); t.join(timeout=10)
    assert out == [True]  # threads=None keeps the old everyone-eligible path


def test_fault_env_parsing():
    s = faults.parse_env("seed:7,rate:0.5,max:3,sites:engine_loop|page_alloc")
    assert (s.seed, s.rate, s.max_faults) == (7, 0.5, 3)
    assert s.sites == ("engine_loop", "page_alloc")
    assert faults.parse_env("") is None
    with pytest.raises(ValueError):
        faults.parse_env("rate:0.5")  # seed is mandatory
    with pytest.raises(ValueError):
        faults.FaultSchedule(seed=1, sites=("bogus",))


def test_fault_fire_respects_max_and_scoping():
    sched = faults.FaultSchedule(seed=1, rate=1.0, sites=("page_alloc",),
                                 max_faults=2)
    with faults.active(sched):
        fired = 0
        for _ in range(10):
            try:
                faults.fire("page_alloc")
            except faults.InjectedFault:
                fired += 1
            faults.fire("device_dispatch")  # not in sites — never raises
        assert fired == 2
    faults.fire("page_alloc")  # inactive outside the context


def _churn_traffic(eng, n_req=8, seed=0, deadline_s=60.0):
    """Mixed traffic against a (possibly faulting) engine. Returns the
    per-request outcomes; asserts NOTHING hangs."""
    outcomes = [None] * n_req

    def one(i):
        ids = [(seed * 131 + i * 37 + j) % 255 + 1
               for j in range(4 + (i * 7) % 40)]
        try:
            h = eng.submit(GenRequest(
                prompt_ids=ids, max_new_tokens=4 + (i % 3) * 8,
                ignore_eos=True, deadline_s=deadline_s,
                temperature=0.8 if i % 3 == 0 else 0.0, seed=i,
                stop=["\x00\x01"] if i % 4 == 0 else [],
            ))
        except QueueFullError:
            outcomes[i] = "shed"
            return
        if i % 5 == 4:
            time.sleep(0.02)
            h.cancel()  # mid-stream client disconnect
        evs = _drain(h)
        outcomes[i] = evs[-1].kind

    threads = [threading.Thread(target=one, args=(i,), name=f"churn-{i}")
               for i in range(n_req)]
    for t in threads:
        t.start()
    _join_all(threads)
    assert all(o is not None for o in outcomes), outcomes
    return outcomes


def _run_engine_schedule(tiny, seed, sites, rate=0.12, max_faults=3,
                         n_req=8):
    """One seeded schedule end-to-end at the engine level: every request
    must terminate; a surviving engine must quiesce fully accounted and
    serve post-fault traffic; a dead engine must be fully released."""
    eng = _mk_engine(tiny, max_slots=2, max_seq=256, kv_pages=10,
                     kv_page_size=PAGE, max_pending=16)
    try:
        sched = faults.FaultSchedule(seed=seed, rate=rate, sites=sites,
                                     max_faults=max_faults)
        with faults.active(sched):
            outcomes = _churn_traffic(eng, n_req=n_req, seed=seed)
        if sched.fired.get("engine_loop", 0):
            # An engine_loop injection ALWAYS kills the loop, but the raise
            # may still be mid-flight on the loop thread when the window
            # closes (idle iterations keep drawing from the schedule after
            # the last outcome drains). Settle it — join the thread so the
            # crash-only teardown (release + postmortem) has fully run —
            # before branching on is_dead; otherwise this check races the
            # death and the recovery probe below hits a dying engine.
            t = eng._thread
            if t is not None:
                t.join(timeout=60.0)
            assert eng.is_dead, "engine_loop fault fired but the loop lives"
        if eng.is_dead:
            assert len(eng._free_pages) == eng.ecfg.kv_pages
            assert eng._host_bytes == 0
        else:
            _quiesce(eng)
            # Recovery: the engine serves post-fault traffic.
            _, ev = eng.generate([65, 66], max_new_tokens=2, ignore_eos=True)
            assert ev.kind == "done"
            _quiesce(eng)
        _assert_pool_accounted(eng)
        return outcomes, eng.is_dead, sched.total_fired()
    finally:
        eng.stop()


SMOKE_SITES = ("device_dispatch", "page_alloc", "engine_loop")


def test_fault_smoke_fixed_seeds(tiny):
    """Tier-1 fault smoke (fast, fixed seeds): injected dispatch/allocator/
    loop faults under mixed traffic — zero hung callers, pool accounted,
    survivors keep serving."""
    any_fired = 0
    for seed in (3, 11, 29):
        _outcomes, _died, fired = _run_engine_schedule(
            tiny, seed, SMOKE_SITES, rate=0.15, max_faults=2, n_req=6
        )
        any_fired += fired
    assert any_fired > 0, "smoke seeds never injected a fault"


@pytest.mark.slow
def test_fault_sweep_seeded_schedules(tiny, tmp_path):
    """ISSUE 4 acceptance: under hundreds of seeded fault schedules
    (injected loop deaths, allocator faults, swap faults, mid-stream
    disconnects) against mixed traffic — zero hung callers, the pool +
    host tier fully accounted at quiesce, and (via the shared manager) the
    model auto-restarts after deaths and quarantines once the budget is
    exhausted. LOCALAI_FAULT_SWEEP overrides the schedule count."""
    n_sched = int(os.environ.get("LOCALAI_FAULT_SWEEP", "200"))
    sites = ("device_dispatch", "page_alloc", "host_swap", "engine_loop")
    deaths = total_fired = 0
    for seed in range(n_sched):
        _outcomes, died, fired = _run_engine_schedule(
            tiny, seed, sites, rate=0.10, max_faults=3, n_req=6
        )
        deaths += int(died)
        total_fired += fired
    assert total_fired > 0
    assert deaths > 0, "no schedule exercised the loop-death path"

    # Manager tier: deaths inside the window auto-restart until the budget
    # trips, then quarantine answers instead of a respawn loop.
    mgr = _mk_manager(tmp_path, restart_budget=2, restart_window_s=3600.0,
                      quarantine_s=3600.0)
    try:
        for i in range(3):
            lm = mgr.get("m")
            _, ev = lm.engine.generate([65], max_new_tokens=2,
                                       ignore_eos=True)
            assert ev.kind == "done", f"restart {i} did not serve"
            _kill_engine(lm.engine)
        with pytest.raises(ModelQuarantinedError):
            mgr.get("m")
    finally:
        mgr.shutdown()


def test_manager_load_fault_is_contained(tmp_path):
    """An injected manager-load failure errors that one call and leaves
    serving up (initializers.go:123-150 parity), and the next un-faulted
    load succeeds."""
    mgr = _mk_manager(tmp_path)
    try:
        with faults.active(faults.FaultSchedule(
                seed=5, rate=1.0, sites=("manager_load",), max_faults=1)):
            with pytest.raises(RuntimeError, match="failed to load"):
                mgr.get("m")
        lm = mgr.get("m")
        _, ev = lm.engine.generate([65], max_new_tokens=2, ignore_eos=True)
        assert ev.kind == "done"
    finally:
        mgr.shutdown()
