"""Chaos harness driver (ISSUE 19, docs/ROBUSTNESS.md).

Runs a 2-replica tiny-model mini-cluster under phase-scheduled fault
scripts (localai_tpu.testing.faults.ChaosScript) and asserts the
robustness invariants the membership/failover layer promises:

  * zero hung callers — every drain thread joins inside its deadline;
  * every submitted request reaches exactly one terminal event;
  * a drained replica admits no new work, finishes its in-flight streams,
    and hands its span affinity to a survivor (snapshot reads 0 held);
  * grammar-constrained greedy output survives a mid-stream replica death
    byte-identical to the no-fault run (stateful replay, not abort);
  * the per-replica circuit breaker sends at most ONE probe per half-open
    window (asserted from journal events);
  * every journaled resource protocol balances (ISSUE 20): for each
    protocol declared with a `journal=` pair in tools/lint/resources.py
    (the same registry the resource-leak lint verifies statically), each
    begin event in the stream is eventually followed by one of its end
    events — runtime evidence that nothing leaked under chaos.

Usage:
    JAX_PLATFORMS=cpu python -m tools.chaos_run                 # all
    JAX_PLATFORMS=cpu python -m tools.chaos_run -s kill_mid_decode
    JAX_PLATFORMS=cpu python -m tools.chaos_run --seed 7 --list

Each scenario is also importable (tests/test_chaos.py runs the cheap ones
in tier-1); a scenario returns a metrics dict and raises AssertionError on
any invariant violation.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

PAGE = 32
PROMPT = [(i * 37) % 251 + 1 for i in range(70)]  # spans 2 full pages

_TINY = None


def _tiny():
    """Tiny model arch+params, built once per process (CLI runs several
    scenarios; each builds its own replicas over the SHARED weight tree)."""
    global _TINY
    if _TINY is None:
        import jax

        from localai_tpu.models import get_arch
        from localai_tpu.models.llama import init_params

        cfg = get_arch("tiny")
        _TINY = (cfg, init_params(cfg, jax.random.key(0)))
    return _TINY


def _ecfg(**kw):
    from localai_tpu.engine.engine import EngineConfig

    defaults = dict(
        max_slots=2, max_seq=256, min_prefill_bucket=32,
        kv_pages=16, kv_page_size=PAGE,
        prefix_cache_entries=4, prefix_cache_min=PAGE,
        prefix_admit_async_compile=False,
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def _build(roles, **client_kw):
    from localai_tpu.cluster import ClusterClient, build_local_replicas
    from localai_tpu.engine.tokenizer import ByteTokenizer

    cfg, params = _tiny()
    replicas = build_local_replicas(
        cfg, params, ByteTokenizer(cfg.vocab_size), n=len(roles),
        engine_cfg=_ecfg(), roles=list(roles))
    client_kw.setdefault("gauge_refresh_s", 0.0)
    client = ClusterClient(replicas, **client_kw)
    return replicas, client


def _stop_all(replicas):
    for rep in replicas:
        rep.engine.stop()
        rep.engine.params = None
        rep.engine.cache = None


def _submit_streams(client, n_req, n_new, prompt_fn=None):
    """Submit n_req streaming requests, waiting for each one's FIRST token
    before the next submit (every request is live when a fault lands, and
    the load gauges spread traffic over the fleet)."""
    from localai_tpu.engine.engine import GenRequest

    handles, firsts = [], []
    for i in range(n_req):
        prompt = (prompt_fn(i) if prompt_fn
                  else [(i * 13 + j) % 251 + 1 for j in range(40)])
        h = client.submit(GenRequest(prompt_ids=prompt,
                                     max_new_tokens=n_new, ignore_eos=True))
        handles.append(h)
        firsts.append(h._q.get(timeout=60.0))
    assert all(ev.kind == "token" for ev in firsts), firsts
    return handles, firsts


def _drain_all(handles, firsts=None, timeout=120.0):
    """Drain every handle on its own thread. Returns ({i: [events]}, hung);
    the zero-hung-callers invariant is `assert not hung`."""
    results: dict[int, list] = {}

    def drain(i, h, first):
        evs = [first] if first is not None else []
        for ev in h:
            evs.append(ev)
        results[i] = evs

    firsts = firsts or [None] * len(handles)
    threads = [threading.Thread(target=drain, args=(i, h, f), daemon=True,
                                name=f"chaos-drain-{i}")
               for i, (h, f) in enumerate(zip(handles, firsts))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + timeout
    for t in threads:
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    hung = [t.name for t in threads if t.is_alive()]
    return results, hung


def _assert_all_terminal(results, n_req, kinds=("done",)):
    assert len(results) == n_req, (len(results), n_req)
    for i, evs in results.items():
        assert evs and evs[-1].kind in kinds, (i, evs[-1:])


def _member_transitions(events):
    """[(rid, old_state, new_state)] from member_state journal events."""
    from localai_tpu.cluster import MEMBER_STATES

    out = []
    for e in events:
        if e["event"] == "member_state":
            old = (MEMBER_STATES[int(e["b"])] if e["b"] >= 0 else None)
            out.append((e["rid"], old, MEMBER_STATES[int(e["a"])]))
    return out


def assert_breaker_probe_discipline(events):
    """≤ 1 breaker probe per half-open window, from journal events: between
    consecutive breaker_open events (or open→close) for one breaker there
    is at most one breaker_probe — the half-open gate admits a single
    in-flight probe and every probe outcome closes or re-opens the window."""
    windows: dict[str, int] = {}
    for e in events:
        rid = e["rid"]
        if e["event"] == "breaker_open":
            windows[rid] = 0
        elif e["event"] == "breaker_probe":
            assert rid in windows, f"probe with no open window on {rid}"
            windows[rid] += 1
            assert windows[rid] <= 1, \
                f"{windows[rid]} probes in one half-open window on {rid}"
        elif e["event"] == "breaker_close":
            windows.pop(rid, None)


def assert_journal_balance(events):
    """Registry-driven lifecycle balance (ISSUE 20): for every protocol
    with a `journal=(begin, ends)` declaration in tools/lint/resources.py,
    each begin event is eventually followed by one of its end events for
    the same rid. This is the runtime mirror of the resource-leak lint —
    the static pass proves no code path drops the resource, this proves no
    scenario actually did."""
    from tools.lint.resources import JOURNAL_BALANCE

    names = {e["event"] for e in events}
    for pid, (begin, ends) in JOURNAL_BALANCE.items():
        if begin not in names:
            continue  # scenario never exercised this protocol
        open_by_rid: dict[str, int] = {}
        for e in events:
            rid = e["rid"]
            if e["event"] == begin:
                assert open_by_rid.get(rid, 0) == 0, (
                    f"{pid}: second {begin} on {rid} while the previous "
                    f"one is still unresolved")
                open_by_rid[rid] = 1
            elif e["event"] in ends:
                # Ends without a begin are legal (breaker_open fires on a
                # plain trip too) — the check is begin ⇒ eventual end.
                open_by_rid[rid] = 0
        stuck = [rid for rid, n in open_by_rid.items() if n]
        assert not stuck, (
            f"{pid}: {begin} never followed by any of {ends} for {stuck}")


# --------------------------------------------------------------------- #
# Scenarios
# --------------------------------------------------------------------- #


def kill_mid_decode(seed=99):
    """Kill one replica's engine loop while every request is streaming:
    all requests reroute to the survivor and deliver their full length."""
    from localai_tpu.testing import faults

    replicas, client = _build(["mixed", "mixed"])
    try:
        n_req, n_new = 4, 32
        handles, firsts = _submit_streams(client, n_req, n_new)
        loop_idents = {
            r.engine._thread.ident for r in replicas
            if any(len(s.generated) <= n_new - 8
                   for s in r.engine._tenants())
        }
        assert loop_idents, "no replica mid-stream at fault activation"
        script = faults.ChaosScript(seed=seed, threads=loop_idents, phases=[
            faults.ChaosPhase("engine_loop", after_calls=0, rate=1.0,
                              max_faults=1)])
        with faults.active(script):
            deadline = time.monotonic() + 60.0
            while (not any(r.engine.is_dead for r in replicas)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
        assert any(r.engine.is_dead for r in replicas), \
            "injected loop death never landed"
        results, hung = _drain_all(handles, firsts)
        assert not hung, f"hung callers: {hung}"
        _assert_all_terminal(results, n_req)
        for i, evs in results.items():
            n_toks = sum(1 for ev in evs if ev.kind == "token")
            assert n_toks == n_new, (i, n_toks)
        assert client.m_reroutes >= 1
        assert not client._pending, "records leaked past their terminals"
        events = client.scheduler.journal_events()
        assert_journal_balance(events)
        trans = _member_transitions(events)
        assert any(new == "dead" for _, _, new in trans), trans
        return {"reroutes": client.m_reroutes,
                "dead": sum(r.engine.is_dead for r in replicas)}
    finally:
        _stop_all(replicas)


def slow_gauge(seed=5):
    """Gauge scrapes flap BELOW the death threshold: routing continues on
    last-good gauges, nobody is marked dead, every request completes."""
    from localai_tpu.testing import faults

    replicas, client = _build(["mixed", "mixed"])
    try:
        # Warm-up promotes both joiners to active before the flap starts.
        client.generate(PROMPT, max_new_tokens=2, ignore_eos=True)
        thr = client.scheduler.gauge_fail_threshold
        script = faults.ChaosScript(seed=seed, phases=[
            faults.ChaosPhase("gauge_scrape", after_calls=0, rate=1.0,
                              max_faults=thr - 1)])
        with faults.active(script):
            handles, firsts = _submit_streams(client, 4, 16)
            results, hung = _drain_all(handles, firsts)
        assert not hung, f"hung callers: {hung}"
        _assert_all_terminal(results, 4)
        assert script.exhausted(), "the gauge flap never fired"
        events = client.scheduler.journal_events()
        assert_journal_balance(events)
        assert any(e["event"] == "fault_gauge_scrape" for e in events)
        trans = _member_transitions(events)
        assert not any(new == "dead" for _, _, new in trans), \
            f"sub-threshold gauge flaps killed a replica: {trans}"
        assert all(not r.engine.is_dead for r in replicas)
        return {"flaps": sum(p.fired for p in script.phases)}
    finally:
        _stop_all(replicas)


def partition_during_transfer(seed=1234):
    """Network partition while a KV span is in flight: the prefill→decode
    handoff degrades to recompute-on-decode — same bytes, no hung caller."""
    from localai_tpu.testing import faults

    replicas, client = _build(["prefill", "decode"])
    try:
        falls0 = client.m_handoff_fallbacks
        script = faults.ChaosScript(seed=seed, phases=[
            faults.ChaosPhase("span_transfer", after_calls=0, rate=1.0,
                              max_faults=2)])
        with faults.active(script):
            text, ev = client.generate(PROMPT, max_new_tokens=8,
                                       ignore_eos=True)
        assert ev.kind == "done" and len(text) > 0
        assert client.m_handoff_fallbacks == falls0 + 1
        # Recovery: the partition healed — the next handoff lands and
        # produces exactly what the recompute fallback produced.
        text2, ev2 = client.generate(PROMPT, max_new_tokens=8,
                                     ignore_eos=True)
        assert ev2.kind == "done" and text2 == text
        assert client.m_handoffs >= 1
        assert not client._pending
        return {"fallbacks": client.m_handoff_fallbacks - falls0,
                "handoffs": client.m_handoffs}
    finally:
        _stop_all(replicas)


def join_under_load(seed=0):
    """A replica joins while requests stream: it walks joining → active on
    its first successful gauge scrape and becomes routable, without
    perturbing in-flight streams."""
    from localai_tpu.cluster import build_local_replicas
    from localai_tpu.engine.tokenizer import ByteTokenizer

    cfg, params = _tiny()
    replicas, client = _build(["mixed"])
    joiner = None
    try:
        handles, firsts = _submit_streams(client, 2, 24)
        [joiner] = build_local_replicas(
            cfg, params, ByteTokenizer(cfg.vocab_size), n=1,
            engine_cfg=_ecfg(), roles=["mixed"], name_prefix="joiner")
        client.replicas.append(joiner)
        client.scheduler.add_replica(
            joiner.name, target=joiner, role=joiner.role,
            gauge_fn=joiner.gauges)
        assert client.scheduler.state(joiner.name) == "joining"
        client.scheduler.refresh(force=True)
        assert client.scheduler.state(joiner.name) == "active"
        # Routable: a pick excluding the incumbent lands on the joiner.
        assert client.scheduler.pick([], exclude=("r0",)) == joiner.name
        results, hung = _drain_all(handles, firsts)
        assert not hung, f"hung callers: {hung}"
        _assert_all_terminal(results, 2)
        # New traffic reaches the joiner's engine.
        before = joiner.engine.m_prompt_tokens
        h2, f2 = _submit_streams(client, 3, 8)
        r2, hung2 = _drain_all(h2, f2)
        assert not hung2 and len(r2) == 3
        events = client.scheduler.journal_events()
        assert_journal_balance(events)
        trans = _member_transitions(events)
        assert (joiner.name, None, "joining") in trans, trans
        assert (joiner.name, "joining", "active") in trans, trans
        return {"joiner_prompt_tokens":
                joiner.engine.m_prompt_tokens - before}
    finally:
        _stop_all(replicas)
        if joiner is not None:
            _stop_all([joiner])


def drain_under_load(seed=0):
    """Drain a replica mid-stream: no NEW admissions land on it, in-flight
    streams finish, its span affinity moves to the survivor, and leave()
    removes it once in-flight hits zero."""
    replicas, client = _build(["mixed", "mixed"])
    try:
        # Establish affinity + traffic on both replicas.
        handles, firsts = _submit_streams(client, 4, 24)
        sched = client.scheduler
        # The victim must HOLD affinity (so the handoff is observable) —
        # prefer one that is also mid-stream.
        snap = sorted(sched.snapshot(),
                      key=lambda s: (s["affinity_spans_held"] > 0,
                                     s["inflight"]), reverse=True)
        assert snap[0]["affinity_spans_held"] > 0, snap
        victim = snap[0]["name"]
        veng = next(r for r in replicas if r.name == victim).engine
        admitted0 = veng.m_prompt_tokens
        assert sched.begin_drain(victim)
        assert sched.state(victim) == "draining"
        # New work: every admission must land on the survivor.
        h2, f2 = _submit_streams(client, 3, 8)
        results, hung = _drain_all(handles + h2, firsts + f2)
        assert not hung, f"hung callers: {hung}"
        _assert_all_terminal(results, 7)
        assert veng.m_prompt_tokens == admitted0, \
            "a drained replica admitted new work"
        snap = {s["name"]: s for s in sched.snapshot()}
        assert snap[victim]["inflight"] == 0
        assert snap[victim]["affinity_spans_held"] == 0, \
            "drain left affinity behind"
        events = sched.journal_events()
        assert_journal_balance(events)
        handed = [e for e in events if e["event"] == "affinity_handoff"]
        assert handed and handed[0]["rid"] == victim, events
        # Graceful exit completes now that in-flight is zero.
        assert sched.leave(victim) == "removed"
        assert victim not in sched.names()
        trans = _member_transitions(events)
        assert any(t == (victim, "active", "draining") for t in trans), trans
        return {"victim": victim,
                "spans_handed": int(handed[0]["a"])}
    finally:
        _stop_all(replicas)


def grammar_replay(seed=0):
    """Mid-stream replica death under a grammar constraint: the survivor
    replays the emitted tokens through a fresh grammar machine and the
    greedy output is byte-identical to the no-fault run — and valid."""
    from localai_tpu.engine.engine import GenRequest
    from localai_tpu.functions.jsonschema import GrammarConstraint
    from localai_tpu.testing import faults

    schema = {"type": "object",
              "properties": {"a": {"type": "integer"},
                             "b": {"type": "boolean"}},
              "required": ["a", "b"]}
    n_new = 120

    def req():
        return GenRequest(prompt_ids=[10, 20, 30], max_new_tokens=n_new,
                          temperature=0.0,
                          grammar=GrammarConstraint(schema))

    # No-fault oracle on a fresh cluster.
    replicas, client = _build(["mixed", "mixed"])
    try:
        h = client.submit(req())
        want, wev = h.result()
        assert wev.kind == "done", wev
        json.loads(want)
    finally:
        _stop_all(replicas)

    replicas, client = _build(["mixed", "mixed"])
    try:
        h = client.submit(req())
        first = h._q.get(timeout=60.0)
        assert first.kind == "token", first
        # Exactly one engine is serving it — kill that loop.
        serving = [r for r in replicas
                   if r.engine._tenants()]
        assert serving, "request not live on any replica"
        idents = {r.engine._thread.ident for r in serving}
        script = faults.ChaosScript(seed=seed + 99, threads=idents, phases=[
            faults.ChaosPhase("engine_loop", after_calls=0, rate=1.0,
                              max_faults=1)])
        with faults.active(script):
            deadline = time.monotonic() + 60.0
            while (not any(r.engine.is_dead for r in replicas)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
        assert any(r.engine.is_dead for r in replicas)
        results, hung = _drain_all([h], [first])
        assert not hung, f"hung callers: {hung}"
        evs = results[0]
        assert evs[-1].kind == "done", evs[-1]
        got = "".join(ev.text for ev in evs if ev.kind == "token")
        assert got == want, (got, want)
        json.loads(got)  # no grammar-invalid bytes ever reached the caller
        assert client.m_grammar_replays >= 1
        events = client.scheduler.journal_events()
        assert_journal_balance(events)
        assert any(e["event"] == "reroute_replay" for e in events), events
        return {"replays": client.m_grammar_replays, "bytes": len(got)}
    finally:
        _stop_all(replicas)


def breaker_window(seed=0):
    """Circuit-breaker probe discipline without engines: a flapping remote
    trips the breaker; journal events prove ≤ 1 probe per half-open
    window and recovery closes it."""
    from localai_tpu.cluster import BreakerOpen, CircuitBreaker
    from localai_tpu.observe.journal import EventJournal

    journal = EventJournal(capacity=256)

    def hook(event, a=0.0):
        journal.stage(event, rid="peer", a=a)

    clock = {"t": 0.0}
    br = CircuitBreaker(name="peer", failure_threshold=2, reset_s=1.0,
                        on_event=hook, clock=lambda: clock["t"])
    # Trip it.
    for _ in range(2):
        br.record_failure()
    assert br.state == "open"
    refused = 0
    for _ in range(5):  # refused while open — no probes before reset_s
        if not br.allow():
            refused += 1
    assert refused == 5
    # Half-open: exactly one probe per window; a failed probe re-opens.
    clock["t"] = 1.1
    assert br.allow() is True      # the single probe
    assert br.allow() is False     # second caller refused in-window
    br.record_failure()            # probe failed → re-open
    assert br.state == "open"
    clock["t"] = 2.2
    assert br.allow() is True
    br.record_success()            # probe succeeded → closed
    assert br.state == "closed"
    events = journal.snapshot()
    assert_breaker_probe_discipline(events)
    assert_journal_balance(events)
    kinds = [e["event"] for e in events]
    assert kinds.count("breaker_open") == 2
    assert kinds.count("breaker_probe") == 2
    assert kinds.count("breaker_close") == 1
    return {"refused": br.m_refused, "probes": br.m_probes}


SCENARIOS = {
    "kill_mid_decode": kill_mid_decode,
    "slow_gauge": slow_gauge,
    "partition_during_transfer": partition_during_transfer,
    "join_under_load": join_under_load,
    "drain_under_load": drain_under_load,
    "grammar_replay": grammar_replay,
    "breaker_window": breaker_window,
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="run cluster chaos scenarios and assert invariants")
    ap.add_argument("-s", "--scenario", action="append", default=None,
                    choices=sorted(SCENARIOS), help="run only this scenario "
                    "(repeatable; default: all)")
    ap.add_argument("--seed", type=int, default=None,
                    help="override each scenario's default fault seed")
    ap.add_argument("--list", action="store_true",
                    help="list scenarios and exit")
    args = ap.parse_args(argv)
    if args.list:
        for name, fn in SCENARIOS.items():
            print(f"{name}: {fn.__doc__.strip().splitlines()[0]}")
        return 0
    names = args.scenario or list(SCENARIOS)
    failed = []
    for name in names:
        fn = SCENARIOS[name]
        t0 = time.monotonic()
        try:
            out = fn() if args.seed is None else fn(seed=args.seed)
            print(f"PASS {name} ({time.monotonic() - t0:.1f}s): "
                  f"{json.dumps(out)}")
        except AssertionError as e:
            failed.append(name)
            print(f"FAIL {name} ({time.monotonic() - t0:.1f}s): {e}")
    if failed:
        print(f"{len(failed)}/{len(names)} scenario(s) failed: "
              + ", ".join(failed))
        return 1
    print(f"all {len(names)} scenario(s) passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
