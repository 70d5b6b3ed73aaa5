"""Cluster scheduler tests (ISSUE 6, docs/CLUSTER.md): affinity hashing
stability, scheduler scoring/death-draining properties, and the 2-replica
single-host acceptance paths — prefix-affinity routing asserted via
prefix-hit gauges, prefill→decode handoff byte-identical to a mixed-role
run, replica death mid-stream rerouting with terminal events, and seeded
fault schedules (cluster_dispatch / span_transfer) with zero hung callers.
"""

import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from localai_tpu.cluster import (
    ClusterClient,
    ClusterScheduler,
    SpanTransferError,
    build_local_replicas,
    decode_span,
    encode_span,
    leading_overlap,
    parse_roles,
    span_hashes,
)
from localai_tpu.engine.engine import Engine, EngineConfig, GenRequest
from localai_tpu.engine.tokenizer import ByteTokenizer
from localai_tpu.models import get_arch
from localai_tpu.models.llama import init_params
from localai_tpu.testing import faults

PAGE = 32
PROMPT = [(i * 37) % 251 + 1 for i in range(70)]  # 70 tokens = 2 full pages
PROMPT2 = [(i * 41) % 251 + 1 for i in range(70)]


@pytest.fixture(scope="module")
def tiny():
    cfg = get_arch("tiny")
    return cfg, init_params(cfg, jax.random.key(0))


@pytest.fixture(scope="module")
def mixed_baseline(tiny):
    """One mixed-role engine — the oracle for cluster output identity."""
    cfg, params = tiny
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=_ecfg())
    eng.start()
    yield eng
    eng.stop()
    eng.params = None
    eng.cache = None


@pytest.fixture(scope="module")
def pd_pair(tiny):
    """A shared prefill+decode replica pair (tests assert counter DELTAS)."""
    replicas, client = _mk_cluster(tiny, ["prefill", "decode"])
    yield replicas, client
    _stop_all(replicas)


@pytest.fixture(scope="module")
def mixed_pair(tiny):
    """A shared mixed+mixed replica pair. The affinity test runs first (file
    order) and needs a cold pair; later tests assert deltas only."""
    replicas, client = _mk_cluster(tiny, ["mixed", "mixed"])
    yield replicas, client
    _stop_all(replicas)


def _ecfg(**kw):
    defaults = dict(
        max_slots=2, max_seq=256, min_prefill_bucket=32,
        kv_pages=16, kv_page_size=PAGE,
        prefix_cache_entries=4, prefix_cache_min=PAGE,
        prefix_admit_async_compile=False,  # deterministic hits
    )
    defaults.update(kw)
    return EngineConfig(**defaults)


def _mk_cluster(tiny, roles, **client_kw):
    cfg, params = tiny
    replicas = build_local_replicas(
        cfg, params, ByteTokenizer(cfg.vocab_size), n=len(roles),
        engine_cfg=_ecfg(), roles=list(roles),
    )
    client_kw.setdefault("gauge_refresh_s", 0.0)  # always-fresh gauges
    client = ClusterClient(replicas, **client_kw)
    return replicas, client


def _stop_all(replicas):
    for rep in replicas:
        rep.engine.stop()
        rep.engine.params = None
        rep.engine.cache = None


# --------------------------------------------------------------------- #
# Affinity hashing: stability + chaining
# --------------------------------------------------------------------- #


def test_span_hashes_page_boundaries_and_chaining():
    hs = span_hashes(PROMPT, span_tokens=PAGE, max_spans=8)
    assert len(hs) == 2  # only FULL spans: 70 // 32
    assert all(len(h) == 8 for h in hs)
    # Shared leading span, divergent second span → shared first digest only.
    other = PROMPT[:PAGE] + [9] * PAGE
    ho = span_hashes(other, span_tokens=PAGE, max_spans=8)
    assert ho[0] == hs[0] and ho[1] != hs[1]
    # The chain makes digest i cover the whole prefix: a prompt differing
    # only in span 0 shares NO digests.
    shifted = [t % 250 + 2 for t in PROMPT]
    assert span_hashes(shifted, PAGE, 8)[0] != hs[0]
    assert leading_overlap({hs[0]: 1}, hs) == 1
    assert leading_overlap({hs[0]: 1, hs[1]: 1}, hs) == 2
    assert leading_overlap({hs[1]: 1}, hs) == 0  # no leading match


def test_span_hashes_stable_across_processes_and_hash_seeds():
    """Same token ids → same digests in fresh interpreters with different
    PYTHONHASHSEED (no raw hash() anywhere in the path)."""
    script = (
        "from localai_tpu.cluster.affinity import span_hashes;"
        f"print(','.join(h.hex() for h in span_hashes({PROMPT!r}, {PAGE}, 8)))"
    )
    outs = []
    for seed in ("0", "4242"):
        env = {**os.environ, "PYTHONHASHSEED": seed}
        proc = subprocess.run(
            [sys.executable, "-c", script],
            capture_output=True, text=True, timeout=120, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert proc.returncode == 0, proc.stderr
        outs.append(proc.stdout.strip())
    assert outs[0] == outs[1]
    assert outs[0] == ",".join(
        h.hex() for h in span_hashes(PROMPT, PAGE, 8))


def test_parse_roles():
    assert parse_roles(3, "") == ["mixed"] * 3
    assert parse_roles(2, "prefill") == ["prefill", "prefill"]
    assert parse_roles(3, "prefill,decode") == ["prefill", "decode", "mixed"]
    with pytest.raises(ValueError):
        parse_roles(2, "bogus")


# --------------------------------------------------------------------- #
# Scheduler core properties (no engines)
# --------------------------------------------------------------------- #


def _fake_sched(**kw):
    kw.setdefault("span_tokens", PAGE)
    kw.setdefault("gauge_refresh_s", 0.0)
    return ClusterScheduler(**kw)


def test_scheduler_prefers_affinity_then_load():
    sched = _fake_sched()
    g = {"a": {"queue_depth": 0.0}, "b": {"queue_depth": 0.0}}
    sched.add_replica("a", gauge_fn=lambda: g["a"])
    sched.add_replica("b", gauge_fn=lambda: g["b"])
    hs = sched.hashes_for(PROMPT)
    # No signal → deterministic least-loaded tie-break; record lands on it.
    first = sched.pick(hs)
    sched.record(first, hs)
    # Affinity now dominates an equal-load fleet.
    for _ in range(3):
        assert sched.pick(hs) == first
    # ... but heavy load on the affine replica flips the pick.
    g[first]["queue_depth"] = 50.0
    other = {"a", "b"} - {first}
    assert sched.pick(hs) == next(iter(other))
    # Affinity off (hit_weight 0) is pure least-loaded.
    flat = _fake_sched(hit_weight=0.0)
    flat.add_replica("a", gauge_fn=lambda: {"queue_depth": 5.0})
    flat.add_replica("b", gauge_fn=lambda: {"queue_depth": 0.0})
    flat.record("a", hs)
    assert flat.pick(hs) == "b"


def test_scheduler_dead_replica_stops_attracting_within_one_refresh():
    state = {"dead": 0.0}
    sched = _fake_sched()
    sched.add_replica("a", gauge_fn=lambda: {"loop_dead": state["dead"]})
    sched.add_replica("b", gauge_fn=lambda: {})
    hs = sched.hashes_for(PROMPT)
    sched.record("a", hs)
    assert sched.pick(hs) == "a"
    state["dead"] = 1.0  # the engine loop died; next gauge refresh sees it
    assert sched.pick(hs) == "b"
    snap = {r["name"]: r for r in sched.snapshot()}
    assert snap["a"]["alive"] is False
    assert snap["a"]["affinity_spans_held"] == 0  # entries drained
    # Crash-only restart: gauges recover, but the old affinity stays gone —
    # the replica re-earns it from live admissions.
    state["dead"] = 0.0
    sched.record("b", hs)
    assert sched.pick(hs) == "b"


def test_scheduler_role_typed_picks_fall_back():
    state = {"d_dead": 0.0}
    sched = _fake_sched()
    sched.add_replica("p", role="prefill", gauge_fn=dict)
    sched.add_replica("d", role="decode",
                      gauge_fn=lambda: {"loop_dead": state["d_dead"]})
    assert sched.pick([], role="prefill") == "p"
    assert sched.pick([], role="decode") == "d"
    state["d_dead"] = 1.0
    # Degraded fleet: a decode-typed pick serves from what is alive.
    assert sched.pick([], role="decode") == "p"
    assert sched.pick([], exclude=("p",)) is None
    # Gauges are the source of truth: recovery resurrects the replica.
    state["d_dead"] = 0.0
    assert sched.pick([], role="decode") == "d"


def test_scheduler_drain_intent_survives_crash_recovery():
    """A draining member that crashes and recovers comes back DRAINING —
    recovery must not silently undo an operator's drain request."""
    state = {"dead": 0.0}
    sched = _fake_sched()
    sched.add_replica("a", gauge_fn=lambda: {"loop_dead": state["dead"]})
    sched.add_replica("b", gauge_fn=dict)
    sched.refresh(force=True)
    assert sched.state("a") == "active"
    assert sched.begin_drain("a")
    state["dead"] = 1.0
    sched.refresh(force=True)
    assert sched.state("a") == "dead"
    state["dead"] = 0.0
    sched.refresh(force=True)
    assert sched.state("a") == "draining"  # intent survived the crash
    assert sched.pick([]) == "b"           # still takes no new work

    # A deferred leave() keeps its removal intent across the crash too:
    # the recovered member resumes draining and the last end_stream
    # completes the removal.
    state_b = {"dead": 1.0}
    sched2 = _fake_sched()
    sched2.add_replica("c", gauge_fn=lambda: {"loop_dead": state_b["dead"]})
    sched2.begin_stream("c")
    assert sched2.leave("c") == "draining"
    sched2.refresh(force=True)
    assert sched2.state("c") == "dead"
    state_b["dead"] = 0.0
    sched2.refresh(force=True)
    assert sched2.state("c") == "draining"
    sched2.end_stream("c")
    assert sched2.state("c") == "removed"


def test_scheduler_pick_reserve_blocks_concurrent_leave():
    """pick(reserve=True) counts the stream under the pick lock itself, so
    a leave() racing the dispatch defers on the just-picked stream instead
    of removing the replica out from under it."""
    sched = _fake_sched()
    sched.add_replica("a", gauge_fn=dict)
    sched.refresh(force=True)
    assert sched.pick([], reserve=True) == "a"
    assert sched.leave("a") == "draining"  # deferred: the pick holds it
    sched.end_stream("a")                  # the dispatch leg finishes
    assert sched.state("a") == "removed"


# --------------------------------------------------------------------- #
# Transfer frame format
# --------------------------------------------------------------------- #


def _fake_span(npg=2):
    hk = np.arange(4 * npg * PAGE * 2 * 3, dtype=np.float32).reshape(
        4, npg, PAGE, 2, 3)
    hv = hk + 0.5
    geom = {"layers": 4, "kv_heads": 2, "k_dim": 3, "v_dim": 3,
            "page_size": PAGE, "dtype": "float32"}
    return hk, hv, geom


def test_transfer_roundtrip_and_rejections():
    hk, hv, geom = _fake_span()
    key = list(range(2 * PAGE))
    frame = encode_span(key, len(key), hk, hv, geom)
    k2, valid, rk, rv = decode_span(frame, geom)
    assert valid == len(key) and (k2 == np.asarray(key)).all()
    assert (rk == hk).all() and (rv == hv).all() and rk.dtype == hk.dtype
    # geometry mismatch
    with pytest.raises(SpanTransferError):
        decode_span(frame, {**geom, "page_size": PAGE * 2})
    # truncation / corruption
    with pytest.raises(SpanTransferError):
        decode_span(frame[:-8], geom)
    with pytest.raises(SpanTransferError):
        decode_span(b"NOTKV" + frame[5:], geom)
    # version gate
    bad = bytearray(frame)
    bad[5] = 99
    with pytest.raises(SpanTransferError):
        decode_span(bytes(bad), geom)
    # size cap, both directions
    with pytest.raises(SpanTransferError):
        encode_span(key, len(key), hk, hv, geom, max_bytes=128)
    with pytest.raises(SpanTransferError):
        decode_span(frame, geom, max_bytes=128)


# --------------------------------------------------------------------- #
# 2-replica single-host cluster (the acceptance paths)
# --------------------------------------------------------------------- #


def test_affinity_routes_repeat_prompt_to_span_holder(mixed_pair):
    replicas, client = mixed_pair
    for _ in range(3):
        text, ev = client.generate(PROMPT, max_new_tokens=4,
                                   ignore_eos=True)
        assert ev.kind == "done"
    hits = [rep.engine.m_prefix_hits for rep in replicas]
    admits = [rep.engine.m_prompt_tokens for rep in replicas]
    # Every repeat followed the spans: one replica served all three
    # (2 prefix hits), the other never saw the prompt.
    assert sorted(hits) == [0, 2], (hits, admits)
    holder = hits.index(2)
    assert admits[1 - holder] == 0, "a repeat leaked off the span holder"


def test_prefill_decode_handoff_byte_identical_to_mixed(mixed_baseline,
                                                        pd_pair):
    replicas, client = pd_pair
    pre, dec = replicas
    for prompt, req_kw in ((PROMPT, dict(temperature=0.0)),
                           (PROMPT2, dict(temperature=0.9, top_k=8, seed=7))):
        want, ev = mixed_baseline.generate(prompt, max_new_tokens=10,
                                           ignore_eos=True, **req_kw)
        before = (client.m_handoffs, pre.engine.m_span_exports,
                  dec.engine.m_span_imports, dec.engine.m_prefix_hits,
                  dec.engine.m_prefix_host_hits, client.m_handoff_fallbacks)
        got, gev = client.generate(prompt, max_new_tokens=10,
                                   ignore_eos=True, **req_kw)
        assert got == want, (req_kw, got, want)
        assert gev.completion_tokens == ev.completion_tokens
        assert client.m_handoffs == before[0] + 1
        assert client.m_handoff_fallbacks == before[5]
        assert pre.engine.m_span_exports == before[1] + 1
        assert dec.engine.m_span_imports == before[2] + 1
        # The decode replica served the span from the imported host-tier
        # entry — prefix-hit gauges prove the route.
        assert dec.engine.m_prefix_hits >= before[3] + 1
        assert dec.engine.m_prefix_host_hits >= before[4] + 1


def test_span_transfer_fault_falls_back_to_recompute(pd_pair):
    """ISSUE 6 satellite smoke: a fixed-seed injected transfer failure
    degrades the handoff to recompute-on-decode-replica — same output,
    terminal event posted, zero hung callers."""
    replicas, client = pd_pair
    prompt = [(i * 43) % 251 + 1 for i in range(70)]
    imports0 = replicas[1].engine.m_span_imports
    falls0, hands0 = client.m_handoff_fallbacks, client.m_handoffs
    with faults.active(faults.FaultSchedule(
            seed=1234, rate=1.0, sites=("span_transfer",), max_faults=2)):
        t0 = time.monotonic()
        got, ev = client.generate(prompt, max_new_tokens=8,
                                  ignore_eos=True)
        assert time.monotonic() - t0 < 60.0
    assert ev.kind == "done" and len(got) > 0
    assert client.m_handoff_fallbacks == falls0 + 1
    assert client.m_handoffs == hands0
    assert replicas[1].engine.m_span_imports == imports0
    # Recovery: with the schedule exhausted the next handoff lands, and
    # the recompute fallback produced exactly what the handed-off (cached)
    # admission produces.
    got2, _ = client.generate(prompt, max_new_tokens=8, ignore_eos=True)
    assert got2 == got
    assert client.m_handoffs == hands0 + 1
    assert not client._pending, "records leaked past their terminals"


def test_cluster_dispatch_fault_posts_terminal_error(mixed_pair):
    replicas, client = mixed_pair
    with faults.active(faults.FaultSchedule(
            seed=7, rate=1.0, sites=("cluster_dispatch",), max_faults=1)):
        handle = client.submit(GenRequest(prompt_ids=PROMPT[:40],
                                          max_new_tokens=4,
                                          ignore_eos=True))
        evs = list(handle)
    assert evs[-1].kind == "error" and "injected" in evs[-1].error
    assert not client._pending
    # Containment: the cluster keeps serving.
    _, ev = client.generate(PROMPT[:40], max_new_tokens=4,
                            ignore_eos=True)
    assert ev.kind == "done"


def test_replica_death_mid_stream_reroutes_with_terminal_events(tiny):
    """Kill one replica's loop mid-stream (seeded engine_loop fault): every
    affected request must reroute to the survivor and reach its terminal
    event — no hung callers, full requested length delivered."""
    replicas, client = _mk_cluster(tiny, ["mixed", "mixed"])
    try:
        n_req, n_new = 4, 32
        handles, firsts = [], []
        for i in range(n_req):
            h = client.submit(GenRequest(
                prompt_ids=[(i * 13 + j) % 251 + 1 for j in range(40)],
                max_new_tokens=n_new, ignore_eos=True))
            handles.append(h)
            # Wait for the first token before the next submit: each request
            # is streaming when the death lands, and the load gauges see
            # the previous admission — traffic spreads over BOTH replicas.
            firsts.append(h._q.get(timeout=60.0))
        assert all(ev.kind == "token" for ev in firsts), firsts
        assert all(r.engine.m_prompt_tokens > 0 for r in replicas), \
            "traffic did not spread across both replicas"
        # Scope the injection to THIS cluster's mid-stream loop threads:
        # the module-scoped fixture engines idle in the background and
        # their loops also call fire() — unscoped, the single fault can
        # land on a bystander and neither replica ever dies. Eligible
        # replicas must hold a request with real HEADROOM (≥8 tokens to
        # go — the last request just streamed its first, so one always
        # qualifies): a near-done request can drain in the instants
        # between this snapshot and the fault landing, and a death with
        # nothing live reroutes nothing.
        loop_idents = {
            r.engine._thread.ident for r in replicas
            if any(len(s.generated) <= n_new - 8
                   for s in r.engine._tenants())
        }
        assert loop_idents, "no replica mid-stream at fault activation"
        with faults.active(faults.FaultSchedule(
                seed=99, rate=1.0, sites=("engine_loop",), max_faults=1,
                threads=loop_idents)):
            deadline = time.monotonic() + 60.0
            while (not any(r.engine.is_dead for r in replicas)
                   and time.monotonic() < deadline):
                time.sleep(0.005)
        assert any(r.engine.is_dead for r in replicas), \
            "injected loop death never landed"

        results = {}

        def drain(i, h, first_ev):
            toks = [first_ev]
            for ev in h:
                toks.append(ev)
            results[i] = toks

        threads = [threading.Thread(target=drain, args=(i, h, f))
                   for i, (h, f) in enumerate(zip(handles, firsts))]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 120.0
        for t in threads:
            t.join(timeout=max(0.1, deadline - time.monotonic()))
        hung = [t.name for t in threads if t.is_alive()]
        assert not hung, f"hung callers after replica death: {hung}"

        for i, evs in results.items():
            assert evs[-1].kind == "done", (i, evs[-1])
            n_toks = sum(1 for ev in evs if ev.kind == "token")
            assert n_toks == n_new, (i, n_toks)
            assert evs[-1].completion_tokens == n_new
        dead = [r for r in replicas if r.engine.is_dead]
        assert len(dead) == 1
        assert client.m_reroutes >= 1  # the dead replica was mid-stream
        assert not client._pending
    finally:
        _stop_all(replicas)


def test_dense_engine_has_no_span_transfer(tiny):
    cfg, params = tiny
    eng = Engine(cfg, params, ByteTokenizer(cfg.vocab_size),
                 engine_cfg=EngineConfig(max_slots=2, max_seq=128,
                                         min_prefill_bucket=32))
    eng.start()
    try:
        eng.generate(PROMPT, max_new_tokens=2, ignore_eos=True)
        assert eng.export_prefix_span(PROMPT) is None
        assert eng.import_span_bytes(b"LAIKV") is False
    finally:
        eng.stop()


# --------------------------------------------------------------------- #
# Server wiring: manager fan-out behind ApplicationConfig.cluster_replicas
# --------------------------------------------------------------------- #


def test_manager_fans_out_cluster_replicas(tmp_path):
    import yaml

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager

    d = tmp_path / "models"
    d.mkdir()
    (d / "cm.yaml").write_text(yaml.safe_dump({
        "name": "cm", "model": "tiny", "context_size": 128,
        "max_slots": 2, "max_tokens": 8,
        "kv_pages": 8, "kv_page_size": 32,
    }))
    mgr = ModelManager(ApplicationConfig(
        models_dir=str(d), cluster_replicas=2, cluster_role="mixed"))
    try:
        lm = mgr.get("cm")
        from localai_tpu.cluster import ClusterEngine

        assert isinstance(lm.engine, ClusterEngine)
        text, ev = lm.engine.generate([1, 2, 3, 4], max_new_tokens=3,
                                      ignore_eos=True)
        assert ev.kind == "done" and ev.completion_tokens == 3
        m = lm.engine.metrics()
        assert m["cluster_replicas"] == 2.0
        assert m["loop_dead"] == 0.0 and "cluster_dispatches" in m
    finally:
        mgr.shutdown()


def test_cluster_membership_endpoints(tmp_path):
    """ISSUE 19 membership surface over real HTTP: /v1/cluster/join walks
    a (down) peer in at `joining`, duplicate joins 409, /v1/cluster/drain
    stops new routing without breaking service, /v1/cluster/leave removes,
    and /v1/cluster/status exposes the lifecycle + journal event tail."""
    import json
    import urllib.error
    import urllib.request

    import yaml

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager, Router, create_server
    from localai_tpu.server.openai_api import OpenAIApi

    d = tmp_path / "models"
    d.mkdir()
    (d / "cm.yaml").write_text(yaml.safe_dump({
        "name": "cm", "model": "tiny", "context_size": 128,
        "max_slots": 2, "max_tokens": 8,
        "kv_pages": 8, "kv_page_size": 32,
    }))
    app_cfg = ApplicationConfig(
        address="127.0.0.1", port=0, models_dir=str(d),
        cluster_replicas=2, cluster_role="mixed")
    mgr = ModelManager(app_cfg)
    router = Router()
    OpenAIApi(mgr).register(router)
    server = create_server(app_cfg, router)
    base = f"http://127.0.0.1:{server.server_address[1]}"
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()

    def post(path, payload):
        req = urllib.request.Request(
            base + path, data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    try:
        # Load the cluster-served model, then exercise membership.
        out = post("/v1/completions", {"model": "cm", "prompt": "hi",
                                       "max_tokens": 2})
        assert out["choices"]
        # Join a peer that is DOWN: it must enter at joining/probing and
        # never become routable — service is unaffected.
        out = post("/v1/cluster/join", {"model": "cm", "name": "peer9",
                                        "url": "http://127.0.0.1:9"})
        assert out["joined"] == "peer9"
        assert out["state"] in ("joining", "probing")
        with pytest.raises(urllib.error.HTTPError) as ei:
            post("/v1/cluster/join", {"model": "cm", "name": "peer9",
                                      "url": "http://127.0.0.1:9"})
        assert ei.value.code == 409
        # Drain r0: state flips, requests still serve (r1 takes them).
        out = post("/v1/cluster/drain", {"model": "cm", "name": "r0"})
        assert out["state"] == "draining"
        out = post("/v1/completions", {"model": "cm", "prompt": "hi",
                                       "max_tokens": 2})
        assert out["choices"]
        with urllib.request.urlopen(base + "/cluster/status",
                                    timeout=30) as r:
            status = json.loads(r.read())
        snap = {s["name"]: s for s in status["engines"]["cm"]["replicas"]}
        assert snap["r0"]["state"] == "draining"
        assert snap["peer9"]["state"] in ("joining", "probing")
        events = status["engines"]["cm"]["events"]
        assert any(e["event"] == "member_state" for e in events)
        # Leave: the down peer goes first, then the drained replica.
        out = post("/v1/cluster/leave", {"model": "cm", "name": "peer9",
                                         "force": True})
        assert out["state"] == "removed"
        out = post("/v1/cluster/leave", {"model": "cm", "name": "r0"})
        assert out["state"] == "removed"  # nothing in flight → immediate
        assert {s["name"] for s in out["replicas"]} == {"r1"}
        # A one-replica fleet still serves.
        out = post("/v1/completions", {"model": "cm", "prompt": "hi",
                                       "max_tokens": 2})
        assert out["choices"]
    finally:
        server.shutdown()
        mgr.shutdown()
