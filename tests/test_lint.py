"""localai-lint (tools/lint, ISSUE 5) wired into tier-1: the full pass
suite must be CLEAN on the repo on every PR, every pass must fire on its
seeded known-bad fixture and stay silent on the known-good one, and the
framework's suppression contract (reason required) must hold. The whole
module is pure AST analysis — no jax import, must stay well under 10 s.
"""

import json
import os
import re
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import Repo, run_passes, run_repo  # noqa: E402
from tools.lint.passes import all_passes  # noqa: E402
from tools.lint.passes.attr_init import AttrInitPass  # noqa: E402
from tools.lint.passes.config_drift import ConfigDriftPass  # noqa: E402
from tools.lint.passes.counter_balance import CounterBalancePass  # noqa: E402
from tools.lint.passes.donation_safety import DonationSafetyPass  # noqa: E402
from tools.lint.passes.double_resolve import DoubleResolvePass  # noqa: E402
from tools.lint.passes.fault_sites import FaultSitesPass  # noqa: E402
from tools.lint.passes.handoff_escape import HandoffEscapePass  # noqa: E402
from tools.lint.passes.journal_events import JournalEventsPass  # noqa: E402
from tools.lint.passes.lock_discipline import LockDisciplinePass  # noqa: E402
from tools.lint.passes.lock_order import LockOrderPass  # noqa: E402
from tools.lint.passes.metric_counters import MetricCountersPass  # noqa: E402
from tools.lint.passes.net_call_deadline import (  # noqa: E402
    NetCallDeadlinePass,
)
from tools.lint.passes.page_refcount import PageRefcountPass  # noqa: E402
from tools.lint.passes.resource_leak import ResourceLeakPass  # noqa: E402
from tools.lint.passes.rng_key_reuse import RngKeyReusePass  # noqa: E402
from tools.lint.passes.sharding_consistency import (  # noqa: E402
    ShardingConsistencyPass,
)
from tools.lint.passes.shared_state_race import (  # noqa: E402
    SharedStateRacePass,
)
from tools.lint.passes.terminal_event import TerminalEventPass  # noqa: E402
from tools.lint.passes.thread_affinity import ThreadAffinityPass  # noqa: E402
from tools.lint.passes.trace_safety import TraceSafetyPass  # noqa: E402
from tools.lint.threads import (  # noqa: E402
    GUARDED_THREAD_PREFIXES,
    UNGUARDED_THREAD_ROLES,
    threads_for,
)

FIX = os.path.join(REPO, "tests", "lint_fixtures")


_repo_result = None


def _full_run():
    """One shared full-suite run over the repo — three tests consume it."""
    global _repo_result
    if _repo_result is None:
        t0 = time.monotonic()
        _repo_result = (run_repo(REPO), time.monotonic() - t0)
    return _repo_result


# --------------------------------------------------------------------- #
# The acceptance gate: the repo itself is clean under all 20 passes.
# --------------------------------------------------------------------- #

def test_repo_is_clean_under_all_passes():
    result, elapsed = _full_run()
    assert len(result.pass_ids) == 20, result.pass_ids
    assert result.clean, "lint findings on the repo:\n" + "\n".join(
        f.render() for f in result.active
    )
    # Tier-1 budget (ISSUE 5/8/15/20): unloaded wall time is 10-11 s
    # (exception-edge CFG + may-raise fixpoint on top of the summary
    # index). The driver runs this beside five other workers' compiles:
    # 20 runs under such load here took 13.0-33.1 s, median 24 (PR 28), so
    # the bound is 50 s of wall clock: a pass that doubles the suite
    # still trips it. result.timings names the pass that regressed.
    assert elapsed < 50.0, (
        f"lint suite took {elapsed:.1f}s — slowest passes: "
        + ", ".join(f"{pid}={secs*1000:.0f}ms" for pid, secs in
                    sorted(result.timings.items(), key=lambda kv: -kv[1])[:3])
    )
    # Per-pass wall time is reported so budget regressions are attributable
    # (ISSUE 8 satellite).
    assert set(result.timings) == set(result.pass_ids)
    by_pass = result.by_pass()
    assert all("wall_time_ms" in by_pass[pid] for pid in result.pass_ids)


def test_cli_json_exits_zero():
    """CLI plumbing (arg parsing, JSON shape, exit code) on a cheap pass
    subset — the full-suite cleanliness is pinned in-process above."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--json",
         "--pass", "attr-init,fault-sites"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["clean"] is True
    assert set(payload["passes"]) >= {"attr-init", "fault-sites"}


def test_suppression_count_never_grows():
    """LINT_r07.json pins the suppression budget: future PRs may only
    shrink it (fix the code instead of silencing the pass)."""
    with open(os.path.join(REPO, "LINT_r07.json")) as f:
        pinned = json.load(f)
    result, _ = _full_run()
    assert len(result.suppressed) <= pinned["total_suppressions"], (
        "suppression count grew past the pinned budget "
        f"({len(result.suppressed)} > {pinned['total_suppressions']}) — "
        "fix the finding instead of suppressing it, or justify lowering "
        "the bar by regenerating LINT_rNN.json in its own PR"
    )
    # The budget itself stays <= 3 unless each extra carries a written
    # reason AND the baseline regen documents it (ISSUE 8/15 satellite).
    assert pinned["total_suppressions"] <= 3, pinned
    # The r07 baseline covers the full 20-pass registry with per-pass
    # timings (ISSUE 19/20 satellite).
    assert len(pinned["passes"]) == 20, sorted(pinned["passes"])
    assert all("wall_time_ms" in v for v in pinned["passes"].values())


# --------------------------------------------------------------------- #
# Per-pass fixtures: every pass fires on its seeded bad case and stays
# silent on the good one. No pass ships untested.
# --------------------------------------------------------------------- #

def _run_single(p, root=REPO):
    return run_passes(Repo(root), [p])


def test_attr_init_fixtures():
    bad = AttrInitPass(targets=[(os.path.join(FIX, "attr_init_bad.py"), "Engine")])
    r = _run_single(bad)
    assert [f for f in r.active if "_hold" in f.message], r.findings
    good = AttrInitPass(targets=[(os.path.join(FIX, "attr_init_good.py"), "Engine")])
    assert _run_single(good).clean


def test_metric_counters_fixtures():
    bad = MetricCountersPass(globs=["tests/lint_fixtures/metric_counters_bad.py"])
    r = _run_single(bad)
    assert [f for f in r.active if "m_preemptions" in f.message], r.findings
    good = MetricCountersPass(globs=["tests/lint_fixtures/metric_counters_good.py"])
    assert _run_single(good).clean


def test_lock_discipline_fixtures():
    bad = LockDisciplinePass(globs=["tests/lint_fixtures/lock_discipline_bad.py"])
    r = _run_single(bad)
    assert [f for f in r.active
            if "_pending" in f.message and "bad_reset" in f.message], r.findings
    good = LockDisciplinePass(globs=["tests/lint_fixtures/lock_discipline_good.py"])
    assert _run_single(good).clean


def test_trace_safety_fixtures():
    broot = os.path.join(FIX, "trace_safety", "bad")
    bad = TraceSafetyPass(
        traced_globs=["ops_mod.py"], engine_target=("engine_mod.py", "Engine"),
    )
    r = _run_single(bad, root=broot)
    msgs = "\n".join(f.message for f in r.active)
    assert "branch on a traced value" in msgs, msgs
    assert "block_until_ready" in msgs, msgs
    assert ".tolist()" in msgs, msgs
    assert "traced local" in msgs, msgs  # float(y)
    assert "recompile trigger" in msgs, msgs  # jnp.zeros((m, 4))
    assert "device value in engine hot path" in msgs, msgs
    groot = os.path.join(FIX, "trace_safety", "good")
    good = TraceSafetyPass(
        traced_globs=["ops_mod.py"], engine_target=("engine_mod.py", "Engine"),
    )
    assert _run_single(good, root=groot).clean


def test_terminal_event_fixtures():
    bad = TerminalEventPass(targets=[(
        os.path.join(FIX, "terminal_event_bad.py"), "Engine", "_pending", "slots",
    )])
    r = _run_single(bad)
    methods = {m for f in r.active for m in ("bad_drop", "bad_clear", "bad_teardown")
               if m in f.message}
    assert methods == {"bad_drop", "bad_clear", "bad_teardown"}, r.findings
    good = TerminalEventPass(targets=[(
        os.path.join(FIX, "terminal_event_good.py"), "Engine", "_pending", "slots",
    )])
    assert _run_single(good).clean


def test_page_refcount_fixtures():
    bad = PageRefcountPass(targets=[(
        os.path.join(FIX, "page_refcount_bad.py"), "Engine",
    )])
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "rogue_share" in msgs, msgs      # refcount bump outside primitives
    assert "rogue_grab" in msgs, msgs       # free-list pop outside primitives
    assert "unchecked_admit" in msgs, msgs  # None never handled
    assert "_my_secret_pages" in msgs, msgs  # escaped page ids
    good = PageRefcountPass(targets=[(
        os.path.join(FIX, "page_refcount_good.py"), "Engine",
    )])
    assert _run_single(good).clean


def test_config_drift_fixtures():
    broot = os.path.join(FIX, "config_drift", "bad")
    bad = ConfigDriftPass(
        engine_py="localai_tpu/engine/engine.py",
        model_cfg_py="localai_tpu/config/model_config.py",
        app_cfg_py="localai_tpu/config/app_config.py",
        manager_py="localai_tpu/server/manager.py",
        config_md="docs/CONFIG.md",
    )
    r = _run_single(bad, root=broot)
    msgs = "\n".join(f.message for f in r.active)
    assert "kv_shiny" in msgs, msgs          # undocumented YAML key (D1)
    assert "secret_knob" in msgs, msgs       # undocumented app field (D1)
    assert "kv_ghost_knob" in msgs, msgs     # dead doc row (D2)
    assert "LOCALAI_SECRET_KNOB" in msgs, msgs  # read, undocumented (D3)
    assert "LOCALAI_GHOST_VAR" in msgs, msgs    # documented, never read (D4)
    assert "LOCALAI_KV_SHINY" in msgs, msgs     # comment claim, never read (D4)
    assert ("does not forward" in msgs and "kv_shiny" in msgs), msgs  # D5
    groot = os.path.join(FIX, "config_drift", "good")
    good = ConfigDriftPass()
    assert _run_single(good, root=groot).clean


# ---- interprocedural passes (ISSUE 8) ---- #

def test_lock_order_fixtures():
    rel = "tests/lint_fixtures/lock_order_bad.py"
    bad = LockOrderPass(globs=(rel,))
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "lock-order cycle" in msgs, r.findings
    assert "_sched_lock" in msgs and "_pool_lock" in msgs, msgs
    good = LockOrderPass(globs=("tests/lint_fixtures/lock_order_good.py",))
    assert _run_single(good).clean


def test_rng_key_reuse_fixtures():
    bad = RngKeyReusePass(globs=("tests/lint_fixtures/rng_key_reuse_bad.py",))
    r = _run_single(bad)
    # All four flavors fire: double draw, parent-after-split, per-iteration
    # loop reuse, and reuse through a key-consuming helper.
    lines = sorted(f.line for f in r.active)
    assert len(lines) == 4, r.findings
    good = RngKeyReusePass(globs=("tests/lint_fixtures/rng_key_reuse_good.py",))
    assert _run_single(good).clean


def test_donation_safety_fixtures():
    bad = DonationSafetyPass(
        globs=("tests/lint_fixtures/donation_safety_bad.py",))
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "'cache'" in msgs, msgs            # read-after-donate + loop
    assert "'self.counts'" in msgs, msgs      # builder + *args form
    assert len(r.active) == 3, r.findings
    good = DonationSafetyPass(
        globs=("tests/lint_fixtures/donation_safety_good.py",))
    assert _run_single(good).clean


def test_sharding_consistency_fixtures():
    broot = os.path.join(FIX, "sharding_consistency", "bad")
    r = _run_single(ShardingConsistencyPass(), root=broot)
    msgs = "\n".join(f.message for f in r.active)
    assert "wq_proj" in msgs, msgs          # stale spec (drift)
    assert "'wq'" in msgs, msgs             # tree name with no spec
    assert "'mp'" in msgs, msgs             # ghost mesh axis
    assert "rogue_reduce" in msgs, msgs     # collective outside boundary
    assert "stale declaration" in msgs, msgs
    groot = os.path.join(FIX, "sharding_consistency", "good")
    assert _run_single(ShardingConsistencyPass(), root=groot).clean


def test_since_limit_narrows_file_scoped_passes():
    """--since semantics: a limit that matches no files silences
    file-scoped passes but leaves project-wide passes running in full."""
    limited = Repo(REPO, limit=["no/such/file.py"])
    r = run_passes(limited, [RngKeyReusePass(), DonationSafetyPass(),
                             MetricCountersPass(), TraceSafetyPass(),
                             AttrInitPass(), LockDisciplinePass()])
    assert r.clean and not r.findings
    # Project-wide passes ignore the limit entirely (the invariant spans
    # files): sharding-consistency still sees the whole repo.
    r2 = run_passes(limited, [ShardingConsistencyPass()])
    assert r2.pass_ids == ["sharding-consistency"]
    assert ShardingConsistencyPass.project_wide is True
    assert LockOrderPass.project_wide is True


def test_cli_since_mode():
    """`--since HEAD` (the verify-skill pre-commit step) parses, runs, and
    keeps the JSON contract."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--json", "--since", "HEAD",
         "--pass", "rng-key-reuse,donation-safety"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode in (0, 1), proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert set(payload["passes"]) >= {"rng-key-reuse", "donation-safety"}
    bad = subprocess.run(
        [sys.executable, "-m", "tools.lint", "--since",
         "no-such-rev-zzz"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    assert bad.returncode == 2, bad.stdout + bad.stderr


def test_journal_events_fixtures():
    """Flight-recorder consistency (ISSUE 11): SITES ↔ FAULT_EVENTS both
    ways, fault-sites style."""
    broot = os.path.join(FIX, "journal_events", "bad")
    r = _run_single(JournalEventsPass(), root=broot)
    msgs = "\n".join(f.message for f in r.active)
    assert "ghost_site" in msgs, msgs          # site without journal event
    assert "fault_page_allok" in msgs, msgs    # event naming no site
    assert "badly_named_event" in msgs, msgs   # not fault_<site> shaped
    groot = os.path.join(FIX, "journal_events", "good")
    assert _run_single(JournalEventsPass(), root=groot).clean
    assert JournalEventsPass.project_wide is True


# ---- thread-model passes (ISSUE 15) ---- #

def test_shared_state_race_fixtures():
    """The known-bad file carries the PRE-FIX shape of the PR 11
    Metrics._gauge_sources bug — the incident class is demonstrably
    covered — plus a loop-vs-reader container iterate and a two-root
    scalar lost-update. The known-good file is every blessed idiom."""
    bad = SharedStateRacePass(
        globs=("tests/lint_fixtures/shared_state_race_bad.py",))
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "_gauge_sources" in msgs, r.findings       # the PR 11 incident
    assert "http-handler" in msgs, msgs               # scrape-side root
    assert "_stats" in msgs, msgs                     # loop-vs-main iterate
    assert "m_hits" in msgs, msgs                     # scalar lost update
    assert len(r.active) == 3, r.findings
    good = SharedStateRacePass(
        globs=("tests/lint_fixtures/shared_state_race_good.py",))
    assert _run_single(good).clean, _run_single(good).findings


def test_staged_plan_race_fixtures():
    """ISSUE-17 pipelined-runtime shapes: the known-bad file strips the
    `# thread:` declarations off the prepare-ahead staging slot, the
    sidecar's deferred-work list and the stager's upload cache — a
    two-root epoch RMW, a live-list iteration and a scrape-side dict
    iterate. The known-good file is the shipped discipline (loop-only
    entry points, single-writer counters, instance-owned cache, locked
    deadline heap) and must stay silent."""
    bad = SharedStateRacePass(
        globs=("tests/lint_fixtures/staged_plan_race_bad.py",))
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "_ctrl_epoch" in msgs, r.findings          # lost epoch bump
    assert "_deferred_saves" in msgs, msgs            # live sidecar list
    assert "_cache" in msgs, msgs                     # scrape-side iterate
    assert len(r.active) == 3, r.findings
    good = SharedStateRacePass(
        globs=("tests/lint_fixtures/staged_plan_race_good.py",))
    assert _run_single(good).clean, _run_single(good).findings


def test_thread_affinity_fixtures():
    bad = ThreadAffinityPass(
        globs=("tests/lint_fixtures/thread_affinity_bad.py",))
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "fixture-watchdog" in msgs, r.findings     # foreign-root reach
    assert "ghost-pump" in msgs, msgs                 # stale declaration
    assert len(r.active) == 2, r.findings
    good = ThreadAffinityPass(
        globs=("tests/lint_fixtures/thread_affinity_good.py",))
    assert _run_single(good).clean, _run_single(good).findings


def test_handoff_escape_fixtures():
    bad = HandoffEscapePass(
        globs=("tests/lint_fixtures/handoff_escape_bad.py",))
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "self.limit" in msgs, r.findings           # publish-before-init
    assert "handed off" in msgs, msgs                 # mutate-after-put
    assert "self.ready" in msgs, msgs                 # self into registry
    assert len(r.active) == 3, r.findings
    good = HandoffEscapePass(
        globs=("tests/lint_fixtures/handoff_escape_good.py",))
    assert _run_single(good).clean, _run_single(good).findings


def test_thread_pass_project_wide():
    """--since must never narrow the thread model: roots/effects span
    files by construction."""
    assert SharedStateRacePass.project_wide is True
    assert ThreadAffinityPass.project_wide is True
    assert HandoffEscapePass.project_wide is True


def test_thread_root_discovery_covers_known_roles():
    """The model discovers the serving core's real thread roles over the
    repo (cached SummaryIndex — this rides the _full_run build)."""
    model = threads_for(Repo(REPO))
    roles = {r.role for r in model.roots}
    for expected in ("engine-loop", "engine-drain", "watchdog",
                     "config-watcher", "cluster-pump", "http-handler",
                     "main", "fed-health"):
        assert expected in roles, (expected, sorted(roles))
    # The engine loop reaches its own dispatch machinery...
    loop = next(r for r in model.roots if r.role == "engine-loop")
    reach = model.reach(loop)
    assert any(fid.endswith("Engine._loop") for fid in reach), len(reach)
    # ...and the journal's declared loop-only append.
    assert any("EventJournal.append" in fid for fid in reach)


def test_thread_guard_drift_against_discovery():
    """Conftest's thread-leak guard and lint discovery share one source
    (tools.lint.threads): every discovered threading.Thread site must be
    covered by a guarded prefix or a documented exemption. A new Thread
    site that is covered by neither fails HERE, not three PRs later when
    a leaked thread wedges CI."""
    import fnmatch as _fn

    from tests.conftest import _GUARDED_THREAD_PREFIXES

    assert _GUARDED_THREAD_PREFIXES == GUARDED_THREAD_PREFIXES  # one source
    model = threads_for(Repo(REPO))
    sites = model.discovered_roles()
    assert sites, "thread-root discovery found no Thread sites at all?"
    uncovered = []
    for s in sites:
        role = s.pattern or s.role
        guarded = any(role.startswith(p) for p in GUARDED_THREAD_PREFIXES)
        exempt = any(_fn.fnmatch(s.role, pat) or _fn.fnmatch(role, pat)
                     for pat in UNGUARDED_THREAD_ROLES)
        if not (guarded or exempt):
            uncovered.append(f"{s.path}:{s.line} role={s.role!r}")
    assert not uncovered, (
        "threading.Thread sites covered by neither the conftest leak-guard "
        "prefixes nor tools.lint.threads.UNGUARDED_THREAD_ROLES (add a "
        "guard prefix or a written exemption):\n" + "\n".join(uncovered)
    )
    # Exemptions carry written reasons, suppression-style.
    assert all(reason.strip() for reason in UNGUARDED_THREAD_ROLES.values())


def test_net_call_deadline_fixtures():
    """ISSUE 19 remote-call hardening: outbound calls must state their
    deadline — the retry/breaker layer only works if calls return."""
    bad = NetCallDeadlinePass(
        code_globs=["tests/lint_fixtures/net_call_deadline_bad.py"])
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "without an explicit timeout" in msgs, r.findings
    assert "timeout=None" in msgs, msgs
    assert "create_connection" in msgs, msgs
    assert "setdefaulttimeout" in msgs, msgs
    assert len(r.active) == 5, r.findings
    good = NetCallDeadlinePass(
        code_globs=["tests/lint_fixtures/net_call_deadline_good.py"])
    assert _run_single(good).clean, _run_single(good).findings


def test_fault_sites_fixtures():
    broot = os.path.join(FIX, "fault_sites", "bad")
    bad = FaultSitesPass()
    r = _run_single(bad, root=broot)
    msgs = "\n".join(f.message for f in r.active)
    assert "ghost_site" in msgs, msgs   # declared but never fired
    assert "page_allok" in msgs, msgs   # fired but undeclared (typo)
    assert "non-literal" in msgs, msgs  # fire(variable)
    groot = os.path.join(FIX, "fault_sites", "good")
    good = FaultSitesPass()
    assert _run_single(good, root=groot).clean


# --------------------------------------------------------------------- #
# Resource-lifecycle passes (ISSUE 20): exception-edge CFG + may-raise
# fixpoint. The bad fixtures are minimized replays of real incidents —
# the PR 19 breaker probe-slot leak and the pick→begin_stream window.
# --------------------------------------------------------------------- #

_WITNESS_HOP = re.compile(r"^[^ ]+:\d+( \([a-z-]+\))?$")


def _assert_exception_witness(finding):
    """Every resource-lifecycle finding ships a line-numbered edge trace
    ending on the exception edge that loses the resource."""
    assert finding.witness, finding
    for hop in finding.witness:
        assert _WITNESS_HOP.match(hop), finding.witness
    assert any("(raise)" in hop or "(except)" in hop
               for hop in finding.witness), finding.witness


def test_resource_leak_fixtures():
    bad = ResourceLeakPass(globs=["tests/lint_fixtures/resource_leak_bad.py"])
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    # Minimized PR 19 incident: urlopen raises after guard() admits the
    # probe, and no record_* runs on that edge.
    assert "call_probe_leak" in msgs, r.findings
    assert "breaker-probe" in msgs, msgs
    # The pick→begin_stream window: submit raises after reserve=True.
    assert "dispatch_window_leak" in msgs, msgs
    assert "sched-inflight" in msgs, msgs
    assert "lock_leak" in msgs, msgs
    assert len(r.active) == 3, r.findings
    for f in r.active:
        _assert_exception_witness(f)
    good = ResourceLeakPass(globs=["tests/lint_fixtures/resource_leak_good.py"])
    assert _run_single(good).clean, _run_single(good).findings


def test_double_resolve_fixtures():
    bad = DoubleResolvePass(globs=["tests/lint_fixtures/double_resolve_bad.py"])
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "double_end" in msgs, r.findings          # handler + fall-through
    assert "double_release" in msgs, msgs            # two releases, one addref
    assert len(r.active) == 2, r.findings
    for f in r.active:
        assert f.witness, f
        for hop in f.witness:
            assert _WITNESS_HOP.match(hop), f.witness
    good = DoubleResolvePass(
        globs=["tests/lint_fixtures/double_resolve_good.py"])
    assert _run_single(good).clean, _run_single(good).findings


def test_counter_balance_fixtures():
    bad = CounterBalancePass(
        globs=["tests/lint_fixtures/counter_balance_bad.py"])
    r = _run_single(bad)
    msgs = "\n".join(f.message for f in r.active)
    assert "m_decode_begin" in msgs, r.findings
    assert len(r.active) == 1, r.findings
    _assert_exception_witness(r.active[0])
    good = CounterBalancePass(
        globs=["tests/lint_fixtures/counter_balance_good.py"])
    assert _run_single(good).clean, _run_single(good).findings


def test_witness_json_round_trip():
    """--json contract (ISSUE 20 satellite): the witness rides to_json()
    as a stable ordered list of "file:line[ (kind)]" strings."""
    r = _run_single(
        ResourceLeakPass(globs=["tests/lint_fixtures/resource_leak_bad.py"]))
    payload = json.loads(json.dumps(r.to_json()))
    witnessed = [f for f in payload["findings"] if f["witness"]]
    assert witnessed, payload["findings"]
    for f in witnessed:
        assert isinstance(f["witness"], list), f
        assert f["witness"] == [str(h) for h in f["witness"]]
        for hop in f["witness"]:
            assert _WITNESS_HOP.match(hop), f["witness"]
    # Order is the edge trace: the acquisition line leads.
    first = witnessed[0]
    assert first["witness"][0].endswith(f":{first['line']}"), first


def test_resource_leak_catches_netretry_regression():
    """The acceptance bar from ISSUE 20: reverting the PR 19
    release_probe fix in the REAL cluster/netretry.py must fail the lint.
    We stage a scratch copy so the working tree stays untouched."""
    src = os.path.join(REPO, "localai_tpu", "cluster", "netretry.py")
    with open(src) as f:
        original = f.read()
    assert "breaker.release_probe()" in original
    tmp = tempfile.mkdtemp(prefix="lint_netretry_")
    try:
        # Unmodified copy: clean.
        shutil.copy(src, os.path.join(tmp, "netretry.py"))
        ok = run_passes(Repo(tmp), [ResourceLeakPass(globs=("netretry.py",))])
        assert ok.clean, ok.findings
        # Revert the fix: the BaseException handler no longer releases the
        # half-open probe slot — the breaker wedges until restart.
        broken = original.replace("breaker.release_probe()", "pass")
        assert broken != original
        with open(os.path.join(tmp, "netretry.py"), "w") as f:
            f.write(broken)
        r = run_passes(Repo(tmp), [ResourceLeakPass(globs=("netretry.py",))])
        probe = [f for f in r.active if "breaker-probe" in f.message]
        assert probe, r.findings
        _assert_exception_witness(probe[0])
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def test_since_limit_covers_cfg_passes():
    """--since semantics extend to the CFG passes: per-function CFGs are
    only built for in-scope files (may-raise summaries stay full-repo)."""
    both = ["tests/lint_fixtures/resource_leak_bad.py",
            "tests/lint_fixtures/resource_leak_good.py"]
    # Limit to the good file: the bad file's leaks fall out of scope.
    limited = Repo(REPO, limit=[both[1]])
    r = run_passes(limited, [ResourceLeakPass(globs=both)])
    assert r.clean, r.findings
    # Limit to the bad file: the findings come back.
    limited = Repo(REPO, limit=[both[0]])
    r = run_passes(limited, [ResourceLeakPass(globs=both)])
    assert len(r.active) == 3, r.findings


# --------------------------------------------------------------------- #
# Framework contracts: suppressions need reasons; unknown ids are errors.
# --------------------------------------------------------------------- #

def test_suppression_with_reason_counts_as_suppressed():
    p = AttrInitPass(targets=[(
        os.path.join(FIX, "suppression_with_reason.py"), "Engine",
    )])
    r = _run_single(p)
    assert r.clean
    assert len(r.suppressed) == 1
    assert "monkeypatched" in r.suppressed[0].reason


def test_suppression_without_reason_is_a_finding():
    p = AttrInitPass(targets=[(
        os.path.join(FIX, "suppression_no_reason.py"), "Engine",
    )])
    r = _run_single(p)
    assert not r.clean
    assert any(f.pass_id == "lint" and "no reason" in f.message
               for f in r.active), r.findings


def test_registry_has_the_twenty_passes():
    ids = [p.id for p in all_passes()]
    assert ids == [
        "attr-init", "metric-counters", "lock-discipline", "trace-safety",
        "terminal-event", "page-refcount", "config-drift", "fault-sites",
        "lock-order", "rng-key-reuse", "sharding-consistency",
        "donation-safety", "journal-events", "shared-state-race",
        "thread-affinity", "handoff-escape", "net-call-deadline",
        "resource-leak", "double-resolve", "counter-balance",
    ], ids
    assert len(set(ids)) == 20
