"""Tier-1 coverage of a round-5 hang (a benchmark run cut at its time limit,
rc=124), re-pointed (ISSUE 5) at the migrated lint passes: the Engine class must never read a `self._x`
attribute that construction does not assign — the admission path once read
_admit_hold_start/_last_submit_t before any assignment, the loop thread
died of AttributeError, and every caller hung on its token queue forever.

The passes now live in tools/lint (attr-init, metric-counters,
lock-discipline — see docs/STATIC_ANALYSIS.md). Detector self-tests (the synthetic bad/good classes that used to live here)
moved to tests/lint_fixtures/ and run from test_lint.py, so this file pins
only the production target: Engine stays clean under all three passes.
"""

import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import Repo, run_passes  # noqa: E402
from tools.lint.passes.attr_init import AttrInitPass  # noqa: E402
from tools.lint.passes.lock_discipline import LockDisciplinePass  # noqa: E402
from tools.lint.passes.metric_counters import MetricCountersPass  # noqa: E402

ENGINE_PY = "localai_tpu/engine/engine.py"


def _findings(p):
    return [f.render() for f in run_passes(Repo(REPO), [p]).active]


def test_engine_reads_are_all_initialized():
    p = AttrInitPass(targets=[(ENGINE_PY, "Engine")])
    assert _findings(p) == [], (
        "Engine reads attributes never assigned during construction "
        "(loop-thread AttributeError — BENCH_r05 rc=124 bug class)"
    )


def test_metric_counter_pass_covers_engine():
    p = MetricCountersPass(globs=[ENGINE_PY])
    assert _findings(p) == [], (
        "Engine.metrics() reads m_* counters never initialized in __init__"
    )


def test_lock_discipline_pass_covers_engine():
    """ISSUE 4: engine state read under _pending_lock must never be rebound
    outside it at runtime (submit() and the loop thread share that state)."""
    p = LockDisciplinePass(globs=[ENGINE_PY])
    assert _findings(p) == [], (
        "Engine rebinds lock-protected state without its lock"
    )
