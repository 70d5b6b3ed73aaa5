"""attr-init: `self.x` read somewhere in a class but never assigned during
construction.

The exact bug class that killed BENCH_r05 (rc=124): the engine-loop admission
path read `self._admit_hold_start` / `self._last_submit_t` before any code
path had ever assigned them — the loop thread died of AttributeError on the
first idle admission and every caller hung on a token queue forever. Python
has no compiler to catch this; this AST pass does.

Rule: every attribute the class loads (`self.x` in Load context, or reads via
`self.x += ...`) must be assigned by construction — in `__init__`, in a
method `__init__` (transitively) calls on self, or at class level — or be a
method/property of the class. Attributes probed with `hasattr(self, "x")`
anywhere in the class are exempt (lazy-init caches declare themselves that
way).
"""

from __future__ import annotations

from .. import astutil
from ..core import Finding, Pass, Repo

DEFAULT_TARGETS = [
    ("localai_tpu/engine/engine.py", "Engine"),
    ("localai_tpu/server/manager.py", "ModelManager"),
    ("localai_tpu/federation/router.py", "WorkerRegistry"),
    ("localai_tpu/federation/router.py", "Federator"),
    ("localai_tpu/testing/faults.py", "FaultSchedule"),
    ("localai_tpu/cluster/scheduler.py", "ClusterScheduler"),
    ("localai_tpu/cluster/scheduler.py", "ClusterClient"),
    ("localai_tpu/cluster/replica.py", "ClusterEngine"),
    # Multi-host subsystem (ISSUE 13): the stream assembler and remote
    # replica are touched from dispatch pumps and scheduler refreshes —
    # the same cross-thread AttributeError class as the Engine.
    ("localai_tpu/cluster/replica.py", "RemoteReplica"),
    ("localai_tpu/cluster/netspan.py", "StreamAssembler"),
    ("localai_tpu/testing/multihost.py", "WorkerProc"),
    ("localai_tpu/parallel/sharding.py", "ShardingPlanError"),
    # Observability layer (ISSUE 11): the journal/trace structures are
    # touched from the engine loop and HTTP threads — an unassigned attr
    # here is the same loop-killing class as on the Engine.
    ("localai_tpu/observe/journal.py", "EventJournal"),
    ("localai_tpu/observe/trace.py", "RequestTrace"),
    ("localai_tpu/observe/trace.py", "TraceStore"),
]


def uninitialized_reads(cls, module_classes=None):
    """[(attr, method, line)] of self-attribute reads no construction path
    assigns."""
    assigned = astutil.construction_assigned(cls, module_classes)
    exempt = astutil.hasattr_probes(cls)
    found: list[tuple[str, str, int]] = []
    for mname, fn in astutil.methods_of(cls).items():
        for attr, line in sorted(
            astutil.attr_reads(fn).items(), key=lambda kv: kv[1]
        ):
            if attr in assigned or attr in exempt:
                continue
            if attr.startswith("__") and attr.endswith("__"):
                continue  # dunders resolve on the type
            found.append((attr, mname, line))
    return sorted(set(found), key=lambda f: f[2])


class AttrInitPass(Pass):
    id = "attr-init"
    description = (
        "self.x read but never assigned during construction "
        "(loop-thread AttributeError — the BENCH_r05 rc=124 class)"
    )

    def __init__(self, targets=None):
        self.targets = DEFAULT_TARGETS if targets is None else targets

    def run(self, repo: Repo) -> list[Finding]:
        out: list[Finding] = []
        for path, class_name in self.targets:
            if not repo.exists(path) or not repo.in_scope(path):
                continue
            cls = repo.find_class(path, class_name)
            if cls is None:
                continue
            for attr, mname, line in uninitialized_reads(cls, repo.classes(path)):
                out.append(self.finding(
                    path, line,
                    f"self.{attr} read in {class_name}.{mname}() but "
                    f"never assigned during construction — an "
                    f"AttributeError waiting for the first code path "
                    f"that reads it before any writer ran",
                ))
        return out
