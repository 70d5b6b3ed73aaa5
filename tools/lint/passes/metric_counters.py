"""metric-counters: every `self.m_*` counter a class's `metrics()` method
reads must be UNCONDITIONALLY initialized during construction.

The general attr-init pass already catches never-assigned reads; this
stricter companion exists because metric counters are the repeat offender
(the BENCH_r05 rc=124 class) — they get added at a dispatch site (so
attr-init sees an assignment *somewhere*), read in metrics(), and the
__init__ line is what gets forgotten: the first /metrics scrape of a fresh
engine then raises AttributeError.

Generalized from the hard-coded Engine check: applies to every class under
localai_tpu/ that defines a `metrics()` method.
"""

from __future__ import annotations

import ast

from .. import astutil
from ..core import Finding, Pass, Repo

DEFAULT_GLOBS = ["localai_tpu/**/*.py", "localai_tpu/*.py"]


def uninitialized_counters(cls, module_classes=None):
    """[(attr, line)] of m_* counters metrics() reads but construction never
    assigns."""
    methods = astutil.methods_of(cls)
    if "metrics" not in methods:
        return []
    init_assigned: set[str] = set()
    for name in astutil.construction_methods(methods):
        init_assigned |= astutil.attr_stores(methods[name])
    if module_classes:
        # super().__init__ runs same-module base constructors.
        for base in cls.bases:
            bname = (base.id if isinstance(base, ast.Name)
                     else getattr(base, "attr", ""))
            bcls = module_classes.get(bname)
            if bcls is not None and bcls is not cls:
                init_assigned |= astutil.construction_assigned(
                    bcls, module_classes
                )
    exempt = astutil.hasattr_probes(cls)
    return sorted(
        (attr, line)
        for attr, line in astutil.attr_reads(methods["metrics"]).items()
        if attr.startswith("m_")
        and attr not in init_assigned
        and attr not in exempt
    )


class MetricCountersPass(Pass):
    id = "metric-counters"
    description = (
        "m_* counter read in metrics() but not initialized in __init__ "
        "(fresh-instance scrape AttributeError)"
    )

    def __init__(self, globs=None):
        self.globs = DEFAULT_GLOBS if globs is None else globs

    def run(self, repo: Repo) -> list[Finding]:
        out: list[Finding] = []
        for path in repo.files(*self.globs):
            if not repo.in_scope(path):
                continue  # --since incremental mode
            tree = repo.tree(path)
            module_classes = repo.classes(path)
            for cls in ast.walk(tree):
                if not isinstance(cls, ast.ClassDef):
                    continue
                for attr, line in uninitialized_counters(cls, module_classes):
                    out.append(self.finding(
                        path, line,
                        f"metric counter self.{attr} read in "
                        f"{cls.name}.metrics() but never initialized in "
                        f"__init__ — the scrape would AttributeError on "
                        f"a fresh instance",
                    ))
        return out
