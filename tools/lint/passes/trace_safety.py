"""trace-safety: host syncs and recompile triggers where they serialize the
pipeline.

TPU serving lives or dies on keeping the host out of the per-token critical
path ("Ragged Paged Attention", arxiv 2604.15464; pjit training at scale,
arxiv 2204.06514: one stray device→host sync serializes the whole pipeline).
Three bug classes, three sub-checks:

1. TRACED MODULES (localai_tpu/ops/*.py, localai_tpu/models/llama.py —
   everything there runs under jit/pjit or inside a Pallas kernel): flag
   `.item()` / `.tolist()` / `.block_until_ready()` / `jax.device_get` /
   `np.asarray`-on-traced, `int()`/`float()`/`bool()` of a traced local, and
   Python `if`/`while`/`assert` branching on a traced value (use `jnp.where`
   / `lax.cond`). "Traced" is inferred by local dataflow: a name assigned
   from a jnp/lax/jax.random call, or arithmetic/indexing thereof. numpy on
   STATIC values (building trace-time constants, e.g. rope tables) is fine
   and not flagged.

2. ENGINE HOT PATH (the decode/admission methods of Engine): flag
   `.item()` / `.tolist()` / `block_until_ready` / `jax.device_get`, and
   `np.asarray` / `np.array` whose argument references a device-resident
   root (self.cache/rngs/counts/bias/d_tokens/d_positions/d_gstate/d_cache,
   or an entry's toks/tk/lp). Host-side numpy on python lists is fine.
   Known-good sync points (the drainer-backed inline pull) carry
   suppressions with written reasons.

3. RECOMPILE TRIGGERS: inside the hot path, array constructors
   (jnp.zeros/ones/full/empty/arange) whose shape derives from a per-call
   Python value (a local not derived from self.cfg/self.ecfg constants) —
   every distinct value compiles a new program. Intentional per-(m, bucket)
   program families carry suppressions documenting that contract.
"""

from __future__ import annotations

import ast
from typing import Optional

from .. import astutil
from ..core import Finding, Pass, Repo

TRACED_MODULE_GLOBS = [
    "localai_tpu/ops/*.py",
    "localai_tpu/models/llama.py",
    # The cluster layer is host-side BY CONTRACT (it sits on every dispatch
    # path): any jnp/lax value it manufactures — and then branches on or
    # pulls — is a sync the scheduler would pay per request.
    "localai_tpu/cluster/*.py",
    # The parallel layer traces inside every sharded program (shard_map
    # bodies, ring rotation) — a host sync here stalls ALL chips (ISSUE 7).
    "localai_tpu/parallel/*.py",
    # The observability layer (ISSUE 11) rides the engine loop between
    # every dispatch: journal appends, trace notes, timeline/postmortem
    # reads must never sync the device. observe/profile.py is EXCLUDED by
    # design — it is the declared measurement point (LOCALAI_PROFILE),
    # exactly like the engine drainer thread is excluded from HOT_METHODS.
    "localai_tpu/observe/journal.py",
    "localai_tpu/observe/trace.py",
    "localai_tpu/observe/timeline.py",
    "localai_tpu/observe/postmortem.py",
    # Prompt-lookup drafting (ISSUE 12): the suffix index runs on the
    # engine loop between every dispatch — it must stay pure Python/numpy
    # (a traced value or device pull here stalls the whole decode cadence).
    "localai_tpu/engine/speclookup.py",
]

ENGINE_TARGET = ("localai_tpu/engine/engine.py", "Engine")

# The decode/admission steady state: every loop iteration flows through
# these. Excluded by design: warmup (pre-traffic), preemption/swap
# (_preempt_youngest, _swap_*_pages — declared drain points where the loop
# has already quiesced the device), and the drainer thread (its whole job
# is to host-sync off the critical path).
HOT_METHODS = {
    "_loop", "_admit_pending", "_purge_pending", "_enforce_deadlines",
    "_advance_chunked", "_chunk_start", "_dispatch_chunk_mid",
    "_dispatch_chunk_final", "_dispatch_admit", "_dispatch_admit_cached",
    "_dispatch_resume_swap", "_dispatch_block", "_dispatch_spec_block",
    "_process_entry", "_post_token", "_finish", "_release",
    "_grow_for_decode", "_pages_grow_slot", "_pages_alloc", "_pages_free",
    "_pick_block_size", "_has_unscheduled", "_charge", "_track",
    "_note_admitted", "_grammar_choose", "_grammar_advance",
    # Speculative scheduling (ISSUE 12): planning + lookup mining run
    # between every dispatch; the sd-sync walks per-slot state each round.
    "_spec_plan", "_spec_len_for", "_lookup_propose", "_spec_sd_sync",
}

DEVICE_ROOTS = {
    "cache", "d_cache", "counts", "rngs", "bias", "d_tokens", "d_positions",
    "d_gstate", "toks", "tk", "lp",
}

def _walk_scope(fn):
    """Walk a function's own body without descending into nested defs —
    nested functions are visited as scopes of their own (with their own
    traced-locals inference), so flagging them here would double-report."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (*astutil.FunctionNode, ast.ClassDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


_TRACED_CALL_ROOTS = ("jnp", "lax", "jax")
_SYNC_METHOD_CALLS = {"item", "tolist", "block_until_ready"}
_SHAPE_CTORS = {"jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty",
                "jnp.arange"}
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size"}


def _is_traced_call(node: ast.AST) -> bool:
    """Calls that produce traced values: jnp.* / lax.* and the value-level
    jax namespaces. Host-side jax introspection (default_backend, devices,
    config, debug) does not count."""
    if not isinstance(node, ast.Call):
        return False
    name = astutil.dotted_name(node.func)
    if name.startswith(("jnp.", "lax.")):
        return True
    return name.startswith(("jax.lax.", "jax.nn.", "jax.numpy.",
                            "jax.random.", "jax.scipy."))


def _traced_locals(fn) -> set[str]:
    """Names assigned (directly or via arithmetic/indexing) from jnp/lax
    calls within this function. Two fixpoint rounds cover the chains that
    occur in practice."""
    traced: set[str] = set()

    def expr_traced(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if _is_traced_call(sub):
                return True
            if isinstance(sub, ast.Name) and sub.id in traced:
                return True
        return False

    for _ in range(2):
        for node in _walk_scope(fn):
            if isinstance(node, ast.Assign) and expr_traced(node.value):
                for t in node.targets:
                    for tt in ast.walk(t):
                        if isinstance(tt, ast.Name):
                            traced.add(tt.id)
            elif isinstance(node, ast.AugAssign) and expr_traced(node.value):
                if isinstance(node.target, ast.Name):
                    traced.add(node.target.id)
    return traced


def _test_is_static(node: ast.AST) -> bool:
    """True when every Name/Attribute in a branch test resolves through
    static metadata (.shape/.ndim/.dtype/len()) or plain python values —
    conservative: only attribute chains ending in static attrs count."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr not in _STATIC_ATTRS:
            return False
    return True


class TraceSafetyPass(Pass):
    id = "trace-safety"
    description = (
        "host sync / python-branch-on-traced / per-request recompile "
        "trigger in trace-context or engine hot-path code"
    )

    def __init__(self, traced_globs=None, engine_target=None,
                 hot_methods=None):
        self.traced_globs = (TRACED_MODULE_GLOBS if traced_globs is None
                             else traced_globs)
        self.engine_target = (ENGINE_TARGET if engine_target is None
                              else engine_target)
        self.hot_methods = HOT_METHODS if hot_methods is None else hot_methods

    # ---------------- traced modules ---------------- #

    def _check_traced_fn(self, path: str, fn, out: list[Finding]) -> None:
        traced = _traced_locals(fn)

        def is_traced_expr(node: ast.AST) -> bool:
            for sub in ast.walk(node):
                if _is_traced_call(sub):
                    return True
                if isinstance(sub, ast.Name) and sub.id in traced:
                    return True
            return False

        for node in _walk_scope(fn):
            if isinstance(node, ast.Call):
                name = astutil.dotted_name(node.func)
                if (isinstance(node.func, ast.Attribute)
                        and node.func.attr in _SYNC_METHOD_CALLS):
                    out.append(self.finding(
                        path, node.lineno,
                        f".{node.func.attr}() inside trace-context code — "
                        f"a device→host sync that serializes the pipeline "
                        f"(and a TracerError under jit)",
                    ))
                elif name in ("jax.device_get", "jax.block_until_ready"):
                    out.append(self.finding(
                        path, node.lineno,
                        f"{name}() inside trace-context code — host sync",
                    ))
                elif (name in ("np.asarray", "np.array", "numpy.asarray",
                               "numpy.array")
                      and node.args and is_traced_expr(node.args[0])):
                    out.append(self.finding(
                        path, node.lineno,
                        f"{name}() of a traced value — device→host pull "
                        f"inside trace-context code (use jnp)",
                    ))
                elif (name in ("int", "float", "bool") and node.args
                      and isinstance(node.args[0], ast.Name)
                      and node.args[0].id in traced):
                    out.append(self.finding(
                        path, node.lineno,
                        f"{name}(...) of traced local "
                        f"{node.args[0].id!r} — concretizes a tracer "
                        f"(host sync / TracerError)",
                    ))
            elif isinstance(node, (ast.If, ast.While)):
                if is_traced_expr(node.test) and not _test_is_static(node.test):
                    out.append(self.finding(
                        path, node.lineno,
                        "python branch on a traced value — use jnp.where / "
                        "lax.cond / lax.select (branching concretizes the "
                        "tracer; at best a recompile per outcome, at worst "
                        "a TracerBoolConversionError)",
                    ))
            elif isinstance(node, ast.Assert):
                if is_traced_expr(node.test) and not _test_is_static(node.test):
                    out.append(self.finding(
                        path, node.lineno,
                        "assert on a traced value — concretizes the tracer; "
                        "use checkify or move the check to the host caller",
                    ))

    # ---------------- engine hot path ---------------- #

    def _expr_touches_device(self, node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr in DEVICE_ROOTS:
                return True
        return False

    def _static_locals(self, fn) -> set[str]:
        """Names assigned only from constants or self.cfg/self.ecfg/self.plan
        attribute chains — per-engine constants, safe as shapes."""
        static: set[str] = set()
        dynamic: set[str] = set()
        for node in ast.walk(fn):
            if not isinstance(node, ast.Assign):
                continue
            ok = True
            for sub in ast.walk(node.value):
                if isinstance(sub, ast.Name) and sub.id not in ("self",):
                    if sub.id not in static:
                        ok = False
                elif isinstance(sub, ast.Attribute):
                    root = astutil.dotted_name(sub)
                    if not root.startswith(("self.cfg", "self.ecfg",
                                            "self.plan", "self._max_pages")):
                        ok = False
            for t in node.targets:
                if isinstance(t, ast.Name):
                    (static if ok and t.id not in dynamic else dynamic).add(t.id)
                    if not ok:
                        static.discard(t.id)
        return static

    def _check_hot_method(self, path: str, mname: str, fn,
                          out: list[Finding]) -> None:
        static = self._static_locals(fn)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            name = astutil.dotted_name(node.func)
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SYNC_METHOD_CALLS
                    and (node.func.attr == "block_until_ready"
                         or self._expr_touches_device(node.func.value))):
                # .item()/.tolist() on host numpy (already-drained entry
                # results, request fields) is free; only receivers rooted
                # at device-resident state are syncs.
                out.append(self.finding(
                    path, node.lineno,
                    f".{node.func.attr}() in engine hot path "
                    f"({mname}) — blocking device→host sync on the "
                    f"decode/admission critical path",
                ))
            elif name in ("jax.device_get", "jax.block_until_ready"):
                out.append(self.finding(
                    path, node.lineno,
                    f"{name}() in engine hot path ({mname}) — blocking "
                    f"device sync; results should flow through the drainer "
                    f"thread / _host_copy_async instead",
                ))
            elif (name in ("np.asarray", "np.array") and node.args
                  and self._expr_touches_device(node.args[0])):
                out.append(self.finding(
                    path, node.lineno,
                    f"{name}() of a device value in engine hot path "
                    f"({mname}) — synchronous device→host pull; route it "
                    f"through the drainer thread or _host_copy_async",
                ))
            elif name in _SHAPE_CTORS and node.args:
                shape = node.args[0]
                dyn = [
                    sub.id for sub in ast.walk(shape)
                    if isinstance(sub, ast.Name) and sub.id != "self"
                    and sub.id not in static
                ]
                if dyn:
                    out.append(self.finding(
                        path, node.lineno,
                        f"{name}() in engine hot path ({mname}) with shape "
                        f"from per-call value(s) {sorted(set(dyn))} — every "
                        f"distinct value compiles a new XLA program "
                        f"(recompile trigger); bucket it or hoist it",
                    ))

    def run(self, repo: Repo) -> list[Finding]:
        out: list[Finding] = []
        for path in repo.files(*self.traced_globs):
            if not repo.in_scope(path):
                continue  # --since incremental mode
            for node in ast.walk(repo.tree(path)):
                if isinstance(node, astutil.FunctionNode):
                    self._check_traced_fn(path, node, out)
        epath, ecls = self.engine_target
        if repo.exists(epath) and repo.in_scope(epath):
            cls = repo.find_class(epath, ecls)
            if cls is not None:
                for mname, fn in astutil.methods_of(cls).items():
                    if mname in self.hot_methods:
                        self._check_hot_method(epath, mname, fn, out)
        return out
