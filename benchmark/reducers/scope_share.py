"""Device time by program and named scope, from the capture alone.

The program writes every op under a `jax.named_scope` of one vocabulary
(`localai_tpu/observe/scopes.py`; `SCOPES` and `SLICES` below are this
reader's copy, pinned equal by tests/test_scopes.py) and the capture holds
each op's `op_name` (its `tf_op` stat) and the fingerprint of the program it
ran in (`harness/xplane_meta.py`). An op belongs to the leaf its path ENDS in
once everything that is no word of the vocabulary is dropped (`jit(..)`,
`while`, `body`, `cond`, `branch_*`, `closed_call`, `shard_map`, `vmap(..)`,
the trailing primitive); to "slices" where the path holds a per-layer slice
scope; to "none" where it ends in no leaf (an op XLA made itself carries no
name at all). One kind of op loses jax's name on the way: XLA:TPU rewrites
`lax.ragged_dot` into custom calls it names `ragged-dot-none.N`; the program
calls that primitive in one place, so `REWRITTEN` reads the bare name as that
place's leaf.

`read(ctx, scope, programs=None)`: self time, inside the marked window, of
the ops of the programs whose module name starts with one of `programs` (all
programs without it) that belong to `scope` (a leaf; a prefix of leaves such
as `mlp`; `"slices"`; `"none"`), over the self time of all ops of those
programs, mean over chips, in %. None without a capture, and where no op of
those programs carries a scope of the vocabulary (the parent of PR 37: its
scopes were `attention`, `mlp`, `lm_head` and the slices).

Every traced run prints the whole table to standard error once: program kind
x scope, ms in the window (mean chip), share of the kind, and XLA's own
`flops` and `bytes_accessed` summed over the ops that nest nothing, with what
they make of the chip's peaks. Those two are NOT a registered roofline: a
Pallas call counts 0 flops and XLA's bytes are of operands, not of what a
kernel moves. The limit of the method: a fusion carries ONE `op_name`, so an
op XLA fused across a scope boundary is booked whole to one side.
"""
from __future__ import annotations

import sys

from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X

SCOPES = (
    "embed", "lm_head", "sample", "control",
    "attention/proj", "attention/rope", "attention/mix",
    "attention/cache_write", "attention/out",
    "mlp/router", "mlp/experts", "mlp/shared", "mlp/dense", "layer",
)
SLICES = ("layer_weights", "layer_kv_pool", "layer_conv_rows", "layer_state")
# What XLA:TPU names an op it rewrites into a custom call of its own, in place
# of jax's op_name (`lax.ragged_dot` -> `ragged-dot-none.N`), and the leaf the
# program's one call site of that primitive is written under.
REWRITTEN = (("ragged-dot", "mlp/experts"),)
_WORDS = frozenset(w for leaf in SCOPES for w in leaf.split("/")) | set(SLICES)
_printed: dict = {}


def leaf_of(tf_op: str) -> str:
    """The owner of an op by its `tf_op` (`<op_name>:<op type>`; XLA joins
    the names of ops it merged with `;`, the first one counts)."""
    name = tf_op.rsplit(":", 1)[0].split(";")[0]
    if "/" not in name:
        return next((leaf for prefix, leaf in REWRITTEN
                     if name.startswith(prefix)), "none")
    path = [p for p in name.split("/")[:-1] if p in _WORDS]
    if any(p in SLICES for p in path):
        return "slices"
    for i in range(len(path)):
        if "/".join(path[i:]) in SCOPES:
            return "/".join(path[i:])
    return "none"


def kind_of(module_name: str) -> str:
    """`jit_admit(151…)` -> `jit_admit`."""
    return module_name.split("(", 1)[0]


def account(planes, ops_out: dict | None = None) -> list[dict]:
    """Per chip, {(program kind, scope): [self ns, flops, bytes]} of the ops
    that start inside the marked window (the device span without a mark).
    With `ops_out`, it also receives {(program kind, scope, op): self ns}
    summed over chips, the op named as `trace_reduce.short_name` names it."""
    lo, hi = TRD.marked_window(planes) or TRD.device_span(planes)
    out = []
    for p in planes:
        if not p.get("ops"):
            continue
        kinds = {m.program_id: kind_of(m.name) for m in p["modules"]}
        own, parent = X.self_ns(p["ops"])
        table: dict = {}
        names: dict = {}  # an op's event name is its whole instruction
        for op, t, nests in zip(p["ops"], own, parent):
            if not lo <= op.start_ns < hi:
                continue
            key = (kinds.get(op.program_id, "?"), leaf_of(op.tf_op))
            cell = table.setdefault(key, [0.0, 0.0, 0.0])
            cell[0] += t
            if not nests:
                cell[1] += op.flops
                cell[2] += op.bytes_accessed
            if ops_out is not None:
                short = names.get(op.name)
                if short is None:
                    short = names[op.name] = TRD.short_name(op.name)
                ops_out[key + (short,)] = ops_out.get(key + (short,), 0.0) + t
        out.append(table)
    return out


def matches(leaf: str, scope: str) -> bool:
    return leaf == scope or leaf.startswith(scope + "/")


def share(tables, scope, programs=None):
    shares = []
    for table in tables:
        mine = {k: v[0] for k, v in table.items()
                if programs is None or any(k[0].startswith(p) for p in programs)}
        total = sum(mine.values())
        if not total or all(k[1] == "none" for k in mine):
            continue
        part = sum(t for k, t in mine.items() if matches(k[1], scope))
        shares.append(100.0 * part / total)
    return sum(shares) / len(shares) if shares else None


def print_ops(ops, chips, busy_ns, top=16, out=sys.stderr):
    """Per program kind that takes over 2% of the device, its largest ops by
    self time (mean chip), each with its scope: what `breakdown.device_ops`
    cannot say of an op is whose it is."""
    kinds: dict = {}
    for (kind, leaf, name), t in ops.items():
        kinds.setdefault(kind, []).append((t / chips, leaf, name))
    for kind, rows in sorted(kinds.items(), key=lambda kv: -sum(r[0] for r in kv[1])):
        total = sum(r[0] for r in rows)
        if total < 0.02 * busy_ns:
            continue
        print(f"[scope_share] {kind}: its {top} largest ops, self ms (mean "
              "chip), share of the program, scope", file=out)
        for t, leaf, name in sorted(rows, reverse=True)[:top]:
            print(f"[scope_share]   {t / 1e6:10.3f} ms {100.0 * t / total:6.2f}%  "
                  f"{leaf}  {name}", file=out)
    out.flush()


def print_table(tables, peaks, window_s, out=sys.stderr):
    """Program kind x scope, mean chip: ms, share of the kind, XLA's flops
    and bytes and what they make of the peaks."""
    n = len(tables)
    mean: dict = {}
    for table in tables:
        for k, v in table.items():
            cell = mean.setdefault(k, [0.0, 0.0, 0.0])
            for i in range(3):
                cell[i] += v[i] / n
    by_kind: dict = {}
    for (kind, leaf), v in mean.items():
        by_kind.setdefault(kind, {})[leaf] = v
    busy = sum(v[0] for v in mean.values())
    print(f"[scope_share] device time by program and scope, {n} chip(s), "
          f"window {window_s:.3f} s, busy {busy / 1e9:.3f} s", file=out)
    for kind, leaves in sorted(by_kind.items(),
                               key=lambda kv: -sum(v[0] for v in kv[1].values())):
        total = sum(v[0] for v in leaves.values())
        print(f"[scope_share] {kind}: {total / 1e6:.3f} ms, "
              f"{100.0 * total / busy:.2f}% of busy", file=out)
        for leaf, (t, flops, nbytes) in sorted(leaves.items(),
                                               key=lambda kv: -kv[1][0]):
            line = (f"[scope_share]   {leaf:22s} {t / 1e6:10.3f} ms "
                    f"{100.0 * t / total:6.2f}%  flops {flops:.3e} "
                    f"bytes {nbytes:.3e}")
            if peaks and t:
                line += (f"  of peak: flops {100.0 * flops / (t / 1e9) / peaks['bf16_flops']:.1f}%"
                         f" hbm {100.0 * nbytes / (t / 1e9) / peaks['hbm_bytes_per_s']:.1f}%")
            print(line, file=out)
    out.flush()


def read(ctx, scope, programs=None):
    planes = X.load(ctx)
    if planes is None:
        return None
    if _printed.get("planes") is not planes:
        _printed.clear()
        ops: dict = {}
        _printed.update(planes=planes, tables=account(planes, ops))
        tables = _printed["tables"]
        if tables:
            lo, hi = TRD.marked_window(planes) or TRD.device_span(planes)
            print_table(tables, ctx.get("peaks"), (hi - lo) / 1e9)
            print_ops(ops, len(tables),
                      sum(v[0] for t in tables for v in t.values()) / len(tables))
    return share(_printed["tables"], scope, programs)
