"""Microseconds of device time an admission spends on a prompt token.

Device time of the executions of the admission programs (modules whose name
starts with one of `programs`: `jit_admit*`, `jit_prefill_chunk*`) that the
capture holds whole, over the prompt tokens those executions carried. The
tokens come from the host's `dispatch/<program>` spans (`tokens=`, written
where the group is built: `Engine._dispatch_admit` and the cached and chunk
dispatches), paired with the executions in dispatch order: a chip runs its
programs in the order they were dispatched, and a span begins before its
execution does. What the order alone cannot tell is how many executions at
the capture's start were dispatched before it began (a span is recorded only
if it began inside the capture): the offset is the smallest one under which
every span begins before its execution AND spans of one (program, m, bucket)
always meet the same program fingerprint, and different ones different
fingerprints; an offset that leaves most spans without an execution is no
pairing. Where no offset is consistent on some chip (flags that share an
(m, bucket), a capture that lost events) nothing is reported and standard
error says so: a broken pairing shows as a missing metric. On four
chips the mean chip's; None without a capture, without spans that carry
`tokens` (the parent of PR 37), or without a paired whole execution.

The cross-check, to standard error: the journal holds one `admit_rows` event
a program in the same dispatch order, so the spans' tokens are a run of the
journal's `b`s, and the executions the capture holds before its first span
are the events before that run. us a token x the journal's tokens of ALL the
whole admission executions of the capture is set beside what the module line
holds for the same executions; the ones an end of the capture cut (whole
tokens, part of the time) are listed apart, and with them the sum is what
`admit_device_share` is made of. A program's cost follows its rows, not its
tokens, so the few executions outside the paired ones carry their own cost a
token: the line gives it.
"""
from __future__ import annotations

import sys

from benchmark.harness import trace_reduce as TRD
from benchmark.harness import xplane_meta as X

MAX_OFFSET = 16


def admission_runs(plane, programs):
    """[(Module, whole)] of the admission executions on this chip, by start;
    `whole` is False for the first and the last event of the chip's module
    line, which the capture's two ends may have cut."""
    mods = sorted(plane["modules"], key=lambda m: m.start_ns)
    return [(m, 0 < i < len(mods) - 1) for i, m in enumerate(mods)
            if any(m.name.startswith(p) for p in programs)]


def spans(planes, programs):
    """[(key, start_ns, tokens)] of the dispatch spans of those programs that
    carry `tokens`, by start; key = (span name, m, bucket)."""
    names = tuple("dispatch/" + p[len("jit_"):] for p in programs)
    out = []
    for p in planes:
        for name, start, _dur, st in p.get("dispatch", ()):
            if name.startswith(names) and "tokens" in st:
                key = (name, str(st.get("m")), str(st.get("bucket")))
                out.append((key, start, float(st["tokens"])))
    return sorted(out, key=lambda s: s[1])


def pair(runs, sp):
    """The smallest consistent offset k (runs[k + i] ran what sp[i]
    dispatched), or None."""
    for k in range(min(MAX_OFFSET, len(runs)) + 1):
        pairs = list(zip(runs[k:], sp))
        if 2 * len(pairs) < len(sp) or not pairs:
            return None  # most spans would be left without an execution
        seen: dict = {}
        back: dict = {}
        ok = True
        for (mod, _whole), (key, start, _tok) in pairs:
            fp = mod.program_id
            if (start > mod.start_ns or seen.setdefault(key, fp) != fp
                    or back.setdefault(fp, key) != key):
                ok = False
                break
        if ok:
            return k
    return None


def per_chip(planes, programs):
    """[(device ns, tokens, executions, runs, offset)] per chip: the first
    three over the paired executions the capture holds whole."""
    sp = spans(planes, programs)
    out = []
    for p in planes:
        if not sp or not p.get("modules") or not TRD.device_planes([p]):
            continue
        runs = admission_runs(p, programs)
        k = pair(runs, sp)
        if k is None:
            print(f"[admit_per_token] {p['name']}: no consistent pairing of "
                  f"{len(runs)} executions with {len(sp)} spans: nothing "
                  "is reported", file=sys.stderr, flush=True)
            return []
        whole = [(m.dur_ns, tok) for (m, w), (_key, _start, tok)
                 in zip(runs[k:], sp) if w]
        if whole:
            out.append((sum(d for d, _ in whole), sum(t for _, t in whole),
                        len(whole), runs, k))
    return out


def journal_run(rows, sp):
    """Where the spans' tokens sit in the journal's `admit_rows` events (both
    in dispatch order): the one index j with rows[j + i]["b"] == sp[i]'s
    tokens for every i, or None (no such run, or more than one)."""
    toks = [tok for _key, _start, tok in sp]
    bs = [e["b"] for e in rows]
    found = [j for j in range(len(bs) - len(toks) + 1)
             if bs[j:j + len(toks)] == toks]
    return found[0] if len(found) == 1 else None


def crosscheck(ctx, programs, us, chips, sp, out=sys.stderr):
    """To standard error, like with like (module docstring); returns the
    residual over the whole executions, in %."""
    n = len(chips)
    line = (f"[admit_per_token] {us:.2f} us a prompt token over "
            f"{sum(c[2] for c in chips) // n} whole executions "
            f"and {sum(c[1] for c in chips) / n:.0f} tokens a chip")
    rows = [e for e in ctx.get("journal") or () if e["event"] == "admit_rows"]
    mods = ((ctx.get("trace") or {}).get("reduced") or {}).get("modules") or {}
    total_s = sum(m["total_s"] for m in mods.values())
    j = journal_run(rows, sp) if rows and sp else None
    if j is None or not total_s:
        print(line + f"; the journal's {len(rows)} admit_rows hold no one run "
              f"of the {len(sp)} spans' tokens: no cross-check",
              file=out, flush=True)
        return None
    # [executions, device ns, journal tokens], mean chip, of the executions
    # that are paired and whole (what `us` is made of), of the whole ones
    # dispatched before the capture began, and of those an end has cut
    paired, early, cut = [0.0] * 3, [0.0] * 3, [0.0] * 3
    for _ns, _tok, _n, runs, k in chips:
        for i, (mod, whole) in enumerate(runs):
            if not 0 <= j + i - k < len(rows):
                continue  # outside the journal's window
            kind = cut if not whole else early if i < k else paired
            for x, v in enumerate((1.0, mod.dur_ns, rows[j + i - k]["b"])):
                kind[x] += v / n
    whole_ns, whole_tok = paired[1] + early[1], paired[2] + early[2]
    if not whole_ns:
        print(line + "; none of the capture's whole admission executions has "
              "a journal event: no cross-check", file=out, flush=True)
        return None
    residual = 100.0 * (us * whole_tok * 1e3 / whole_ns - 1.0)
    with_cut = 100.0 * (us * (whole_tok + cut[2]) * 1e3
                        / (whole_ns + cut[1]) - 1.0)
    line += (f"; journal events {j - max(c[4] for c in chips)}.. of {len(rows)}"
             f": {early[0]:.1f} executions dispatched before the capture began"
             f" took {early[1] / 1e6:.3f} ms for {early[2]:.0f} tokens"
             + (f" ({early[1] / 1e3 / early[2]:.2f} us a token)" if early[2] else "")
             + f", {cut[0]:.1f} cut by an end of the capture show "
             f"{cut[1] / 1e6:.3f} ms for {cut[2]:.0f} tokens; us x the "
             f"{whole_tok:.0f} tokens of all {paired[0] + early[0]:.1f} whole "
             f"executions = {us * whole_tok / 1e3:.3f} ms against "
             f"{whole_ns / 1e6:.3f} ms on the module line: residual "
             f"{residual:+.2f}%; with the cut ones {with_cut:+.2f}%, of "
             f"{(whole_ns + cut[1]) / 1e6:.3f} ms = "
             f"{100.0 * (whole_ns + cut[1]) / 1e9 / total_s:.3f}% of all "
             f"programs' {total_s * 1e3:.1f} ms (admit_device_share)")
    print(line, file=out, flush=True)
    return residual


def read(ctx, programs):
    planes = X.load(ctx)
    if planes is None:
        return None
    chips = [c for c in per_chip(planes, programs) if c[1]]
    if not chips:
        return None
    us = sum(c[0] / 1e3 / c[1] for c in chips) / len(chips)
    crosscheck(ctx, programs, us, chips, spans(planes, programs))
    return us
