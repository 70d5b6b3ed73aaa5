"""From the load generator's stamp file to end-to-end numbers. No JAX.

Every time in a stamp file is `time.monotonic()` of the child; on Linux that
clock is CLOCK_MONOTONIC for every process of the machine, so the parent's
own stamps (process start, trace window) are on it too.
"""

from __future__ import annotations

import json
import math


def percentile(values: list[float], q: float) -> float:
    """Linear-interpolated percentile (q in 0..100) of a non-empty list."""
    if not values:
        raise ValueError("percentile of no values")
    xs = sorted(values)
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def load(path: str) -> dict:
    """{"t0", "seconds", "requests": [...]} from a stamp file: the
    first line is the header, each further line one request."""
    with open(path) as f:
        head = json.loads(f.readline())
        head["requests"] = [json.loads(line) for line in f if line.strip()]
    return head


def ok(r: dict) -> bool:
    """Returned 200, streamed to [DONE] and carried no error event."""
    return r.get("status") == 200 and r.get("done") and not r.get("error")


def in_window(head: dict, t: float | None) -> bool:
    return t is not None and head["t0"] <= t <= head["t0"] + head["seconds"]


def measured(head: dict) -> list[dict]:
    """The requests the window judges: sent or finished in it (the ramp's
    requests that end inside count because their tokens do)."""
    return [r for r in head["requests"]
            if in_window(head, r.get("send")) or in_window(head, r.get("end"))]


def failed(head: dict) -> list[dict]:
    """Measured requests that were refused, shed, errored, or ended without
    [DONE]. A request still streaming when the window closes was cut by the
    generator, not failed."""
    out = []
    for r in measured(head):
        if r.get("cut"):
            continue
        if not ok(r):
            out.append(r)
    return out


def tokens_in_window(head: dict) -> int:
    """Content chunks (one per token) that arrived inside the window, over
    every request that did not fail: all the work of the window."""
    n = 0
    for r in head["requests"]:
        if r.get("error") or r.get("status") not in (200, None):
            continue
        n += sum(1 for t in r.get("chunks", ()) if in_window(head, t))
    return n


def end_to_end(head: dict) -> dict:
    """name -> value for every end-to-end number the stamps can give."""
    out = {"out_tokens_per_s": tokens_in_window(head) / head["seconds"]}
    tpot = [(r["chunks"][-1] - r["chunks"][0]) * 1000.0 / (len(r["chunks"]) - 1)
            for r in head["requests"]
            if ok(r) and in_window(head, r.get("end"))
            and len(r.get("chunks", ())) > 1]
    if tpot:
        out["tpot_ms_p95"] = percentile(tpot, 95)
    return out


def streams_consistent(head: dict) -> list[str]:
    """What `correct` asks of the window: every request that returned 200 and
    was not cut by the generator ended in [DONE] with SSE content chunks =
    usage.completion_tokens = the max_tokens asked. Nothing here depends on
    load or timing."""
    bad = []
    for r in head["requests"]:
        if r.get("status") != 200 or r.get("cut") or r.get("error"):
            continue
        n = len(r.get("chunks", ()))
        if not r.get("done"):
            bad.append(f"{r['i']}: 200 but no [DONE]")
        elif not (n == r.get("completion_tokens") == r["max_tokens"]):
            bad.append(f"{r['i']}: chunks {n}, usage "
                       f"{r.get('completion_tokens')}, asked {r['max_tokens']}")
    return bad
