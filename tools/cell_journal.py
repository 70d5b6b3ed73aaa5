#!/usr/bin/env python3
"""One run of a benchmark cell as `benchmark/run.py` makes it, with what the
run itself throws away kept in a file: the measured window's journal, the
load generator's stamps, both scrapes of `Engine.metrics()`, the traced
span's ends and the collector's ring (`observe/gcwatch`). An untraced run
saves no journal (`PERF.md` section 7, D12), so until a `benchmark` PR does,
this is how the stalls of one are read.

    python tools/cell_journal.py <out.json> --workload <cell> --seed <n> \\
        [--seconds <s>] [--trace <0|1>]
    python tools/cell_journal.py --read <out.json> [...]

The first form runs from the root of a checkout, on the chip, and prints
`run.py`'s result line last; it wraps `benchmark.run.drive_window` and edits
nothing. Both forms print to standard error the account of the loop's time
(`benchmark/reducers/loop_causes.table`): per phase ms in call, collector,
off the CPU and Python, the late wake-ups, and every `loop_stall` and
`gc_pause` with its parts; then the same window by the rise of the gauges
between the two scrapes (`counters`), which no ring overwrites, and whether
the journal still holds all of it. `--read` needs no jax.
"""
from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.reducers import loop_causes as LC  # noqa: E402


def account(dump: dict, title: str) -> list[str]:
    """The stall table of a dump: the marked span of a traced run, the whole
    window of an untraced one."""
    tr = dump.get("trace") or {}
    span = (tr.get("t_start"), tr.get("t_end"))
    if not LC.has_account(dump["events"]):
        return [f"[cell_journal] {title}: the journal holds no account of "
                "the loop's causes (a program before PR 51)"]
    return LC.table(dump["events"], [span], title)


# The gauges of `Engine.metrics()` that count what the journal's events
# carry window by window: their rise between the two scrapes is the whole
# window's, whatever the journal's ring has overwritten since.
_TOTALS = ("loop_blocks", "loop_host_ms_total", "loop_blocked_ms_total",
           "loop_call_ms_total", "loop_gc_ms_total", "loop_off_cpu_ms_total",
           "loop_stalls", "host_gc_pauses", "host_gc_gen2_pauses",
           "host_gc_pause_ms_total")
_MAXIMA = ("loop_stretch_ms_max", "loop_late_ms_max", "host_gc_pause_ms_max")


def counters(dump: dict) -> list[str]:
    """The window by the gauges alone, and the journal held against them:
    where the ring kept every `loop_iter` of the window the two agree."""
    before, after = dump["before"], dump["after"]
    if "loop_call_ms_total" not in after:
        return []
    rise = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in _TOTALS}
    busy = rise["loop_host_ms_total"] - rise["loop_blocked_ms_total"]
    parts = (rise["loop_call_ms_total"], rise["loop_gc_ms_total"],
             rise["loop_off_cpu_ms_total"])
    per = LC.account([e for e in dump["events"] if e["event"] == "loop_iter"])
    kept = LC.split(per)[1]
    out = ["[cell_journal] the gauges' rise over the window: "
           f"{rise['loop_blocks']:.0f} blocks, busy {busy:.1f} ms = in call "
           f"{parts[0]:.1f} + collector {parts[1]:.1f} + off-CPU "
           f"{parts[2]:.1f} + python {busy - sum(parts):.1f}; "
           f"{rise['loop_stalls']:.0f} stalls; the process collected "
           f"{rise['host_gc_pauses']:.0f} times for "
           f"{rise['host_gc_pause_ms_total']:.1f} ms, "
           f"{rise['host_gc_gen2_pauses']:.0f} of them generation 2",
           "[cell_journal] since start (warm-up's compiles included): "
           + ", ".join(f"{k} {after[k]:.1f}" for k in _MAXIMA if k in after),
           f"[cell_journal] the journal holds {kept:.1f} of the "
           f"{parts[0]:.1f} ms in calls the gauges count"]
    if kept < 0.99 * parts[0]:
        # `drive_window` scrapes where a traced run's capture has been
        # parsed, which may be seconds after the window it cuts the journal to
        late = dump.get("scraped_after_s", 0.0)
        out[-1] += (f": the second scrape came {late:.1f} s after the "
                    "window's end" if late > 0.5 else
                    ": the ring has overwritten part of the window")
    return out


def run(out_path: str, argv: list[str]) -> int:
    import benchmark.run as R
    from localai_tpu.observe import gcwatch

    drive = R.drive_window

    def keeping(jax, system, compiles, cell, seconds, *rest):
        win = drive(jax, system, compiles, cell, seconds, *rest)
        tr = win["trace"] or {}
        dump = {"t0": win["t0"], "events": win["events"], "head": win["head"],
                "before": win["before"]["metrics"],
                "after": win["after"]["metrics"],
                "trace": {k: tr.get(k) for k in
                          ("t_start", "t_end", "capture_wall_s")},
                "scraped_after_s": win["after"]["t"] - win["t0"] - seconds,
                "gc_ring": gcwatch.WATCH.recent()}
        os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(dump, f, default=str)
        print("\n".join(account(dump, out_path) + counters(dump)),
              file=sys.stderr, flush=True)
        return win

    R.drive_window = keeping
    sys.argv = [sys.argv[0]] + argv
    return R.main()


def main(argv: list[str]) -> int:
    if len(argv) >= 2 and argv[0] == "--read":
        for path in argv[1:]:
            with open(path) as f:
                dump = json.load(f)
            print("\n".join(account(dump, path) + counters(dump)),
                  file=sys.stderr)
        return 0
    if not argv or argv[0].startswith("-"):
        print(__doc__, file=sys.stderr)
        return 2
    return run(argv[0], argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
