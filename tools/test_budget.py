#!/usr/bin/env python3
"""What each test module costs, from a junit file of a whole tier-1 run.

    python tools/test_budget.py <junit.xml> [--write]

The driver runs tier-1 with `-n 6 --dist loadfile`: a module is one worker's,
so the run is no shorter than its longest module. Prints the case-seconds of
every module (set-up and tear-down included, as junit counts them), its
share of the total, and the total; exits 1 when a module is over SHARE_LIMIT
of the total (a share reads the same on a fast machine and a loaded one).
`--write` rewrites `tests/module_seconds.json`, from which
`tests/conftest.py` starts the longest modules first.
"""
import collections
import json
import os
import sys
import xml.etree.ElementTree as ET

SHARE_LIMIT = 0.06  # a third of one worker's load at six workers
SECONDS_FILE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tests", "module_seconds.json")


def module_of(classname: str) -> str:
    """`tests.test_compose.TestGemma2Matrix` -> `tests/test_compose.py`: a
    case's module is its classname without the test classes at its end."""
    parts = classname.split(".")
    while len(parts) > 1 and parts[-1][:1].isupper():
        parts.pop()
    return "/".join(parts) + ".py"


def module_seconds(junit_path: str) -> dict:
    """{module: (case-seconds, cases)} of a junit file, longest first."""
    seconds, cases = collections.Counter(), collections.Counter()
    for case in ET.parse(junit_path).iter("testcase"):
        module = module_of(case.get("classname", ""))
        seconds[module] += float(case.get("time", 0.0))
        cases[module] += 1
    return {m: (s, cases[m]) for m, s in seconds.most_common()}


def over_budget(by_module: dict) -> list:
    total = sum(s for s, _ in by_module.values())
    return [m for m, (s, _) in by_module.items() if s > SHARE_LIMIT * total]


def write_seconds(by_module: dict, path: str = SECONDS_FILE) -> None:
    with open(path, "w") as f:
        json.dump({m: round(s, 1) for m, (s, _) in by_module.items()}, f,
                  indent=0)
        f.write("\n")


def main(argv) -> int:
    args = [a for a in argv if a != "--write"]
    if len(args) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    by_module = module_seconds(args[0])
    total = sum(s for s, _ in by_module.values())
    late = over_budget(by_module)
    for m, (s, n) in by_module.items():
        print(f"{m:44s} {n:5d} cases {s:8.1f} s {100 * s / total:5.1f}%"
              + ("  OVER" if m in late else ""))
    print(f"{'total':44s} {sum(n for _, n in by_module.values()):5d} cases "
          f"{total:8.1f} s; limit {100 * SHARE_LIMIT:.0f}% = "
          f"{SHARE_LIMIT * total:.1f} s a module")
    if "--write" in argv:
        write_seconds(by_module)
    return 1 if late else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
