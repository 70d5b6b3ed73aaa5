#!/usr/bin/env python3
"""A REHEARSAL of the benchmark on the CPU: every cell end to end at the
`tiny` width (the tp cell on virtual devices), for a few seeds, asserting
`correct` and the shape of the last line. It measures nothing: it prints no
device metric, and no number it prints is a speed.

    python3 benchmark/rehearse.py [--seeds 5] [--seconds 4] [--workload NAME]
    python3 benchmark/rehearse.py --workload mistral-7b-int8.decode-saturated \\
        --seeds 2 --yaml prefill_chunk=64     # shows the stale-token fault, PERF.md section 7
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = (0, 7, 2**31 + 5, 123456789, 4242424242)


def tiny(workload: str, yaml_override: dict) -> dict:
    """The cell as `spec.cell` resolves it, with the configuration's own
    tiny variant (its file's `rehearsal` block) in place of the served one
    and the mix's stand-in from benchmark/rehearsal/<mix>.json."""
    sys.path.insert(0, ROOT)
    from benchmark.harness import spec as S

    cell = S.cell(workload)
    config = cell["config"]
    over = config["rehearsal"]
    mix_name = next(w["traffic"] for w in S.manifest()["workloads"]
                    if w["name"] == workload)
    with open(os.path.join(S.BENCH, "rehearsal", f"{mix_name}.json")) as f:
        stand_in = json.load(f)
    cell["config"] = {"name": config["name"], "reference": config["reference"],
                      "yaml": {**over["yaml"], **yaml_override},
                      "check": over["check"]}
    cell["mix"] = {**cell["mix"], **stand_in["mix"]}
    cell["cell"] = {**cell["cell"], "load": stand_in["load"]}
    return cell


def one(workload: str, seed: int, seconds: float, trace: int,
        yaml_override: dict | None = None) -> dict:
    """One rehearsal run in a process of its own (the run holds its devices
    and its server until it exits)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (
        "import json, sys; sys.path.insert(0, %r)\n"
        "from benchmark import rehearse, run\n"
        "r = run.measure(rehearse.tiny(%r, %r), %d, %f, bool(%d))\n"
        "print(json.dumps(r))\n"
        % (ROOT, workload, yaml_override or {}, seed, seconds, trace))
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=1200)
    if p.returncode != 0:
        raise RuntimeError(f"{workload} seed {seed}: exit {p.returncode}\n"
                           f"{p.stderr[-3000:]}")
    return json.loads(p.stdout.strip().splitlines()[-1])


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--workload", default=None)
    ap.add_argument("--trace", type=int, default=None)
    ap.add_argument("--yaml", default="", help="key=value,... changes to the "
                    "rehearsal's model YAML, e.g. prefill_chunk=64")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        man = json.load(f)
    names = [w["name"] for w in man["workloads"]
             if args.workload in (None, w["name"])]
    e2e = {m["name"] for m in man["end_to_end"]}
    override = {k: json.loads(v) for k, v in
                (kv.split("=", 1) for kv in args.yaml.split(",") if kv)}
    failures = 0
    for name in names:
        for k, seed in enumerate(SEEDS[: args.seeds]):
            trace = args.trace if args.trace is not None else int(k == 1)
            r = one(name, seed, args.seconds, trace, override)
            keys_ok = {"correct", "attempted", "failed", "metrics",
                       "device"} <= set(r)
            names_ok = all((n in e2e) != bool(trace) for n in r["metrics"])
            good = (r["correct"] and keys_ok and names_ok and r["metrics"]
                    and r["device"]["platform"] == "cpu"
                    and r["attempted"] > 0)
            failures += not good
            print(json.dumps({
                "REHEARSAL": "cpu, tiny width: not a measurement",
                "workload": name, "seed": seed, "trace": trace,
                "passed": bool(good), "correct": r["correct"],
                "attempted": r["attempted"], "failed": r["failed"],
                "metric_names": sorted(r["metrics"])}), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
