#!/usr/bin/env python3
"""Does `correct` see the `jamba` mixer's inner norms? The second control of
a configuration whose Mamba-1 layers norm dt, B and C (`tolerance.py` has the
first: a precision lower).

    python3 benchmark/tools/norms_control.py --config <name> [--seeds a,b,c]

One set-up (the configuration's model through the normal server objects),
then for every seed the system's greedy sample with top-20 logprobs over the
check's prompts, and the plain reference twice over the SAME ids:

  float32    the reference proper: must PASS at the file's tolerance
  no_norms   the reference with the three inner norms taken OUT (plain
             Mamba-1's mixer; `forward(inner_norms=False)`): must FAIL it on
             every seed, so that a mixer which forgets what makes this the
             `jamba` mixer cannot pass.

Writes chiprun_out/norms_control.<config>.json and prints one row per seed.
Not part of a run. `--rehearsal` runs the tiny CPU variant (no number it
prints is a device number).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0,1,2147483653")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    import jax

    from benchmark.harness import check as C
    from benchmark.harness import spec as S
    from benchmark.harness import system as SY
    from benchmark.tools.tolerance import worst

    config = S.config(args.config)
    if args.rehearsal:
        config = {**config, "yaml": config["rehearsal"]["yaml"],
                  "check": config["rehearsal"]["check"]}
    elif jax.devices()[0].platform != "tpu":
        print("needs a TPU (or --rehearsal)", file=sys.stderr)
        return 3
    spec = config["check"]
    tol = float(spec["tolerance"])
    system = SY.System(config, os.path.join(S.SCRATCH, "norms_control"))
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    rows = []
    try:
        for seed in [int(x) for x in args.seeds.split(",")]:
            t0 = time.monotonic()
            prompts = C.sample_prompts(seed, int(system.cfg.vocab_size),
                                       spec["prompt_tokens"])
            sys_out = C.run_system(system.engine, prompts, int(spec["new_tokens"]))
            row = {"seed": seed}
            for k, kw in (("float32", {}), ("no_norms", {"inner_norms": False})):
                errs = [C.compare(r, C.reference_logprobs(
                    ref.forward, system.engine.params, system.cfg, p,
                    r["ids"], **kw)) for p, r in zip(prompts, sys_out)]
                row[f"system_vs_{k}"] = worst(errs)
                row[f"{k}_passes"] = C.verdict(errs, tol)
            row["seconds"] = time.monotonic() - t0
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        system.stop()
    dev = jax.devices()[0]
    out = {"config": args.config, "tolerance": tol,
           "rehearsal": bool(args.rehearsal),
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())},
           "rows": rows}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    tag = ".rehearsal" if args.rehearsal else ""
    with open(os.path.join(ROOT, "chiprun_out",
                           f"norms_control.{args.config}{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
