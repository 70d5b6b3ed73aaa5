#!/usr/bin/env python3
"""Derive the tolerance of `correct` on the chip, and show it discriminates.

    python3 benchmark/tools/tolerance.py --config <name> [--seeds a,b,c]

One set-up (the configuration's model through the normal server objects),
then for every seed: the system's greedy sample with top-20 logprobs, and the
plain reference four times over the SAME ids:

  float32      the reference proper; `system vs float32` is what a run checks
  bfloat16     honest bf16 rounding over all layers; `bfloat16 vs float32` is
               the error a correct bf16 server may show, and sets the tolerance
  int4 weights `system vs int4` must FAIL at the tolerance
  fp8 k/v      `system vs fp8` must FAIL at the tolerance

Writes chiprun_out/tolerance.<config>.json and prints one row per seed. Not
part of a run. `--rehearsal` runs the tiny CPU variant (no number it prints
is a device number).
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


def worst(errors: list[dict]) -> dict:
    return {k: max(e[k] for e in errors)
            for k in ("chosen", "top", "argmax_gap", "best_missing")}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--seeds", default="0,1,2,3,4,2147483653")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--variants", default="float32,bfloat16,int4,fp8")
    args = ap.parse_args()
    import jax
    import numpy as np

    from benchmark.harness import check as C
    from benchmark.harness import spec as S
    from benchmark.harness import system as SY

    config = S.config(args.config)
    if args.rehearsal:
        config = {**config, "yaml": config["rehearsal"]["yaml"],
                  "check": config["rehearsal"]["check"]}
    elif jax.devices()[0].platform != "tpu":
        print("needs a TPU (or --rehearsal)", file=sys.stderr)
        return 3
    spec = config["check"]
    tol = float(spec["tolerance"])
    t0 = time.monotonic()
    system = SY.System(config, os.path.join(S.SCRATCH, "tolerance"))
    ref = importlib.import_module(f"benchmark.reference.{config['reference']}")
    kinds = {"float32": {}, "bfloat16": {"compute": "bfloat16"},
             "int4": {"weight_round": "int4"}, "fp8": {"kv_round": "fp8"}}
    rows = []
    try:
        print(f"loaded in {time.monotonic() - t0:.1f}s", file=sys.stderr)
        for seed in [int(x) for x in args.seeds.split(",")]:
            t1 = time.monotonic()
            prompts = C.sample_prompts(seed, int(system.cfg.vocab_size),
                                       spec["prompt_tokens"])
            sys_out = C.run_system(system.engine, prompts, int(spec["new_tokens"]))
            lp = {k: [C.reference_logprobs(ref.forward, system.engine.params,
                                           system.cfg, p, r["ids"], **kw)
                      for p, r in zip(prompts, sys_out)]
                  for k, kw in kinds.items() if k in args.variants.split(",")}
            row = {"seed": seed, "seconds": None}
            for k, refs in lp.items():
                errs = [C.compare(r, x) for r, x in zip(sys_out, refs)]
                row[f"system_vs_{k}"] = worst(errs)
                row[f"system_vs_{k}_passes"] = C.verdict(errs, tol)
            if "bfloat16" in lp:
                # The honest bf16 error: the bf16 reference's log-probabilities
                # against the float32 reference's, at the chosen ids and over
                # the float32 top 5, the same quantities a run compares.
                e_chosen = e_top = 0.0
                for r, a, b in zip(sys_out, lp["bfloat16"], lp["float32"]):
                    for j, tok in enumerate(r["ids"]):
                        e_chosen = max(e_chosen, abs(float(a[j, tok] - b[j, tok])))
                        top = np.argsort(-b[j])[:5]
                        e_top = max(e_top, float(np.max(np.abs(a[j, top] - b[j, top]))))
                row["bfloat16_vs_float32"] = {"chosen": e_chosen, "top": e_top}
            row["seconds"] = time.monotonic() - t1
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
                           f"tolerance.{args.config}{tag}.json"), "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
