"""How `correct` is decided: numbers, never text.

Before the window, after warm-up, a few prompts drawn from --seed are decoded
greedily through the SAME loaded engine that serves the window, asking for
the top-20 log-probabilities of every generated token (`Engine.submit` with
`GenRequest.logprobs`; ids in, ids out, nothing keyed by token text). The
plain float32 reference then runs one teacher-forced forward over prompt +
the ids the system chose, and per generated position:

  a. the system's logprob of its chosen id is within `tolerance` of the
     reference's logprob of that id;
  b. the same for every id of the reference's top 5 that is in the system's
     top 20, and the reference's best id is in the system's top 20;
  c. the chosen id's reference logprob is within `tolerance` of the
     reference's best (an argmax flip inside the tolerance is agreement).

The tolerance is one number per configuration, in its file, derived on the
chip (benchmark/tools/tolerance.py). Limits of the check: logprob requests
admit one at a time and run the engine's logprob variants of the admission
and decode programs, not the batched programs of the window.
"""

from __future__ import annotations

import random

import numpy as np

SPECIAL_IDS = (256, 257, 258)  # bos, eos, pad of the byte tokenizers


def sample_prompts(seed: int, vocab: int, lengths: list[int]) -> list[list[int]]:
    rng = random.Random(seed ^ 0x5EED)
    out = []
    for n in lengths:
        ids = []
        while len(ids) < n:
            t = rng.randrange(vocab)
            if t not in SPECIAL_IDS:
                ids.append(t)
        out.append(ids)
    return out


def run_system(engine, prompts: list[list[int]], n_new: int,
               top: int = 20, timeout: float = 900.0) -> list[dict]:
    """Greedy decode of each prompt, one after another, through the engine.
    Returns per prompt {"ids", "lp", "top"}: chosen ids, their logprobs, and
    per position a dict id -> logprob of the system's top `top`."""
    from localai_tpu.engine import GenRequest

    out = []
    for ids in prompts:
        h = engine.submit(GenRequest(
            prompt_ids=list(ids), max_new_tokens=n_new, temperature=0.0,
            ignore_eos=True, logprobs=top))
        rec: dict = {"ids": [], "lp": [], "top": []}
        for ev in h:
            if ev.kind == "error":
                raise RuntimeError(f"check request failed: {ev.error}")
            if ev.kind == "token":
                rec["ids"].append(int(ev.token_id))
                rec["lp"].append(float(ev.logprob))
                rec["top"].append({int(i): float(v)
                                   for i, v in (ev.top_logprobs or [])})
        if len(rec["ids"]) != n_new:
            raise RuntimeError(
                f"check request returned {len(rec['ids'])} of {n_new} tokens")
        out.append(rec)
    return out


def reference_logprobs(ref_forward, params, cfg, prompt: list[int],
                       chosen: list[int], **kw) -> np.ndarray:
    """[n_new, V] reference log-probabilities at the generated positions."""
    ids = list(prompt) + list(chosen[:-1])
    rows = [len(prompt) - 1 + j for j in range(len(chosen))]
    return ref_forward(params, cfg, ids, rows, **kw)


def compare(sys_rec: dict, ref_lp: np.ndarray, ref_top: int = 5) -> dict:
    """The three errors of the docstring, each as its worst case over the
    generated positions, plus what was missing from the system's top list."""
    worst = {"chosen": 0.0, "top": 0.0, "argmax_gap": 0.0,
             "best_missing": 0, "positions": len(sys_rec["ids"])}
    for j, (tok, lp, top) in enumerate(
            zip(sys_rec["ids"], sys_rec["lp"], sys_rec["top"])):
        row = ref_lp[j]
        worst["chosen"] = max(worst["chosen"], abs(lp - float(row[tok])))
        order = np.argsort(-row)[:ref_top]
        if int(order[0]) not in top:
            worst["best_missing"] += 1
        for i in order:
            if int(i) in top:
                worst["top"] = max(worst["top"],
                                   abs(top[int(i)] - float(row[int(i)])))
        worst["argmax_gap"] = max(worst["argmax_gap"],
                                  float(row[order[0]]) - float(row[tok]))
    return worst


def verdict(errors: list[dict], tolerance: float) -> bool:
    return all(e["chosen"] <= tolerance and e["top"] <= tolerance
               and e["argmax_gap"] <= tolerance and e["best_missing"] == 0
               for e in errors)


def check(engine, ref_forward, cfg, seed: int, spec: dict) -> dict:
    """Run the whole check; spec is the configuration's "check" block:
    {"prompt_tokens": [...], "new_tokens": n, "tolerance": x}."""
    prompts = sample_prompts(seed, int(cfg.vocab_size), spec["prompt_tokens"])
    sys_out = run_system(engine, prompts, int(spec["new_tokens"]))
    errors = []
    for prompt, rec in zip(prompts, sys_out):
        ref = reference_logprobs(ref_forward, engine.params, cfg, prompt,
                                 rec["ids"])
        errors.append(compare(rec, ref))
    return {"correct": verdict(errors, float(spec["tolerance"])),
            "tolerance": float(spec["tolerance"]), "errors": errors}
