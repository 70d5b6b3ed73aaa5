#!/usr/bin/env python3
"""Did an edit of `engine/engine.py`'s program bodies leave the compiled
programs alone? (PRs 27-37 each asked.)

    PYTHONPATH=<tree> python tools/same_program.py

Prints, for the tree on PYTHONPATH (this checkout, or a `git archive` of the
parent), per program that tiny engines of the four model kinds jit for two
requests (`decode_block`, `admit`, ...), a digest of the multiset of (opcode,
result shape, custom-call target) of `compiled.as_text()`, metadata stripped;
run it once per tree and compare the lines. Compiled on the CPU at tiny
shapes: nothing here is a speed, and nothing says what the TPU compiler makes
of a cell's shapes (PERF.md section 6 "PR 37" has that listing).
"""
import collections
import contextlib
import dataclasses
import hashlib
import os
import re
import sys

_INSTR = re.compile(r"\s*(?:ROOT )?%?[\w.\-]+ = (\S+) (\w[\w\-]*)\(")

TINY = ("tiny", "tiny-olmoe", "tiny-kimi-linear", "tiny-solar-open2",
        "tiny-lfm2", "tiny-granite-h", "tiny-jamba2")


def digest(texts) -> str:
    ops = collections.Counter()
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                cc = re.search(r'custom_call_target="([^"]+)"', line)
                ops[(m.group(2), m.group(1), cc.group(1) if cc else "")] += 1
    blob = "\n".join(f"{k} {v}" for k, v in sorted(ops.items()))
    return (f"{sum(ops.values())} instructions, {len(ops)} distinct, sha256 "
            f"{hashlib.sha256(blob.encode()).hexdigest()[:16]}")


@contextlib.contextmanager
def recorded_programs():
    """While open, every program an `Engine` jits is also compiled, once per
    signature of its arguments, and its compiled text kept: yields
    {program name: [compiled text]} (tests/test_scopes.py reads it too)."""
    import jax

    from localai_tpu.engine import engine as E

    texts = collections.defaultdict(list)
    real = E._named_jit

    def named(fn, name, sites=None, **kw):
        jitted = real(fn, name, sites=sites, **kw)
        seen = set()

        class Recording:
            def __call__(self, *a, **k):
                sig = str(jax.tree.map(
                    lambda x: (getattr(x, "shape", None),
                               str(getattr(x, "dtype", type(x)))), (a, k)))
                if sig not in seen:
                    seen.add(sig)
                    texts[name].append(jitted.lower(*a, **k).compile().as_text())
                return jitted(*a, **k)

            def __getattr__(self, attr):
                return getattr(jitted, attr)

        return Recording()

    E._named_jit = named
    try:
        yield texts
    finally:
        E._named_jit = real


def tiny_engine_programs(name: str) -> dict:
    """The programs a tiny engine of one model kind builds for two requests,
    one greedy and one sampled: {program name: [compiled text]}."""
    import jax

    from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig, GenRequest
    from localai_tpu.models import get_arch
    from localai_tpu.models import llama as L

    cfg = get_arch(name)
    if cfg.recurrent_kind in ("kda", "ssd"):  # as served: a share of the experts
        cfg = dataclasses.replace(cfg, expert_share=(0, 2))
    with recorded_programs() as texts:
        eng = Engine(cfg, L.init_params(cfg, jax.random.key(0)),
                     ByteTokenizer(cfg.vocab_size),
                     engine_cfg=EngineConfig(max_slots=4, max_seq=256,
                                             block_sizes=(8, 1), kv_pages=64,
                                             kv_page_size=16))
        eng.start()
        try:
            handles = [eng.submit(GenRequest(
                prompt_ids=list(range(1, 1 + n)), max_new_tokens=10,
                temperature=t, ignore_eos=True, seed=5))
                for n, t in ((20, 0.0), (30, 0.7))]
            done = [h.result()[1].kind for h in handles]
        finally:
            eng.stop()
    if done != ["done", "done"]:
        raise RuntimeError(f"{name}: requests ended {done}")
    return dict(texts)


def main() -> None:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    from localai_tpu.models.config import PRESETS

    for name in TINY:
        if name not in PRESETS:  # a parent tree that has no such model yet
            print(f"{name}: not a preset of this tree")
            continue
        texts = tiny_engine_programs(name)
        for prog in sorted(texts):
            print(f"{name} {prog} x{len(texts[prog])}: {digest(texts[prog])}")


if __name__ == "__main__":
    if len(sys.argv) > 1:
        sys.exit(__doc__)
    main()
