#!/usr/bin/env python3
"""Does cell X's `decode_block` hold op Y? A benchmark cell's decode block
compiled for a DESCRIBED TPU v5e (no chip: libtpu compiles for a topology it
is told about, `.claude/skills/verify/SKILL.md`), at the cell's widths, pool
and slots, as the engine composes it: a `lax.scan` of
`llama.decode_step_windowed` over a block-local window, then
`llama.write_block_to_pool`, the pool donated, parameters and pool under the
engine's shardings (`parallel/sharding.param_shardings_for`, the pool split
by kv head over "tp").

    python tools/cell_program.py [cell ...] [--layers N] [--steps 16]
        [--tp N] [--kv-dtype float8_e4m3fn] [--out DIR]
        [--program decode_block|admit|both] [--admit 4x256]
    PYTHONPATH=<another tree> python tools/cell_program.py ...   # a parent

(`--program admit`: the model's part of an admission of 4 prompts of the
256 bucket, `llama.prefill` and `llama.write_prefill_to_pool`)
prints per cell and program the digest of the compiled text (the multiset of (opcode,
result shape, custom-call target), `tools/same_program.digest`), the Pallas
kernels by name, the call-site tallies (`ops/stacked.SiteCounts`) and every
`copy` whose result has the pool's per-chip shape. `tests/test_pool_write.py`
keeps one such compile as a test. Nothing here runs, so nothing here is a
time. Only ONE process at a time can describe a topology (libtpu's lock).
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import inspect
import json
import os
import pathlib
import re
import sys
import types

ROOT = pathlib.Path(__file__).resolve().parent.parent
CONFIGS = ROOT / "benchmark" / "configs"  # <cell>.json, the served YAML in it
_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')


def describe(topology: str = "v5e:2x2"):
    """The described topology (raises where libtpu cannot describe one).
    Call it from a fixture or a main, never while a module is imported."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    return topologies.get_topology_desc(platform="tpu", topology_name=topology)


@contextlib.contextmanager
def as_on_tpu():
    """While open, `jax.default_backend()` says "tpu", so the dispatchers
    (`paged_flash.use_pallas`, `quant_matmul`, ...) pick their Pallas kernels
    with `interpret=False` as they do on the chip, and the compilation cache
    is off (a described compile is written to it but can never be read)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    real = jax.default_backend
    cache = jax.config.jax_enable_compilation_cache
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.default_backend = real
        jax.config.update("jax_enable_compilation_cache", cache)
        cc.reset_cache()


def cell_yaml(cell: str) -> dict:
    return json.loads((CONFIGS / f"{cell}.json").read_text())["yaml"]


def cell_arch(y: dict, layers: int | None = None):
    """The cell's ArchConfig as the manager builds it (deployment share
    applied), cut to its first `layers` layers if asked."""
    from localai_tpu.models import get_arch
    from localai_tpu.server.manager import _apply_deployment_share

    cfg = types.SimpleNamespace(
        name=y["model"], expert_share=y.get("expert_share"),
        stage_layers=y.get("stage_layers"), vocab_rows=y.get("vocab_rows"))
    arch = _apply_deployment_share(get_arch(y["model"]), cfg)
    if layers is not None and layers < arch.num_layers:
        arch = dataclasses.replace(
            arch, num_layers=layers,
            layer_kinds=tuple(arch.layer_kinds[:layers]))
    return arch


@dataclasses.dataclass
class Program:
    fn: object  # the jitted program
    args: tuple  # ShapeDtypeStructs under the described shardings
    pool_local: tuple  # one chip's K pool shape [L, P, page, K / tp, D]
    sites: dict | None = None  # SiteCounts of the trace, once compiled
    name: str = "decode_block"
    memory: object = None  # the compiler's memory analysis, once compiled

    def compile_text(self) -> str:
        from localai_tpu.ops.stacked import SiteCounts

        sites = SiteCounts()
        with as_on_tpu(), sites.tracing(self.name):
            traced = self.fn.trace(*self.args)
        with as_on_tpu():
            compiled = traced.lower(lowering_platforms=("tpu",)).compile()
        self.sites = sites.by_program[self.name]
        self.memory = compiled.memory_analysis()
        return compiled.as_text()


def _operands(y: dict, topo, layers, tp, kv_dtype) -> types.SimpleNamespace:
    """What both programs take, as shapes under the described shardings: the
    cell's ArchConfig, its parameters, the pool, the recurrent rows, the page
    table, the pool's scales."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import (
        Mesh,
        NamedSharding,
        SingleDeviceSharding,
        PartitionSpec as P,
    )

    from localai_tpu.models import llama
    from localai_tpu.models.quant import init_params_quantized

    cfg = cell_arch(y, layers)
    tp = int(y.get("tensor_parallel", 1)) if tp is None else tp
    B, S = y["max_slots"], y["context_size"]
    page, pages = y["kv_page_size"], y["kv_pages"]
    if y.get("quantization"):
        params = jax.eval_shape(lambda: init_params_quantized(
            cfg, jax.random.key(0), mode=y["quantization"]))
    else:
        params = jax.eval_shape(
            lambda: llama.init_params(cfg, jax.random.key(0)))
    if tp > 1:
        from localai_tpu.parallel.sharding import param_shardings_for

        mesh = Mesh(np.array(topo.devices[:tp]).reshape(1, 1, 1, tp),
                    ("dp", "sp", "ep", "tp"))
        psh = param_shardings_for(cfg, mesh, params)
        rep = NamedSharding(mesh, P())
        pool_sh = NamedSharding(mesh, P(None, None, None, "tp", None))
    else:
        mesh = None
        rep = pool_sh = SingleDeviceSharding(topo.devices[0])
        psh = jax.tree.map(lambda _: rep, params)
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        params, psh)

    def sds(shape, dt, sh=rep):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sh)

    dt = jnp.dtype(cfg.dtype)
    pool_dt = dt if kv_dtype is None else jnp.dtype(kv_dtype)
    scaled = pool_dt.itemsize == 1
    base = (cfg.cache_layers, pages + 1, page, cfg.cache_kv_heads)
    pool = llama.KVCache(k=sds(base + (cfg.cache_k_dim,), pool_dt, pool_sh),
                         v=sds(base + (cfg.cache_v_dim,), pool_dt, pool_sh))
    rec = None
    if cfg.is_hybrid:
        from localai_tpu.engine import state as ST

        st, cv = jax.eval_shape(lambda: ST.allocate(cfg, B, dt))
        rec = (None if st is None else sds(st.shape, st.dtype),
               sds(cv.shape, cv.dtype))
    return types.SimpleNamespace(
        cfg=cfg, tp=tp, mesh=mesh, B=B, S=S, page=page, dt=dt, sds=sds,
        params=params, pool=pool, rec=rec, table=sds((B, S // page), jnp.int32),
        kv_scale=(sds((2, cfg.cache_kv_heads), jnp.float32) if scaled
                  else None),
        pool_local=base[:3] + (cfg.cache_kv_heads // tp, cfg.cache_k_dim))


def decode_block(y: dict, topo, *, layers: int | None = None,
                 steps: int = 16, tp: int | None = None,
                 kv_dtype: str | None = None) -> Program:
    """The decode block of a cell's YAML (`cell_yaml`) for `topo`."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import llama

    o = _operands(y, topo, layers, tp, kv_dtype)
    cfg, mesh, B, S, dt, sds = o.cfg, o.mesh, o.B, o.S, o.dt, o.sds
    # a tree older than the in-place write takes no impl / mesh there
    takes = inspect.signature(llama.write_block_to_pool).parameters
    write_kw = {k: v for k, v in (("paged_impl", "auto"), ("mesh", mesh))
                if k in takes}

    def block(params, pool, table, tokens, positions, rec, kv_scale):
        win = (cfg.cache_layers, B, steps, cfg.cache_kv_heads)
        lk = jnp.zeros(win + (cfg.cache_k_dim,), dt)
        lv = jnp.zeros(win + (cfg.cache_v_dim,), dt)
        start = positions
        # a tree with window layers carries their rings and the block's rows
        carried = hasattr(llama, "block_recurrent") and rec is not None
        if carried:
            rec = llama.block_recurrent(
                cfg, pool._replace(state=rec[0], conv=rec[1]), B, steps)

        def body(carry, step):
            tokens, positions, lk, lv, rec = carry
            hyb = {} if rec is None else {"recurrent": rec}
            logits, lk, lv, *routed = llama.decode_step_windowed(
                cfg, params, tokens, positions, pool, lk, lv, step,
                ptable=table, paged_impl="auto", mesh=mesh,
                kv_scale=kv_scale, expert_rows=cfg.is_moe, **hyb)
            if rec is not None:
                rec = routed.pop()
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            return (nxt, jnp.minimum(positions + 1, S - 1), lk, lv, rec), nxt

        (tokens, positions, lk, lv, rec), toks = jax.lax.scan(
            body, (tokens, positions, lk, lv, rec), jnp.arange(steps))
        pool = llama.write_block_to_pool(pool, table, lk, lv, start,
                                         kv_scale=kv_scale, **write_kw)
        if carried:
            done = llama.block_recurrent_done(cfg, pool, rec, start)
            rec = (done.state, done.conv)
        return pool, toks, rec

    args = (o.params, o.pool, o.table, sds((B,), jnp.int32),
            sds((B,), jnp.int32), o.rec, o.kv_scale)
    # the engine donates the cache whole: the pool and a hybrid's rows
    return Program(jax.jit(block, donate_argnums=(1, 5)), args, o.pool_local)


def admit(y: dict, topo, *, layers: int | None = None, m: int = 4,
          bucket: int = 256, tp: int | None = None,
          kv_dtype: str | None = None) -> Program:
    """The model's part of a cell's admission program for a group of `m`
    prompts of one `bucket`, as the engine composes it: `llama.prefill` (a
    hybrid model's recurrent rows written to their slots, the grouped
    kernel's rows counted under an expert share) and each prompt's rows into
    its pages (`llama.write_prefill_to_pool`), pool and rows donated. The
    sampling of the first token is the engine's own and is not here."""
    import jax
    import jax.numpy as jnp

    from localai_tpu.models import llama
    from localai_tpu.ops import ptable as PT

    o = _operands(y, topo, layers, tp, kv_dtype)
    cfg, sds = o.cfg, o.sds
    held_rows = cfg.expert_share is not None

    def program(params, pool, rec, table, toks, lens, slots, kv_scale):
        kw = {} if rec is None else {"recurrent": (*rec, slots)}
        logits, ks, vs, *rest = llama.prefill(
            cfg, params, toks, lens, mesh=None if rec else o.mesh,
            expert_rows=held_rows, **kw)
        if rec is not None:
            rec = rest.pop()
        for j in range(m):
            pool = llama.write_prefill_to_pool(
                pool, PT.select_row(table, j), ks, vs, j, kv_scale=kv_scale)
        return pool, rec, logits, rest

    args = (o.params, o.pool, o.rec, sds((m, o.S // o.page), jnp.int32),
            sds((m, bucket), jnp.int32), sds((m,), jnp.int32),
            sds((m,), jnp.int32), o.kv_scale)
    return Program(jax.jit(program, donate_argnums=(1, 2)), args,
                   o.pool_local, name="admit")


def pool_copies(text: str, pool_local: tuple) -> list[str]:
    """The `copy` instructions of a compiled text whose result holds one
    chip's whole pool, in whatever order of axes: a relayout of the pool."""
    want = sorted(pool_local)
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]+)\]\S* "
                     r"copy\(", line)
        if m and sorted(int(d) for d in m.group(2).split(",")) == want:
            out.append(line.strip().split(", metadata=")[0])
    return out


def kernels(text: str) -> dict[str, int]:
    """Pallas kernels of a compiled text by their `name=`."""
    names = collections.Counter()
    for line in text.splitlines():
        if _KERNEL.search(line):
            m = re.match(r"\s*(?:ROOT )?%?([A-Za-z_][\w\-]*?)[.\d]* = ", line)
            names[m.group(1) if m else "?"] += 1
    return dict(names)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("cells", nargs="*",
                    default=sorted(p.stem for p in CONFIGS.glob("*.json")))
    ap.add_argument("--layers", type=int)
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--tp", type=int)
    ap.add_argument("--kv-dtype")
    ap.add_argument("--out", help="directory for <cell>.<program>.hlo")
    ap.add_argument("--program", default="decode_block",
                    choices=("decode_block", "admit", "both"))
    ap.add_argument("--admit", default="4x256",
                    help="the admission group: prompts x bucket")
    a = ap.parse_args()
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.append(str(ROOT))  # tools.same_program; PYTHONPATH's tree first
    from tools.same_program import digest

    topo = describe()
    m, bucket = (int(n) for n in a.admit.split("x"))
    for cell in a.cells:
        y = cell_yaml(cell)
        progs = []
        if a.program in ("decode_block", "both"):
            progs.append(decode_block(y, topo, layers=a.layers, steps=a.steps,
                                      tp=a.tp, kv_dtype=a.kv_dtype))
        if a.program in ("admit", "both"):
            progs.append(admit(y, topo, layers=a.layers, m=m, bucket=bucket,
                               tp=a.tp, kv_dtype=a.kv_dtype))
        for prog in progs:
            text = prog.compile_text()
            if a.out:
                pathlib.Path(a.out).mkdir(parents=True, exist_ok=True)
                pathlib.Path(a.out, f"{cell}.{prog.name}.hlo").write_text(text)
            copies = pool_copies(text, prog.pool_local)
            print(f"{cell} {prog.name}: {digest([text])} kernels "
                  f"{kernels(text)}", flush=True)
            print(f"{cell} {prog.name}: sites " + json.dumps(
                {k: v for k, v in prog.sites.items() if v and k != "traces"}))
            mem = prog.memory
            print(f"{cell} {prog.name}: temporaries "
                  f"{getattr(mem, 'temp_size_in_bytes', 0) / 1e9:.3f} GB, "
                  f"{len(copies)} pool-shaped copies {list(prog.pool_local)}")
            for line in copies:
                print("   ", line[:200])


if __name__ == "__main__":
    main()
