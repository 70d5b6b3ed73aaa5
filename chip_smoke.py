#!/usr/bin/env python3
"""chip_smoke.py — does the serving path still start on the TPU?

Drives HTTP -> ModelManager -> Engine once through the real CLI at the
published widths of `mistral-7b` (full depth, random int8 weights from a
seed), and compiles every Pallas kernel in `ops/` with Mosaic at the served
shapes against its XLA oracle. It is a smoke test: its timings are labelled
"smoke" and are never benchmark numbers; it claims nothing.

    python chip_smoke.py                      # on a machine with a TPU
    python chip_smoke.py --cpu-rehearsal      # tiny widths on the CPU; says so
    python chip_smoke.py --phases four_chip   # only the named phases

The parent process never imports jax: a chip belongs to one process at a
time, so the parent only starts children, one after another, and reads what
they print. Without a TPU the bare command fails and prints no result.

Standard output holds two lines: the detailed summary (versions, compile
cache, every phase with its smoke timings, `"claim": null`; also written to
`chiprun_out/chip_smoke/summary.json`), then, last, the verdict
`{"ok": ..., "device": {"platform", "kind", "count"}}` with exactly those keys.

Phases (each passes or fails on its own; any failure makes the exit code 1):
  kernels    every Pallas kernel, jitted through its dispatcher with
             impl="auto": the lowered module must hold a Mosaic custom call,
             and the result must agree with the impl="xla" oracle.
  serve      `python -m localai_tpu run` serving mistral-7b int8 twice over —
             a dense-cache YAML, then a paged one (loading it evicts the
             first): a lone request, 8 concurrent streams, a ~1,500-token
             prompt repeated until the prefix-cached admission lands, and
             (paged) an n=2 request that forks a slot. Checked from outside:
             status 200, [DONE], one SSE content chunk per completion token,
             /system says tpu, engine counters, and a server log with no
             traceback and no ERROR record.
  restart    the server again, same lone request: the compile cache must
             gain no entry.
  four_chip  with >= 4 devices: the serve phase in bf16 at tensor_parallel 4
             (plan, shard placement and per-device HBM asserted from
             /system), then four tp=1 cluster replicas (one device each).
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("kernels", "serve", "restart", "four_chip")

# Served shapes. "full" is mistral-7b as published (models/config.py); "tiny"
# exists only for the CPU rehearsal of this script's own control flow.
SIZES = {
    "full": dict(
        arch="mistral-7b", slots=8, context=2048, page=128,
        short_prompt=100, long_prompt=1450, max_tokens=96,
        # kernel phase: B slots, K kv heads, G q heads per kv head, head dim
        B=8, K=8, G=4, D=128, hidden=4096, ffn=14336, vocab=32000,
        chunk=512, verify=6, lora_rank=16, flash_lens=(32, 512, 2048), tp=4,
        row_limit=256,  # ops/quant_matmul.QUANT_PALLAS_MAX_ROWS
        # olmoe-1b-7b's expert stack as published: layers, experts, widths
        moe=dict(layers=16, experts=64, hidden=2048, ffn=1024),
        # kimi-linear-48b-a3b as published: KDA layers, slots, heads, head
        # width; MLA's padded latent row and heads; chip 0's 32 held experts
        hybrid=dict(kda_layers=20, slots=64, heads=32, dk=128, latent=640,
                    mla_layers=7, mla_heads=32, pages=513, vocab=163840,
                    moe=dict(layers=26, experts=32, hidden=2304, ffn=1024)),
        # solar-open2-250b as chip 0 of stage 0 holds it: 6 KDA layers of 64
        # heads, GQA at 8 KV heads x 8, 40 held experts of width 1280 in each
        # of 8 layers
        hybrid_gqa=dict(kda_layers=6, slots=64, heads=64, dk=128, K=8, G=8,
                        moe=dict(layers=8, experts=40, hidden=4096, ffn=1280)),
        # granite-4.0-h-small's SSD state as published, 4 of its 36 layers:
        # 32 slots of 128 heads of 64 x 128 float32 (4 MiB a slot and layer)
        ssd=dict(layers=4, slots=32, heads=128, P=64, N=128),
        # glm-4.7-flash as published: 47 latent layers, 20 heads (no multiple
        # of 8) of 256-wide q/k/v; 8 slots of 20 pages for contexts of 2,000
        # tokens (1.2 GB of pool); the block write over 16 slots of 3 pages
        # (0.4 GB: the scatter it is held against relays the pool twice)
        mla=dict(layers=47, heads=20, head=256, latent=640, slots=8,
                 pages=20, write_slots=16, write_pages=49, prefill=(512, 2048)),
        # laguna-xs.2 as published: window layers of 64 query heads over 8
        # KV heads of 128 whose slots hold a 512-row ring (4 pages; 30 such
        # layers of 16 slots here, 0.5 GB a pool), full layers of 48 query
        # heads (6 rows a KV head) over 10 layers of pages
        swa=dict(layers=30, slots=16, window=512, heads=64, full_heads=48,
                 full_layers=10, pages=20, prefill=(1024, 2048)),
        # ai21-jamba2-3b as published: the S6 state of 128 slots, 16 states
        # x 5,120 channels float32 (4 of its 26 layers), and 20 query heads
        # over ONE 128-wide K/V head in 2 layers of pages
        s6=dict(layers=4, slots=128, N=16, E=5120, heads=20, kv_layers=2,
                kv_slots=16, pages=20, prefill=(512, 2048)),
    ),
    "tiny": dict(
        arch="tiny", slots=4, context=512, page=16,
        short_prompt=40, long_prompt=300, max_tokens=12,
        B=4, K=2, G=2, D=16, hidden=64, ffn=128, vocab=512,
        chunk=32, verify=3, lora_rank=4, flash_lens=(32,),
        row_limit=256,
        tp=2,  # the tiny preset has two kv heads
        moe=dict(layers=2, experts=4, hidden=64, ffn=32),
        hybrid=dict(kda_layers=3, slots=4, heads=4, dk=16, latent=64,
                    mla_layers=2, mla_heads=4, pages=33, vocab=640,
                    moe=dict(layers=2, experts=4, hidden=64, ffn=32)),
        hybrid_gqa=dict(kda_layers=2, slots=4, heads=4, dk=16, K=2, G=4,
                        moe=dict(layers=2, experts=3, hidden=64, ffn=40)),
        ssd=dict(layers=3, slots=4, heads=8, P=16, N=32),
        mla=dict(layers=3, heads=5, head=32, latent=128, slots=4,
                 pages=6, write_slots=4, write_pages=17, prefill=(32,)),
        swa=dict(layers=3, slots=4, window=32, heads=8, full_heads=6,
                 full_layers=2, pages=6, prefill=(64,)),
        s6=dict(layers=3, slots=8, N=8, E=128, heads=4, kv_layers=2,
                kv_slots=4, pages=6, prefill=(32,)),
    ),
}


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# Children — the only code here that imports jax
# --------------------------------------------------------------------------- #


def child_probe() -> dict:
    """What jax sees, and where the program keeps its compile cache."""
    import jax
    import jaxlib

    from localai_tpu.utils.compile_cache import configure_compile_cache

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — not installed on CPU-only hosts
        libtpu = None
    devs = jax.devices()
    arr = jax.numpy.zeros((8, 128))
    return {
        "device": {"platform": devs[0].platform, "kind": devs[0].device_kind,
                   "count": len(devs)},
        "default_backend": jax.default_backend(),
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "cache_dir": configure_compile_cache(),
        # engine.py calls both without a guard (PR 21)
        "array_has_is_ready": hasattr(arr, "is_ready"),
        "array_has_copy_to_host_async": hasattr(arr, "copy_to_host_async"),
    }


def child_kernels(size: str, rehearsal: bool, only: str = "") -> dict:
    """Compile each Pallas kernel through its dispatcher and compare it with
    its XLA oracle; `only` (comma list of substrings) keeps the cases whose
    name holds one. Returns {"cases": {name: {...}}, "failed": [...]}."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from localai_tpu.models import quant as Q
    from localai_tpu.ops import attention as A
    from localai_tpu.ops import lora_matmul as LM
    from localai_tpu.ops import paged_flash as PF
    from localai_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    s = SIZES[size]
    B, K, G, D, page = s["B"], s["K"], s["G"], s["D"], s["page"]
    H = K * G
    keys = iter(jax.random.split(jax.random.key(21), 256))  # one a drawn input
    cases: dict[str, dict] = {}

    def rnd(shape, dtype=jnp.bfloat16, scale=1.0):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * scale).astype(dtype)

    def rel_err(got, want) -> float:
        got = np.asarray(got, np.float32)
        want = np.asarray(want, np.float32)
        if got.shape != want.shape or not np.isfinite(got).all():
            return float("inf")
        return float(np.max(np.abs(got - want))
                     / (np.max(np.abs(want)) + 1e-6))

    def case(name, fn_auto, fn_oracle, args, tol, kernel=True):
        """tol bounds max|got-want| / max|want| over every output. A case
        of plain XLA (`kernel` false) must hold no custom call and no
        triangular solve, which XLA:TPU expands into one."""
        if only and not any(part in name for part in only.split(",")):
            return
        t0 = time.time()
        rec: dict = {"tol": tol}
        try:
            jitted = jax.jit(fn_auto)
            text = jitted.lower(*args).as_text()
            rec["mosaic"] = "tpu_custom_call" in text
            got = jax.block_until_ready(jitted(*args))
            want = jax.block_until_ready(jax.jit(fn_oracle)(*args))
            errs = [rel_err(g, w) for g, w in
                    zip(jax.tree.leaves(got), jax.tree.leaves(want))]
            rec["err"] = max(errs)
            form = (rec["mosaic"] or rehearsal) if kernel else not any(
                op in text for op in ("custom_call", "triangular_solve"))
            rec["ok"] = bool(rec["err"] <= tol and form)
        except Exception as e:  # noqa: BLE001 — one refused kernel must not hide the rest
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {str(e)[:600]}"
        rec["smoke_s"] = round(time.time() - t0, 2)
        cases[name] = rec
        log(f"kernel {name}: {rec}")

    # -- flash prefill (ops/flash.py) vs dense causal attention --------------
    # bf16 in/out, f32 accumulation on both sides: one bf16 ulp of the
    # largest output (2^-8) plus matmul-pass differences -> 2e-2.
    for S in s["flash_lens"]:
        lens = jnp.array([S, max(1, S - 7)], jnp.int32)
        q, k, v = rnd((2, S, H, D)), rnd((2, S, K, D)), rnd((2, S, K, D))

        def valid(lens, S=S):
            return jnp.arange(S)[None, :] < lens[:, None]

        def on_valid_rows(out, mask):
            # Compared on the query rows inside each length: flash zeroes
            # the padded rows, the dense form leaves them unspecified.
            return jnp.where(mask[:, :, None, None], out, 0)

        def flash(q, k, v, lens):
            return on_valid_rows(
                A.prefill_attention(q, k, v, None, lengths=lens), valid(lens))

        def dense(q, k, v, lens):
            mask = valid(lens)
            return on_valid_rows(
                A.causal_prefill_attention(q, k, v, mask), mask)

        case(f"flash_prefill_S{S}", flash, dense, (q, k, v, lens), 2e-2)

    # -- ragged paged attention (ops/paged_flash.py) vs the XLA page walk -----
    # f32 partials (acc, m, l) from a bf16 pool. Both sides feed the MXU
    # f32 operands at default precision (softmax weights rounded to bf16,
    # 2^-8 relative); the decode oracle folds 8 pages per step where the
    # kernel folds one, so the roundings land differently -> 5e-3. (The
    # multi-query oracle walks page by page like the kernel and agrees to
    # the last bit on the v5e.)
    max_pages = s["context"] // page
    n_pool = B * max_pages + 1
    k_pool, v_pool = rnd((n_pool, page, K, D)), rnd((n_pool, page, K, D))
    perm = jax.random.permutation(next(keys), n_pool - 1)[: B * max_pages] + 1
    table = perm.reshape(B, max_pages).astype(jnp.int32)

    def ragged_limits(n):
        """n slots' live rows: ragged, one idle slot, one full but a row."""
        return jnp.array(
            [(i * 37 + 11) % (max_pages * page - page) + 1 for i in range(n)],
            jnp.int32).at[0].set(0).at[1].set(max_pages * page - 1)

    limits = ragged_limits(B)

    def settled(partials):
        """(acc, m, l) -> (acc / l, m, l) with the rows of an idle slot
        (l == 0, m == -1e30) zeroed, so one sentinel cannot set the scale
        every other entry is compared on."""
        acc, m, l = partials
        live = l > 0
        return (jnp.where(live, acc / jnp.where(live, l, 1.0), 0.0),
                jnp.where(live, m, 0.0), l)

    def paged(impl):
        return lambda q, kp, vp, t, lim: settled(A.paged_partials(
            q, kp, vp, t, lim, impl=impl))

    case("paged_decode", paged("auto"), paged("xla"),
         (rnd((B, H, D)), k_pool, v_pool, table, limits), 5e-3)

    # The decode block's form: 32 rows, the pools still stacked over layers,
    # the page DMAs read pool[layer, page] with the layer a scalar-prefetch
    # operand (first and last layer of four). Same arithmetic and oracle as
    # paged_decode -> the same 5e-3.
    rows = 32
    n_pool4 = rows * max_pages + 1
    k_pool4 = rnd((4, n_pool4, page, K, D))
    v_pool4 = rnd((4, n_pool4, page, K, D))
    table4 = (jax.random.permutation(next(keys), n_pool4 - 1) + 1).reshape(
        rows, max_pages).astype(jnp.int32)
    limits4 = ragged_limits(rows)

    def paged_stacked(impl):
        return lambda q, kp, vp, t, lim, first, last: tuple(
            settled(A.paged_partials(q, Q.StackedLayer(kp, i),
                                     Q.StackedLayer(vp, i), t, lim, impl=impl))
            for i in (first, last))

    case("paged_decode_stacked", paged_stacked("auto"), paged_stacked("xla"),
         (rnd((rows, H, D)), k_pool4, v_pool4, table4, limits4,
          jnp.int32(0), jnp.int32(3)), 5e-3)

    # The three decode cells' own shapes (PERF.md §4): 32 rows, K/G of
    # mistral-7b (8/4), OLMoE (16/1) and mistral-7b on one chip of four
    # (2/4), contexts of 300-700 rows (3-6 pages), the page handed to the MXU
    # as it is stored (ISSUE 32). The oracle is the XLA walk at
    # Precision.HIGHEST, so the error read here is the kernel's own: q and p
    # rounded to bfloat16, as Mosaic's one-pass float32 dot always rounded
    # them. On the v5e the kernel before ISSUE 32 and the one after read the
    # same 3.74e-3 / 2.78e-3 / 3.66e-3 here (PERF.md §6, PR 32) -> 6e-3. A
    # change that rounds anything more (the scores, an accumulator, p in
    # fp8) fails here on the chip.
    def exact_walk(q, kp, vp, t, lim, layer):
        with jax.default_matmul_precision("highest"):
            return settled(A.paged_partials(
                q, Q.StackedLayer(kp, layer), Q.StackedLayer(vp, layer), t,
                lim, impl="xla"))

    def kernel_walk(q, kp, vp, t, lim, layer):
        return settled(A.paged_partials(
            q, Q.StackedLayer(kp, layer), Q.StackedLayer(vp, layer), t, lim,
            impl="auto"))

    lo, hi = (300, 700) if page == 128 else (page + 1, 4 * page)  # rehearsal
    cell_pages = -(-hi // page)
    n_cell = rows * cell_pages + 1
    # Since ISSUE 41 a visit at K = 2 is six pages side by side under one
    # dot and at K = 4 three (ops/paged_flash._visit_pages), a slot's last
    # visit ragged: the same oracle and 6e-3, the K = 4 shape beside the
    # cells' three, and K = 2 once more with every context ending on a
    # page's last row (no dead row in any page, whole pages dead in the
    # last visit). Since ISSUE 54 every one of these walks is one stream of
    # visits across slots (K = 2 six pages a visit, K = 8 a page; the ring
    # below, `window_attention_ring*`, is the third form the cells run).
    for kc, gc, ends in ((8, 4, False), (16, 1, False), (2, 4, False),
                         (4, 4, False), (2, 4, True)):
        table_c = (jax.random.permutation(next(keys), n_cell - 1) + 1).reshape(
            rows, cell_pages).astype(jnp.int32)
        limits_c = jax.random.randint(next(keys), (rows,), lo, hi + 1)
        # the slots' visits are one stream (ISSUE 54): a run of idle slots
        # and a run of one-token slots between live ones, an idle last one
        limits_c = limits_c.at[jnp.array([1, 2, 9, rows - 1])].set(0).at[
            jnp.array([4, 5])].set(1)
        if ends:
            limits_c = -(-limits_c // page) * page
        case(f"paged_decode_cell_K{kc}_G{gc}" + ("_page_ends" if ends else ""),
             kernel_walk, exact_walk,
             (rnd((rows, kc * gc, D)), rnd((2, n_cell, page, kc, D)),
              rnd((2, n_cell, page, kc, D)), table_c, limits_c,
              jnp.int32(1)), 6e-3)

    # The second hybrid's cache layers (solar-open2-250b): 64 rows at 8 KV
    # heads x 8 query heads, a shape no other cell runs. Same oracle and
    # rounding as the three above -> 6e-3.
    hg = s["hybrid_gqa"]
    n_hg = hg["slots"] * cell_pages + 1
    table_g = (jax.random.permutation(next(keys), n_hg - 1) + 1).reshape(
        hg["slots"], cell_pages).astype(jnp.int32)
    case(f"paged_decode_cell_K{hg['K']}_G{hg['G']}_rows{hg['slots']}",
         kernel_walk, exact_walk,
         (rnd((hg["slots"], hg["K"] * hg["G"], D)),
          rnd((2, n_hg, page, hg["K"], D)), rnd((2, n_hg, page, hg["K"], D)),
          table_g, jax.random.randint(next(keys), (hg["slots"],), lo, hi + 1),
          jnp.int32(1)), 6e-3)

    # LFM2's cache layers (lfm2-8b-a1b): 32 rows at 8 KV heads x 4 query
    # heads of HALF the width, a pool of two heads a 128-lane row
    # (`ArchConfig.cache_pack`: Mosaic refuses the `[page, 8, 64]` tile as
    # stored, PERF.md §7 item 6b): the kernel walks the rows as stored with q
    # in its own head's lanes, the oracle a reshape of the pool to a head a
    # row. Same rounding -> 6e-3.
    Dn = D // 2
    table_n = (jax.random.permutation(next(keys), n_cell - 1) + 1).reshape(
        rows, cell_pages).astype(jnp.int32)
    case(f"paged_decode_cell_K8_G4_D{Dn}_two_heads_a_row",
         kernel_walk, exact_walk,
         (rnd((rows, 8 * 4, Dn)), rnd((2, n_cell, page, 4, D)),
          rnd((2, n_cell, page, 4, D)), table_n,
          jax.random.randint(next(keys), (rows,), lo, hi + 1),
          jnp.int32(1)), 6e-3)

    # A decode block's window written into the pool (ISSUE 44): at the tp = 4
    # shard's shape (2 KV heads a chip, 32 layers, the cell's 257 pages and
    # 32 slots, a 16-step block) and at tp = 2's (4 heads), where
    # `attention.write_window` takes the `pool_write` DMA kernel, against
    # XLA's scatter: the same pool, exactly (tol 0). Starts that lie inside
    # a page, straddle two, begin one and end one; one idle slot on the
    # SCRATCH page; no two rows to one address, where neither form promises
    # an order. On ONE chip: the kernel's faults are found here, before any
    # four-chip call.
    from localai_tpu.models import llama as LL

    wl, wn, wmp = (32, 16, 8) if page == 128 else (2, 4, 4)
    w_pages = rows * wmp + 1

    def block_write(impl):
        return lambda kp, vp, t, wk, wv, st: tuple(LL.write_block_to_pool(
            LL.KVCache(kp, vp), t, wk, wv, st, paged_impl=impl)[:2])

    for kc in (2, 4):
        w_table = (jax.random.permutation(next(keys), w_pages - 1) + 1).reshape(
            rows, wmp).astype(jnp.int32).at[3].set(0)
        w_start = jax.random.randint(
            next(keys), (rows,), 0, wmp * page - wn).at[0].set(
            page - wn // 2).at[1].set(2 * page - 1).at[2].set(
            page - wn).at[4].set(3 * page).at[3].set(7)

        case(f"pool_write_K{kc}_n{wn}", block_write("auto"), block_write("xla"),
             (rnd((wl, w_pages, page, kc, D)), rnd((wl, w_pages, page, kc, D)),
              w_table, rnd((wl, rows, wn, kc, D)), rnd((wl, rows, wn, kc, D)),
              w_start), 0.0)

    T = s["verify"]
    qpos = limits[:, None] + jnp.arange(T)[None, :]

    def paged_mq(impl):
        return lambda q, kp, vp, t, lim, qp: settled(A.paged_partials_mq(
            q, kp, vp, t, lim, q_pos=qp, impl=impl))

    case("paged_verify_chunk", paged_mq("auto"), paged_mq("xla"),
         (rnd((B, T, H, D)), k_pool, v_pool, table, limits, qpos), 5e-3)

    C = s["chunk"]
    lim1 = jnp.array([max_pages * page - C - 3], jnp.int32)
    qpos1 = lim1[:, None] + jnp.arange(C)[None, :]

    def paged_chunk(impl):
        return lambda q, kp, vp, t, lim, qp: settled(A.paged_prefill_partials(
            q, kp, vp, t, lim, q_pos=qp, impl=impl))

    case("paged_prefill_chunk", paged_chunk("auto"), paged_chunk("xla"),
         (rnd((1, C, H, D)), k_pool, v_pool, table[:1], lim1, qpos1), 5e-3)

    # -- dequant-matmul (ops/quant_matmul.py) vs models/quant's XLA forms ----
    # bf16 x and bf16 result over a 4096-long f32 reduction: the kernel
    # dequantizes to f32 where XLA dequantizes to bf16 -> 2e-2.
    hid = s["hidden"]
    x = rnd((B, hid))
    for out_dim, tag in ((s["ffn"], "ffn"), (s["vocab"], "vocab")):
        w = rnd((hid, out_dim), jnp.float32, 0.02)
        gq = jnp.clip(jnp.round(w.reshape(hid // 32, 32, out_dim) / 5e-4),
                      -127, 127).astype(jnp.int8)
        forms = {
            "int8_channel": Q.quantize_tensor(w),
            "int8_grouped": {"gq": gq, "gs": jnp.full(
                (hid // 32, 1, out_dim), 5e-4, jnp.float32)},
            "int4_packed": Q.quantize_tensor_g4(w),
        }
        for form, wq in forms.items():
            case(f"quant_{form}_{tag}",
                 lambda x, wq: Q.matmul(x, wq, impl="auto"),
                 lambda x, wq: Q.matmul(x, wq, impl="xla"), (x, wq), 2e-2)
    # The decode block's form: N = 32 rows, the weights still stacked over
    # layers, the layer picked by the scalar-prefetched index in the kernel's
    # index maps (first and last layer of four).
    stack = Q.quantize_tensor(rnd((4, hid, s["ffn"]), jnp.float32, 0.02))

    def stacked(impl):
        return lambda x, wq, first, last: tuple(
            Q.matmul(x, Q.StackedLayer(wq, i), impl=impl) for i in (first, last))

    case("quant_int8_channel_ffn_stacked", stacked("auto"), stacked("xla"),
         (rnd((32, hid)), stack, jnp.int32(0), jnp.int32(3)), 2e-2)
    # The kernel's row limit at the same widths (a speculative verify chunk):
    # a full-width float32 accumulator does not fit the block rule's VMEM
    # budget there, so this is the narrowed branch (a lane-multiple column
    # strip, ops/quant_matmul._blocks) on the chip.
    case("quant_int8_channel_ffn_row_limit", stacked("auto"), stacked("xla"),
         (rnd((s["row_limit"], hid)), stack, jnp.int32(1), jnp.int32(2)), 2e-2)
    # The other forms where the block rule has least room (grouped int8 and
    # packed int4 hold more copies of a tile, and no cell runs them): the row
    # limit at hidden -> ffn and ffn -> hidden, groups of 32 and of 128 (8
    # groups of 128 are a 1,024-row block), and 32 rows at the ffn's tp-local
    # width (14336 / 4 = 3584 = 28 lane tiles), all under Mosaic's default
    # scoped VMEM: the kernels ask for no limit of their own.
    def grouped_forms(kin, kout, group):
        w = rnd((kin, kout), jnp.float32, 0.02)
        gq = jnp.clip(jnp.round(w.reshape(kin // group, group, kout) / 5e-4),
                      -127, 127).astype(jnp.int8)
        return {"int8_channel": Q.quantize_tensor(w),
                "int8_grouped": {"gq": gq, "gs": jnp.full(
                    (kin // group, 1, kout), 5e-4, jnp.float32)},
                "int4_packed": Q.quantize_tensor_g4(w, group)}

    ffn, local = s["ffn"], s["ffn"] // s["tp"]
    for tag, rows, kin, kout, group in (
            ("row_limit_g32", s["row_limit"], hid, ffn, 32),
            ("row_limit_g128", s["row_limit"], hid, ffn, 128),
            ("down_row_limit_g128", s["row_limit"], ffn, hid, 128),
            ("tp_local", 32, hid, local, 32),
            ("down_tp_local", 32, local, hid, 32)):
        if kin % group:
            continue  # the rehearsal's tiny widths
        for form, wq in grouped_forms(kin, kout, group).items():
            if form == "int8_channel" and "tp_local" not in tag:
                continue  # the stacked row-limit case above
            case(f"quant_{form}_{tag}",
                 lambda x, wq: Q.matmul(x, wq, impl="auto"),
                 lambda x, wq: Q.matmul(x, wq, impl="xla"),
                 (rnd((rows, kin)), wq), 2e-2)
    # The MoE decode block's form at olmoe-1b-7b's published widths: 32 rows,
    # the int8 experts still stacked over layers AND experts ([16·64, in, out]
    # blocks, block layer·E + e by scalar prefetch), both einsum shapes, two
    # non-zero layers. Drawn a layer at a time, as models/quant.py does. Same
    # arithmetic as the dense case (bf16 rows, f32 accumulation) -> 2e-2.
    from localai_tpu.models import llama as LL

    mo = s["moe"]

    def expert_stack(kin, kout):
        return jax.jit(lambda kk: jax.lax.map(
            lambda k1: Q.quantize_tensor(jax.random.normal(
                k1, (mo["experts"], kin, kout), jnp.float32) * 0.02),
            jax.random.split(kk, mo["layers"])))(next(keys))

    def experts(impl):
        def fn(x, up, down, a, b):
            out = []
            for i in (a, b):
                h = LL._moe_mm(x, Q.StackedLayer(up, i), "...d,edf->...ef", impl)
                out += [h, LL._moe_mm(h, Q.StackedLayer(down, i),
                                      "...ef,efd->...ed", impl)]
            return tuple(out)
        return fn

    up, down = (expert_stack(mo["hidden"], mo["ffn"]),
                expert_stack(mo["ffn"], mo["hidden"]))
    case("moe_int8_experts_stacked", experts("auto"), experts("xla"),
         (rnd((32, mo["hidden"])), up, down,
          jnp.int32(mo["layers"] // 3), jnp.int32(mo["layers"] - 1)), 2e-2)

    # Admission's form (ISSUE 38): expert-sorted rows walk their groups over
    # the same stack in `int8_grouped_matmul` (a visit a (row tile, expert)
    # pair, the tiles of rows in no held group not visited), against
    # `lax.ragged_dot` on the layer's slice; up then down at a non-zero
    # layer, the rows of a group compared. Rows are a cell's common
    # admission program's: 512 or 1,024 prompt rows x top-8.
    from localai_tpu.ops import quant_matmul as QM

    def grouped(held, impl):
        def fn(xg, up, down, sizes, i):
            if QM.grouped_engaged(xg, dict(up), impl, None, i):
                walk = QM.group_visits(sizes, xg.shape[0])
                h = QM.grouped_moe_mm(xg, dict(up), walk, layer=i)
                y = QM.grouped_moe_mm(h, dict(down), walk, layer=i)
            else:  # rows in no group ride in the last one (llama._ragged_mms)
                m, e = xg.shape[0], sizes.shape[0]
                rest = sizes.at[-1].add(m - held)
                group = jnp.repeat(jnp.arange(e), rest, total_repeat_length=m)
                h = LL._ragged_mm(xg, Q.layer_slice(Q.StackedLayer(up, i)),
                                  rest, group)
                y = LL._ragged_mm(h, Q.layer_slice(Q.StackedLayer(down, i)),
                                  rest, group)
            return h[:held], y[:held]
        return fn

    def group_sizes(rows, experts, share):
        """Uneven groups (a few experts idle, one busy), `share` of the
        sorted rows held."""
        p = 1.0 / (1.0 + np.arange(experts)) ** 0.7
        p[experts // 2] = 0.0  # an expert no row chose
        n = np.floor(rows * share * p / p.sum()).astype(np.int32)
        n[0] += int(rows * share) - int(n.sum())
        return jnp.asarray(n)

    def grouped_case(name, rows, m, share, up, down):
        rows = rows if not rehearsal else 256
        sizes = group_sizes(rows, m["experts"], share)
        held = int(sizes.sum())
        case(name, grouped(held, "auto"), grouped(held, "xla"),
             (rnd((rows, m["hidden"])), up, down, sizes,
              jnp.int32(m["layers"] - 1)), 2e-2)

    grouped_case("moe_int8_grouped_olmoe", 4096, mo, 1.0, up, down)
    del up, down
    # -- the hybrid model's kernels (kimi-linear-48b-a3b's shapes) -----------
    hy = s["hybrid"]
    from localai_tpu.ops import kda as KDA

    # KDA decode on the stacked float32 state, first and last KDA layer, the
    # layer a scalar-prefetch operand, the state aliased: against the XLA
    # form (slice, update, put back). Elementwise float32 both sides; the
    # sums over dk run in another order -> 1e-4.
    Lk, Bk, Hk, dk = hy["kda_layers"], hy["slots"], hy["heads"], hy["dk"]

    def unit(x):
        x = x.astype(jnp.float32)
        return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    def kda_two(impl):
        def fn(state, q, k, v, g, beta, first, last):
            outs = []
            for i in (first, last):
                o, state = KDA.kda_decode(state, i, q, k, v, g, beta, impl=impl)
                outs.append(o)
            return tuple(outs) + (state[first], state[last])
        return fn

    kda_args = (
        rnd((Lk, Bk, Hk, dk, dk), jnp.float32),
        unit(rnd((Bk, Hk, dk))) * dk ** -0.5, unit(rnd((Bk, Hk, dk))),
        rnd((Bk, Hk, dk), jnp.float32),
        -jnp.exp(rnd((Bk, Hk, dk), jnp.float32) * 2.0 - 3.0),
        jax.nn.sigmoid(rnd((Bk, Hk), jnp.float32)),
        jnp.int32(0), jnp.int32(Lk - 1))
    case("kda_decode_stacked", kda_two("auto"), kda_two("xla"), kda_args, 1e-4)
    # The same at solar-open2-250b's 64 heads ([6, 64, 64, 128, 128], a slot's
    # row twice as large) and with beta in (0, 2), as `kda_neg_eigval` asks.
    Lg, Bg, Hg, dg = hg["kda_layers"], hg["slots"], hg["heads"], hg["dk"]
    case("kda_decode_stacked_h64_beta2", kda_two("auto"), kda_two("xla"), (
        rnd((Lg, Bg, Hg, dg, dg), jnp.float32),
        unit(rnd((Bg, Hg, dg))) * dg ** -0.5, unit(rnd((Bg, Hg, dg))),
        rnd((Bg, Hg, dg), jnp.float32),
        -jnp.exp(rnd((Bg, Hg, dg), jnp.float32) * 2.0 - 3.0),
        2.0 * jax.nn.sigmoid(rnd((Bg, Hg), jnp.float32)),
        jnp.int32(0), jnp.int32(Lg - 1)), 1e-4)

    # The chunkwise prefill (plain XLA; since PR 47 its chunk's unit-triangular
    # system is solved by blocks, dots at HIGHEST, and no custom call is left
    # in it) against the token-by-token recurrence at the two published head
    # shapes, 256 tokens (four chunks: every block step of the solve, three
    # hand-overs of the state), the second with beta in (0, 2). Both sides
    # under `default_matmul_precision("highest")`: the function's other
    # einsums run at the MXU's default, one bfloat16 pass, which alone reads
    # 4e-3 here (the same on the parent, my chip run 2, PR 47) and would hide
    # what the solve's own dots give -> 1e-4 as the float32 cases above.
    def kda_prefill(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return KDA.kda_chunk_prefill(q, k, v, g, beta,
                                         jnp.ones(q.shape[:2], bool))

    def kda_walk(q, k, v, g, beta):
        with jax.default_matmul_precision("highest"):
            return KDA.kda_recurrent(q, k, v, g, beta)

    for name, heads, d, top in (
            ("kda_chunk_prefill_h32_t256", Hk, dk, 1.0),
            ("kda_chunk_prefill_h64_t256_beta2", Hg, dg, 2.0)):
        case(name, kda_prefill, kda_walk, (
            unit(rnd((2, 256, heads, d))) * d ** -0.5,
            unit(rnd((2, 256, heads, d))), rnd((2, 256, heads, d), jnp.float32),
            -jnp.exp(rnd((2, 256, heads, d), jnp.float32) * 2.0 - 3.0),
            top * jax.nn.sigmoid(rnd((2, 256, heads), jnp.float32))),
            1e-4, kernel=False)

    # SSD (Mamba-2) decode on the stacked float32 state at Granite-4.0-H's
    # published 128 heads of 64 x 128: first and last layer, the layer a
    # scalar-prefetch operand, the state aliased, against the XLA step. Half
    # the rows live (a state of unit size), half dead (a released tenant's
    # garbage, 1e4 as large: the kernel updates every row alike); the layers
    # between keep their rows. Elementwise float32 both sides; the sum over
    # d_state runs in another order -> 1e-4.
    sd = s["ssd"]
    from localai_tpu.ops import ssd as SSD

    Ls, Bs, Hs, Ps, Ns = sd["layers"], sd["slots"], sd["heads"], sd["P"], sd["N"]

    def ssd_two(impl):
        def fn(state, x, dt, A, Bm, Cm, Dk, first, last):
            outs = []
            for i in (first, last):
                y, state = SSD.ssd_decode(state, i, x, dt, A, Bm, Cm, Dk,
                                          impl=impl)
                outs.append(y)
            return tuple(outs) + (state[first], state[last], state[1])
        return fn

    dead = jnp.where(jnp.arange(Bs) % 2 == 1, 1e4, 1.0)[None, :, None, None, None]
    ssd_args = (
        rnd((Ls, Bs, Hs, Ps, Ns), jnp.float32) * dead,
        rnd((Bs, Hs, Ps)), jax.nn.softplus(rnd((Bs, Hs), jnp.float32) - 3.0),
        -jnp.exp(rnd((Hs,), jnp.float32)), rnd((Bs, 1, Ns)), rnd((Bs, 1, Ns)),
        jnp.ones((Hs,), jnp.float32), jnp.int32(0), jnp.int32(Ls - 1))
    ssd_name = "ssd_decode_stacked_h128_p64_n128"
    case(ssd_name, ssd_two("auto"), ssd_two("xla"), ssd_args, 1e-4)
    # The kernel reads the state out on the MXU (a float32 dot at HIGHEST;
    # ISSUE 52), the XLA step by an einsum: each form's y of both layers
    # against a float64 walk of the same inputs, a slot's worst error over
    # the slot's largest |y| (a dead slot's y is 1e4 as large). The kernel's
    # may be no more than twice the XLA step's.
    if cases.get(ssd_name, {}).get("ok"):
        S0, x, dt, Am, Bm, Cm, Dk = (np.asarray(a, np.float64)
                                     for a in ssd_args[:7])
        dx = (dt[..., None] * x)[..., None] * Bm[:, :, None, :]
        want = [np.einsum("bhpn,bn->bhp", S0[i] * np.exp(dt * Am)[
            ..., None, None] + dx, Cm[:, 0]) + Dk[:, None] * x
            for i in (0, Ls - 1)]

        def walk_err(impl):
            got = jax.jit(ssd_two(impl))(*ssd_args)[:2]
            return max(float(np.max(
                np.abs(np.asarray(g, np.float64) - w).max((1, 2))
                / np.abs(w).max((1, 2)))) for g, w in zip(got, want))

        rec = cases[ssd_name]
        rec["y_err_f64_kernel"] = walk_err("auto")
        rec["y_err_f64_xla"] = walk_err("xla")
        rec["ok"] = bool(rec["y_err_f64_kernel"] <= 2.0 * rec["y_err_f64_xla"])
        log(f"kernel {ssd_name}: y against the float64 walk, kernel "
            f"{rec['y_err_f64_kernel']:.3e}, XLA step {rec['y_err_f64_xla']:.3e}")
        del S0, dx, want

    # MLA's absorbed decode over the latent pool stacked over the MLA layers
    # ([L, P, page, 1, 640]: one row a token, key and value): the latent
    # walk (the as-stored visit over the one pool, six pages a visit at the
    # published row; ISSUE 48) against the XLA walk. Same arithmetic as
    # paged_decode -> 5e-3. Ragged contexts with an idle slot, a one-page
    # slot in front of a live one, contexts that end on a page's last row
    # and on the next one's first, and exactly a visit and a row more. The
    # call states the value width the cells state (kv_lora_rank, 512 of the
    # 640 lanes: the value dot on those lanes alone; ISSUE 50) and the lanes
    # that are read are compared; the slots' visits are one stream of copies.
    Lm, Hm, W = hy["mla_layers"], hy["mla_heads"], hy["latent"]
    lat_pool = rnd((Lm, hy["pages"], page, 1, W))
    lat_pages = (hy["pages"] - 1) // Bk
    lat_table = (jax.random.permutation(next(keys), hy["pages"] - 1)[
        : Bk * lat_pages] + 1).reshape(Bk, lat_pages).astype(jnp.int32)
    lat_limits = jnp.array(
        [(i * 37 + 11) % (lat_pages * page - page) + 1 for i in range(Bk)],
        jnp.int32).at[0].set(0).at[1].set(lat_pages * page - 1)
    for i, n in enumerate((page, 3 * page, 3 * page + 1, 6 * page,
                           6 * page + 1)):
        if i + 2 < Bk:
            lat_limits = lat_limits.at[i + 2].set(n)

    def latent(impl):
        def fn(q, pool, t, lim, first, last):
            values = q.shape[-1] * 4 // 5  # kv_lora_rank of the padded row
            read = PF.value_lanes(values, q.shape[-1])
            out = []
            for i in (first, last):
                c = Q.StackedLayer(pool, i)
                acc, m, l = A.paged_partials(
                    q, c, c, t, lim, impl=impl, latent=True, values=values)
                out.append(settled((acc[..., :read], m, l)))
            return tuple(out)
        return fn

    case("latent_paged_decode_stacked", latent("auto"), latent("xla"),
         (rnd((Bk, Hm, W)), lat_pool, lat_table, lat_limits,
          jnp.int32(0), jnp.int32(Lm - 1)), 5e-3)
    del lat_pool
    # the same walk where a slot is several visits: contexts of half to all
    # of a 32-page table (2,048-4,096 tokens at the published page), two
    # layers of such a pool
    long_pages = 32
    long_pool = rnd((2, Bk * long_pages + 1, page, 1, W))
    long_table = (jax.random.permutation(next(keys), Bk * long_pages)
                  + 1).reshape(Bk, long_pages).astype(jnp.int32)
    long_limits = jnp.array(
        [(i * 37 + 11) % (long_pages * page // 2) + long_pages * page // 2 + 1
         for i in range(Bk)], jnp.int32).at[0].set(0).at[1].set(
             long_pages * page).at[2].set(18 * page)
    case("latent_paged_decode_stacked_long", latent("auto"), latent("xla"),
         (rnd((Bk, Hm, W)), long_pool, long_table, long_limits,
          jnp.int32(0), jnp.int32(1)), 5e-3)
    del long_pool

    # GLM-4.7-Flash's shapes (ISSUE 49). The latent walk at 20 query rows a
    # slot, which is no multiple of a sublane tile, over the first and the
    # last of 47 layers, contexts of 1,500-2,500 tokens: same arithmetic as
    # the two cases above -> 5e-3.
    gm = s["mla"]
    Lg, Bg, gp = gm["layers"], gm["slots"], gm["pages"]
    def rnd16(shape):  # a pool drawn in its own 16 bits: no float32 twin
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    g_pool = rnd16((Lg, Bg * gp + 1, page, 1, gm["latent"]))
    g_table = (jax.random.permutation(next(keys), Bg * gp) + 1).reshape(
        Bg, gp).astype(jnp.int32)
    g_limits = jnp.array(
        [(i * 61 + 17) % (gp * page * 2 // 5) + gp * page * 3 // 5
         for i in range(Bg)], jnp.int32).at[0].set(0).at[1].set(gp * page)
    case(f"latent_paged_decode_h{gm['heads']}_l{Lg}_long",
         latent("auto"), latent("xla"),
         (rnd((Bg, gm["heads"], gm["latent"])), g_pool, g_table, g_limits,
          jnp.int32(0), jnp.int32(Lg - 1)), 5e-3)
    # ... and contexts as `decode-reasoning` holds them, 150 to 2,560 tokens:
    # slots of one visit and of two, three and four, whose first visits the
    # slots before them start (the stream), a last visit of every live size
    # 1..6, a page's last row and first
    g_mix = jnp.array(  # (live pages, rows of the last one) a slot
        [(min(n, gp) - 1) * page + min(rows, page) for n, rows in (
            (2, 22), (5, page - 7), (7, 1), (9, page - 3), (3, page),
            (10, page), (gp, page), (gp - 2, 5))][:Bg], jnp.int32)
    case(f"latent_paged_decode_h{gm['heads']}_l{Lg}_mix",
         latent("auto"), latent("xla"),
         (rnd((Bg, gm["heads"], gm["latent"])), g_pool, g_table, g_mix,
          jnp.int32(0), jnp.int32(Lg - 1)), 5e-3)
    del g_pool
    # The latent pool's block write (`ops/pool_write.latent_pool_write`: a
    # slot's rows staged through VMEM in their one or two 16-row tile
    # groups) at the cell's 47 layers and 16-step block over a small pool
    # (the scatter it is held against relays the whole pool twice and does
    # not fit at the cell's): the same pool, exactly (tol 0).
    # Starts at every offset of a tile group among them, a straddle of two
    # pages, a page's first and last rows, an idle slot on the SCRATCH page;
    # no row past the table, which the kernel drops and the scatter clamps.
    lw_slots, lw_n = gm["write_slots"], 16 if page == 128 else 4
    lw_mp = (gm["write_pages"] - 1) // lw_slots
    lw_table = (jax.random.permutation(next(keys), gm["write_pages"] - 1)[
        : lw_slots * lw_mp] + 1).reshape(lw_slots, lw_mp).astype(
        jnp.int32).at[3].set(0)
    lw_start = ((jnp.arange(lw_slots) * 37 + jnp.arange(lw_slots) % 16)
                % (lw_mp * page - lw_n)).astype(jnp.int32).at[0].set(
        page - lw_n // 2).at[1].set(page - lw_n).at[2].set(page).at[3].set(7)

    def latent_write(impl):
        return lambda kp, t, wk, st: LL.write_block_to_pool(
            LL.KVCache(kp, kp[..., :0]), t, wk, wk[..., :0], st,
            paged_impl=impl).k

    case(f"latent_pool_write_l{Lg}_n{lw_n}", latent_write("auto"),
         latent_write("xla"),
         (rnd16((Lg, gm["write_pages"], page, 1, gm["latent"])), lw_table,
          rnd((Lg, lw_slots, lw_n, 1, gm["latent"])), lw_start), 0.0)
    # The prefill's flash kernel at MLA's full-rank heads, 20 of 256 wide
    # for q, k and v alike (the cells so far prefill at 128 and 64): same
    # rounding as flash_prefill_S* -> 2e-2.
    for S in gm["prefill"]:
        lens = jnp.array([S, max(1, S - 7)], jnp.int32)
        qkv = [rnd((2, S, gm["heads"], gm["head"])) for _ in range(3)]

        def valid_rows(out, lens, S=S):
            return jnp.where((jnp.arange(S)[None, :] < lens[:, None])[
                :, :, None, None], out, 0)

        case(f"flash_prefill_h{gm['heads']}_d{gm['head']}_S{S}",
             lambda q, k, v, lens: valid_rows(A.prefill_attention(
                 q, k, v, None, lengths=lens), lens),
             lambda q, k, v, lens: valid_rows(A.causal_prefill_attention(
                 q, k, v, jnp.arange(q.shape[1])[None, :] < lens[:, None]),
                 lens),
             (*qkv, lens), 2e-2)

    # Laguna-XS.2's shapes (ISSUE 53). The window layers' reader: the paged
    # walk over a per-slot RING (position p at row p mod 512 of the slot's
    # four pages, `limits` the positions written so far, a row masked at the
    # position it holds) at 8 query rows a KV head, the first and the last
    # of 30 layers; contexts under a ring, exactly one, and some that have
    # wrapped once and several times, each at a query 0, 7 and 15 steps into
    # its block (the rows a block is about to replace are dead); an idle
    # slot. Same arithmetic as paged_decode -> 5e-3.
    sw = s["swa"]
    Ls, Bs, Wn = sw["layers"], sw["slots"], sw["window"]
    rp = -(-Wn // page)
    ring_k, ring_v = (rnd16((Ls, Bs * rp, page, K, D)) for _ in range(2))
    ring_tab = jnp.arange(Bs * rp, dtype=jnp.int32).reshape(Bs, rp)
    ring_n0 = jnp.array(
        [(0, 1, Wn // 2 + 3, Wn - 1, Wn, Wn + 1, Wn + page - 5, 2 * Wn,
          2 * Wn + 17, 4 * Wn - 1, 700, 2000)[i % 12] + 3 * (i // 12)
         for i in range(Bs)], jnp.int32)
    ring_step = jnp.array([(0, 7, 15)[i % 3] for i in range(Bs)], jnp.int32)
    ring_step = jnp.where(ring_n0 > 0, ring_step, 0)

    def ring_read(impl):
        def fn(q, kp, vp, t, n0, step, first, last):
            return tuple(settled(A.paged_partials(
                q, Q.StackedLayer(kp, i), Q.StackedLayer(vp, i), t, n0,
                window=Wn, sliding=np.True_, q_pos=n0 + step, impl=impl,
                ring=rp * page)) for i in (first, last))
        return fn

    case(f"window_attention_ring{Wn}_G{sw['heads'] // K}_l{Ls}",
         ring_read("auto"), ring_read("xla"),
         (rnd((Bs, sw["heads"], D)), ring_k, ring_v, ring_tab, ring_n0,
          ring_step, jnp.int32(0), jnp.int32(Ls - 1)), 5e-3)
    # The ring's write: a block's rows at their positions mod the ring (a
    # block that wraps the ring's end among them), XLA's scatter over the
    # donated rings as the engine runs it (8 rows of D a token: stored as
    # scattered), against the rows placed by hand: the same rings, exactly.
    rw_n = 16 if page == 128 else 4
    rw_start = jnp.array(
        [(0, Wn - rw_n, Wn - rw_n // 2, Wn, 700, 2000 - 3, page - 1,
          3 * Wn + page)[i % 8] + i // 8 for i in range(Bs)], jnp.int32)

    def ring_write(kp, t, wk, st):
        return LL.write_block_to_pool(
            LL.KVCache(kp, kp), t, wk, wk, st, paged_impl="auto", ring=True).k

    def ring_by_hand(kp, t, wk, st):
        row = (st[:, None] + jnp.arange(wk.shape[2])[None, :]) % (rp * page)
        pid = jnp.take_along_axis(t, row // page, axis=1)
        return kp.at[:, pid, row % page].set(wk)

    case(f"ring_write_l{Ls}_n{rw_n}", ring_write, ring_by_hand,
         (ring_k, ring_tab, rnd((Ls, Bs, rw_n, K, D)), rw_start), 0.0,
         kernel=False)
    del ring_k, ring_v
    # The full layers' reader: the paged walk at 6 query rows a KV head (48
    # heads over 8: every GQA cell so far has 1, 4 or 8), 48 query rows a
    # slot, no multiple of 32; contexts of 150 to 2,560 tokens over the
    # first and the last of 10 layers. Same arithmetic -> 5e-3.
    Lf, fp = sw["full_layers"], sw["pages"]
    f_k, f_v = (rnd16((Lf, Bs * fp + 1, page, K, D)) for _ in range(2))
    f_tab = (jax.random.permutation(next(keys), Bs * fp) + 1).reshape(
        Bs, fp).astype(jnp.int32)
    f_lim = jnp.array([(i * 61 + 17) % (fp * page - 150) + 150
                       for i in range(Bs)], jnp.int32).at[0].set(0)

    def full_read(impl):
        def fn(q, kp, vp, t, lim, first, last):
            return tuple(settled(A.paged_partials(
                q, Q.StackedLayer(kp, i), Q.StackedLayer(vp, i), t, lim,
                impl=impl)) for i in (first, last))
        return fn

    case(f"paged_decode_G{sw['full_heads'] // K}_l{Lf}",
         full_read("auto"), full_read("xla"),
         (rnd((Bs, sw["full_heads"], D)), f_k, f_v, f_tab, f_lim,
          jnp.int32(0), jnp.int32(Lf - 1)), 5e-3)
    del f_k, f_v
    # The prefill's flash kernel under the window, at the window layers' 64
    # heads over 8: prompts two and four windows long (what the check and a
    # preempted request admit), against the dense form under the same mask.
    # Same rounding as flash_prefill_S* -> 2e-2.
    for S in sw["prefill"]:
        lens = jnp.array([S, max(1, S - 7)], jnp.int32)
        qkv = (rnd((2, S, sw["heads"], D)), rnd((2, S, K, D)),
               rnd((2, S, K, D)))

        def valid_rows(out, lens, S=S):
            return jnp.where((jnp.arange(S)[None, :] < lens[:, None])[
                :, :, None, None], out, 0)

        case(f"flash_prefill_window{Wn}_h{sw['heads']}_S{S}",
             lambda q, k, v, lens: valid_rows(A.prefill_attention(
                 q, k, v, None, lengths=lens, window=Wn, sliding=np.True_),
                 lens),
             lambda q, k, v, lens: valid_rows(A.causal_prefill_attention(
                 q, k, v, jnp.arange(q.shape[1])[None, :] < lens[:, None],
                 window=Wn, sliding=jnp.bool_(True)), lens),
             (*qkv, lens), 2e-2)

    # AI21-Jamba2's S6 (Mamba-1) decode on the stacked float32 state at the
    # published [128 slots, 16, 5120]: first and last layer, the layer a
    # scalar-prefetch operand, the state aliased, against the XLA step. Odd
    # slots hold a released tenant's garbage, 1e4 as large (the kernel
    # updates every row alike); the layers between keep their rows.
    # Elementwise float32 both sides, one exp an element -> 1e-4.
    js = s["s6"]
    from localai_tpu.ops import s6 as S6

    Lj, Bj, Nj, Ej = js["layers"], js["slots"], js["N"], js["E"]

    def s6_two(impl):
        def fn(state, x, dt, At, Bm, Cm, Dk, first, last):
            outs = []
            for i in (first, last):
                y, state = S6.s6_decode(state, i, x, dt, At, Bm, Cm, Dk,
                                        impl=impl)
                outs.append(y)
            return tuple(outs) + (state[first], state[last], state[1])
        return fn

    case(f"s6_decode_stacked_b{Bj}_n{Nj}_e{Ej}", s6_two("auto"), s6_two("xla"),
         (rnd((Lj, Bj, Nj, Ej), jnp.float32)
          * jnp.where(jnp.arange(Bj) % 2 == 1, 1e4, 1.0)[None, :, None, None],
          rnd((Bj, Ej)), jax.nn.softplus(rnd((Bj, Ej), jnp.float32) - 3.0),
          -jnp.exp(jnp.broadcast_to(jnp.log(jnp.arange(
              1, Nj + 1, dtype=jnp.float32))[:, None], (Nj, Ej))),
          rnd((Bj, Nj)), rnd((Bj, Nj)), jnp.ones((Ej,), jnp.float32),
          jnp.int32(0), jnp.int32(Lj - 1)), 1e-4)
    # Its attention layers' reader: the paged walk at 20 query rows over ONE
    # K/V head (a multi-query pool: the page as stored, `_flat_rows`; no cell
    # so far has one head, and 20 rows are no multiple of the 8-row tile),
    # contexts of 150 to 2,560 tokens over both layers. Same arithmetic as
    # paged_decode -> 5e-3.
    Lq, Bq, jp, Hj = js["kv_layers"], js["kv_slots"], js["pages"], js["heads"]
    j_k, j_v = (rnd16((Lq, Bq * jp + 1, page, 1, D)) for _ in range(2))
    j_tab = (jax.random.permutation(next(keys), Bq * jp) + 1).reshape(
        Bq, jp).astype(jnp.int32)
    j_lim = jnp.array([(i * 61 + 17) % (jp * page - 150) + 150
                       for i in range(Bq)], jnp.int32).at[0].set(0)
    case(f"paged_decode_G{Hj}_one_kv_head_l{Lq}",
         full_read("auto"), full_read("xla"),
         (rnd((Bq, Hj, D)), j_k, j_v, j_tab, j_lim,
          jnp.int32(0), jnp.int32(Lq - 1)), 5e-3)
    del j_k, j_v
    # ... and the prefill's flash kernel at 20 : 1, the cell's bucket and the
    # check's longest prompt, against the dense form. Same rounding as
    # flash_prefill_S* -> 2e-2.
    for S in js["prefill"]:
        lens = jnp.array([S, max(1, S - 7)], jnp.int32)

        def valid_rows(out, lens, S=S):
            return jnp.where((jnp.arange(S)[None, :] < lens[:, None])[
                :, :, None, None], out, 0)

        case(f"flash_prefill_h{Hj}_one_kv_head_S{S}",
             lambda q, k, v, lens: valid_rows(A.prefill_attention(
                 q, k, v, None, lengths=lens), lens),
             lambda q, k, v, lens: valid_rows(A.causal_prefill_attention(
                 q, k, v, jnp.arange(q.shape[1])[None, :] < lens[:, None]),
                 lens),
             (rnd((2, S, Hj, D)), rnd((2, S, 1, D)), rnd((2, S, 1, D)), lens),
             2e-2)

    # The held experts' stacks [26 x 32, 2304, 1024] at 64 rows through the
    # same kernel and block rule as olmoe's [16 x 64, 2048, 1024]: a whole
    # expert matrix (2.3 MB) a grid step.
    hm = hy["moe"]

    def held_stack(kin, kout, hm=hm):
        return jax.jit(lambda kk: jax.lax.map(
            lambda k1: Q.quantize_tensor(jax.random.normal(
                k1, (hm["experts"], kin, kout), jnp.float32) * 0.02),
            jax.random.split(kk, hm["layers"])))(next(keys))

    up, down = (held_stack(hm["hidden"], hm["ffn"]),
                held_stack(hm["ffn"], hm["hidden"]))
    case("moe_int8_held_experts_stacked", experts("auto"), experts("xla"),
         (rnd((Bk, hm["hidden"])), up, down,
          jnp.int32(hm["layers"] // 3), jnp.int32(hm["layers"] - 1)), 2e-2)
    # an eighth of the sorted rows are picks of an expert held here
    grouped_case("moe_int8_grouped_kimi_held_eighth", 4096, hm, 0.125, up, down)
    del up, down

    # solar-open2-250b's held stacks [8 x 40, 4096, 1280] and [8 x 40, 1280,
    # 4096]: the first expert width (10 lane tiles) and the first share (40
    # experts a layer) that are no power of two.
    hm = hg["moe"]
    up, down = (held_stack(hm["hidden"], hm["ffn"], hm),
                held_stack(hm["ffn"], hm["hidden"], hm))
    case("moe_int8_held_experts_stacked_w1280", experts("auto"), experts("xla"),
         (rnd((Bg, hm["hidden"])), up, down,
          jnp.int32(hm["layers"] // 3), jnp.int32(hm["layers"] - 1)), 2e-2)
    # a 5.2 MB expert matrix: two k-chunks a visit
    grouped_case("moe_int8_grouped_solar_w1280", 8192, hm, 1.0, up, down)
    del up, down

    head = rnd((s["vocab"], hid), jnp.float32, 0.02)
    hs = jnp.maximum(jnp.max(jnp.abs(head), axis=-1, keepdims=True) / 127.0,
                     1e-9)
    hq = {"q": jnp.clip(jnp.round(head / hs), -127, 127).astype(jnp.int8),
          "s": hs}
    case("quant_unembed", lambda h, w: Q.unembed_matmul(h, w, impl="auto"),
         lambda h, w: Q.unembed_matmul(h, w, impl="xla"), (x, hq), 2e-2)
    # kimi-linear-48b-a3b's head [163840, 2304] at 64 rows: whole rows of an
    # 18-lane-tile width, 1,024 of them a block.
    hv, hd = hy["vocab"], hy["moe"]["hidden"]
    head = jax.jit(lambda k1: jnp.clip(jnp.round(jax.random.normal(
        k1, (hv, hd), jnp.float32) * 40.0), -127, 127).astype(jnp.int8))(
            next(keys))
    case("quant_unembed_v163840",
         lambda h, w: Q.unembed_matmul(h, w, impl="auto"),
         lambda h, w: Q.unembed_matmul(h, w, impl="xla"),
         (rnd((Bk, hd)), {"q": head, "s": jnp.full((hv, 1), 5e-4, jnp.float32)}),
         2e-2)

    # -- ragged LoRA delta (ops/lora_matmul.py) vs the XLA gather form --------
    # bf16 factors, f32 accumulation; the oracle rounds the rank-r
    # intermediate to bf16 and the kernel keeps it f32 -> 2e-2.
    r = s["lora_rank"]
    fac = {"a": rnd((4, hid, r), scale=0.05), "b": rnd((4, r, hid), scale=0.05)}
    ids = jnp.arange(B, dtype=jnp.int32) % 4  # the batch mixes all four
    case("lora_delta",
         lambda x, f, i: LM.lora_delta(x, f, i, impl="auto"),
         lambda x, f, i: LM.lora_delta_xla(x, f["a"], f["b"], i),
         (x, fac, ids), 2e-2)

    failed = sorted(n for n, c in cases.items() if not c["ok"])
    return {"cases": cases, "failed": failed}


def run_child(mode: str, size: str, rehearsal: bool, timeout: float,
              only: str = "") -> dict:
    """Run one jax child to its end and parse the JSON line it prints last."""
    cmd = [sys.executable, os.path.abspath(__file__), "--child", mode,
           "--size", size, "--cases", only] + (
               ["--cpu-rehearsal"] if rehearsal else [])
    proc = subprocess.run(cmd, cwd=HERE, capture_output=True, text=True,
                          timeout=timeout)
    sys.stderr.write(proc.stderr[-6000:])
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    if proc.returncode != 0 or not lines:
        raise RuntimeError(
            f"child {mode!r} exited {proc.returncode}: "
            f"{proc.stderr.strip().splitlines()[-1:] or 'no output'}")
    return json.loads(lines[-1])


# --------------------------------------------------------------------------- #
# Parent — HTTP client and server supervision, no jax
# --------------------------------------------------------------------------- #


class PhaseFailed(Exception):
    pass


def need(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def free_port() -> int:
    # `run --port 0` is dropped by the CLI (`if args.port:`), so the port is
    # chosen here.
    with socket.socket() as sk:
        sk.bind(("127.0.0.1", 0))
        return sk.getsockname()[1]


def cache_entries(path: str) -> int:
    if not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


class Server:
    """One `python -m localai_tpu run` child and its log."""

    def __init__(self, models_dir: str, log_path: str, extra_args=()):
        self.port = free_port()
        self.log_path = log_path
        self.t_spawn = time.time()
        self._log = open(log_path, "w")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "localai_tpu", "run",
             "--models-path", models_dir, "--port", str(self.port),
             "--address", "127.0.0.1", "--max-active-models", "1",
             *extra_args],
            cwd=HERE, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )

    def get(self, path: str, timeout: float = 30.0):
        conn = http.client.HTTPConnection("127.0.0.1", self.port,
                                          timeout=timeout)
        try:
            conn.request("GET", path)
            resp = conn.getresponse()
            return resp.status, json.loads(resp.read() or b"null")
        finally:
            conn.close()

    def wait_ready(self, timeout: float = 180.0) -> float:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if self.proc.poll() is not None:
                raise PhaseFailed(
                    f"server exited {self.proc.returncode} before /readyz; "
                    f"log tail: {self.log_tail()}")
            try:
                status, _ = self.get("/readyz", timeout=2.0)
                if status == 200:
                    return time.time() - self.t_spawn
            except OSError:
                time.sleep(0.5)
        raise PhaseFailed(f"/readyz not answering after {timeout:.0f}s")

    def log_tail(self, n: int = 2000) -> str:
        self._log.flush()
        with open(self.log_path, errors="replace") as f:
            return f.read()[-n:]

    def log_problems(self) -> list[str]:
        """Tracebacks and ERROR/CRITICAL records: the engine's containment
        paths keep serving after a failed compile and only log it."""
        self._log.flush()
        bad = []
        with open(self.log_path, errors="replace") as f:
            for line in f:
                if ("Traceback (most recent call last)" in line
                        or re.match(r"^\S+ \S+ (ERROR|CRITICAL) ", line)):
                    bad.append(line.strip()[:300])
        return bad

    def stop(self) -> None:
        if self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGTERM)
                self.proc.wait(timeout=60)
            except (subprocess.TimeoutExpired, ProcessLookupError):
                pass
        if self.proc.poll() is None:
            os.killpg(self.proc.pid, signal.SIGKILL)
            self.proc.wait(timeout=30)
        self._log.close()


def stream_chat(port: int, model: str, prompt: str, max_tokens: int,
                n: int = 1, timeout: float = 900.0) -> dict:
    """One streamed /v1/chat/completions. Raises unless the response is 200,
    ends in [DONE] and carries one content chunk per completion token."""
    body = {"model": model, "stream": True, "ignore_eos": True, "n": n,
            "max_tokens": max_tokens, "temperature": 0.0,
            "messages": [{"role": "user", "content": prompt}]}
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    t0 = time.time()
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps(body),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise PhaseFailed(f"HTTP {resp.status}: {resp.read()[:300]!r}")
        chunks, usage, ttft, done = 0, None, None, False
        for raw in resp:
            line = raw.strip()
            if not line.startswith(b"data:"):
                continue
            data = line[5:].strip()
            if data == b"[DONE]":
                done = True
                resp.read()  # drain, so close() is a FIN and not a reset
                break
            ev = json.loads(data)
            if "error" in ev:
                raise PhaseFailed(f"stream error event: {ev['error']}")
            usage = ev.get("usage") or usage
            for ch in ev.get("choices") or ():
                delta = ch.get("delta") or {}
                if "content" in delta and "role" not in delta:
                    ttft = ttft if ttft is not None else time.time() - t0
                    chunks += 1
    finally:
        conn.close()
    need(done, "stream closed before [DONE]")
    need(usage is not None, "no usage in the stream")
    need(chunks == usage["completion_tokens"] == n * max_tokens,
         f"SSE content chunks {chunks} != usage.completion_tokens "
         f"{usage['completion_tokens']} (asked {n}x{max_tokens})")
    return {"chunks": chunks, "prompt_tokens": usage["prompt_tokens"],
            "smoke_first_content_s": round(ttft or 0.0, 3),
            "smoke_wall_s": round(time.time() - t0, 3)}


def burst(port: int, model: str, letters: str, length: int,
          max_tokens: int) -> list[dict]:
    """One concurrent stream per letter. Each prompt repeats its own letter,
    so no two share a cacheable prefix and the burst goes through batched
    admission, not through the prefix cache."""
    out: list = [None] * len(letters)

    def one(i: int) -> None:
        try:
            out[i] = stream_chat(port, model, letters[i] * length, max_tokens)
        except Exception as e:  # noqa: BLE001 — reported below, per request
            out[i] = e

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(len(letters))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    errs = [f"req {i}: {e}" for i, e in enumerate(out)
            if isinstance(e, Exception)]
    need(not errs, "; ".join(errs[:3]))
    return out


def write_models(models_dir: str, s: dict, quant: str, tp: int) -> dict:
    """The dense and the paged YAML of the serve phase; returns name->cfg."""
    import yaml

    base = {
        "model": s["arch"], "tokenizer": "synthetic-bytes",
        "max_slots": s["slots"], "context_size": s["context"],
        "temperature": 0.0, "template": {"family": "chatml"},
    }
    if quant:
        base["quantization"] = quant
    if tp:
        base["tensor_parallel"] = tp
    models = {
        "smoke-dense": dict(base),
        # a pool covering the same tokens as the dense cache
        "smoke-paged": dict(
            base, kv_pages=s["slots"] * s["context"] // s["page"],
            kv_page_size=s["page"]),
    }
    for name, cfg in models.items():
        with open(os.path.join(models_dir, f"{name}.yaml"), "w") as f:
            yaml.safe_dump({"name": name, **cfg}, f)
    return models


def drive_model(srv: Server, name: str, s: dict, paged: bool,
                platform: str) -> dict:
    """The traffic one model sees, and what /system must say afterwards."""
    rec: dict = {}
    short = "x" * s["short_prompt"]
    letters = "abcdefghijklmnopqrstuvw"
    t0 = time.time()
    first = stream_chat(srv.port, name, short, s["max_tokens"])
    rec["smoke_setup_s"] = round(time.time() - t0, 1)  # load + first compiles
    rec["prompt_tokens_short"] = first["prompt_tokens"]
    for tag, lo in (("cold", 0), ("warm", s["slots"])):
        t1 = time.time()
        burst(srv.port, name, letters[lo:lo + s["slots"]],
              s["short_prompt"], s["max_tokens"])
        rec[f"smoke_burst_{tag}_s"] = round(time.time() - t1, 1)

    def prefix_hits() -> float:
        _, system = srv.get("/system")
        return system["backends"][name].get("prefix_cache_hits", 0.0)

    # Long prompt: the first send fills the prefix cache, the second finds
    # it and starts the cached-admission compile on a background thread
    # (serving that request through full admission), a later one runs it.
    long_prompt = "y" * s["long_prompt"]
    hits0, sends = prefix_hits(), 0
    deadline = time.time() + 300
    while True:
        r = stream_chat(srv.port, name, long_prompt, 8)
        sends += 1
        rec["prompt_tokens_long"] = r["prompt_tokens"]
        long_hits = prefix_hits() - hits0
        if long_hits >= 1 or time.time() > deadline:
            break
        if sends >= 2:
            time.sleep(2.0)
    rec["long_prompt_sends"] = sends
    if paged:
        stream_chat(srv.port, name, short + " fork", 16, n=2)

    _, system = srv.get("/system")
    m = system["backends"][name]
    info = system["sysinfo"]
    rec["metrics"] = {k: m.get(k) for k in (
        "loop_dead", "prefix_cache_hits", "fork_branches",
        "fork_clone_fallbacks", "kv_pages_peak", "tokens_generated",
        "prompt_tokens_processed", "peak_active_slots")}
    rec["placement"] = system["placement"][name]
    rec["hbm_in_use_bytes"] = [d.get("hbm_in_use_bytes")
                               for d in info["devices"]]
    need(info["platform"] == platform,
         f"/system platform {info['platform']!r}, expected {platform!r}")
    need(bool(info["devices"][0].get("kind")), "/system has no device kind")
    need(m["loop_dead"] == 0, "engine loop died")
    need(m.get("fork_clone_fallbacks", 0) == 0,
         f"{m.get('fork_clone_fallbacks')} fork branch(es) fell back to clone")
    need(long_hits >= 1,
         f"no prefix-cached admission ran in {sends} sends of the long prompt")
    need(m["peak_active_slots"] >= 2, "the burst never batched")
    if paged:
        need(m.get("kv_pages_peak", 0) > 0, "paged engine used no pages")
        need(m.get("fork_branches", 0) >= 1, "n=2 did not fork a slot")
    problems = srv.log_problems()
    need(not problems, f"server log: {problems[:3]}")
    return rec


def phase_serve(s: dict, out_dir: str, platform: str, tag: str,
                quant: str, tp: int = 0) -> dict:
    rec: dict = {"models": {}}
    models_dir = tempfile.mkdtemp(prefix=f"chip_smoke_{tag}_")
    models = write_models(models_dir, s, quant, tp)
    srv = Server(models_dir, os.path.join(out_dir, f"server_{tag}.log"))
    try:
        rec["smoke_ready_s"] = round(srv.wait_ready(), 1)
        for name in models:
            log(f"{tag}: driving {name}")
            rec["models"][name] = drive_model(
                srv, name, s, paged="paged" in name, platform=platform)
            if tp:
                p = rec["models"][name]["placement"]
                need(p["plan"]["tp"] == tp, f"plan {p['plan']}, asked tp={tp}")
                for what in ("param_devices", "kv_devices"):
                    need(len(set(p[what])) == tp,
                         f"{what} on {p[what]}, expected {tp} devices")
                hbm = rec["models"][name]["hbm_in_use_bytes"][:tp]
                # CPU devices report no memory stats (rehearsal)
                need(platform != "tpu"
                     or (all(hbm) and max(hbm) <= 1.25 * min(hbm)),
                     f"uneven HBM use across the tp devices: {hbm}")
    finally:
        srv.stop()
        shutil.rmtree(models_dir, ignore_errors=True)
    return rec


def phase_restart(s: dict, out_dir: str, cache_dir: str) -> dict:
    """Second start of the same server: the lone request of the serve phase
    again. Every program it needs was cached by the first start."""
    before = cache_entries(cache_dir)
    models_dir = tempfile.mkdtemp(prefix="chip_smoke_restart_")
    write_models(models_dir, s, "int8", 0)
    srv = Server(models_dir, os.path.join(out_dir, "server_restart.log"))
    try:
        srv.wait_ready()
        t0 = time.time()
        stream_chat(srv.port, "smoke-paged", "x" * s["short_prompt"],
                    s["max_tokens"])
        setup = round(time.time() - t0, 1)
        problems = srv.log_problems()
    finally:
        srv.stop()
        shutil.rmtree(models_dir, ignore_errors=True)
    after = cache_entries(cache_dir)
    rec = {"smoke_setup_s": setup, "cache_entries_before": before,
           "cache_entries_after": after}
    need(not problems, f"server log: {problems[:3]}")
    need(before > 0, f"the first start left nothing in {cache_dir}")
    need(after == before,
         f"second start added {after - before} cache entries")
    return rec


def phase_replicas(s: dict, out_dir: str, n: int) -> dict:
    """Four tp=1 same-host cluster replicas: each on its own device?"""
    models_dir = tempfile.mkdtemp(prefix="chip_smoke_replicas_")
    write_models(models_dir, s, "int8", 1)
    os.remove(os.path.join(models_dir, "smoke-dense.yaml"))
    srv = Server(models_dir, os.path.join(out_dir, "server_replicas.log"),
                 extra_args=("--cluster-replicas", str(n)))
    try:
        srv.wait_ready()
        burst(srv.port, "smoke-paged", "abcdefghijklmnop"[:2 * n],
              s["short_prompt"], s["max_tokens"])
        _, system = srv.get("/system")
        problems = srv.log_problems()
    finally:
        srv.stop()
        shutil.rmtree(models_dir, ignore_errors=True)
    reps = system["placement"]["smoke-paged"]["replicas"]
    where = {r: p["param_devices"] for r, p in reps.items()}
    need(not problems, f"server log: {problems[:3]}")
    need(len(where) == n, f"{len(where)} replicas, asked {n}")
    need(len({tuple(v) for v in where.values()}) == n,
         f"replicas share devices: {where}")
    return {"replica_param_devices": where}


def phase_kernels(size: str, rehearsal: bool, only: str = "") -> dict:
    res = run_child("kernels", size, rehearsal, timeout=900, only=only)
    need(not res["failed"], f"kernels failed: {res['failed']}: " + "; ".join(
        f"{n}: {res['cases'][n].get('error', res['cases'][n])}"
        for n in res["failed"][:3]))
    keep = ("mosaic", "err", "tol", "y_err_f64_kernel", "y_err_f64_xla")
    return {"cases": {n: {k: c[k] for k in keep if k in c}
                      for n, c in res["cases"].items()}}


def phase_four_chip(s: dict, out_dir: str, device: dict) -> dict:
    if device["count"] < 4:
        return {"status": f"skipped: {device['count']} device(s)"}
    rec = phase_serve(s, out_dir, device["platform"], "tp", "", tp=s["tp"])
    rec["replicas"] = phase_replicas(s, out_dir, 4)
    return rec


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny widths on the CPU backend; not a chip result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help=f"comma list out of {PHASES}")
    ap.add_argument("--cases", default="",
                    help="kernels phase: comma list of substrings; only the "
                         "cases whose name holds one run (default: all)")
    ap.add_argument("--child", choices=("probe", "kernels"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--size", choices=tuple(SIZES), default="full",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(HERE, "localai_tpu")):
        print("chip_smoke.py: no localai_tpu/ beside this script — it drives "
              "the repository's server and must run from a checkout",
              file=sys.stderr)
        return 2
    if args.child:
        sys.path.insert(0, HERE)
        res = (child_probe() if args.child == "probe"
               else child_kernels(args.size, args.cpu_rehearsal, args.cases))
        print(json.dumps(res))
        return 0

    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}")
    rehearsal = args.cpu_rehearsal
    size = "tiny" if rehearsal else "full"
    s = SIZES[size]
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"  # children inherit it
        log("CPU REHEARSAL at tiny widths — this is not a chip result")

    probe = run_child("probe", size, rehearsal, timeout=300)
    device = probe["device"]
    if device["platform"] != "tpu" and not rehearsal:
        print(f"chip_smoke.py: jax found no TPU (devices: {device}); the "
              "smoke only means something on the chip. --cpu-rehearsal "
              "walks the same script at tiny widths.", file=sys.stderr)
        return 1
    array_api_ok = all(probe[k] for k in ("array_has_is_ready",
                                          "array_has_copy_to_host_async"))
    cache_dir = probe["cache_dir"]
    out_dir = os.path.join(HERE, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    summary: dict = {
        "ok": False, "device": device, "rehearsal": rehearsal,
        "default_backend": probe["default_backend"],
        "versions": probe["versions"],
        "compile_cache": {"dir": cache_dir,
                          "entries_before": cache_entries(cache_dir)},
        "phases": {},
    }

    def run_phase(name: str, fn) -> None:
        t0 = time.time()
        rec: dict = {}
        try:
            rec.update(fn() or {})
            rec["status"] = rec.get("status", "passed")
        except (PhaseFailed, RuntimeError, OSError, KeyError,
                subprocess.TimeoutExpired, json.JSONDecodeError) as e:
            rec.update(status="failed", error=f"{type(e).__name__}: {e}"[:800])
        rec["smoke_wall_s"] = round(time.time() - t0, 1)
        summary["phases"][name] = rec
        log(f"phase {name}: {rec['status']} in {rec['smoke_wall_s']}s"
            + (f" — {rec['error']}" if "error" in rec else ""))

    table = {
        "kernels": lambda: phase_kernels(size, rehearsal, args.cases),
        "serve": lambda: phase_serve(s, out_dir, device["platform"],
                                     "int8", "int8"),
        "restart": lambda: phase_restart(s, out_dir, cache_dir),
        "four_chip": lambda: phase_four_chip(s, out_dir, device),
    }
    for name in PHASES:
        if name in phases:
            run_phase(name, table[name])

    summary["compile_cache"]["entries_after"] = cache_entries(cache_dir)
    statuses = [p["status"] for p in summary["phases"].values()]
    summary["ok"] = bool(array_api_ok and statuses and all(
        st == "passed" or st.startswith("skipped") for st in statuses))
    summary["claim"] = None
    # The detailed summary first, and on disk; the last stdout line is the
    # driver's verdict and holds exactly "ok" and "device".
    with open(os.path.join(out_dir, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps(summary))
    print(json.dumps({"ok": summary["ok"], "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)
    return 0 if summary["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
