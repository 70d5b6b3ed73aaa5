"""Benchmark: aggregate decode throughput through the serving engine.

Measures the north-star metric path (BASELINE.md): output tokens/sec of the
continuous-batching engine, full public API (submit → slots → jitted decode →
streamed events), random-init weights (zero-egress environment; shapes match
the public model card so the compute is real).

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": N, "unit": "tok/s", "vs_baseline": null}
vs_baseline is null because the reference publishes no numbers (SURVEY.md §6).

Env knobs: BENCH_ARCH (default llama-3.2-1b; "tiny" for smoke),
BENCH_SLOTS, BENCH_PROMPT, BENCH_GEN, BENCH_MAX_SEQ.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time


def _join_or_die(threads, eng, what: str, timeout: float = 900.0) -> None:
    """Join request threads with a deadline instead of hanging to the
    harness timeout (BENCH_r05 was rc=124 exactly this way). The engine's
    loop-guard already errors out every live handle when the loop thread
    dies (so the request threads unblock and the row reports rc=1 with the
    error list); this is the backstop for anything it misses — a dead loop
    thread or a blown deadline fails the bench NOW with a message."""
    deadline = time.time() + timeout
    for t in threads:
        while t.is_alive():
            t.join(timeout=5.0)
            loop = eng._thread
            if t.is_alive() and loop is not None and not loop.is_alive():
                print(
                    f"{what}: engine loop thread died "
                    f"({getattr(eng, '_loop_dead', None)!r}) — failing fast",
                    file=sys.stderr,
                )
                sys.exit(1)
            if t.is_alive() and time.time() > deadline:
                print(
                    f"{what}: request threads still running after "
                    f"{timeout:.0f}s — failing fast",
                    file=sys.stderr,
                )
                sys.exit(1)


def main() -> None:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        # A CPU timing is not a device metric: no chip, no number.
        print(
            f"bench.py needs a TPU and found none (jax.devices() = "
            f"{devices}); refusing to measure on {devices[0].platform!r}",
            file=sys.stderr,
        )
        sys.exit(1)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"bench devices: {devices}", file=sys.stderr)

    from localai_tpu.engine import ByteTokenizer, Engine, EngineConfig
    from localai_tpu.models import get_arch
    from localai_tpu.models.llama import init_params

    arch = os.environ.get("BENCH_ARCH", "llama-3.2-1b")
    slots = int(os.environ.get("BENCH_SLOTS", "8"))
    prompt_len = int(os.environ.get("BENCH_PROMPT", "128"))
    # 256 generated tokens per request: at 128 the run is only ~2 decode
    # blocks long, so fixed edges (first/last block round trip, admission
    # ramp) are a large share of the measured wall and the row understates
    # steady-state decode. 256 halves the edge share while staying a
    # realistic response length.
    gen_len = int(os.environ.get("BENCH_GEN", "256"))
    max_seq = int(os.environ.get("BENCH_MAX_SEQ", "1024"))

    cfg = get_arch(arch)
    params = jax.jit(lambda k: init_params(cfg, k))(jax.random.key(0))
    eng = Engine(
        cfg,
        params,
        ByteTokenizer(cfg.vocab_size),
        engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq),
    )
    t0 = time.time()
    eng.warmup(prompt_len)
    print(f"warmup/compile: {time.time() - t0:.1f}s", file=sys.stderr)

    # Reset counters after warmup so the measurement covers steady state only.
    eng._decode_time = 0.0
    eng._decode_tokens = 0

    ttfts: list[float] = []
    errors: list[str] = []
    lock = threading.Lock()

    def one(i: int) -> None:
        ids = [(i * 37 + j) % 255 + 1 for j in range(prompt_len)]
        try:
            _, ev = eng.generate(ids, max_new_tokens=gen_len, ignore_eos=True)
            with lock:
                ttfts.append(ev.timing_prompt_processing)
        except Exception as e:  # noqa: BLE001 — a partial run must not report a fake metric
            with lock:
                errors.append(f"request {i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=one, args=(i,)) for i in range(slots)]
    wall0 = time.time()
    for t in threads:
        t.start()
    _join_or_die(threads, eng, "main decode row")
    wall = time.time() - wall0

    if errors:
        for err in errors:
            print(err, file=sys.stderr)
        print(f"bench failed: {len(errors)}/{slots} requests errored", file=sys.stderr)
        sys.exit(1)

    decode_tps = eng._decode_tokens / eng._decode_time if eng._decode_time else 0.0
    total_tokens = slots * gen_len
    ttfts.sort()
    p50_ttft = ttfts[len(ttfts) // 2]

    # HBM roofline: each decode step streams the weights once plus the live
    # KV prefix for every slot; v5e ≈ 819 GB/s. steps/s * batch = tok/s.
    param_bytes = sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(eng.params)
    )
    avg_len = prompt_len + gen_len / 2
    kv_bytes = 2 * cfg.num_layers * slots * avg_len * cfg.num_kv_heads * cfg.head_dim_ * 2
    hbm_bw = 819e9
    roofline_tps = hbm_bw / (param_bytes + kv_bytes) * slots
    pct = 100.0 * decode_tps / roofline_tps if roofline_tps else 0.0
    print(
        f"arch={arch} slots={slots} gen={gen_len} wall={wall:.2f}s "
        f"end_to_end_tps={total_tokens / wall:.1f} decode_tps={decode_tps:.1f} "
        f"p50_ttft={p50_ttft * 1000:.1f}ms "
        f"roofline={roofline_tps:.0f}tok/s achieved={pct:.1f}%",
        file=sys.stderr,
    )
    out = {
        "metric": f"decode_tokens_per_sec_{arch}_bs{slots}",
        "value": round(decode_tps, 2),
        "unit": "tok/s",
        "vs_baseline": None,
        "p50_ttft_ms": round(p50_ttft * 1000, 1),
        "pct_of_hbm_roofline": round(pct, 1),
    }

    # (The prefix-cache rows moved to dedicated long-prefix engines after
    # the paged row — at a 512-token prefix both paths are about one
    # device→host round trip and the ratio is noise; r4 recorded a 0.34x
    # artifact that way.)

    # Request-lifecycle journal overhead row (ISSUE 11, BENCH_TRACE):
    # decode tok/s with the flight-recorder journal detached vs attached
    # on the SAME warmed engine (no recompiles — the journal is host-side
    # bookkeeping only), plus the /debug/timeline export cost. Guards the
    # "observability is free" claim with a number every round.
    if os.environ.get("BENCH_TRACE", "1") != "0":
        def _trace_round() -> float:
            eng._decode_time = 0.0
            eng._decode_tokens = 0
            errs0 = len(errors)
            tthreads = [threading.Thread(target=one, args=(i,))
                        for i in range(slots)]
            for t in tthreads:
                t.start()
            _join_or_die(tthreads, eng, "trace overhead row")
            if len(errors) > errs0:
                for err in errors[errs0:]:
                    print(err, file=sys.stderr)
                print("trace overhead row failed", file=sys.stderr)
                sys.exit(1)
            return (eng._decode_tokens / eng._decode_time
                    if eng._decode_time else 0.0)

        saved_journal = eng._journal
        eng._journal = None
        tps_journal_off = _trace_round()
        if saved_journal is None:
            from localai_tpu.observe.journal import EventJournal

            saved_journal = EventJournal(4096)
        eng._journal = saved_journal
        tps_journal_on = _trace_round()
        from localai_tpu.observe import timeline as _timeline

        t_exp = time.time()
        tl = _timeline.chrome_trace({"bench": saved_journal})
        export_ms = (time.time() - t_exp) * 1000.0
        overhead_pct = (
            100.0 * (tps_journal_off - tps_journal_on) / tps_journal_off
            if tps_journal_off else 0.0
        )
        print(
            f"trace row: journal_off={tps_journal_off:.1f} tok/s "
            f"journal_on={tps_journal_on:.1f} tok/s "
            f"overhead={overhead_pct:.2f}% "
            f"timeline_export={export_ms:.1f}ms "
            f"({len(tl['traceEvents'])} events)",
            file=sys.stderr,
        )
        out["trace_journal_off_tps"] = round(tps_journal_off, 2)
        out["trace_journal_on_tps"] = round(tps_journal_on, 2)
        out["trace_journal_overhead_pct"] = round(overhead_pct, 2)
        out["timeline_export_ms"] = round(export_ms, 2)

    # Grammar-constrained decode row: on-device DFA masking vs the host
    # candidate-walk fallback (same schema, greedy). The DFA path keeps full
    # block depth and no per-token host round-trip (functions/dfa.py).
    if os.environ.get("BENCH_GRAMMAR", "1") != "0":
        try:
            from localai_tpu.functions.jsonschema import GrammarConstraint

            g_schema = {
                "type": "object",
                "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"},
                               "c": {"type": "string"}},
                "required": ["a", "b", "c"],
            }

            eng.prewarm_grammar(g_schema)  # sync table build (async otherwise)

            def g_run(env_val, n=3):
                # greedy: constrained completion length is content-dependent
                # and unseeded sampling made this row swing 3x run-to-run
                os.environ["LOCALAI_GRAMMAR_DFA"] = env_val
                eng.generate([1, 2, 3], max_new_tokens=96, ignore_eos=False,
                             temperature=0.0,
                             grammar=GrammarConstraint(g_schema))  # compile
                t0 = time.time()
                toks0 = eng.m_generated_tokens
                for i in range(n):
                    eng.generate([1, 2, 3 + i], max_new_tokens=96,
                                 temperature=0.0,
                                 grammar=GrammarConstraint(g_schema))
                toks = max(eng.m_generated_tokens - toks0, 1)
                return toks / (time.time() - t0)

            tps_dfa = g_run("1")
            tps_walk = g_run("0")
            os.environ["LOCALAI_GRAMMAR_DFA"] = "1"
            out["grammar_dfa_tps"] = round(tps_dfa, 1)
            out["grammar_hostwalk_tps"] = round(tps_walk, 1)
            out["grammar_dfa_speedup"] = round(tps_dfa / max(tps_walk, 1e-9), 2)
            print(
                f"grammar: dfa {tps_dfa:.1f} tok/s vs host-walk {tps_walk:.1f} "
                f"tok/s -> {tps_dfa / max(tps_walk, 1e-9):.2f}x",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"grammar row failed: {type(e).__name__}: {e}", file=sys.stderr)

    # Mixed constrained/unconstrained batch (VERDICT r3 weak 4: the grammar
    # row was single-stream and dispatch-RTT-bound). Half the slots decode
    # under the device DFA, half free-run — DFA slots pipeline at full block
    # depth, so aggregate throughput should sit near the plain bs row.
    if os.environ.get("BENCH_GRAMMAR", "1") != "0":
        try:
            from localai_tpu.functions.jsonschema import GrammarConstraint

            g_schema = {
                "type": "object",
                "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"},
                               "c": {"type": "string"}},
                "required": ["a", "b", "c"],
            }
            eng.prewarm_grammar(g_schema)

            def mixed_round():
                hs = []
                for i in range(slots):
                    kw = dict(max_new_tokens=gen_len, ignore_eos=True,
                              temperature=0.0)
                    if i % 2 == 0:
                        # greedy: run-to-run comparability (see g_run note)
                        kw = dict(max_new_tokens=gen_len, temperature=0.0,
                                  grammar=GrammarConstraint(g_schema))
                    ids = [(i * 31 + j) % 255 + 1 for j in range(8)]
                    hs.append(threading.Thread(
                        target=lambda ids=ids, kw=kw: eng.generate(ids, **kw)))
                for t in hs:
                    t.start()
                for t in hs:
                    t.join()

            mixed_round()  # compile/warm the dfa+filtered block variants
            eng._decode_time = 0.0
            eng._decode_tokens = 0
            dfa0 = eng.m_dfa_tokens
            t0 = time.time()
            mixed_round()
            mixed_wall = time.time() - t0
            mtps = (eng._decode_tokens / eng._decode_time
                    if eng._decode_time else 0.0)
            out["grammar_mixed_bs_decode_tps"] = round(mtps, 1)
            # Attribution for run variance: did every constrained slot ride
            # the device DFA (tokens accrue), or did one fall to the
            # host-walk path (single-step serialized blocks)?
            out["grammar_mixed_dfa_tokens"] = int(eng.m_dfa_tokens - dfa0)
            print(
                f"mixed constrained bs{slots}: {mtps:.1f} tok/s decode "
                f"({slots // 2} DFA + {slots - slots // 2} free slots, "
                f"wall {mixed_wall:.2f}s, dfa_tokens {eng.m_dfa_tokens - dfa0})",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"mixed grammar row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    # Constrained-vs-unconstrained THROUGHPUT DELTA at full batch (VERDICT
    # r4 weak 8: the 20.9x row is DFA-vs-hostwalk at bs1; what a serving
    # operator cares about is how much enforcing grammar on every slot
    # costs next to free-running the same batch).
    if os.environ.get("BENCH_GRAMMAR", "1") != "0":
        try:
            from localai_tpu.functions.jsonschema import GrammarConstraint

            g_schema = {
                "type": "object",
                "properties": {"a": {"type": "integer"}, "b": {"type": "boolean"},
                               "c": {"type": "string"}},
                "required": ["a", "b", "c"],
            }
            eng.prewarm_grammar(g_schema)

            def all_round(constrained: bool):
                hs = []
                for i in range(slots):
                    if constrained:
                        kw = dict(max_new_tokens=gen_len, temperature=0.0,
                                  grammar=GrammarConstraint(g_schema))
                    else:
                        kw = dict(max_new_tokens=gen_len, ignore_eos=True,
                                  temperature=0.0)
                    ids = [(i * 29 + j) % 255 + 1 for j in range(8)]
                    hs.append(threading.Thread(
                        target=lambda ids=ids, kw=kw: eng.generate(ids, **kw)))
                for t in hs:
                    t.start()
                for t in hs:
                    t.join()

            rates = {}
            for constrained in (True, False):
                all_round(constrained)  # warm this variant
                eng._decode_time = 0.0
                eng._decode_tokens = 0
                all_round(constrained)
                rates[constrained] = (
                    eng._decode_tokens / eng._decode_time
                    if eng._decode_time else 0.0
                )
            out["grammar_all_constrained_tps"] = round(rates[True], 1)
            out["grammar_all_free_tps"] = round(rates[False], 1)
            out["grammar_constrained_vs_free"] = round(
                rates[True] / max(rates[False], 1e-9), 2)
            print(
                f"grammar bs{slots}: all-constrained {rates[True]:.1f} vs "
                f"all-free {rates[False]:.1f} tok/s decode -> "
                f"{rates[True] / max(rates[False], 1e-9):.2f}x",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"constrained-vs-free row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    # Single-request latency row (VERDICT r3 weak 6: bs1 p50 had no recorded
    # row). Sequential bs1 requests, p50 of end-to-end wall and decode rate.
    if os.environ.get("BENCH_BS1", "1") != "0":
        try:
            bs1_gen = min(gen_len, 64)
            walls = []
            eng.generate([3] * prompt_len, max_new_tokens=bs1_gen,
                         ignore_eos=True)  # warm the single-slot path
            for i in range(5):
                ids = [(i * 53 + j) % 255 + 1 for j in range(prompt_len)]
                t0 = time.time()
                _, ev = eng.generate(ids, max_new_tokens=bs1_gen,
                                     ignore_eos=True)
                walls.append(time.time() - t0)
            walls.sort()
            p50 = walls[len(walls) // 2]
            out["bs1_p50_latency_ms"] = round(p50 * 1000, 1)
            out["bs1_e2e_tok_per_s"] = round(bs1_gen / max(p50, 1e-9), 1)
            print(
                f"bs1: p50 {p50 * 1000:.1f}ms for {prompt_len}-tok prompt + "
                f"{bs1_gen} tokens -> {bs1_gen / p50:.1f} tok/s single-stream",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"bs1 row failed: {type(e).__name__}: {e}", file=sys.stderr)

    # Host loop-overhead row (ISSUE 17, BENCH_LOOP): ms of host work per
    # dispatched decode block, pipelined runtime vs the serial loop
    # (LOCALAI_LOOP_PREPARE_AHEAD=0), at three occupancies. Uses dedicated
    # tiny engines so the row isolates HOST overhead (planning, control
    # uploads, housekeeping) from device compute, and so the serial
    # comparison engine doesn't double the big arch's cache HBM. The
    # counters come straight from the loop's phase clock
    # (m_loop_host_ms / m_loop_blocks — wait time excluded), the same
    # numbers Engine.metrics() exports as loop_host_overhead_per_block_ms.
    if os.environ.get("BENCH_LOOP", "1") != "0":
        try:
            tcfg = get_arch("tiny")
            tparams = jax.jit(lambda k: init_params(tcfg, k))(jax.random.key(1))
            loop_slots = 16
            occs = (1, 8, loop_slots)
            lgen = 64

            def loop_engine(pipelined: bool) -> Engine:
                le = Engine(
                    tcfg, tparams, ByteTokenizer(tcfg.vocab_size),
                    engine_cfg=EngineConfig(
                        max_slots=loop_slots, max_seq=256,
                        min_prefill_bucket=16, spec_mode="off",
                        loop_prepare_ahead=pipelined))
                le.start()
                return le

            def loop_round(le: Engine, occ: int) -> float:
                lerrs: list[str] = []

                def lone(i: int) -> None:
                    ids = [(i * 13 + j) % 255 + 1 for j in range(8)]
                    try:
                        le.generate(ids, max_new_tokens=lgen,
                                    ignore_eos=True)
                    except Exception as e:  # noqa: BLE001
                        lerrs.append(f"{type(e).__name__}: {e}")

                lthreads = [threading.Thread(target=lone, args=(i,))
                            for i in range(occ)]
                for t in lthreads:
                    t.start()
                for t in lthreads:
                    t.join()
                if lerrs:
                    raise RuntimeError(f"loop row occ={occ}: {lerrs[0]}")
                return le.m_loop_host_ms / max(le.m_loop_blocks, 1)

            overheads: dict[tuple[str, int], float] = {}
            for mode, flag in (("pipelined", True), ("serial", False)):
                le = loop_engine(flag)
                try:
                    for occ in occs:
                        loop_round(le, occ)  # warm this occupancy's variants
                        le.m_loop_host_ms = 0.0
                        le.m_loop_blocks = 0
                        overheads[(mode, occ)] = loop_round(le, occ)
                finally:
                    le.stop()
            for occ in occs:
                p = overheads[("pipelined", occ)]
                s = overheads[("serial", occ)]
                out[f"loop_host_overhead_per_block_ms_bs{occ}_pipelined"] = (
                    round(p, 3))
                out[f"loop_host_overhead_per_block_ms_bs{occ}_serial"] = (
                    round(s, 3))
                out[f"loop_overhead_speedup_bs{occ}"] = round(
                    s / max(p, 1e-9), 2)
                print(
                    f"loop row bs{occ}: serial {s:.3f} ms/block vs "
                    f"pipelined {p:.3f} ms/block -> "
                    f"{s / max(p, 1e-9):.2f}x less host overhead",
                    file=sys.stderr,
                )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"loop row failed: {type(e).__name__}: {e}", file=sys.stderr)

    eng.stop()

    # Paged-KV row (SURVEY §7 ragged/paged KV): same arch/params served from
    # a shared page pool at 60% of the dense cache budget — decode tok/s
    # must hold while HBM scales with live context instead of slots×max_seq.
    if os.environ.get("BENCH_PAGED", "1") != "0" and max_seq % 128 == 0:
        peng = None
        try:
            # Release the stopped dense engine's HBM (cache + sharded params
            # + prefix spans) first — the paged pool must not have to fit ON
            # TOP of the dense cache it is meant to replace.
            eng.cache = None
            eng.params = None
            eng._prefix_entries = []
            page = 128
            pool = max(2, int(slots * (max_seq // page) * 0.6))
            peng = Engine(
                cfg, params, ByteTokenizer(cfg.vocab_size),
                engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq,
                                        kv_pages=pool, kv_page_size=page),
            )
            peng.start()
            # Full warmup (every admission size + block size), like the main
            # engine: a mid-measurement admission compile would otherwise be
            # booked into decode time and crater the row.
            peng.warmup(prompt_len)
            peng._decode_time = 0.0
            peng._decode_tokens = 0

            def pone(i: int) -> None:
                ids = [(i * 37 + j) % 255 + 1 for j in range(prompt_len)]
                peng.generate(ids, max_new_tokens=gen_len, ignore_eos=True)

            pthreads = [threading.Thread(target=pone, args=(i,)) for i in range(slots)]
            for t in pthreads:
                t.start()
            _join_or_die(pthreads, peng, "paged row")
            ptps = (peng._decode_tokens / peng._decode_time
                    if peng._decode_time else 0.0)
            out["decode_tokens_per_sec_paged"] = round(ptps, 2)
            out["paged_pool_fraction_of_dense"] = 0.6
            out["paged_vs_dense_tps"] = round(ptps / max(decode_tps, 1e-9), 2)
            print(
                f"paged kv: {ptps:.1f} tok/s at 60% of the dense cache "
                f"({pool} pages x {page}) vs dense {decode_tps:.1f}",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"paged row failed: {type(e).__name__}: {e}", file=sys.stderr)
        finally:
            if peng is not None:
                peng.stop()
                # Drop the pool + sharded-param HBM now: nulling the attrs
                # releases it even if a straggler thread still holds a
                # reference to the engine object past stop()'s join.
                peng.params = None
                peng.cache = None
                peng = None

    # Page-size sweep on the paged row (ISSUE 9 satellite): the r04 0.73x
    # paged_vs_dense gap is partly a page-size tuning question — smaller
    # pages waste less ragged tail per slot but cost more table columns /
    # DMA descriptors per walk. One tok/s per size, same 60%-of-dense pool
    # BYTES, so the TPU run picks the knee with data instead of folklore.
    if os.environ.get("BENCH_PAGED_SWEEP", "1") != "0" and max_seq % 128 == 0:
        for page_s in (8, 16, 32):
            seng = None
            try:
                pool_s = max(2, int(slots * (max_seq // page_s) * 0.6))
                seng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq,
                                            kv_pages=pool_s,
                                            kv_page_size=page_s),
                )
                seng.start()
                seng.warmup(prompt_len)
                seng._decode_time = 0.0
                seng._decode_tokens = 0
                ths = [threading.Thread(target=lambda i=i: seng.generate(
                    [(i * 37 + j) % 255 + 1 for j in range(prompt_len)],
                    max_new_tokens=gen_len, ignore_eos=True,
                )) for i in range(slots)]
                for t in ths:
                    t.start()
                _join_or_die(ths, seng, f"paged sweep page={page_s}")
                tps_s = (seng._decode_tokens / seng._decode_time
                         if seng._decode_time else 0.0)
                out[f"paged_tps_page{page_s}"] = round(tps_s, 2)
                print(
                    f"paged sweep: page={page_s} -> {tps_s:.1f} tok/s "
                    f"({tps_s / max(decode_tps, 1e-9):.2f}x dense)",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — extra row is best-effort
                print(f"paged sweep page={page_s} failed: "
                      f"{type(e).__name__}: {e}", file=sys.stderr)
            finally:
                if seng is not None:
                    seng.stop()
                    seng.params = None
                    seng.cache = None
                    seng = None

    # Quantized-decode ladder (ISSUE 9, docs/QUANTIZATION.md roofline math):
    # decode tok/s + derived bytes/token for bf16 / int8 / int4 /
    # int8+fp8-KV, all through the paged pool at bs `slots`. bytes/token is
    # the THEORETICAL stream (weight bytes + avg live KV) / batch — the
    # ratio of tok/s across rows against the ratio of bytes/token is
    # exactly how much of the quantization win the fused dequant-matmul
    # kernels actually deliver (XLA's materialized dequant copy made int4
    # stream ~2.5 B/weight; the kernels stream the packed 0.5).
    if os.environ.get("BENCH_QUANT", "1") != "0" and max_seq % 128 == 0:
        page = 128
        pool = max(2, int(slots * (max_seq // page) * 0.6))
        qmodes = [
            ("bf16", "", ""),
            ("int8", "int8", ""),
            ("int4", "int4", ""),
            ("int8_fp8kv", "int8", "fp8"),
        ]
        for tag, qmode, kvdt in qmodes:
            qeng = None
            try:
                qeng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    engine_cfg=EngineConfig(
                        max_slots=slots, max_seq=max_seq, kv_pages=pool,
                        kv_page_size=page, kv_cache_dtype=kvdt,
                    ),
                    quantization=qmode,
                )
                qeng.start()
                qeng.warmup(prompt_len)
                qeng._decode_time = 0.0
                qeng._decode_tokens = 0
                ths = [threading.Thread(target=lambda i=i: qeng.generate(
                    [(i * 37 + j) % 255 + 1 for j in range(prompt_len)],
                    max_new_tokens=gen_len, ignore_eos=True,
                )) for i in range(slots)]
                for t in ths:
                    t.start()
                _join_or_die(ths, qeng, f"quant row {tag}")
                qtps = (qeng._decode_tokens / qeng._decode_time
                        if qeng._decode_time else 0.0)
                wbytes = sum(
                    a.size * a.dtype.itemsize
                    for a in jax.tree.leaves(qeng.params)
                )
                import jax.numpy as _jnp

                kv_item = _jnp.dtype(
                    qeng.ecfg.cache_dtype(cfg.dtype)
                ).itemsize
                avg_len = prompt_len + gen_len / 2
                kv_live = (2 * cfg.num_layers * slots * avg_len
                           * cfg.cache_kv_heads * cfg.head_dim_ * kv_item)
                bpt = (wbytes + kv_live) / slots
                out[f"quant_tps_{tag}"] = round(qtps, 2)
                out[f"quant_bytes_per_token_{tag}"] = int(bpt)
                roof = 819e9 / (wbytes + kv_live) * slots
                out[f"quant_pct_roofline_{tag}"] = round(
                    100.0 * qtps / roof, 1) if roof else 0.0
                print(
                    f"quant {tag}: {qtps:.1f} tok/s, {bpt / 1e6:.1f} MB/tok "
                    f"derived, {out[f'quant_pct_roofline_{tag}']}% of roofline",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — extra row is best-effort
                print(f"quant row {tag} failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            finally:
                if qeng is not None:
                    qeng.stop()
                    qeng.params = None
                    qeng.cache = None
                    qeng = None

    # Speculative decoding under the paged pool (ISSUE 9 satellite — the
    # composition has tier-1 tests but was never MEASURED): accepted
    # tokens/s and decode tok/s with a draft vs the non-draft paged row, at
    # bs 1 and bs `slots`, plus an int8-target variant (the verify pass
    # streams the full target weights — exactly what quantization cuts).
    # Draft and target are random-init, so acceptance is a floor, not the
    # real-checkpoint number; the MACHINERY cost (draft steps + verify
    # chunk + accept scan) is what this row prices.
    if os.environ.get("BENCH_SPEC_PAGED", "1") != "0" and max_seq % 128 == 0:
        draft_arch = os.environ.get(
            "BENCH_DRAFT_ARCH",
            "tiny" if arch.startswith("tiny") else "llama-3.2-1b",
        )
        n_draft = int(os.environ.get("BENCH_N_DRAFT", "4"))
        page = 128
        pool = max(2, int(slots * (max_seq // page) * 0.6))
        dcfg = get_arch(draft_arch)
        dparams = jax.jit(lambda k: init_params(dcfg, k))(jax.random.key(2))
        for tag, qmode in (("spec_paged", ""), ("spec_paged_quant", "int8")):
            deng = None
            try:
                deng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    draft_cfg=dcfg, draft_params=dparams, n_draft=n_draft,
                    engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq,
                                            kv_pages=pool, kv_page_size=page),
                    quantization=qmode,
                )
                deng.start()
                deng.warmup(prompt_len)
                for bs in ((1, slots) if tag == "spec_paged" else (slots,)):
                    deng._decode_time = 0.0
                    deng._decode_tokens = 0
                    deng.m_spec_rounds = 0
                    deng.m_spec_accepted = 0
                    ths = [threading.Thread(target=lambda i=i: deng.generate(
                        [(i * 37 + j) % 255 + 1 for j in range(prompt_len)],
                        max_new_tokens=gen_len, ignore_eos=True,
                    )) for i in range(bs)]
                    for t in ths:
                        t.start()
                    _join_or_die(ths, deng, f"{tag} bs{bs}")
                    stps = (deng._decode_tokens / deng._decode_time
                            if deng._decode_time else 0.0)
                    acc_s = (deng.m_spec_accepted / deng._decode_time
                             if deng._decode_time else 0.0)
                    rate = deng.metrics().get("spec_accept_rate", 0.0)
                    out[f"{tag}_tps_bs{bs}"] = round(stps, 2)
                    out[f"{tag}_accepted_per_s_bs{bs}"] = round(acc_s, 2)
                    out[f"{tag}_accept_rate_bs{bs}"] = round(rate, 3)
                    base = out.get("decode_tokens_per_sec_paged")
                    if bs == slots and base:
                        out[f"{tag}_vs_paged"] = round(stps / base, 2)
                    print(
                        f"{tag} bs{bs}: {stps:.1f} tok/s, "
                        f"{acc_s:.1f} accepted/s, rate {rate:.2f} "
                        f"(draft={draft_arch}, k={n_draft})",
                        file=sys.stderr,
                    )
            except Exception as e:  # noqa: BLE001 — extra row is best-effort
                print(f"{tag} row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            finally:
                if deng is not None:
                    deng.stop()
                    deng.params = None
                    deng.cache = None
                    deng = None
        out["spec_paged_draft_ckpt_bytes"] = int(sum(
            a.size * a.dtype.itemsize for a in jax.tree.leaves(dparams)
        ))
        dparams = None

        # Model-free variants (ISSUE 12, docs/SPECULATIVE.md): prompt-lookup
        # and self-draft rows on a REPETITIVE-CONTINUATION workload (logit
        # bias pins each request to a fixed continuation token, the serving
        # shape that prompt lookup exists for — RAG quoting, code echo).
        # Zero extra checkpoint bytes resident by construction (the
        # draft_ckpt_bytes row above is what these modes delete). ROADMAP
        # target (recorded, gated once the TPU campaign runs):
        # accepted-tokens/s ≥ 1.5x plain paged decode at bs `slots`.
        for smode in ("prompt_lookup", "self_draft"):
            seng = None
            skey = ("spec_lookup" if smode == "prompt_lookup"
                    else "spec_selfdraft")
            try:
                seng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    n_draft=n_draft,
                    engine_cfg=EngineConfig(
                        max_slots=slots, max_seq=max_seq,
                        kv_pages=pool, kv_page_size=page, spec_mode=smode,
                    ),
                )
                seng.start()
                seng.warmup(prompt_len)
                for bs in (1, slots):
                    seng._decode_time = 0.0
                    seng._decode_tokens = 0
                    seng.m_spec_rounds = 0
                    seng.m_spec_accepted = 0
                    seng.m_spec_drafted = 0
                    seng.m_spec_dlen_hist = {}
                    ths = [threading.Thread(target=lambda i=i: seng.generate(
                        [(i * 13 + j) % 17 + 60 for j in range(prompt_len)],
                        max_new_tokens=gen_len, ignore_eos=True,
                        logit_bias={(i * 7) % 200 + 30: 24.0},
                    )) for i in range(bs)]
                    for t in ths:
                        t.start()
                    _join_or_die(ths, seng, f"{skey} bs{bs}")
                    stps = (seng._decode_tokens / seng._decode_time
                            if seng._decode_time else 0.0)
                    acc_s = (seng.m_spec_accepted / seng._decode_time
                             if seng._decode_time else 0.0)
                    rate = seng.metrics().get("spec_accept_rate", 0.0)
                    out[f"{skey}_tps_bs{bs}"] = round(stps, 2)
                    out[f"{skey}_accepted_per_s_bs{bs}"] = round(acc_s, 2)
                    out[f"{skey}_accept_rate_bs{bs}"] = round(rate, 3)
                    base = out.get("decode_tokens_per_sec_paged")
                    if bs == slots and base:
                        out[f"{skey}_vs_paged"] = round(stps / base, 2)
                        out[f"{skey}_accepted_vs_paged"] = round(
                            acc_s / base, 2)
                    print(
                        f"{skey} bs{bs}: {stps:.1f} tok/s, "
                        f"{acc_s:.1f} accepted/s, rate {rate:.2f}",
                        file=sys.stderr,
                    )
                out[f"{skey}_draft_hist"] = {
                    str(k): v
                    for k, v in sorted(seng.m_spec_dlen_hist.items())
                }
            except Exception as e:  # noqa: BLE001 — extra row is best-effort
                print(f"{skey} row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            finally:
                if seng is not None:
                    seng.stop()
                    seng.params = None
                    seng.cache = None
                    seng = None

    # Multi-tenant LoRA row (ISSUE 10, docs/LORA_SERVING.md): decode tok/s
    # at `slots` slots × `slots` DISTINCT adapters (every decode row gathers
    # its own rank factors through the ragged Pallas kernel) vs one shared
    # adapter vs the adapter-less base on the same paged config — the
    # tenancy tax in one ratio (target: mixed ≥ 0.9× single-adapter) —
    # plus adapter_swap_in_ms (cold tenant: disk fetch + device promote +
    # first admission) and an int8-base + LoRA composition variant (the
    # delta runs bf16 beside the fused dequant matmul).
    if os.environ.get("BENCH_LORA", "1") != "0" and max_seq % 128 == 0:
        import shutil
        import tempfile

        lora_tmp = tempfile.mkdtemp(prefix="bench_lora_")
        leng = None
        try:
            import numpy as np

            from safetensors.numpy import save_file as _sf_save

            lrank = int(os.environ.get("BENCH_LORA_RANK", "16"))
            D = cfg.hidden_size
            Hq = cfg.num_heads * cfg.head_dim_
            Kv = cfg.num_kv_heads * cfg.head_dim_
            lrng = np.random.default_rng(0)

            def _mk_adapter(i: int) -> str:
                path = os.path.join(lora_tmp, f"a{i}")
                os.makedirs(path, exist_ok=True)
                t = {}
                for li in range(cfg.num_layers):
                    for mod, od in (("self_attn.q_proj", Hq),
                                    ("self_attn.v_proj", Kv)):
                        pre = f"base_model.model.model.layers.{li}.{mod}"
                        t[f"{pre}.lora_A.weight"] = lrng.normal(
                            0, 0.01, (lrank, D)).astype(np.float32)
                        t[f"{pre}.lora_B.weight"] = lrng.normal(
                            0, 0.01, (od, lrank)).astype(np.float32)
                _sf_save(t, os.path.join(path, "adapter_model.safetensors"))
                with open(os.path.join(path, "adapter_config.json"), "w") as f:
                    json.dump({"r": lrank, "lora_alpha": lrank}, f)
                return path

            adirs = [_mk_adapter(i) for i in range(slots + 1)]
            page = 128
            pool = max(2, int(slots * (max_seq // page) * 0.6))

            def _lora_engine(qmode: str = ""):
                e = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq,
                                            kv_pages=pool, kv_page_size=page),
                    quantization=qmode,
                )
                e.start()
                e.warmup(prompt_len)
                return e

            def _measure(e, tenants: list) -> float:
                e._decode_time = 0.0
                e._decode_tokens = 0
                ths = [threading.Thread(target=lambda i=i, ad=ad: e.generate(
                    [(i * 37 + j) % 255 + 1 for j in range(prompt_len)],
                    max_new_tokens=gen_len, ignore_eos=True, adapter=ad,
                )) for i, ad in enumerate(tenants)]
                for t in ths:
                    t.start()
                _join_or_die(ths, e, "lora row")
                return (e._decode_tokens / e._decode_time
                        if e._decode_time else 0.0)

            leng = _lora_engine()
            base_tps = _measure(leng, [None] * slots)
            for i in range(slots):
                leng.register_adapter(f"tenant{i}", adirs[i])
            # Warm pass promotes every tenant + compiles the lora programs,
            # so the measured passes price steady-state serving.
            _measure(leng, [f"tenant{i}" for i in range(slots)])
            multi_tps = _measure(leng, [f"tenant{i}" for i in range(slots)])
            single_tps = _measure(leng, ["tenant0"] * slots)
            # Cold-tenant swap-in: a registered-but-never-promoted adapter's
            # first admission pays disk fetch + device promote; the same
            # request warm prices the baseline.
            leng.register_adapter("cold", adirs[slots])
            cold_ids = [(7 + j) % 255 + 1 for j in range(prompt_len)]
            t0 = time.time()
            leng.generate(cold_ids, max_new_tokens=4, ignore_eos=True,
                          adapter="cold")
            cold_s = time.time() - t0
            t0 = time.time()
            leng.generate(cold_ids, max_new_tokens=4, ignore_eos=True,
                          adapter="cold")
            warm_s = time.time() - t0
            out["lora_tps_base"] = round(base_tps, 2)
            out["lora_tps_multi8"] = round(multi_tps, 2)
            out["lora_tps_single"] = round(single_tps, 2)
            out["lora_multi_vs_single"] = round(
                multi_tps / max(single_tps, 1e-9), 3)
            out["lora_multi_vs_base"] = round(
                multi_tps / max(base_tps, 1e-9), 3)
            out["adapter_swap_in_ms"] = round(
                max(0.0, (cold_s - warm_s)) * 1e3, 1)
            print(
                f"lora: base {base_tps:.1f} tok/s, {slots}x distinct "
                f"{multi_tps:.1f} ({out['lora_multi_vs_single']}x single "
                f"{single_tps:.1f}), swap-in "
                f"{out['adapter_swap_in_ms']} ms",
                file=sys.stderr,
            )
            leng.stop()
            leng.params = None
            leng.cache = None
            leng = _lora_engine("int8")
            for i in range(slots):
                leng.register_adapter(f"tenant{i}", adirs[i])
            _measure(leng, [f"tenant{i}" for i in range(slots)])
            q_tps = _measure(leng, [f"tenant{i}" for i in range(slots)])
            out["lora_tps_multi8_int8"] = round(q_tps, 2)
            print(f"lora int8 base + bf16 delta: {q_tps:.1f} tok/s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"BENCH_LORA row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if leng is not None:
                leng.stop()
                leng.params = None
                leng.cache = None
                leng = None
            shutil.rmtree(lora_tmp, ignore_errors=True)

    # Over-subscription row (ISSUE 3 on-demand KV growth): 2×slots requests
    # claim max_tokens near max_seq but produce SHORT real outputs (a stop
    # string learned from a probe run) on a pool sized so the old up-front
    # reservation planner admits only pool // worst_pages at a time. Emits
    # the measured on-demand concurrency next to the old planner's, then a
    # second, genuinely-overcommitted phase times the preempt/restore
    # cycle. JSON contract: adds paged_upfront_concurrency,
    # paged_ondemand_concurrency, paged_preempt_recover_ms.
    if os.environ.get("BENCH_OVERSUB", "1") != "0" and max_seq % 128 == 0:
        oeng = None
        try:
            page = 128
            b = 1
            while b < prompt_len:
                b *= 2
            prompt_pages = -(-b // page)
            pool = slots * (prompt_pages + 1)
            oeng = Engine(
                cfg, params, ByteTokenizer(cfg.vocab_size),
                engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq,
                                        kv_pages=pool, kv_page_size=page),
            )
            oeng.start()
            oeng.warmup(prompt_len)
            near = max_seq - prompt_len - 1
            worst = -(-min(prompt_len + near, max_seq) // page)
            upfront = max(1, pool // worst)
            probe_ids = [(j * 31) % 255 + 1 for j in range(prompt_len)]
            probe, _ = oeng.generate(probe_ids, max_new_tokens=24,
                                     ignore_eos=True)
            ostop = [probe[8:14] or "\x00"]
            oeng.m_peak_active = 0

            def oone(i: int) -> None:
                ids = [(i * 41 + j) % 255 + 1 for j in range(prompt_len)]
                oeng.generate(ids, max_new_tokens=near, ignore_eos=True,
                              stop=ostop)

            othreads = [threading.Thread(target=oone, args=(i,))
                        for i in range(2 * slots)]
            for t in othreads:
                t.start()
            _join_or_die(othreads, oeng, "oversubscription row")
            out["paged_upfront_concurrency"] = upfront
            out["paged_ondemand_concurrency"] = int(oeng.m_peak_active)
            # Phase 2: genuinely overcommit (slots × gen_len long outputs
            # against the same small pool) so growth collides and the
            # preempt → swap/recompute → resume cycle gets timed.
            over = [threading.Thread(target=lambda i=i: oeng.generate(
                [(i * 53 + j) % 255 + 1 for j in range(prompt_len)],
                max_new_tokens=gen_len, ignore_eos=True,
            )) for i in range(slots)]
            for t in over:
                t.start()
            _join_or_die(over, oeng, "oversubscription preempt phase")
            recov = (oeng.m_kv_preempt_recover_ms / oeng.m_kv_preemptions
                     if oeng.m_kv_preemptions else 0.0)
            out["paged_preempt_recover_ms"] = round(recov, 2)
            out["paged_preemptions"] = int(oeng.m_kv_preemptions)
            out["paged_pages_grown"] = int(oeng.m_kv_pages_grown)
            print(
                f"oversub: on-demand admits {out['paged_ondemand_concurrency']} "
                f"vs up-front {upfront} on a {pool}-page pool; "
                f"{oeng.m_kv_preemptions} preemptions, recover {recov:.1f} ms",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"oversubscription row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if oeng is not None:
                oeng.stop()
                oeng.params = None
                oeng.cache = None
                oeng = None

    # Backpressure/shed row (ISSUE 4, docs/ROBUSTNESS.md): 2x-oversubscribed
    # traffic (4x slots requests against max_pending = slots) with bounded
    # admission ON vs OFF — shed (429) rate and p99 TTFT of the ADMITTED
    # requests. The point of shedding is visible in the on/off delta: with
    # the bound, admitted requests wait at most ~one queue generation; with
    # an unbounded queue the tail request's TTFT includes every request in
    # front of it. Then an injected loop death (testing/faults engine_loop
    # site) timed through the manager's crash-only evict → reload → first
    # served token: engine_restart_recover_ms.
    if os.environ.get("BENCH_SHED", "1") != "0":
        try:
            from localai_tpu.engine import QueueFullError

            N = 4 * slots
            for tag, mp in (("on", slots), ("off", 0)):
                seng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq,
                                            max_pending=mp),
                )
                seng.start()
                seng.warmup(prompt_len)
                sttfts: list[float] = []
                sheds = [0]
                slock = threading.Lock()

                def sone(i: int, eng=seng) -> None:
                    ids = [(i * 61 + j) % 255 + 1 for j in range(prompt_len)]
                    try:
                        _, ev = eng.generate(ids, max_new_tokens=gen_len,
                                             ignore_eos=True)
                        with slock:
                            sttfts.append(ev.timing_prompt_processing)
                    except QueueFullError:
                        with slock:
                            sheds[0] += 1

                sthreads = [threading.Thread(target=sone, args=(i,))
                            for i in range(N)]
                for t in sthreads:
                    t.start()
                _join_or_die(sthreads, seng, f"shed row ({tag})")
                seng.stop()
                seng.params = None
                seng.cache = None
                sttfts.sort()
                p99 = sttfts[min(len(sttfts) - 1,
                                 int(len(sttfts) * 0.99))] if sttfts else 0.0
                out[f"shed_rate_backpressure_{tag}"] = round(sheds[0] / N, 3)
                out[f"p99_ttft_ms_backpressure_{tag}"] = round(p99 * 1000, 1)
                print(
                    f"shed({tag}): {sheds[0]}/{N} shed, "
                    f"p99 TTFT {p99 * 1000:.1f} ms", file=sys.stderr,
                )

            # Injected loop death → crash-only restart recovery.
            import tempfile

            import yaml as _yaml

            from localai_tpu.config import ApplicationConfig
            from localai_tpu.server import ModelManager
            from localai_tpu.testing import faults as _faults

            md = tempfile.mkdtemp(prefix="bench-shed-models-")
            with open(os.path.join(md, "bm.yaml"), "w") as f:
                _yaml.safe_dump({
                    "name": "bm", "model": arch, "context_size": max_seq,
                    "max_slots": slots, "max_tokens": 8,
                }, f)
            mgr = ModelManager(ApplicationConfig(models_dir=md))
            try:
                lm = mgr.get("bm")
                lm.engine.generate([1, 2, 3], max_new_tokens=2,
                                   ignore_eos=True)
                with _faults.active(_faults.FaultSchedule(
                        seed=0, rate=1.0, sites=("engine_loop",),
                        max_faults=1)):
                    lm.engine._wake.set()
                    deadline = time.time() + 120
                    while not lm.engine.is_dead and time.time() < deadline:
                        time.sleep(0.005)
                if not lm.engine.is_dead:
                    raise RuntimeError("injected loop death never landed")
                t0 = time.time()
                lm2 = mgr.get("bm")  # crash-only evict + reload
                _, ev = lm2.engine.generate([1, 2, 3], max_new_tokens=2,
                                            ignore_eos=True)
                recover_ms = (time.time() - t0) * 1000
                out["engine_restart_recover_ms"] = round(recover_ms, 1)
                print(f"restart after injected loop death: "
                      f"{recover_ms:.0f} ms to first served token",
                      file=sys.stderr)
            finally:
                mgr.shutdown()
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"shed row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    # Cluster scheduler row (ISSUE 6, docs/CLUSTER.md): sustained
    # throughput + p99 TTFT at 4x single-engine saturation across 2 local
    # replicas, prefix-affinity on vs off (hit_weight 0 = least-loaded), a
    # span_transfer_ms microbench of the prefill→decode frame path, and
    # disaggregated vs mixed-role TTFT for a warm prompt. Deadline-joined
    # like the PR 4 rows: a wedged cluster fails the row, not the harness.
    if os.environ.get("BENCH_CLUSTER", "1") != "0" and max_seq % 128 == 0:
        creps = []
        try:
            from localai_tpu.cluster import (
                ClusterClient,
                LocalReplica,
                build_local_replicas,
            )

            ccfg = EngineConfig(
                max_slots=slots, max_seq=max_seq,
                kv_pages=slots * (max_seq // 128), kv_page_size=128,
                prefix_admit_async_compile=False,
            )
            N = 4 * slots  # 4x one engine's concurrent saturation
            n_groups = 4   # repeated prompt groups — the affinity signal
            # Affinity (and span export) needs the prompt to COVER at least
            # one full KV page past the match cap — a prompt at or under the
            # page size has no page-aligned prefix to share.
            cl_prompt = min(max(prompt_len, 2 * 128 + 2),
                            max_seq - gen_len - 8)
            if cl_prompt <= 128:
                raise RuntimeError(
                    f"max_seq {max_seq} too small for a cluster-row prompt "
                    f"covering one 128-row KV page")
            # TWO engines total, shared across every sub-row (a full warmup
            # per engine per row blew the bench wall); priming compiles the
            # exact shapes the measurement uses — the concurrent pair covers
            # the grouped-admission program, the repeat covers cached admit.
            creps = build_local_replicas(
                cfg, params, ByteTokenizer(cfg.vocab_size), n=2,
                engine_cfg=ccfg, roles=["mixed", "mixed"])
            for rep in creps:
                pa, pb = [5] * cl_prompt, [6] * cl_prompt
                pts = [threading.Thread(
                    target=lambda ids=ids_: rep.engine.generate(
                        ids, max_new_tokens=gen_len, ignore_eos=True))
                    for ids_ in (pa, pb)]
                for t in pts:
                    t.start()
                for t in pts:
                    t.join(timeout=600)
                rep.engine.generate(pa, max_new_tokens=4, ignore_eos=True)

            def cluster_row(tag, hw, row_seed):
                client = ClusterClient(creps, hit_weight=hw,
                                       gauge_refresh_s=0.05)
                cttfts: list[float] = []
                cerrs: list[str] = []
                clock = threading.Lock()

                def cone(i: int) -> None:
                    g = i % n_groups
                    ids = [(row_seed + g * 131 + j * 7) % 255 + 1
                           for j in range(cl_prompt)]
                    try:
                        _, ev = client.generate(ids, max_new_tokens=gen_len,
                                                ignore_eos=True)
                        with clock:
                            cttfts.append(ev.timing_prompt_processing)
                    except Exception as e:  # noqa: BLE001
                        with clock:
                            cerrs.append(f"req {i}: {type(e).__name__}: {e}")

                cthreads = [threading.Thread(target=cone, args=(i,))
                            for i in range(N)]
                cw0 = time.time()
                hits0 = sum(r.engine.m_prefix_hits for r in creps)
                for t in cthreads:
                    t.start()
                deadline = time.time() + 600
                for t in cthreads:
                    t.join(timeout=max(1.0, deadline - time.time()))
                if any(t.is_alive() for t in cthreads):
                    raise RuntimeError(
                        f"cluster row ({tag}): requests hung past deadline")
                if cerrs:
                    raise RuntimeError("; ".join(cerrs[:3]))
                cwall = time.time() - cw0
                cttfts.sort()
                p99 = cttfts[min(len(cttfts) - 1, int(len(cttfts) * 0.99))]
                hits = sum(r.engine.m_prefix_hits for r in creps) - hits0
                out[f"cluster_tps_affinity_{tag}"] = round(
                    N * gen_len / cwall, 1)
                out[f"cluster_p99_ttft_ms_affinity_{tag}"] = round(
                    p99 * 1000, 1)
                out[f"cluster_prefix_hits_affinity_{tag}"] = hits
                print(
                    f"cluster({tag}): {N * gen_len / cwall:.1f} tok/s, "
                    f"p99 TTFT {p99 * 1000:.1f} ms, {hits} prefix hits",
                    file=sys.stderr,
                )

            # Distinct prompt sets per row so neither row inherits the
            # other's cached spans.
            cluster_row("off", 0.0, 17)
            cluster_row("on", 4.0, 101)

            # Disaggregated prefill→decode vs mixed-role TTFT + transfer
            # time — same engines, rewrapped with dedicated roles.
            droles = [LocalReplica(r.name, r.engine, role)
                      for r, role in zip(creps, ["prefill", "decode"])]
            dclient = ClusterClient(droles, gauge_refresh_s=0.05)
            ids = [(j * 11) % 255 + 1 for j in range(cl_prompt)]
            # Seed + time the raw span path once.
            droles[0].engine.generate(ids, max_new_tokens=1, ignore_eos=True)
            t0 = time.time()
            frame = droles[0].engine.export_prefix_span(ids)
            ok = (frame is not None
                  and droles[1].engine.import_span_bytes(frame))
            if ok:
                out["span_transfer_ms"] = round((time.time() - t0) * 1000, 2)
                out["span_frame_bytes"] = len(frame)
            _, ev = dclient.generate(ids, max_new_tokens=8, ignore_eos=True)
            out["disagg_ttft_ms"] = round(
                ev.timing_prompt_processing * 1000, 1)
            # Mixed-role baseline: the same prompt shape, cold prefix, full
            # admission on one engine.
            mixed_ids = [(j * 13) % 255 + 2 for j in range(len(ids))]
            _, ev = creps[0].engine.generate(mixed_ids, max_new_tokens=8,
                                             ignore_eos=True)
            out["mixed_ttft_ms"] = round(
                ev.timing_prompt_processing * 1000, 1)
            print(
                f"disagg TTFT {out.get('disagg_ttft_ms')} ms vs mixed "
                f"{out.get('mixed_ttft_ms')} ms "
                f"(span transfer {out.get('span_transfer_ms')} ms, "
                f"frame {out.get('span_frame_bytes')} B)",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"cluster row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            for rep in creps:
                rep.engine.stop()
                rep.engine.params = None
                rep.engine.cache = None

    # Multi-host cluster row (ISSUE 13, docs/CLUSTER.md § multi-host): a
    # 2-process SIMULATED cluster — one spawned prefill-role worker process
    # (own jax runtime, real HTTP hop) + a local decode engine behind the
    # cluster client. Measures aggregate tok/s + p99 TTFT at 4x one-host
    # saturation with cluster-wide disaggregation on, span_transfer_ms over
    # the real network hop (streamed, checksummed), and disagg-vs-recompute
    # TTFT. Deadline-joined; gated in tools/bench_gate.py (tps/ttft/ms
    # direction markers).
    if os.environ.get("BENCH_MULTIHOST", "1") != "0" and max_seq % 128 == 0:
        mh_worker = None
        mh_dec = None
        try:
            import tempfile

            from localai_tpu.cluster import (
                ClusterClient,
                LocalReplica,
                RemoteReplica,
            )
            from localai_tpu.testing import multihost

            mh_pages = slots * (max_seq // 128)
            mdir = tempfile.mkdtemp(prefix="bench-mh-")
            multihost.write_tiny_model_yaml(
                mdir, name="mh", arch=arch, context_size=max_seq,
                max_slots=slots, kv_pages=mh_pages, kv_page_size=128)
            mh_worker = multihost.spawn_worker(mdir, role="prefill",
                                               boot_timeout_s=600.0)
            mh_dec = Engine(
                cfg, params, ByteTokenizer(cfg.vocab_size),
                engine_cfg=EngineConfig(
                    max_slots=slots, max_seq=max_seq,
                    kv_pages=mh_pages, kv_page_size=128,
                    prefix_admit_async_compile=False,
                ))
            mh_dec.start()
            mh_prompt = min(max(prompt_len, 2 * 128 + 2),
                            max_seq - gen_len - 8)
            if mh_prompt <= 128:
                raise RuntimeError(
                    f"max_seq {max_seq} too small for a multihost-row "
                    f"prompt covering one 128-row KV page")
            # Prime the decode engine's programs (concurrent pair + repeat,
            # same recipe as the cluster row).
            pa, pb = [5] * mh_prompt, [6] * mh_prompt
            pts = [threading.Thread(
                target=lambda ids=ids_: mh_dec.generate(
                    ids, max_new_tokens=gen_len, ignore_eos=True))
                for ids_ in (pa, pb)]
            for t in pts:
                t.start()
            for t in pts:
                t.join(timeout=600)
            mh_dec.generate(pa, max_new_tokens=4, ignore_eos=True)

            remote = RemoteReplica("host2", mh_worker.url, model="mh",
                                   timeout_s=600.0)
            mclient = ClusterClient(
                [LocalReplica("d0", mh_dec, role="decode"), remote],
                gauge_refresh_s=0.5, disaggregate=True)

            # Raw network-hop span path, warmed then timed: the worker
            # computes+streams the span once (cold), the timed fetch rides
            # its prefix cache.
            ids = [(j * 11) % 255 + 1 for j in range(mh_prompt)]
            from localai_tpu.cluster import netspan as _netspan

            frame = _netspan.fetch_span(mh_worker.url, "mh", ids,
                                        timeout_s=600.0)
            t0 = time.time()
            frame = _netspan.fetch_span(mh_worker.url, "mh", ids,
                                        timeout_s=600.0)
            ok = mh_dec.import_span_bytes(frame)
            if ok:
                out["multihost_span_transfer_ms"] = round(
                    (time.time() - t0) * 1000, 2)
                out["multihost_span_frame_bytes"] = len(frame)
            # Disaggregated TTFT (remote span already hot in the local host
            # tier) vs recompute TTFT (same shape, cold prefix, full local
            # admission — the fallback path's cost).
            _, ev = mclient.generate(ids, max_new_tokens=8, ignore_eos=True)
            out["multihost_disagg_ttft_ms"] = round(
                ev.timing_prompt_processing * 1000, 1)
            cold_ids = [(j * 13) % 255 + 2 for j in range(mh_prompt)]
            _, ev = mh_dec.generate(cold_ids, max_new_tokens=8,
                                    ignore_eos=True)
            out["multihost_recompute_ttft_ms"] = round(
                ev.timing_prompt_processing * 1000, 1)

            # Aggregate serving at 4x one-host saturation through the
            # 2-process cluster (grouped prompts: first of each group pays
            # the remote handoff, repeats ride local prefix affinity).
            N = 4 * slots
            n_groups = 4
            mttfts: list[float] = []
            merrs: list[str] = []
            mlock = threading.Lock()

            def mone(i: int) -> None:
                g = i % n_groups
                ids_ = [(g * 131 + j * 7) % 255 + 1
                        for j in range(mh_prompt)]
                try:
                    _, ev = mclient.generate(ids_, max_new_tokens=gen_len,
                                             ignore_eos=True)
                    with mlock:
                        mttfts.append(ev.timing_prompt_processing)
                except Exception as e:  # noqa: BLE001
                    with mlock:
                        merrs.append(f"req {i}: {type(e).__name__}: {e}")

            mthreads = [threading.Thread(target=mone, args=(i,))
                        for i in range(N)]
            mw0 = time.time()
            for t in mthreads:
                t.start()
            deadline = time.time() + 600
            for t in mthreads:
                t.join(timeout=max(1.0, deadline - time.time()))
            if any(t.is_alive() for t in mthreads):
                raise RuntimeError("multihost row: requests hung past "
                                   "deadline")
            if merrs:
                raise RuntimeError("; ".join(merrs[:3]))
            mwall = time.time() - mw0
            mttfts.sort()
            p99 = mttfts[min(len(mttfts) - 1, int(len(mttfts) * 0.99))]
            out["multihost_tps"] = round(N * gen_len / mwall, 1)
            out["multihost_p99_ttft_ms"] = round(p99 * 1000, 1)
            out["multihost_remote_handoffs"] = mclient.m_remote_handoffs
            print(
                f"multihost: {out['multihost_tps']} tok/s, p99 TTFT "
                f"{out['multihost_p99_ttft_ms']} ms, disagg TTFT "
                f"{out.get('multihost_disagg_ttft_ms')} ms vs recompute "
                f"{out.get('multihost_recompute_ttft_ms')} ms (span "
                f"{out.get('multihost_span_transfer_ms')} ms over HTTP, "
                f"{mclient.m_remote_handoffs} remote handoffs)",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"multihost row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if mh_dec is not None:
                mh_dec.stop()
                mh_dec.params = None
                mh_dec.cache = None
            if mh_worker is not None:
                mh_worker.stop()

    # Tensor-parallel serving row (ISSUE 7, docs/SHARDED_SERVING.md):
    # paged decode tok/s + p99 TTFT at tp=1 vs tp=4 vs tp=8 (whatever the
    # device count and the arch's kv-head divisibility allow — 8B decode is
    # HBM-bound per chip, so tp multiplies aggregate KV bandwidth), chunked
    # prefill throughput with and without sp, and an ici_collective_ms
    # estimate (timed psum of the layer-boundary reduction shape, scaled to
    # the 2 psums/layer the Megatron layout pays per decode step).
    # Deadline-joined; measurable on the CPU mesh, real-TPU numbers ride
    # the next roofline run.
    if os.environ.get("BENCH_TP", "1") != "0" and max_seq % 128 == 0:
        try:
            from localai_tpu.parallel.mesh import MeshPlan, build_mesh
            from localai_tpu.parallel.sharding import max_valid_tp

            ndev = len(jax.devices())
            tp_gen = min(gen_len, 128)
            # 1/4/8 are the 8B v5e-8 points; the arch's own max rides along
            # so the row stays measurable for archs whose kv heads exclude
            # 4/8 (the tiny CPU smoke measures tp=1 vs tp=2).
            cand = sorted({1, 4, 8, max_valid_tp(cfg, min(8, ndev))})
            tps = [t for t in cand
                   if t <= ndev and max_valid_tp(cfg, t) == t]
            for tp in tps:
                teng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    mesh_plan=MeshPlan(tp=tp),
                    engine_cfg=EngineConfig(
                        max_slots=slots, max_seq=max_seq,
                        kv_pages=slots * (max_seq // 128), kv_page_size=128,
                        prefix_admit_async_compile=False,
                    ),
                )
                try:
                    teng.start()
                    teng.warmup(prompt_len)
                    teng._decode_time = 0.0
                    teng._decode_tokens = 0
                    tttfts: list[float] = []
                    terrs: list[str] = []
                    tlock = threading.Lock()

                    def tone(i: int, e=teng, acc=tttfts, err=terrs, lk=tlock):
                        ids = [(i * 41 + j) % 255 + 1 for j in range(prompt_len)]
                        try:
                            _, ev = e.generate(ids, max_new_tokens=tp_gen,
                                               ignore_eos=True)
                            with lk:
                                acc.append(ev.timing_prompt_processing)
                        except Exception as ex:  # noqa: BLE001
                            with lk:
                                err.append(f"req {i}: {type(ex).__name__}: {ex}")
                    tthreads = [threading.Thread(target=tone, args=(i,))
                                for i in range(slots)]
                    for t in tthreads:
                        t.start()
                    _join_or_die(tthreads, teng, f"tp={tp} decode row")
                    if terrs:
                        raise RuntimeError("; ".join(terrs[:3]))
                    tps_val = (teng._decode_tokens / teng._decode_time
                               if teng._decode_time else 0.0)
                    tttfts.sort()
                    p99 = tttfts[min(len(tttfts) - 1, int(len(tttfts) * 0.99))]
                    out[f"tp{tp}_decode_tps"] = round(tps_val, 2)
                    out[f"tp{tp}_p99_ttft_ms"] = round(p99 * 1000, 1)
                    print(f"tp={tp}: {tps_val:.1f} tok/s, p99 TTFT "
                          f"{p99 * 1000:.1f} ms", file=sys.stderr)
                finally:
                    teng.stop()
                    teng.params = teng.cache = None

            # ICI collective cost estimate: one psum of the o-projection
            # boundary shape ([slots, hidden] f32) over the widest measured
            # tp, scaled to 2 psums/layer (o + MLP down) per decode step.
            tp_max = max(tps)
            if tp_max > 1:
                import jax.numpy as jnp
                from jax.sharding import PartitionSpec as P

                pm = build_mesh(MeshPlan(tp=tp_max))
                x = jnp.ones((slots, cfg.hidden_size), jnp.float32)
                f = jax.jit(jax.shard_map(
                    lambda v: jax.lax.psum(v, "tp"), mesh=pm,
                    in_specs=P(None, "tp"), out_specs=P()))
                f(x).block_until_ready()  # compile
                reps = 50
                t0 = time.time()
                for _ in range(reps):
                    r = f(x)
                r.block_until_ready()
                per_psum = (time.time() - t0) / reps
                out["ici_collective_ms"] = round(
                    per_psum * 2 * cfg.num_layers * 1000, 4)
                print(f"ici_collective_ms/step (tp={tp_max} est.): "
                      f"{out['ici_collective_ms']}", file=sys.stderr)

            # Chunked prefill throughput, with and without sp (dense
            # engines: sp excludes the paged pool). One long admission per
            # engine; prefill tok/s = prompt / TTFT of the second run (the
            # first pays the chunk-program compiles).
            sp_deg = 2 if (ndev >= 2 and max_seq % 2 == 0) else 1
            long_p = min(max_seq - tp_gen - 8, 4 * 512)
            chunk = 512 if long_p > 512 else 256
            for tag, splan in (("nosp", MeshPlan(tp=1)),
                               ("sp", MeshPlan(tp=1, sp=sp_deg))):
                if tag == "sp" and sp_deg == 1:
                    continue
                peng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    mesh_plan=splan,
                    engine_cfg=EngineConfig(
                        max_slots=2, max_seq=max_seq,
                        prefill_chunk=0 if tag == "sp" else chunk,
                        prefix_cache_entries=0,
                    ),
                )
                try:
                    peng.start()
                    ids = [(j * 7) % 255 + 1 for j in range(long_p)]
                    peng.generate(ids, max_new_tokens=1, ignore_eos=True)
                    ids2 = [(j * 11) % 255 + 2 for j in range(long_p)]
                    _, ev = peng.generate(ids2, max_new_tokens=1,
                                          ignore_eos=True)
                    tput = (long_p / ev.timing_prompt_processing
                            if ev.timing_prompt_processing else 0.0)
                    out[f"prefill_chunk_tps_{tag}"] = round(tput, 1)
                    print(f"prefill({tag}, {long_p} tok): {tput:.1f} tok/s",
                          file=sys.stderr)
                finally:
                    peng.stop()
                    peng.params = peng.cache = None
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            import traceback

            traceback.print_exc()
            print(f"BENCH_TP row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)

    # Prompt/prefix-cache rows (VERDICT r4 item 3), dense and paged: a LONG
    # shared prefix (4000 tokens, dedicated 8k-seq engines) so the prefill
    # saving (~0.5 s at measured rates) dominates round-trip noise — at a
    # 512-token prefix cold and cached are both ~1 RTT and the ratio is
    # noise (r4 recorded 0.34x cold/cached scatter that way; instrumented
    # runs show warm ≈ cold there). Sync cached-admit compile (the async
    # default exists to avoid serving stalls, not to change steady state);
    # every measurement is the second run of its path so XLA compiles never
    # enter the ratio. Paged: span pages map copy-on-write, tail-only
    # prefill (reference: cache_prompt, grpc-server.cpp:125).
    if os.environ.get("BENCH_PREFIX", "1") != "0":
        plen = int(os.environ.get("BENCH_PREFIX_LEN", "4000"))
        xmax = 8192
        rows_spec = [
            (False, "prefix", plen),
            (True, "paged_prefix", plen),
            # Legacy comparison row (ROADMAP re-measure item): the OLD
            # 512-token shape r04 recorded 0.34 on. Kept deliberately so the
            # dedicated 4000-token rows above have a release-over-release
            # anchor; at 512 tokens cold and cached are both ~1 round trip,
            # so ~1.0x here is expected, not a regression.
            (False, "prefix512_legacy", 512),
        ]
        for paged_flag, rkey, rlen in rows_spec:
            xeng = None
            try:
                xeng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    engine_cfg=EngineConfig(
                        max_slots=2, max_seq=xmax,
                        kv_pages=(2 * xmax) // 128 if paged_flag else 0,
                        kv_page_size=128,
                        prefix_admit_async_compile=False,
                    ),
                )
                xeng.start()
                mk = lambda seed: [(seed * 911 + j * 13) % 255 + 1
                                   for j in range(rlen)]
                # first calls compile (bucket prefill + block); second cold
                # call is the measurement
                xeng.generate(mk(1) + [7, 8], max_new_tokens=2, ignore_eos=True)
                _, ev_cold = xeng.generate(mk(2) + [7, 8], max_new_tokens=2,
                                           ignore_eos=True)
                shared = mk(3)
                xeng.generate(shared + [9, 10], max_new_tokens=2,
                              ignore_eos=True)  # seeds the span
                xeng.generate(shared + [11, 12], max_new_tokens=2,
                              ignore_eos=True)  # compiles the cached path
                hits0 = xeng.m_prefix_hits
                _, ev_warm = xeng.generate(shared + [13, 14], max_new_tokens=2,
                                           ignore_eos=True)
                if xeng.m_prefix_hits <= hits0:
                    print(f"{rkey} row: no hit recorded (skipped)",
                          file=sys.stderr)
                    continue
                cold_ms = ev_cold.timing_prompt_processing * 1000
                warm_ms = ev_warm.timing_prompt_processing * 1000
                out[f"{rkey}_cold_ttft_ms"] = round(cold_ms, 1)
                out[f"{rkey}_cached_ttft_ms"] = round(warm_ms, 1)
                out[f"{rkey}_ttft_speedup"] = round(
                    cold_ms / max(warm_ms, 1e-6), 2)
                out[f"{rkey}_len_tokens"] = rlen
                print(
                    f"{rkey} cache: cold {cold_ms:.1f}ms -> cached "
                    f"{warm_ms:.1f}ms ({rlen}-token prefix, "
                    f"{xeng.m_prefix_tokens} tokens reused)",
                    file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — extra row is best-effort
                print(f"{rkey} row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            finally:
                if xeng is not None:
                    xeng.stop()
                    xeng.params = None
                    xeng.cache = None
                    xeng._prefix_entries = []
                    xeng = None

    # Tree-batched parallel sampling row (ISSUE 18, docs/TREE_SAMPLING.md,
    # BENCH_FORK): best-of-8 admits ONE shared prefill and forks the slot
    # CoW 7x, vs best-of-1 and vs 8 independent clone admissions of the
    # same prompt. Reports decode tok/s + p99 TTFT for both fan-outs, the
    # allocator-counted KV page ratio (fork target <= 1.5x best-of-1 —
    # branches addref the shared prompt pages and only claim headroom),
    # and the fork-vs-clone TTFT speedup (clone pays N prefills). All
    # request threads are deadline-joined via _join_or_die.
    if os.environ.get("BENCH_FORK", "1") != "0" and max_seq % 128 == 0:
        feng = None
        try:
            import gc

            from localai_tpu.engine import GenRequest

            gc.collect()
            # Dedicated engine shape: the prompt must span enough pages
            # (16 at 2048/128) for page sharing to dominate the per-branch
            # tail/decode pages, or the ratio floor is arithmetic, not CoW:
            # (p + 8) / (p + 1) <= 1.5 needs p >= 13 shared pages.
            f_prompt = 2048
            f_gen = min(gen_len, 64)
            f_seq = max(max_seq, 4096)
            feng = Engine(
                cfg, params, ByteTokenizer(cfg.vocab_size),
                engine_cfg=EngineConfig(
                    max_slots=9, max_seq=f_seq,
                    kv_pages=(9 * (f_prompt + f_gen + 256)) // 128,
                    kv_page_size=128,
                    prefix_cache_entries=0,
                ),
            )
            feng.start()
            fids = [(j * 29) % 255 + 1 for j in range(f_prompt)]

            def fork_round(n: int, fork: bool):
                """(sorted ttfts_s, total_tokens, wall_s) for an n-branch
                seeded fan-out of the shared prompt."""
                reqs = [GenRequest(prompt_ids=list(fids),
                                   max_new_tokens=f_gen, ignore_eos=True,
                                   temperature=0.8, seed=1000 + i)
                        for i in range(n)]
                t_sub = time.monotonic()
                handles = (feng.submit_fork(reqs) if fork and n > 1
                           else [feng.submit(r) for r in reqs])
                ttfts = [None] * n
                toks = [0] * n

                def drain(i, h):
                    for ev in h:
                        if ev.kind == "token":
                            if ttfts[i] is None:
                                ttfts[i] = time.monotonic() - t_sub
                            toks[i] += 1

                thrs = [threading.Thread(target=drain, args=(i, h))
                        for i, h in enumerate(handles)]
                for t in thrs:
                    t.start()
                _join_or_die(thrs, feng, "BENCH_FORK row", timeout=900.0)
                wall = time.monotonic() - t_sub
                return sorted(t for t in ttfts if t is not None), \
                    sum(toks), wall

            # Each measurement is the second run of its exact shape so XLA
            # compiles (bucket prefill, decode block, fork admission,
            # clone fan-out occupancy) never enter a measured number.
            fork_round(1, False)
            feng.m_kv_pages_peak = 0
            tt1, tok1, wall1 = fork_round(1, False)
            peak1 = feng.m_kv_pages_peak
            fork_round(8, True)
            feng.m_kv_pages_peak = 0
            forks0 = feng.m_forks
            tt8, tok8, wall8 = fork_round(8, True)
            peak8 = feng.m_kv_pages_peak
            fork_round(8, False)
            ttc, _tokc, _wallc = fork_round(8, False)
            if feng.m_forks == forks0:
                print("BENCH_FORK: no fork recorded (clone fallback) — "
                      "row skipped", file=sys.stderr)
            else:
                out["fork_best_of_1_decode_tok_per_s"] = round(
                    tok1 / max(wall1, 1e-9), 1)
                out["fork_best_of_8_decode_tok_per_s"] = round(
                    tok8 / max(wall8, 1e-9), 1)
                out["fork_best_of_1_p99_ttft_ms"] = round(tt1[-1] * 1000, 1)
                out["fork_best_of_8_p99_ttft_ms"] = round(tt8[-1] * 1000, 1)
                # Pages are fixed-size, so the allocator page ratio IS the
                # KV bytes ratio.
                out["fork_kv_bytes_ratio"] = round(
                    peak8 / max(peak1, 1), 2)
                out["fork_vs_clone_ttft_speedup"] = round(
                    ttc[-1] / max(tt8[-1], 1e-9), 2)
                print(
                    f"fork best-of-8: {out['fork_best_of_8_decode_tok_per_s']}"
                    f" tok/s (bo1 {out['fork_best_of_1_decode_tok_per_s']}), "
                    f"p99 ttft {out['fork_best_of_8_p99_ttft_ms']}ms (bo1 "
                    f"{out['fork_best_of_1_p99_ttft_ms']}ms), kv ratio "
                    f"{out['fork_kv_bytes_ratio']}x ({peak8}/{peak1} pages), "
                    f"vs-clone ttft speedup "
                    f"{out['fork_vs_clone_ttft_speedup']}x "
                    f"({feng.m_forks - forks0} forks)", file=sys.stderr,
                )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"BENCH_FORK row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if feng is not None:
                feng.stop()
                feng.params = feng.cache = None
                gc.collect()

    # MoE dispatch row (VERDICT r2 item 5): one Mixtral-shaped layer's MLP,
    # dense all-experts vs exact top-k ragged_dot, same inputs.
    if os.environ.get("BENCH_MOE", "1") != "0":
        try:
            import gc

            import jax.numpy as jnp

            from localai_tpu.models import llama as L

            moe_arch = os.environ.get(
                "BENCH_MOE_ARCH",
                "mixtral-8x7b" if jax.default_backend() == "tpu" else "tiny-moe",
            )
            mcfg = get_arch(moe_arch)
            D, F, E = mcfg.hidden_size, mcfg.intermediate_size, mcfg.num_experts
            keys = jax.random.split(jax.random.key(0), 5)
            lp = {
                "router": jax.random.normal(keys[0], (D, E), jnp.bfloat16) * 0.02,
                "w_gate": jax.random.normal(keys[1], (E, D, F), jnp.bfloat16) * 0.02,
                "w_up": jax.random.normal(keys[2], (E, D, F), jnp.bfloat16) * 0.02,
                "w_down": jax.random.normal(keys[3], (E, F, D), jnp.bfloat16) * 0.02,
            }
            ntok = int(os.environ.get("BENCH_MOE_TOKENS", "2048"))
            x = jax.random.normal(keys[4], (ntok, D), jnp.bfloat16)
            dense = jax.jit(lambda lp, x: L._moe_dense(mcfg, lp, x))
            ragged = jax.jit(lambda lp, x: L._moe_ragged(mcfg, lp, x))

            def t(fn):
                jax.block_until_ready(fn(lp, x))  # compile
                t0 = time.time()
                for _ in range(3):
                    jax.block_until_ready(fn(lp, x))
                return (time.time() - t0) / 3

            td, tr = t(dense), t(ragged)
            out["moe_dense_ms"] = round(td * 1000, 2)
            out["moe_topk_ragged_ms"] = round(tr * 1000, 2)
            out["moe_topk_speedup_vs_dense"] = round(td / max(tr, 1e-9), 2)
            print(
                f"moe ({moe_arch}, {ntok} tokens): dense {td * 1000:.1f}ms vs "
                f"top-k ragged {tr * 1000:.1f}ms -> {td / max(tr, 1e-9):.2f}x",
                file=sys.stderr,
            )
            # Decode-phase MoE (VERDICT r3 weak 5): the same layer at decode
            # batch sizes. Honest expectation: at bs=8 BOTH paths stream all
            # E experts' weights from HBM (weight-bandwidth-bound), so top-k
            # saves FLOPs but not time on one chip — the ragged win grows
            # with batch; the row records where the crossover actually is.
            for nb in (slots, 64, 256):
                xb = jax.random.normal(jax.random.key(nb), (nb, D), jnp.bfloat16)

                def tb(fn, xb=xb):
                    jax.block_until_ready(fn(lp, xb))
                    t0 = time.time()
                    for _ in range(5):
                        jax.block_until_ready(fn(lp, xb))
                    return (time.time() - t0) / 5

                tdb, trb = tb(dense), tb(ragged)
                out[f"moe_decode_bs{nb}_dense_ms"] = round(tdb * 1000, 3)
                out[f"moe_decode_bs{nb}_ragged_ms"] = round(trb * 1000, 3)
                print(
                    f"moe decode bs{nb}: dense {tdb * 1000:.2f}ms vs ragged "
                    f"{trb * 1000:.2f}ms -> {tdb / max(trb, 1e-9):.2f}x",
                    file=sys.stderr,
                )
            del lp, x
            gc.collect()
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"moe row failed: {type(e).__name__}: {e}", file=sys.stderr)

    # DeepSeek-class MoE decode (VERDICT r4 #1/weak-2): top-k-of-MANY is
    # where MoE decode is genuinely sparse — top-2-of-8 at bs>=8 touches
    # every expert, but top-6-of-64 (V2-Lite) / top-8-of-256 (R1) leaves
    # most experts idle, and the ragged path's active-expert weight gather
    # (models/llama._moe_ragged, M < E branch) bounds HBM weight traffic by
    # the ACTIVE set. Timing ends in a device->host read of the last
    # result of a dependent chain, so the one round trip is amortized over
    # the chain.
    if os.environ.get("BENCH_DSMOE", "1") != "0":
        try:
            import gc

            import numpy as _np
            import jax.numpy as jnp

            from localai_tpu.models import llama as L

            on_tpu = jax.default_backend() == "tpu"
            ds_arch = os.environ.get(
                "BENCH_DSMOE_ARCH", "deepseek-v2-lite" if on_tpu else "tiny-mla"
            )
            dcfg = get_arch(ds_arch)
            # R1 routing shape at reduced width: 256 experts / top-8 /
            # sigmoid+bias+groups — a full-width R1 MoE layer is 22 GB and
            # needs the multi-host pod, so the routing sparsity is measured
            # at a width that fits one chip (disclosed as such).
            import dataclasses as _dc

            r1cfg = _dc.replace(
                get_arch("deepseek-r1"), hidden_size=1024,
                moe_intermediate_size=512,
            ) if on_tpu else None

            def ds_lp(cfg, key):
                D, Fm, E = cfg.hidden_size, cfg.moe_inter_size, cfg.num_experts
                ks = jax.random.split(key, 4)
                lp = {
                    "router": jax.random.normal(ks[0], (D, E), jnp.bfloat16) * 0.02,
                    "w_gate": jax.random.normal(ks[1], (E, D, Fm), jnp.bfloat16) * 0.02,
                    "w_up": jax.random.normal(ks[2], (E, D, Fm), jnp.bfloat16) * 0.02,
                    "w_down": jax.random.normal(ks[3], (E, Fm, D), jnp.bfloat16) * 0.02,
                }
                if cfg.router_bias:
                    lp["router_bias"] = jnp.zeros((E,), jnp.float32)
                return lp

            def chain_time(fn, lp, x0, iters=10):
                # dependent chain: out feeds the next call, ONE host pull at
                # the end — per-call time excludes the flat round trip.
                y = fn(lp, x0)
                _np.asarray(jax.jit(lambda a: a.reshape(-1)[:4])(y))  # compile+sync
                t0 = time.time()
                y = x0
                for _ in range(iters):
                    y = fn(lp, y)
                _np.asarray(jax.jit(lambda a: a.reshape(-1)[:4])(y))
                return (time.time() - t0) / iters

            for tag, cfg_ in (("dsv2lite", dcfg), ("r1shape", r1cfg)):
                if cfg_ is None:
                    continue
                lp = ds_lp(cfg_, jax.random.key(7))
                dense = jax.jit(lambda lp, x, c=cfg_: L._moe_dense(c, lp, x))
                ragged = jax.jit(lambda lp, x, c=cfg_: L._moe_ragged(c, lp, x))
                for nb in (1, 8):
                    xb = jax.random.normal(
                        jax.random.key(nb), (nb, cfg_.hidden_size), jnp.bfloat16
                    )
                    tdb = chain_time(dense, lp, xb)
                    trb = chain_time(ragged, lp, xb)
                    out[f"ds_moe_{tag}_bs{nb}_dense_ms"] = round(tdb * 1000, 3)
                    out[f"ds_moe_{tag}_bs{nb}_ragged_ms"] = round(trb * 1000, 3)
                    out[f"ds_moe_{tag}_bs{nb}_speedup"] = round(
                        tdb / max(trb, 1e-9), 2
                    )
                    print(
                        f"deepseek moe {tag} (E={cfg_.num_experts} top-"
                        f"{cfg_.num_experts_per_token}) decode bs{nb}: dense "
                        f"{tdb * 1000:.2f}ms vs gathered-ragged {trb * 1000:.2f}ms "
                        f"-> {tdb / max(trb, 1e-9):.2f}x",
                        file=sys.stderr,
                    )
                del lp
                gc.collect()
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"deepseek moe row failed: {type(e).__name__}: {e}", file=sys.stderr)

    # int8 weight-only row (reference parity: quantized GGUF serving is the
    # reference's standard practice; here per-channel int8 with dequant fused
    # into the matmuls — models/quant.py).
    for mode in ("int8", "int4"):
        if os.environ.get(f"BENCH_{mode.upper()}", "1") == "0":
            continue
        try:
            eng.cache = None
            eng.params = None
            import gc

            gc.collect()
            eng_q = Engine(
                cfg, params, ByteTokenizer(cfg.vocab_size),
                engine_cfg=EngineConfig(max_slots=slots, max_seq=max_seq),
                quantization=mode,
            )
            eng_q.warmup(prompt_len)
            eng_q._decode_time = 0.0
            eng_q._decode_tokens = 0
            qthreads = []
            for i in range(slots):
                ids = [(i * 37 + j) % 255 + 1 for j in range(prompt_len)]
                t = threading.Thread(
                    target=lambda ids=ids: eng_q.generate(
                        ids, max_new_tokens=gen_len, ignore_eos=True
                    )
                )
                qthreads.append(t)
            for t in qthreads:
                t.start()
            _join_or_die(qthreads, eng_q, f"{mode} row")
            qtps = (
                eng_q._decode_tokens / eng_q._decode_time
                if eng_q._decode_time else 0.0
            )
            out[f"decode_tokens_per_sec_{mode}"] = round(qtps, 2)
            print(f"{mode} row: decode {qtps:.1f} tok/s", file=sys.stderr)
            eng_q.stop()
            eng_q.cache = None
            eng_q.params = None
            gc.collect()
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"{mode} row failed: {type(e).__name__}: {e}", file=sys.stderr)

    # Long-context row (VERDICT r3 #3): a ≥32k-token prompt served UNDER THE
    # PAGED KV CACHE on a rope-scaled arch (llama-3.2-1b ships llama3
    # scaling to 128k) — prefill rate plus decode at full context.
    default_long = "32768" if jax.default_backend() == "tpu" else "0"
    long_ctx = int(os.environ.get("BENCH_LONG_CTX", default_long))
    if long_ctx:
        # Free the main engine's cache before allocating the long one.
        eng.cache = None
        eng.params = None
        import gc

        gc.collect()
        lpage = 128
        eng_long = Engine(
            cfg,
            params,
            ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(
                max_slots=1, max_seq=long_ctx,
                kv_pages=long_ctx // lpage, kv_page_size=lpage,
                prefix_cache_entries=0,  # single-shot row; keep the pool whole
            ),
        )
        long_prompt = [(j % 255) + 1 for j in range(long_ctx - 64)]
        try:
            # warmup stabilizes state avals — without it every admission at
            # this bucket retraces and the row measures the compiler.
            eng_long.warmup(len(long_prompt))
            eng_long._decode_time = 0.0
            eng_long._decode_tokens = 0
            _, ev = eng_long.generate(long_prompt, max_new_tokens=64, ignore_eos=True)
            # decode_time spans the whole active window INCLUDING the
            # multi-second 32k prefill; subtract it or the row reports the
            # prefill, not decode-at-full-context.
            ldec = max(eng_long._decode_time - ev.timing_prompt_processing, 1e-9)
            ltps = eng_long._decode_tokens / ldec
            out["long_ctx_prompt_tokens"] = len(long_prompt)
            out["long_ctx_paged"] = True
            out["long_ctx_prefill_ms"] = round(ev.timing_prompt_processing * 1000, 1)
            out["long_ctx_prefill_tok_per_s"] = round(
                len(long_prompt) / max(ev.timing_prompt_processing, 1e-9), 1
            )
            out["long_ctx_decode_tok_per_s"] = round(ltps, 1)
            print(
                f"long-context (paged, {eng_long.ecfg.kv_pages} pages): "
                f"{len(long_prompt)} tokens prefill in "
                f"{ev.timing_prompt_processing * 1000:.1f}ms, decode at full "
                f"context {ltps:.1f} tok/s",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — long row is best-effort
            print(f"long-context row failed: {type(e).__name__}: {e}", file=sys.stderr)
        eng_long.stop()
        eng_long.params = eng_long.cache = None

    # TTFT-under-load row (ISSUE 2, chunked ragged prefill): decode slots
    # must keep streaming while a 32k-token prefill is in flight. One slot
    # streams tokens continuously; mid-stream a 32k prompt admits through
    # the chunked path and a short probe lands right behind it. Reported:
    # the probe's TTFT under load vs idle, the longest inter-token gap on
    # the streaming slot during the prefill window (decode_stall_ms — the
    # single-shot baseline stalls for the WHOLE prefill, BENCH_r04: 3560 ms
    # at 32k), and how many tokens the streamer moved while the prefill ran.
    ilv_ctx = int(os.environ.get("BENCH_INTERLEAVE_CTX", default_long))
    if ilv_ctx:
        import gc

        from localai_tpu.engine import GenRequest

        gc.collect()
        ichunk = int(os.environ.get("BENCH_PREFILL_CHUNK", "512"))
        ipage = 128
        ieng = Engine(
            cfg, params, ByteTokenizer(cfg.vocab_size),
            engine_cfg=EngineConfig(
                max_slots=4, max_seq=ilv_ctx,
                kv_pages=(ilv_ctx + 3 * 4096) // ipage, kv_page_size=ipage,
                prefill_chunk=ichunk,
                prefix_cache_entries=0,  # measure raw chunked admission
            ),
        )
        long_prompt = [(j % 255) + 1 for j in range(ilv_ctx - 64)]
        short_ids = [(j * 17) % 255 + 1 for j in range(128)]
        try:
            ieng.start()
            # Warm every shape the measurement touches: the short bucket +
            # decode blocks, then the chunk programs and final-chunk shape.
            ieng.generate(short_ids, max_new_tokens=8, ignore_eos=True)
            _, evw = ieng.generate(long_prompt, max_new_tokens=4,
                                   ignore_eos=True)
            print(
                f"interleave warm: {len(long_prompt)}-token chunked prefill "
                f"{evw.timing_prompt_processing * 1000:.0f}ms "
                f"({ieng.m_prefill_chunks} chunks)", file=sys.stderr,
            )
            idle = []
            for _ in range(3):
                _, ev = ieng.generate(short_ids, max_new_tokens=8,
                                      ignore_eos=True)
                idle.append(ev.timing_prompt_processing)
            ttft_idle = sorted(idle)[1]

            stamps: list[float] = []
            sh = ieng.submit(GenRequest(
                prompt_ids=short_ids, max_new_tokens=4096, ignore_eos=True,
            ))

            def drain() -> None:
                for ev in sh:
                    if ev.kind == "token":
                        stamps.append(time.monotonic())

            dthr = threading.Thread(target=drain)
            dthr.start()
            while len(stamps) < 20:  # streamer must be in steady state
                time.sleep(0.005)
            t_p0 = time.monotonic()
            lh = ieng.submit(GenRequest(
                prompt_ids=long_prompt, max_new_tokens=4, ignore_eos=True,
            ))
            time.sleep(0.2)  # probe lands while the prefill is in flight
            _, ev_probe = ieng.submit(GenRequest(
                prompt_ids=short_ids, max_new_tokens=8, ignore_eos=True,
            )).result()
            _, ev_long = lh.result()
            t_p1 = t_p0 + ev_long.timing_prompt_processing
            sh.cancel()
            dthr.join(timeout=120)
            in_win = [t for t in stamps if t_p0 <= t <= t_p1]
            gaps = [b - a for a, b in zip(in_win, in_win[1:])]
            out["ttft_under_load_ms"] = round(
                ev_probe.timing_prompt_processing * 1000, 1)
            out["ttft_idle_ms"] = round(ttft_idle * 1000, 1)
            out["decode_stall_ms"] = (
                round(max(gaps) * 1000, 1) if gaps else None)
            out["decode_tokens_during_long_prefill"] = len(in_win)
            out["interleaved_prefill_ms"] = round(
                ev_long.timing_prompt_processing * 1000, 1)
            out["prefill_chunk"] = ichunk
            print(
                f"interleave ({len(long_prompt)} tokens, chunk {ichunk}): "
                f"probe ttft {out['ttft_under_load_ms']}ms under load vs "
                f"{out['ttft_idle_ms']}ms idle; decode moved {len(in_win)} "
                f"tokens during the prefill, max stall "
                f"{out['decode_stall_ms']}ms (prefill "
                f"{out['interleaved_prefill_ms']}ms)", file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"interleave row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            ieng.stop()
            ieng.params = ieng.cache = None
            gc.collect()

    # Million-token context ladder (ISSUE 14, docs/LONG_CONTEXT.md,
    # BENCH_LONGCTX): 32k/128k/512k contexts on dedicated long-context
    # engines — paged pool, hierarchical page tables (kv_l1_span),
    # windowed+sink attention with cold-page spill, chunked prefill. Per
    # rung: prefill tok/s, TTFT, decode tok/s; plus an N-users-one-document
    # aggregate (CoW span sharing at scale) on the smallest rung. Rows are
    # gated by tools/bench_gate.py with the standard direction markers
    # (tok_per_s/rate → higher-is-better, ttft_ms → lower-is-better;
    # covered in tests/test_bench_gate.py).
    if os.environ.get("BENCH_LONGCTX", "1") != "0":
        import gc

        ladder = [
            int(x) for x in os.environ.get(
                "BENCH_LONGCTX_LADDER", "32768,131072,524288"
            ).split(",") if x.strip()
        ]
        lc_page = 128
        lc_chunk = int(os.environ.get("BENCH_LONGCTX_CHUNK", "512"))
        lc_window = int(os.environ.get("BENCH_LONGCTX_WINDOW", "4096"))
        lc_sink = int(os.environ.get("BENCH_LONGCTX_SINK", "128"))
        lc_gen = 32
        for ctx in ladder:
            lceng = None
            try:
                gc.collect()
                lmax = -(-(ctx + 4 * lc_page) // lc_page) * lc_page
                lceng = Engine(
                    cfg, params, ByteTokenizer(cfg.vocab_size),
                    engine_cfg=EngineConfig(
                        max_slots=2, max_seq=lmax,
                        kv_pages=lmax // lc_page + 8, kv_page_size=lc_page,
                        kv_l1_span=128,
                        attention_sink=lc_sink, attention_window=lc_window,
                        kv_spill_bytes=2 << 30,
                        prefill_chunk=lc_chunk,
                        prefix_cache_entries=0,  # raw ladder; sharing row below
                        prefix_admit_async_compile=False,
                    ),
                )
                lceng.start()
                # Warm the chunk/final/decode shapes on a short prompt.
                lceng.generate([(j % 250) + 1 for j in range(2 * lc_chunk)],
                               max_new_tokens=4, ignore_eos=True)
                ids = [(j * 31) % 253 + 1 for j in range(ctx - lc_gen - 8)]
                res: list = []

                def lc_one() -> None:
                    res.append(lceng.generate(
                        ids, max_new_tokens=lc_gen, ignore_eos=True,
                    ))

                thr = threading.Thread(target=lc_one)
                thr.start()
                _join_or_die([thr], lceng, f"longctx {ctx} row",
                             timeout=1800.0)
                _, ev = res[0]
                tag = f"{ctx // 1024}k"
                ttft = ev.timing_prompt_processing
                dec_t = ev.timing_token_generation
                out[f"longctx_{tag}_prefill_tok_per_s"] = round(
                    len(ids) / max(ttft, 1e-9), 1)
                out[f"longctx_{tag}_ttft_ms"] = round(ttft * 1000, 1)
                out[f"longctx_{tag}_decode_tok_per_s"] = round(
                    max(ev.completion_tokens - 1, 1) / max(dec_t, 1e-9), 1)
                mtr = lceng.metrics()
                print(
                    f"longctx {tag}: prefill "
                    f"{out[f'longctx_{tag}_prefill_tok_per_s']} tok/s "
                    f"(ttft {out[f'longctx_{tag}_ttft_ms']} ms, "
                    f"{lceng.m_prefill_chunks} chunks), decode "
                    f"{out[f'longctx_{tag}_decode_tok_per_s']} tok/s, "
                    f"{int(mtr.get('kv_pages_spilled', 0))} pages spilled "
                    f"({int(mtr.get('kv_spill_host_bytes', 0)) >> 20} MiB "
                    "on host)", file=sys.stderr,
                )
            except Exception as e:  # noqa: BLE001 — extra row is best-effort
                print(f"longctx {ctx} row failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
            finally:
                if lceng is not None:
                    lceng.stop()
                    lceng.params = lceng.cache = None
                    lceng = None
        # N users over ONE long document: CoW span sharing at scale — the
        # document's pages (and its L1 directory chunks) are paid once, each
        # user prefills only its own tail through the masked chunk path.
        lc_users = int(os.environ.get("BENCH_LONGCTX_USERS", "4"))
        doc_len = min(ladder) if ladder else 32768
        lceng = None
        try:
            gc.collect()
            lmax = -(-(doc_len + 8 * lc_page) // lc_page) * lc_page
            lceng = Engine(
                cfg, params, ByteTokenizer(cfg.vocab_size),
                engine_cfg=EngineConfig(
                    max_slots=max(lc_users, 2), max_seq=lmax,
                    kv_pages=lmax // lc_page + 32 * lc_users,
                    kv_page_size=lc_page, kv_l1_span=128,
                    attention_sink=lc_sink, attention_window=lc_window,
                    kv_spill_bytes=2 << 30, prefill_chunk=lc_chunk,
                    prefix_cache_entries=4,
                    prefix_admit_async_compile=False,
                ),
            )
            lceng.start()
            doc = [(j * 29) % 251 + 1 for j in range(doc_len - 512)]
            # Seed the document span (and warm every shape).
            lceng.generate(doc + [3, 5], max_new_tokens=4, ignore_eos=True)
            lceng.generate(doc + [7, 9], max_new_tokens=4, ignore_eos=True)
            hits0 = lceng.m_prefix_hits
            outs: list = []
            lk = threading.Lock()

            def lc_user(i: int) -> None:
                tail = [(i * 37 + j) % 251 + 1 for j in range(64)]
                r = lceng.generate(doc + tail, max_new_tokens=lc_gen,
                                   ignore_eos=True)
                with lk:
                    outs.append(r)

            thrs = [threading.Thread(target=lc_user, args=(i,))
                    for i in range(lc_users)]
            w0 = time.time()
            for t in thrs:
                t.start()
            _join_or_die(thrs, lceng, "longctx users row", timeout=1800.0)
            wall = time.time() - w0
            hits = lceng.m_prefix_hits - hits0
            total_new = sum(ev.completion_tokens for _, ev in outs)
            out["longctx_users_agg_tok_per_s"] = round(
                total_new / max(wall, 1e-9), 1)
            out["longctx_users_prefix_hit_rate"] = round(
                hits / max(lc_users, 1), 3)
            out["longctx_users_doc_tokens"] = doc_len
            print(
                f"longctx users: {lc_users} users x {doc_len}-token doc — "
                f"{out['longctx_users_agg_tok_per_s']} tok/s aggregate, "
                f"hit rate {out['longctx_users_prefix_hit_rate']} "
                f"({lceng.m_prefix_tokens} prefix tokens reused)",
                file=sys.stderr,
            )
        except Exception as e:  # noqa: BLE001 — extra row is best-effort
            print(f"longctx users row failed: {type(e).__name__}: {e}",
                  file=sys.stderr)
        finally:
            if lceng is not None:
                lceng.stop()
                lceng.params = lceng.cache = None
                lceng = None
            gc.collect()

    # North-star row (BASELINE.md): llama-3-8b int8, served end-to-end over
    # HTTP POST /v1/chat/completions with stream:true. Synthetic weights
    # (zero egress) on the real 8B arch; decode tok/s from the engine's
    # steady-state counters, TTFT measured at the HTTP client.
    default_8b = "1" if jax.default_backend() == "tpu" else "0"
    if os.environ.get("BENCH_HTTP_8B", default_8b) != "0":
        # Drop every live reference to the earlier engines' HBM before the
        # 8 GB int8 tree loads.
        del params
        eng.params = eng.cache = None
        try:
            row = _http_8b_row(slots=slots, prompt_len=prompt_len,
                               gen_len=gen_len, max_seq=max_seq)
        except Exception as e:  # noqa: BLE001 — keep the 1B metric on failure
            import traceback

            traceback.print_exc()
            print(f"8B HTTP row failed: {type(e).__name__}: {e}", file=sys.stderr)
            row = None
        if row:
            # The 8B HTTP number becomes the primary metric; the 1B row
            # stays as a named secondary key.
            out[out.pop("metric")] = out.pop("value")
            out.pop("unit", None)
            out = {**row, **out}

    out["device"] = device
    print(json.dumps(out))


def _http_8b_row(slots: int, prompt_len: int, gen_len: int, max_seq: int):
    """Serve llama-3-8b (int8) through the real HTTP stack and measure it."""
    import gc
    import http.client
    import tempfile

    import jax
    import yaml

    gc.collect()

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager, Router, create_server
    from localai_tpu.server.openai_api import OpenAIApi

    arch_name = os.environ.get("BENCH_HTTP_ARCH", "llama-3-8b")
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "m.yaml"), "w") as f:
            yaml.safe_dump({
                "name": arch_name, "model": arch_name,
                "quantization": "int8", "max_slots": slots,
                "context_size": max_seq, "max_tokens": gen_len,
                "temperature": 0.0,
                "template": {"family": "chatml"},
                # Synthetic weights sample ids a plain ByteTokenizer decodes
                # to nothing (zero content chunks in r3); this tokenizer maps
                # the whole vocab to visible ASCII so client-observed TTFT
                # and per-token SSE cadence are real measurements.
                "tokenizer": "synthetic-bytes",
            }, f)
        app_cfg = ApplicationConfig(address="127.0.0.1", port=0,
                                    models_dir=d, max_active_models=1)
        manager = ModelManager(app_cfg)
        router = Router()
        OpenAIApi(manager).register(router)
        server = create_server(app_cfg, router)
        port = server.server_address[1]
        threading.Thread(target=server.serve_forever, daemon=True).start()

        body_tpl = {
            "model": arch_name, "stream": True, "ignore_eos": True,
            "max_tokens": gen_len,
            "messages": [{"role": "user", "content": "x" * prompt_len}],
        }

        results: list[dict] = []
        errors: list[str] = []
        lock = threading.Lock()

        def one(i: int) -> None:
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
            try:
                t0 = time.time()
                conn.request(
                    "POST", "/v1/chat/completions",
                    body=json.dumps(body_tpl),
                    headers={"Content-Type": "application/json",
                             "Extra-Usage": "1"},
                )
                resp = conn.getresponse()
                if resp.status != 200:
                    raise RuntimeError(f"HTTP {resp.status}: {resp.read()[:200]}")
                ttft = None
                n_tokens = 0
                usage = {}
                buf = b""
                while True:
                    chunk = resp.read(1)
                    if not chunk:
                        # Stream ended without [DONE]: the request must count
                        # as failed, not silently vanish from the stats.
                        raise RuntimeError("stream closed before [DONE]")
                    buf += chunk
                    while b"\n" in buf:
                        line, _, buf = buf.partition(b"\n")
                        line = line.strip()
                        if not line.startswith(b"data:"):
                            continue
                        data = line[len(b"data:"):].strip()
                        if data == b"[DONE]":
                            with lock:
                                results.append({
                                    "ttft": ttft, "tokens": n_tokens,
                                    "wall": time.time() - t0, "usage": usage,
                                })
                            return
                        ev = json.loads(data)
                        if ev.get("usage"):
                            usage = ev["usage"]
                        delta = (ev.get("choices") or [{}])[0].get("delta") or {}
                        # One chunk per generated token (empty text included);
                        # the initial role chunk carries "role" and is skipped.
                        if "content" in delta and "role" not in delta:
                            if ttft is None:
                                ttft = time.time() - t0
                            n_tokens += 1
            except Exception as e:  # noqa: BLE001
                with lock:
                    errors.append(f"req {i}: {type(e).__name__}: {e}")
            finally:
                conn.close()

        def round_(tag: str) -> float:
            threads = [threading.Thread(target=one, args=(i,)) for i in range(slots)]
            w0 = time.time()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.time() - w0
            print(f"8B HTTP {tag}: {wall:.1f}s "
                  f"({len(results)} ok, {len(errors)} err)", file=sys.stderr)
            return wall

        t0 = time.time()
        lm = manager.get(arch_name)  # load + quantize before timing requests
        print(f"8B load: {time.time() - t0:.1f}s", file=sys.stderr)
        # Staggered admission means different block shapes compile across the
        # first rounds; warm until the round wall stops shrinking.
        prev = float("inf")
        for w in range(int(os.environ.get("BENCH_HTTP_WARMUP", "4"))):
            wall = round_(f"warmup{w}")
            if errors:
                raise RuntimeError("; ".join(errors[:3]))
            results.clear()
            if wall > 0.7 * prev:
                break
            prev = wall
        eng = lm.engine
        eng._decode_time = 0.0
        eng._decode_tokens = 0
        wall = round_("measured")
        if errors:
            raise RuntimeError("; ".join(errors[:3]))

        decode_tps = eng._decode_tokens / eng._decode_time if eng._decode_time else 0.0
        total_tokens = sum(r["tokens"] for r in results)
        usage_tokens = sum((r["usage"] or {}).get("completion_tokens", 0) for r in results)
        if usage_tokens and usage_tokens != total_tokens:
            # Hard contract since ISSUE 2: the engine posts exactly one
            # token event per generated token (held-back stop/UTF-8 bytes
            # ride as empty-content chunks and flush later), so streamed
            # chunk count and usage completion_tokens must agree — a
            # mismatch means tokens are being silently merged or dropped on
            # the SSE path. Fail the row instead of fudging the count.
            raise RuntimeError(
                f"SSE chunk count {total_tokens} != usage completion_tokens "
                f"{usage_tokens} — every generated token must emit exactly "
                f"one content chunk"
            )
        # Client-side first-content time exists only when the model emits
        # decodable text (synthetic weights rarely do); engine prefill timing
        # (timing_prompt_processing, the reference's TTFT proxy —
        # BASELINE.md) is always present.
        ttfts = sorted(r["ttft"] for r in results if r["ttft"] is not None)
        p50_ttft = ttfts[len(ttfts) // 2] if ttfts else None
        prefill_s = [
            (r["usage"] or {}).get("timing_prompt_processing") for r in results
        ]
        prefill_s = sorted(v for v in prefill_s if v is not None)
        p50_prefill_ms = (
            round(prefill_s[len(prefill_s) // 2] * 1000, 1) if prefill_s else None
        )

        param_bytes = sum(
            a.size * a.dtype.itemsize for a in jax.tree.leaves(eng.params)
        )
        cfg = eng.cfg
        avg_len = prompt_len + gen_len / 2
        kv_bytes = (2 * cfg.num_layers * slots * avg_len
                    * cfg.num_kv_heads * cfg.head_dim_ * 2)
        roofline_tps = 819e9 / (param_bytes + kv_bytes) * slots
        pct = 100.0 * decode_tps / roofline_tps if roofline_tps else 0.0
        print(
            f"8B HTTP row: decode={decode_tps:.1f} tok/s "
            f"e2e={total_tokens / wall:.1f} tok/s p50_prefill={p50_prefill_ms}ms "
            f"roofline={roofline_tps:.0f} achieved={pct:.1f}%",
            file=sys.stderr,
        )
        server.shutdown()
        manager.shutdown()
        row = {
            "metric": f"decode_tokens_per_sec_{arch_name}-int8_http_bs{slots}",
            "value": round(decode_tps, 2),
            "unit": "tok/s",
            "vs_baseline": None,  # reference publishes no numbers (SURVEY §6)
            "p50_ttft_ms": p50_prefill_ms,
            "p50_first_content_ms_http": (
                round(p50_ttft * 1000, 1) if p50_ttft is not None else None
            ),
            "e2e_tokens_per_sec_http": round(total_tokens / wall, 2),
            "pct_of_hbm_roofline_8b": round(pct, 1),
        }
        return row


if __name__ == "__main__":
    main()
