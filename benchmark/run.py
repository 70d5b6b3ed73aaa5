#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process holds the chip: it writes the cell's model YAML, starts the
normal server on a thread, loads the model, warms the programs this cell's
traffic can reach, decides `correct` against the plain float32 reference,
and only then starts the load generator as a child that imports no JAX. The
last line of standard output is the result. Without a TPU, or with fewer
chips than the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import subprocess
import sys
import time

_T_IMPORT = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import spec as S  # noqa: E402
from benchmark.harness import stamps as ST  # noqa: E402


def process_start() -> float:
    """When this process started, on time.monotonic()'s clock (Linux: both
    count from boot). Falls back to the first line of this file."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        t = ticks / os.sysconf("SC_CLK_TCK")
        if 0.0 <= _T_IMPORT - t < 30.0:
            return t
    except (OSError, ValueError, IndexError):
        pass
    return _T_IMPORT


def log(msg: str) -> None:
    print(f"[bench {time.monotonic() - _T_IMPORT:7.1f}s] {msg}",
          file=sys.stderr, flush=True)


class CompileCounter(logging.Handler):
    """Counts what jax compiles (or loads from its persistent cache) and
    what misses that cache, through jax.monitoring; and keeps the names, from
    jax's own "Compiling <name>" log records (which it swallows)."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        import jax
        import jax.monitoring as M

        self.requests = 0
        self.misses = 0
        self.names: list[str] = []
        M.register_event_duration_secs_listener(self._duration)
        M.register_event_listener(self._event)
        jax.config.update("jax_log_compiles", True)
        for name in ("jax._src.interpreters.pxla", "jax._src.dispatch"):
            lg = logging.getLogger(name)
            lg.addHandler(self)
            lg.propagate = False

    def emit(self, record: logging.LogRecord) -> None:
        msg = record.getMessage()
        if msg.startswith("Compiling "):
            self.names.append(msg.split(" with ", 1)[0][10:70])

    def _duration(self, name: str, _secs: float, **_kw) -> None:
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, name: str, **_kw) -> None:
        if name == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"requests": self.requests, "misses": self.misses,
                "named": len(self.names)}


def memory_now(jax, chips: int, key: str = "bytes_in_use") -> list[int]:
    """One `memory_stats()` reading per chip of the cell (none where the
    backend reports nothing, as on the CPU)."""
    out = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if stats.get(key) is not None:
            out.append(int(stats[key]))
    return out


def device_record(jax, held: list[int]) -> dict:
    """`memory_peak_bytes` is the most the fullest chip held at any sample
    of the measured window (twice a second, and at both its ends): what the
    deployment holds while it serves. The process's lifetime peak also
    counts what loading left behind and the check's float32 layer, which no
    request of the window ever sees; it is on the info line."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(held, default=None)}


def template_overhead(port: int, model: str) -> int:
    """Tokens the chat template adds around the user's content, read off one
    real request (which also warms the HTTP path)."""
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600.0)
    try:
        conn.request("POST", "/v1/chat/completions", body=json.dumps({
            "model": model, "max_tokens": 1, "temperature": 0.0,
            "ignore_eos": True,
            "messages": [{"role": "user", "content": "x" * 40}]}),
            headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        body = json.loads(resp.read())
        if resp.status != 200:
            raise RuntimeError(f"probe request: HTTP {resp.status} {body}")
        return int(body["usage"]["prompt_tokens"]) - 40
    finally:
        conn.close()


def check_sizes(cfg, config: dict) -> None:
    """The configuration file holds the sizes as they are run."""
    pairs = {
        "hidden_size": cfg.hidden_size, "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim_,
        "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.rms_eps,
    }
    bad = {k: (config[k], v) for k, v in pairs.items()
           if k in config and float(config[k]) != float(v)}
    if bad:
        raise RuntimeError(f"configuration file and program disagree: {bad}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of a cell of BENCHMARK.json, on the TPU it asks for."""
    cell = S.cell(workload)
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"needs {cell['chips']} TPU chip(s); jax sees {len(devs)} x "
            f"{devs[0].platform}: no result")
        raise SystemExit(3)
    return measure(cell, seed, seconds, trace)


def measure(cell: dict, seed: int, seconds: float, trace: bool) -> dict:
    """Set up, check and measure a resolved cell (`spec.cell`) on whatever
    devices jax has; `run_cell` is the way in that insists on the TPU."""
    import jax

    t_start = process_start()
    workload, config, mix = cell["name"], cell["config"], cell["mix"]
    load = cell["cell"]["load"]
    devs = jax.devices()
    compiles = CompileCounter()
    from benchmark.harness import check as C
    from benchmark.harness import system as SY
    from benchmark.harness import traffic as TR

    scratch = os.path.join(S.SCRATCH, workload)
    os.makedirs(scratch, exist_ok=True)
    log(f"{workload}: loading {config['name']} on {len(devs)} x {devs[0].device_kind}")
    system = SY.System(config, scratch)
    try:
        check_sizes(system.cfg, config)
        t_loaded = time.monotonic()
        overhead = template_overhead(system.port, system.name)
        lengths = TR.prompt_lengths(mix, overhead)
        clients = int(load["clients"])
        plan = SY.warm_plan(system.engine.ecfg, lengths,
                            max(TR.quantiles(mix["output_tokens"], 64)),
                            clients, 4.0 * clients)
        warm = system.warm(plan, seed=seed)
        t_warm = time.monotonic()
        log(f"warmed {warm['programs']} programs in {warm['seconds']:.1f}s "
            f"(template overhead {overhead} tokens, prompts {min(lengths)}-"
            f"{max(lengths)}, admission groups {plan['admit']})")

        import importlib

        ref = importlib.import_module(
            f"benchmark.reference.{config['reference']}")
        verdict = C.check(system.engine, ref.forward, system.cfg, seed,
                          config["check"])
        system.idle()
        t_checked = time.monotonic()
        worst = {k: max(e[k] for e in verdict["errors"])
                 for k in ("chosen", "top", "argmax_gap", "best_missing")}
        log(f"check: correct={verdict['correct']} tolerance="
            f"{verdict['tolerance']} worst={worst}")
        info = {
            "info": "setup", "workload": workload, "seed": seed,
            "load_s": t_loaded - t_start, "warm_s": t_warm - t_loaded,
            "check_s": t_checked - t_warm, "warm_programs": warm["programs"],
            "template_overhead": overhead, "check_worst": worst,
            "check_tolerance": verdict["tolerance"],
            "compiles_setup": compiles.snapshot(),
        }
        win = drive_window(jax, system, compiles, cell, seconds, seed,
                           overhead, scratch, trace)
        head, before, after = win["head"], win["before"], win["after"]
        events, trace_info, t0 = win["events"], win["trace"], win["t0"]

        # ---- reduction --------------------------------------------------- #
        e2e = ST.end_to_end(head)
        e2e["setup_s"] = t0 - t_start
        stream_faults = ST.streams_consistent(head)
        correct = bool(verdict["correct"]) and not stream_faults
        info.update({
            "info": "run", "ramp_s": head["ramp_s"],
            "requests_total": len(head["requests"]),
            "memory_in_use_at_window_ends": [win["held_first"], win["held_last"]],
            "memory_lifetime_peak_bytes": memory_now(
                jax, cell["chips"], "peak_bytes_in_use"),
            "stream_faults": stream_faults[:5],
            "compiles_window": {k: after["compiles"][k] - before["compiles"][k]
                                for k in after["compiles"]},
            "compiled_in_window": compiles.names[
                before["compiles"]["named"]:after["compiles"]["named"]][:12],
            "end_to_end": e2e,
        })
        if trace_info:
            red = trace_info.get("reduced") or {}
            info["trace"] = {
                "error": trace_info.get("error"),
                "xplane_bytes": trace_info.get("xplane_bytes"),
                "planes": trace_info.get("planes"),
                "capture_wall_s": trace_info["capture_wall_s"],
                **{k: red.get(k) for k in (
                    "window_from", "window_s", "device_span_s",
                    "mark_to_first_event_s", "last_event_to_mark_s",
                    "busy_s_per_chip")},
                "collective_s": red.get("collective_s"),
                "modules": sorted((red.get("modules") or {}).items(),
                                  key=lambda kv: -kv[1]["total_s"])[:12],
            }
        device = device_record(jax, win["held"])
        metrics: dict = {}
        result = {"correct": correct, "attempted": len(ST.measured(head)),
                  "failed": len(ST.failed(head)), "metrics": metrics,
                  "device": device}
        if not trace:
            for m in cell["end_to_end"]:
                if m["name"] in e2e:
                    metrics[m["name"]] = {"value": e2e[m["name"]],
                                          "unit": m["unit"]}
        else:
            ctx = {"stamps": head, "before": before, "after": after,
                   "journal": events, "trace": trace_info, "cell": cell,
                   "config": config, "seconds": seconds, "t0": t0,
                   "device": device, "engine_cfg": system.engine.ecfg,
                   "peaks": (S.peaks(device["kind"])
                             if device["platform"] == "tpu" else None)}
            for m in cell["per_layer"]:
                value = S.reader(m["name"])(ctx)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            if trace_info and trace_info.get("reduced"):
                red = trace_info["reduced"]
                device["busy_s"] = red["busy_s"]
                device["window_s"] = red["window_s"]
                result["breakdown"] = {"device_ops": red["device_ops"],
                                       "idle_gaps": red["idle_gaps"]}
        print(json.dumps(info), flush=True)
        return result
    finally:
        system.stop()


def drive_window(jax, system, compiles, cell: dict, seconds: float,
                 seed: int, overhead: int, scratch: str, trace: bool) -> dict:
    """Start the load generator as a child, snapshot the engine's counters at
    the window's two ends, sample the chips' memory through it (and trace a
    few seconds in its middle when asked), wait for the child and read its
    stamp file."""
    stamp_path = os.path.join(scratch, "stamps.jsonl")
    spec_path = os.path.join(scratch, "loadgen_spec.json")
    if os.path.exists(stamp_path):
        os.remove(stamp_path)
    with open(spec_path, "w") as f:
        json.dump({"port": system.port, "model": system.name,
                   "mix": cell["mix"], "load": cell["cell"]["load"],
                   "seconds": seconds, "seed": seed, "overhead": overhead,
                   "out": stamp_path}, f)
    env = {k: v for k, v in os.environ.items() if k != "BENCH_RUN"}
    child = subprocess.Popen(
        [sys.executable, os.path.join(S.BENCH, "harness", "loadgen.py"),
         spec_path], stdout=subprocess.PIPE, env=env, cwd=ROOT)
    try:
        t0 = float(json.loads(child.stdout.readline())["t0"])
        time.sleep(max(0.0, t0 - time.monotonic()))
        before = {"metrics": system.engine.metrics(),
                  "compiles": compiles.snapshot(), "t": time.monotonic()}
        held_first = held_last = memory_now(jax, cell["chips"])
        held = list(held_first)
        trace_info = None
        if trace:
            trace_info = traced_window(jax, cell, scratch, t0, seconds)
        while time.monotonic() < t0 + seconds:
            time.sleep(min(0.5, max(0.0, t0 + seconds - time.monotonic())))
            held_last = memory_now(jax, cell["chips"])
            held += held_last
        after = {"metrics": system.engine.metrics(),
                 "compiles": compiles.snapshot(), "t": time.monotonic()}
        journal = system.engine.journal
        events = [e for e in (journal.snapshot() if journal else [])
                  if t0 <= e["t"] <= t0 + seconds]
        try:
            child.wait(timeout=75.0)
        except subprocess.TimeoutExpired:
            raise RuntimeError("load generator did not end") from None
        if child.returncode != 0:
            raise RuntimeError(f"load generator exited {child.returncode}")
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    head = ST.load(stamp_path)
    system.idle()
    return {"head": head, "before": before, "after": after, "events": events,
            "trace": trace_info, "t0": t0, "held": held,
            "held_first": held_first, "held_last": held_last}


def traced_window(jax, cell: dict, scratch: str, t0: float,
                  seconds: float) -> dict:
    """Profile a few seconds of the steady window and reduce the trace."""
    import shutil

    from benchmark.harness import trace_reduce as TRD

    span = min(float(cell["cell"].get("trace_s", 4.0)), seconds * 0.5)
    start = t0 + (seconds - span) * 0.5
    trace_dir = os.path.join(scratch, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    time.sleep(max(0.0, start - time.monotonic()))
    kw = {}
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        kw["profiler_options"] = opts
    except AttributeError:
        pass
    t_capture = time.monotonic()
    jax.profiler.start_trace(trace_dir, **kw)
    # Starting and stopping the capture takes seconds; the window that the
    # shares are taken over is this host span, marked inside the trace.
    with jax.profiler.TraceAnnotation(TRD.WINDOW_MARK):
        t_a = time.monotonic()
        time.sleep(span)
        t_b = time.monotonic()
    jax.profiler.stop_trace()
    out = {"t_start": t_a, "t_end": t_b, "dir": trace_dir,
           "capture_wall_s": time.monotonic() - t_capture}
    try:
        path = TRD.find_xplane(trace_dir)
        planes = TRD.load_planes(path)
        out["reduced"] = TRD.reduce(planes)
        out["xplane_bytes"] = os.path.getsize(path)
        out["planes"] = [{"name": p["name"],
                          "lines": {k: len(v) for k, v in p["lines"].items()}}
                         for p in planes]
    except (FileNotFoundError, ValueError) as e:
        out["error"] = str(e)
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0)
    args = ap.parse_args()
    seconds = args.seconds or float(S.manifest()["run_seconds"])
    result = run_cell(args.workload, args.seed, seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
