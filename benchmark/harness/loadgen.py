"""The load generator: a child process that imports no JAX and talks HTTP/SSE
to the server the parent holds.

    python3 benchmark/harness/loadgen.py <spec.json>

It prints one line, `{"t0": ...}`, as soon as the schedule is built (the
parent times its trace window from it), drives the schedule, and writes the
stamp file named in the spec: a header line, then one line per request with
its send time and the arrival time of every content chunk, all on
`time.monotonic()`.

A closed loop: `clients` workers each send their next request when the
previous one ended. The ramp before the window fills the system, and the
window's end cuts what is still streaming (a cut is not a failure).
"""

from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import traffic  # noqa: E402 — a sibling file; this process must not import jax


def stream_chat(port: int, model: str, req: dict, rec: dict,
                stop: threading.Event, timeout: float = 300.0) -> None:
    """One streamed /v1/chat/completions; fills `rec` in place."""
    body = json.dumps({
        "model": model, "stream": True, "ignore_eos": True,
        "max_tokens": req["max_tokens"], "temperature": 0.0,
        "messages": [{"role": "user", "content": req["prompt"]}],
    })
    chunks = rec["chunks"]
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        rec["send"] = time.monotonic()
        conn.request("POST", "/v1/chat/completions", body=body,
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        rec["status"] = resp.status
        if resp.status != 200:
            rec["error"] = resp.read()[:200].decode("utf-8", "replace")
            return
        for raw in resp:
            if not raw.startswith(b"data:"):
                continue
            now = time.monotonic()
            data = raw[5:].strip()
            if data == b"[DONE]":
                rec["done"] = True
                break
            ev = json.loads(data)
            if "error" in ev:
                rec["error"] = str(ev["error"])[:200]
                return
            usage = ev.get("usage")
            if usage:
                rec["completion_tokens"] = usage.get("completion_tokens")
            for ch in ev.get("choices") or ():
                delta = ch.get("delta") or {}
                if "content" in delta and "role" not in delta:
                    chunks.append(now)
            if stop.is_set():
                rec["cut"] = True
                return
    except (OSError, http.client.HTTPException, ValueError) as e:
        if stop.is_set():
            rec["cut"] = True
        else:
            rec["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        rec["end"] = time.monotonic()
        conn.close()


def new_record(i: int, req: dict) -> dict:
    return {"i": i, "send": None, "end": None, "status": None,
            "done": False, "max_tokens": req["max_tokens"],
            "prompt_tokens": req["prompt_tokens"], "chunks": []}


def run_closed(spec: dict, sched: dict, t0: float, records: list) -> None:
    port, model = spec["port"], spec["model"]
    stop = threading.Event()
    lock = threading.Lock()
    queue = iter(enumerate(sched["requests"]))
    start = t0 - sched["ramp_s"]

    def client() -> None:
        time.sleep(max(0.0, start - time.monotonic()))
        while not stop.is_set():
            with lock:
                nxt = next(queue, None)
                if nxt is None:
                    return
                i, req = nxt
                rec = new_record(i, req)
                records.append(rec)
            stream_chat(port, model, req, rec, stop)
            if rec.get("error") and not stop.is_set():
                time.sleep(0.05)  # a refusing server must not be hammered

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(sched["clients"])]
    for th in threads:
        th.start()
    time.sleep(max(0.0, t0 + spec["seconds"] - time.monotonic()))
    stop.set()
    for th in threads:
        th.join(timeout=15.0)


def main(argv: list[str]) -> int:
    with open(argv[1]) as f:
        spec = json.load(f)
    sched = traffic.schedule(spec["mix"], spec["load"], spec["seed"],
                             spec["overhead"])
    t0 = time.monotonic() + 0.25 + sched["ramp_s"]
    print(json.dumps({"t0": t0}), flush=True)
    records: list[dict] = []
    run_closed(spec, sched, t0, records)
    head = {"t0": t0, "seconds": spec["seconds"], "loop": sched["loop"],
            "ramp_s": sched["ramp_s"], "seed": spec["seed"],
            "ended": time.monotonic()}
    tmp = spec["out"] + ".tmp"
    with open(tmp, "w") as f:
        f.write(json.dumps(head) + "\n")
        for rec in sorted(records, key=lambda r: r["i"]):
            f.write(json.dumps(rec) + "\n")
    os.replace(tmp, spec["out"])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
