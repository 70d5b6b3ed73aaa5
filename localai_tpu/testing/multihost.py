"""Simulated multi-host harness: REAL worker processes on this machine.

The multi-host subsystem (ISSUE 13) needs tests and benches that cross an
actual process + network boundary — separate jax runtimes, separate engine
state, a real HTTP hop for the LAIKV span stream — without TPUs. This
module spawns a minimal serving process (CPU backend, one models dir, a
declared cluster role) and hands back its base URL; the `multiproc` pytest
fixture (tests/conftest.py) builds on it, mirroring the PR 7 `multichip`
idiom of simulating hardware topology with host resources.

Run directly it IS the worker:

    python -m localai_tpu.testing.multihost --models-path DIR \
        --cluster-role prefill [--port 0]

which prints "LISTENING <port>" on stdout once the server is up.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time
from typing import Optional


def write_tiny_model_yaml(models_dir: str, name: str = "mh",
                          arch: str = "tiny", context_size: int = 256,
                          max_slots: int = 2, kv_pages: int = 16,
                          kv_page_size: int = 32) -> str:
    """A paged tiny-model YAML whose cache geometry matches the defaults
    the multihost tests/benches use on the local side (the span geometry
    check requires exporter and importer to agree exactly)."""
    import yaml

    os.makedirs(models_dir, exist_ok=True)
    path = os.path.join(models_dir, f"{name}.yaml")
    with open(path, "w") as f:
        yaml.safe_dump({
            "name": name, "model": arch, "context_size": context_size,
            "max_slots": max_slots, "max_tokens": 32,
            "kv_pages": kv_pages, "kv_page_size": kv_page_size,
        }, f)
    return path


class WorkerProc:
    """One spawned worker process + its base URL."""

    def __init__(self, proc: subprocess.Popen, url: str):
        self.proc = proc
        self.url = url

    def alive(self) -> bool:
        return self.proc.poll() is None

    def stop(self, timeout_s: float = 10.0) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(timeout=timeout_s)


def spawn_worker(models_dir: str, role: str = "prefill",
                 boot_timeout_s: float = 180.0,
                 env: Optional[dict] = None) -> WorkerProc:
    """Start a worker process serving `models_dir` with the given cluster
    role on a fresh port; blocks until its HTTP server is accepting.
    Raises RuntimeError (with the child's output) when boot fails."""
    child_env = {
        **os.environ,
        # The worker runs on the CPU backend whatever this machine has: a
        # chip belongs to one process, and the parent may hold it. One
        # virtual device is enough for a tiny engine.
        "JAX_PLATFORMS": "cpu",
        **(env or {}),
    }
    child_env["XLA_FLAGS"] = " ".join(
        f for f in child_env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    proc = subprocess.Popen(
        [sys.executable, "-m", "localai_tpu.testing.multihost",
         "--models-path", models_dir, "--cluster-role", role, "--port", "0"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=child_env,
        cwd=os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))),
    )
    import select

    deadline = time.monotonic() + boot_timeout_s
    lines: list[str] = []
    while time.monotonic() < deadline:
        ready, _, _ = select.select(
            [proc.stdout], [], [], max(0.1, deadline - time.monotonic()))
        if not ready:
            break  # silent child past the deadline
        line = proc.stdout.readline()
        if not line:
            break  # child exited
        lines.append(line)
        if line.startswith("LISTENING "):
            port = int(line.split()[1])
            import threading

            # Keep draining the child's merged stdout/stderr so serving-
            # time log lines can never fill the pipe and wedge the worker.
            threading.Thread(
                target=lambda: [None for _ in proc.stdout],
                daemon=True, name="multihost-drain",
            ).start()
            return WorkerProc(proc, f"http://127.0.0.1:{port}")
    proc.kill()
    raise RuntimeError(
        "multihost worker failed to boot:\n" + "".join(lines[-40:]))


def main(argv: Optional[list] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="localai-tpu-multihost-worker")
    ap.add_argument("--models-path", required=True)
    ap.add_argument("--cluster-role", default="prefill")
    ap.add_argument("--port", type=int, default=0)
    args = ap.parse_args(argv)

    from localai_tpu.config import ApplicationConfig
    from localai_tpu.server import ModelManager, Router, create_server
    from localai_tpu.server.openai_api import OpenAIApi

    app_cfg = ApplicationConfig.from_env(
        address="127.0.0.1", port=args.port, models_dir=args.models_path,
        cluster_role=args.cluster_role,
    )
    manager = ModelManager(app_cfg)
    router = Router()
    OpenAIApi(manager).register(router)
    server = create_server(app_cfg, router)
    # Load every configured model BEFORE announcing readiness so the first
    # span fetch pays no compile inside its socket timeout.
    for name in manager.configs.names():
        manager.get(name)
    print(f"LISTENING {server.server_address[1]}", flush=True)
    try:
        server.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        manager.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
