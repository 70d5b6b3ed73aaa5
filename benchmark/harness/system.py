"""The system under test, in this process: the normal server (the objects
`python -m localai_tpu run` builds: ApplicationConfig -> ModelManager, Router,
OpenAIApi -> create_server) on a thread, one model preloaded from a YAML the
configuration file spells out, and a warm-up of exactly the programs the
cell's traffic can reach.
"""

from __future__ import annotations

import os
import random
import shutil
import socket
import threading
import time


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class System:
    def __init__(self, config: dict, scratch: str):
        import yaml

        from localai_tpu.config.app_config import ApplicationConfig
        from localai_tpu.server import ModelManager, Router, create_server
        from localai_tpu.server.openai_api import OpenAIApi

        self.name = config["name"]
        models = os.path.join(scratch, "models")
        shutil.rmtree(models, ignore_errors=True)
        os.makedirs(models)
        with open(os.path.join(models, f"{self.name}.yaml"), "w") as f:
            yaml.safe_dump({"name": self.name, **config["yaml"]}, f)
        self.port = free_port()
        app_cfg = ApplicationConfig(
            address="127.0.0.1", port=self.port, models_dir=models,
            generated_content_dir=os.path.join(scratch, "generated"),
            postmortem_dir=os.path.join(scratch, "postmortem"))
        self.manager = ModelManager(app_cfg)
        router = Router()
        OpenAIApi(self.manager).register(router)
        self.server = create_server(app_cfg, router)
        self._thread = threading.Thread(target=self.server.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.loaded = self.manager.get(self.name)  # load + place the weights
        self.engine = self.loaded.engine
        self.cfg = self.engine.cfg

    # ------------------------------------------------------------------ #

    def idle(self, timeout: float = 120.0) -> None:
        """Wait until the engine holds no request."""
        end = time.monotonic() + timeout
        while time.monotonic() < end:
            m = self.engine.metrics()
            if m["active_slots"] == 0 and m["queue_depth"] == 0:
                return
            time.sleep(0.01)
        raise RuntimeError("engine did not go idle")

    def _burst(self, rng: random.Random, m: int, length: int,
               new_tokens: int = 1) -> None:
        """m simultaneous requests of one prompt length through the engine's
        own front door; an idle engine admits them as one group."""
        from localai_tpu.engine import GenRequest

        self.idle()
        vocab = int(self.cfg.vocab_size)
        handles = [self.engine.submit(GenRequest(
            prompt_ids=[rng.randrange(259, vocab) for _ in range(length)],
            max_new_tokens=new_tokens, temperature=0.0, ignore_eos=True))
            for _ in range(m)]
        for h in handles:
            _, ev = h.result()
            if ev.kind != "done":
                raise RuntimeError(f"warm-up request failed: {ev.error}")

    def warm(self, plan: dict, seed: int = 0) -> dict:
        """Execute every program in `plan` (see `warm_plan`) once."""
        rng = random.Random(seed)
        t0 = time.monotonic()
        for m, length in plan["admit"]:
            self._burst(rng, m, length)
        for length in plan["chunked"]:
            self._burst(rng, 1, length)
        for n in plan["decode"]:
            self._burst(rng, 1, plan["decode_prompt"], new_tokens=n)
        self._touch_row_updates()
        self.idle()
        return {"programs": len(plan["admit"]) + len(plan["chunked"])
                + len(plan["decode"]), "seconds": time.monotonic() - t0}

    def _touch_row_updates(self) -> None:
        """The engine loop uploads changed rows of its page table and of its
        sampling pack with an eager `x.at[rows].set(...)`; jax compiles one
        tiny scatter per NUMBER of changed rows, the first time that number
        occurs, which would be inside the window. Run the same eager update
        once for every count the loop can produce (it sends the whole array
        when more than half the rows changed), at the same shapes and types.
        """
        import jax
        import jax.numpy as jnp
        import numpy as np

        ecfg = self.engine.ecfg
        shapes = [((10, int(ecfg.max_slots)), np.float32)]
        if ecfg.kv_pages:
            pages = -(-int(ecfg.max_seq) // int(ecfg.kv_page_size))
            shapes.append(((int(ecfg.max_slots), pages), np.int32))
        for shape, dtype in shapes:
            host = np.zeros(shape, dtype)
            dev = jnp.asarray(host)
            for k in range(1, max(1, shape[0] // 2) + 1):
                rows = np.arange(k)
                dev = dev.at[rows].set(jnp.asarray(host[rows]))
            jax.block_until_ready(dev)

    def stop(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.manager.shutdown()
        self._thread.join(timeout=10.0)


# An admission group expected less often than this per window is not warmed.
RARE_GROUP = 1e-4


def bucket_of(n: int, buckets: list[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


def warm_plan(engine_cfg, prompt_lengths: list[int], output_max: int,
              concurrency: int, requests: int) -> dict:
    """Which requests reach every program the traffic can: admission groups
    (m a power of two, one prompt bucket), chunked admissions (one per
    reachable pair of tail bucket and whole-prompt bucket) and the decode
    block sizes.

    It mirrors the engine's own rules: prompt buckets are powers of two from
    `min_prefill_bucket`; the head of the queue is admitted together with the
    requests behind it for as long as they fall in its bucket, the run then
    splits into powers of two; a prompt longer than `prefill_chunk` (when
    chunking is on) is admitted alone, in chunks; the smallest block size
    covering the tokens still owed is dispatched. A group of m needs a run of
    m prompts of one bucket: with a share p of the mix's prompts in the
    bucket and `requests` arrivals in a run, the plan keeps every m whose
    expected count `requests * p**m` is at least RARE_GROUP, and that the pool
    can hold at once. If a later PR changes these rules,
    `compiles_in_window` stops being 0 and says so.

    prompt_lengths: the mix's prompt lengths as the engine sees them (its
    quantiles), from which the bucket shares are counted.
    """
    buckets = list(engine_cfg.buckets())
    chunk = int(engine_cfg.prefill_chunk)
    page = int(engine_cfg.kv_page_size)
    pool = int(engine_cfg.kv_pages)
    single = [n for n in prompt_lengths if not chunk or n <= chunk]
    share: dict[int, list[int]] = {}
    for n in single:
        share.setdefault(bucket_of(n, buckets), []).append(n)
    admit = []
    for b in sorted(share):
        p = len(share[b]) / len(prompt_lengths)
        length = max(share[b])
        m = 1
        while m <= min(concurrency, int(engine_cfg.max_slots)):
            fits = not pool or m * (-(-b // page) + 1) <= pool
            if m == 1 or (fits and requests * p ** m >= RARE_GROUP):
                admit.append((m, length))
            m *= 2
    chunked = {}
    for n in sorted(set(prompt_lengths) - set(single)):
        tail = n - chunk * ((n - 1) // chunk)
        chunked.setdefault((bucket_of(tail, buckets), bucket_of(n, buckets)), n)
    decode = [n + 1 for n in sorted(engine_cfg.block_sizes) if n < output_max
              or n == min(engine_cfg.block_sizes)]
    return {"admit": admit, "chunked": sorted(chunked.values()),
            "decode": decode, "decode_prompt": min(prompt_lengths)}
