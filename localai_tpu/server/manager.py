"""Model lifecycle manager: lazy engine loading with singleflight + LRU.

TPU re-design of pkg/model (loader.go singleflight :163-221, watchdog LRU
eviction :135-195): "loading" compiles and shards weights into the resident
process; "evicting" drops an engine's HBM buffers instead of killing a
subprocess. One manager owns all engines for the slice.
"""

from __future__ import annotations

import gc
import logging
import threading
import time
from typing import Optional

import jax

from localai_tpu.config import ApplicationConfig, ModelConfig, ModelConfigLoader
from localai_tpu.engine import Engine, EngineConfig
from localai_tpu.engine.tokenizer import load_tokenizer
from localai_tpu.parallel.mesh import MeshPlan
from localai_tpu.templates import Evaluator
from localai_tpu.testing import faults
from localai_tpu.utils.compile_cache import configure_compile_cache

log = logging.getLogger("localai_tpu.manager")


class ModelQuarantinedError(RuntimeError):
    """The model's engine died more than restart_budget times inside
    restart_window_s, so the manager stopped respawning it (crash-only
    supervision with a bounded restart budget — ISSUE 4; the reference
    watchdog can kill a backend but relies on the operator to notice a
    crash loop). Requests get this clean, typed error — mapped to HTTP 503
    + Retry-After — instead of feeding an expensive reload/crash cycle."""

    def __init__(self, name: str, retry_after_s: float, deaths: int) -> None:
        super().__init__(
            f"model {name!r} quarantined after {deaths} engine deaths in "
            f"its restart window — retry in ~{retry_after_s:.0f}s"
        )
        self.model = name
        self.retry_after_s = max(1.0, retry_after_s)
        self.deaths = deaths


class LoadedModel:
    def __init__(self, cfg: ModelConfig, engine: Engine, evaluator: Evaluator):
        self.cfg = cfg
        # thread: instance-owned — teardown mutates engine/params only
        # after winning the `_loaded.pop()` ownership handoff, so exactly
        # one thread ever tears a given instance down
        self.engine = engine
        self.evaluator = evaluator
        self.loaded_at = time.monotonic()
        self.last_used = time.monotonic()
        self.busy_since: Optional[float] = None
        self.in_flight = 0
        self._lock = threading.Lock()

    def touch(self) -> None:
        self.last_used = time.monotonic()

    def acquire(self) -> None:
        with self._lock:
            self.in_flight += 1
            if self.busy_since is None:
                self.busy_since = time.monotonic()
            self.touch()

    def release(self) -> None:
        with self._lock:
            self.in_flight = max(0, self.in_flight - 1)
            if self.in_flight == 0:
                self.busy_since = None
            self.touch()

    def lease(self) -> "Lease":
        return Lease(self)


class VirtualModel(LoadedModel):
    """A per-tenant view over a shared base engine (ISSUE 10,
    docs/LORA_SERVING.md): same Engine object (the adapter is registered
    as a runtime tenant and every request carries `adapter=<name>`), but
    the tenant's OWN ModelConfig — templates, system prompt, generation
    defaults — so the OpenAI `model` field selects a fully-skinned tenant
    while N virtual models share one set of base weights. In-flight
    accounting delegates to the base LoadedModel so eviction/drain logic
    sees the engine's true load."""

    def __init__(self, cfg: ModelConfig, base: LoadedModel, adapter: str,
                 evaluator: Evaluator):
        super().__init__(cfg, base.engine, evaluator)
        self.base = base
        self.adapter = adapter

    def touch(self) -> None:
        super().touch()
        self.base.touch()

    def acquire(self) -> None:
        self.base.acquire()

    def release(self) -> None:
        self.base.release()


class Lease:
    """Idempotent in-flight marker: release() is safe to call from both a
    streaming generator's finally and an error path without double-counting."""

    def __init__(self, lm: "LoadedModel"):
        self._lm = lm
        self._released = False
        self._lock = threading.Lock()
        lm.acquire()

    def release(self) -> None:
        with self._lock:
            if self._released:
                return
            self._released = True
        self._lm.release()


class ModelManager:
    def __init__(self, app_cfg: ApplicationConfig, config_loader: Optional[ModelConfigLoader] = None):
        self.app_cfg = app_cfg
        self.configs = config_loader or ModelConfigLoader(app_cfg.models_dir)
        self.configs.load_all()
        self._loaded: dict[str, LoadedModel] = {}
        self._lock = threading.Lock()
        self._loading: dict[str, threading.Event] = {}
        # Crash-only supervision (ISSUE 4): per-model engine-death
        # timestamps inside the rolling restart window, lifetime totals,
        # and the quarantine clock (monotonic deadline; 0/absent = clear).
        self._death_times: dict[str, list[float]] = {}
        self._restart_total: dict[str, int] = {}
        self._quarantined_until: dict[str, float] = {}
        self._quarantine_total: dict[str, int] = {}
        faults.ensure_env_installed()
        # Every engine kind this manager loads (LLM, bert, image, audio)
        # compiles into one persistent cache, placed before the first load.
        configure_compile_cache()
        # Multi-host serving bootstrap (ISSUE 13): wire this process into
        # the global device mesh BEFORE any engine touches jax. Idempotent
        # — a no-op for single-process deployments and for entrypoints
        # (__main__) that already ran it.
        if app_cfg.coordinator_address:
            from localai_tpu.parallel import distributed

            distributed.init_from_config(app_cfg)
        self._wd_stop = threading.Event()
        self._wd_thread: Optional[threading.Thread] = None
        if app_cfg.watchdog_idle_timeout_s > 0 or app_cfg.watchdog_busy_timeout_s > 0:
            self._wd_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True, name="watchdog"
            )
            self._wd_thread.start()
        self._cw_thread: Optional[threading.Thread] = None
        if app_cfg.watch_configs:
            self.start_config_watcher(app_cfg.config_watch_interval_s)

    # ------------------------------------------------------------------ #

    def list_configs(self) -> list[ModelConfig]:
        return [self.configs.get(n) for n in self.configs.names()]

    def loaded_names(self) -> list[str]:
        with self._lock:
            return sorted(self._loaded)

    @staticmethod
    def _engine_dead(lm: LoadedModel) -> bool:
        """Crash-only death probe; non-LLM engines never report dead."""
        return bool(getattr(lm.engine, "is_dead", False))

    def _note_death_locked(self, name: str, now: float) -> None:
        """Record one observed engine death; trip the quarantine when the
        restart budget for the rolling window is exhausted. Caller holds
        self._lock."""
        window = max(0.0, self.app_cfg.restart_window_s)
        times = [t for t in self._death_times.get(name, ())
                 if now - t < window]
        times.append(now)
        self._death_times[name] = times
        self._restart_total[name] = self._restart_total.get(name, 0) + 1
        budget = self.app_cfg.restart_budget
        if budget >= 0 and len(times) > budget:
            self._quarantined_until[name] = now + self.app_cfg.quarantine_s
            self._quarantine_total[name] = self._quarantine_total.get(name, 0) + 1
            log.error(
                "model %s: %d engine deaths within %.0fs (budget %d) — "
                "quarantined for %.0fs", name, len(times), window, budget,
                self.app_cfg.quarantine_s,
            )

    def _reap_dead(self, name: str) -> bool:
        """Evict a loaded model whose engine loop died (the in-process
        analogue of a crashed backend process): the next get() loads a
        fresh engine — transparent restart — unless the restart budget is
        exhausted, in which case the model sits in quarantine and callers
        get ModelQuarantinedError until it expires. Returns True if a dead
        engine was reaped."""
        with self._lock:
            lm = self._loaded.get(name)
            if lm is None or not self._engine_dead(lm):
                return False
            self._loaded.pop(name)
            self._note_death_locked(name, time.monotonic())
        pm = getattr(lm.engine, "postmortem_path", "")
        log.warning(
            "model %s: engine loop died (%s) — evicted for crash-only "
            "restart%s",
            name, getattr(lm.engine, "_loop_dead", "?"),
            f" — postmortem: {pm}" if pm else "",
        )
        threading.Thread(
            target=self._teardown, args=(lm,), daemon=True,
            name="model-teardown",
        ).start()
        return True

    def _check_quarantine(self, name: str) -> None:
        with self._lock:
            until = self._quarantined_until.get(name, 0.0)
            now = time.monotonic()
            if until > now:
                deaths = len(self._death_times.get(name, ()))
                raise ModelQuarantinedError(name, until - now, deaths)
            if until:
                self._quarantined_until.pop(name, None)

    def restart_stats(self, name: str) -> dict:
        """Supervision counters for one model (monitoring surface)."""
        with self._lock:
            now = time.monotonic()
            return {
                "restarts_total": self._restart_total.get(name, 0),
                "deaths_in_window": len(self._death_times.get(name, ())),
                "quarantines_total": self._quarantine_total.get(name, 0),
                "quarantined_for_s": max(
                    0.0, self._quarantined_until.get(name, 0.0) - now
                ),
            }

    def health_gauges(self):
        """(name, labels, value) supervision gauges for the /metrics scrape
        (rides the same gauge source as the per-engine gauges)."""
        with self._lock:
            restarts = dict(self._restart_total)
            quarantines = dict(self._quarantine_total)
            until = dict(self._quarantined_until)
        now = time.monotonic()
        out = []
        for n, c in restarts.items():
            out.append(("localai_model_restarts", {"model": n}, float(c)))
        for n, c in quarantines.items():
            out.append(("localai_model_quarantines", {"model": n}, float(c)))
            out.append((
                "localai_model_quarantined", {"model": n},
                1.0 if until.get(n, 0.0) > now else 0.0,
            ))
        return out

    def get(self, name: str) -> LoadedModel:
        """Singleflight load (reference: loader.go:163-221). Raises KeyError
        for unknown models, ModelQuarantinedError while the model's restart
        budget is exhausted. Virtual models (base_model + adapter,
        ISSUE 10) resolve to the base's shared engine with the adapter
        registered as a runtime tenant."""
        vcfg = self.configs.get(name)
        if vcfg is not None and (vcfg.base_model or vcfg.adapter):
            return self._get_virtual(name, vcfg)
        while True:
            self._reap_dead(name)
            self._check_quarantine(name)
            with self._lock:
                lm = self._loaded.get(name)
                if lm is not None and not self._engine_dead(lm):
                    lm.touch()
                    return lm
                if lm is not None:
                    continue  # died between reap and here — re-reap
                ev = self._loading.get(name)
                if ev is None:
                    ev = threading.Event()
                    self._loading[name] = ev
                    break  # we are the loader
            ev.wait()  # someone else is loading; retry

        try:
            cfg = self.configs.get(name)
            if cfg is None:
                raise KeyError(f"model {name!r} not found")
            # Make room BEFORE loading: a model that fills more than half
            # the HBM (7B int8 on a 16 GB chip) cannot load beside the one
            # it replaces. Idle LRU victims only; when everything is busy
            # the load proceeds over budget and the post-load pass below
            # evicts once a victim goes idle.
            with self._lock:
                evicting = self._evict_lru_locked(incoming=1)
            for t in evicting:
                t.join()
            if evicting:
                gc.collect()  # an engine's jit closures cycle back to it
            try:
                lm = self._load(cfg)
            except (KeyError, RuntimeError):
                raise
            except Exception as e:
                # Containment: a failed load (bad checkpoint, HBM OOM,
                # compile error) errors this one call and leaves serving up
                # (reference: initializers.go:123-150).
                gc.collect()
                raise RuntimeError(f"failed to load model {name!r}: {e}") from e
            with self._lock:
                self._loaded[name] = lm
                self._evict_lru_locked(protect=name)
            return lm
        finally:
            with self._lock:
                self._loading.pop(name, None)
            ev.set()

    def _get_virtual(self, name: str, cfg: ModelConfig) -> "VirtualModel":
        """Resolve a virtual model (ISSUE 10): load/reuse the base engine,
        register the adapter as a runtime tenant (idempotent — the loop
        thread fetches/promotes its factors lazily at first admission),
        and hand back a per-tenant view. Rebuilt per call so a crash-only
        base restart transparently re-registers every tenant."""
        from localai_tpu.config.model_config import LoraConfigError

        cfg.validate()  # typed LoraConfigError on half-configured entries
        base_cfg = self.configs.get(cfg.base_model)
        if base_cfg is None:
            raise KeyError(
                f"virtual model {name!r}: base model {cfg.base_model!r} "
                "not found"
            )
        if base_cfg.base_model or base_cfg.adapter:
            raise LoraConfigError(
                f"virtual model {name!r}: base {cfg.base_model!r} is itself "
                "a virtual model — adapters do not nest"
            )
        if base_cfg.lora_adapters:
            # The merge/runtime seam (ISSUE 10 satellite): the base already
            # folded adapters into its weights at load; registering another
            # runtime tenant on top would serve base+merged+runtime deltas
            # with no way to reason about which tenant sees what.
            raise LoraConfigError(
                f"virtual model {name!r}: base {cfg.base_model!r} merges "
                "`lora_adapters` at load — a base serving runtime adapter "
                "tenants must keep its weights pristine "
                "(docs/LORA_SERVING.md)"
            )
        base = self.get(cfg.base_model)
        engine = base.engine
        if not hasattr(engine, "register_adapter"):
            raise LoraConfigError(
                f"virtual model {name!r}: backend {base_cfg.backend!r} has "
                "no runtime adapter support"
            )
        engine.register_adapter(
            name, self._resolve_ckpt_dir(cfg.adapter),
            weight=cfg.adapter_weight,
        )
        return VirtualModel(
            cfg, base, adapter=name,
            evaluator=Evaluator(cfg, engine.tokenizer),
        )

    def lease(self, name: str) -> tuple[LoadedModel, Lease]:
        """get() + acquire, atomically w.r.t. eviction: the lease is taken
        while the model is verifiably still resident, so LRU/drain logic sees
        in_flight > 0 before any teardown can start. Virtual models anchor
        on their BASE LoadedModel (they are never in _loaded themselves)."""
        while True:
            lm = self.get(name)
            anchor = getattr(lm, "base", lm)
            with self._lock:
                if self._loaded.get(anchor.cfg.name) is anchor:
                    return lm, lm.lease()
            # evicted in the window between get() and now — reload and retry

    def peek(self, name: str) -> Optional[LoadedModel]:
        """Loaded model without triggering a load (monitoring paths)."""
        with self._lock:
            return self._loaded.get(name)

    def unload(self, name: str, drain_s: float = 30.0) -> bool:
        """Shutdown endpoint semantics (reference: /backend/shutdown).

        Drains in-flight requests (up to drain_s) in the background before
        dropping HBM buffers, so an active stream isn't cut mid-generation.
        """
        with self._lock:
            lm = self._loaded.pop(name, None)
        if lm is None:
            return False
        threading.Thread(
            target=self._drain_and_teardown, args=(lm, drain_s), daemon=True,
            name="unload-drain",
        ).start()
        return True

    def _drain_and_teardown(self, lm: LoadedModel, drain_s: float) -> None:
        deadline = time.monotonic() + drain_s
        while lm.in_flight > 0 and time.monotonic() < deadline:
            time.sleep(0.1)
        self._teardown(lm)

    def shutdown(self) -> None:
        self._wd_stop.set()
        with self._lock:
            loaded = list(self._loaded.values())
            self._loaded.clear()
        for lm in loaded:
            self._teardown(lm)

    # ------------------------------------------------------------------ #
    # Config hot-reload (reference: startup.go:209-319 fsnotify watcher on
    # the models dir; here mtime polling — no inotify dependency, works on
    # network filesystems TPU pods actually mount)
    # ------------------------------------------------------------------ #

    def ensure_watchdog(self) -> None:
        """Start the watchdog thread if settings enabled it at runtime."""
        if self._wd_thread is None:
            self._wd_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True, name="watchdog"
            )
            self._wd_thread.start()

    def start_config_watcher(self, interval_s: float = 2.0) -> None:
        if self._cw_thread is not None:
            return
        # Baseline taken synchronously: changes made after construction are
        # always detected, even if the thread is slow to start.
        baseline = self._config_snapshot()
        self._cw_thread = threading.Thread(
            target=self._config_watch_loop, args=(interval_s, baseline),
            daemon=True, name="config-watcher",
        )
        self._cw_thread.start()

    def _config_snapshot(self) -> dict[str, float]:
        import os

        out: dict[str, float] = {}
        try:
            for fname in os.listdir(self.app_cfg.models_dir):
                if fname.endswith((".yaml", ".yml")):
                    path = os.path.join(self.app_cfg.models_dir, fname)
                    try:
                        out[path] = os.stat(path).st_mtime
                    except OSError:
                        pass
        except OSError:
            pass
        return out

    def _config_watch_loop(self, interval_s: float, last: dict[str, float]) -> None:
        while not self._wd_stop.wait(interval_s):
            snap = self._config_snapshot()
            if snap == last:
                continue
            last = snap
            try:
                self.reload_configs()
            except Exception:  # noqa: BLE001 — a bad yaml must not kill the loop
                log.exception("config reload failed")

    def reload_configs(self) -> int:
        """Re-read every model YAML; evict loaded models whose config changed
        or disappeared (the next request reloads them fresh). Returns the
        number of evictions."""
        old = {n: self.configs.get(n) for n in self.configs.names()}
        self.configs.load_all()
        evicted = 0
        with self._lock:
            loaded = list(self._loaded.keys())
        for name in loaded:
            new_cfg = self.configs.get(name)
            if new_cfg is None or new_cfg != old.get(name):
                log.info("config for %s changed — evicting for reload", name)
                self.unload(name, drain_s=10.0)
                evicted += 1
        return evicted

    # ------------------------------------------------------------------ #
    # Watchdog (reference: pkg/model/watchdog.go:197-279)
    # ------------------------------------------------------------------ #

    def _watchdog_loop(self) -> None:
        while not self._wd_stop.wait(self.app_cfg.watchdog_interval_s):
            try:
                self._watchdog_tick()
            except Exception:  # noqa: BLE001 — the watchdog must survive
                log.exception("watchdog tick failed")

    def _watchdog_tick(self, now: Optional[float] = None) -> None:
        now = time.monotonic() if now is None else now
        idle_t = self.app_cfg.watchdog_idle_timeout_s
        busy_t = self.app_cfg.watchdog_busy_timeout_s
        with self._lock:
            snapshot = list(self._loaded.items())
        for name, lm in snapshot:
            if self._engine_dead(lm):
                # Crash-only supervision (ISSUE 4): don't wait for the next
                # request to notice — reap the corpse now so its HBM frees
                # and the restart-budget clock starts from the real death.
                self._reap_dead(name)
                continue
            if busy_t > 0 and lm.busy_since is not None and now - lm.busy_since > busy_t:
                # A wedged generation holds its slot forever otherwise. The
                # reference kills the backend process (watchdog.go:250-279);
                # here the engine's requests are cancelled (slots drain to
                # their clients as finish_reason=stop) and the engine is
                # evicted so the next request gets a fresh one.
                n = lm.engine.cancel_all()
                log.warning(
                    "watchdog: model %s busy for >%gs — cancelled %d requests and evicting",
                    name, busy_t, n,
                )
                self.unload(name, drain_s=5.0)
            elif idle_t > 0 and lm.in_flight == 0 and now - lm.last_used > idle_t:
                log.info("watchdog: model %s idle for >%gs — evicting", name, idle_t)
                self.unload(name, drain_s=0.0)

    # ------------------------------------------------------------------ #

    def _teardown(self, lm: LoadedModel) -> None:
        log.info("evicting model %s from HBM", lm.cfg.name)
        lm.engine.stop()
        # Drop device buffer references; XLA frees HBM when the last ref dies.
        lm.engine.params = None
        lm.engine.cache = None
        gc.collect()

    def _evict_lru_locked(self, protect: str = "",
                          incoming: int = 0) -> list[threading.Thread]:
        """Reference: watchdog.go:135-195 LRU to MaxActiveBackends.

        `protect` is the model a get() is about to hand to its caller — never
        evict it, even though its lease hasn't been acquired yet. `incoming`
        counts models about to load: the budget is met as if they already
        had. Returns the teardown threads it started, for a caller that
        needs the HBM back before it goes on."""
        started: list[threading.Thread] = []
        budget = self.app_cfg.max_active_models
        if budget <= 0:
            return started  # unlimited — HBM is the only budget (reference default)
        while len(self._loaded) + incoming > budget:
            idle = [
                (lm.last_used, n)
                for n, lm in self._loaded.items()
                if lm.in_flight == 0 and n != protect
            ]
            if not idle:
                break  # everything busy; let the next call retry
            _, victim = min(idle)
            lm = self._loaded.pop(victim)
            t = threading.Thread(
                target=self._drain_and_teardown, args=(lm, 30.0), daemon=True,
                name="unload-drain",
            )
            t.start()
            started.append(t)
        return started

    def _resolve_ckpt_dir(self, model: str) -> str:
        import os

        ckpt_dir = model
        if not os.path.isabs(ckpt_dir):
            ckpt_dir = os.path.join(self.app_cfg.models_dir, ckpt_dir)
        return ckpt_dir

    def _parse_lora_entries(self, cfg: ModelConfig) -> list[tuple[str, float]]:
        """lora_adapters YAML entries → [(resolved_path, weight)] (entries:
        "path" or {"path": ..., "weight": 1.0}; reference: backend.proto
        LoraAdapter/LoraScale)."""
        out = []
        for entry in cfg.lora_adapters:
            if isinstance(entry, dict):
                apath = str(entry.get("path", ""))
                w = float(entry.get("weight", 1.0))
            else:
                apath, w = str(entry), 1.0
            if not apath:
                raise ValueError(
                    f"model {cfg.name!r}: lora_adapters entry missing a path"
                )
            out.append((self._resolve_ckpt_dir(apath), w))
        return out

    def _load(self, cfg: ModelConfig) -> LoadedModel:
        import os

        faults.fire("manager_load")  # injected load failure (ISSUE 4)

        from localai_tpu.models.config import PRESETS, get_arch
        from localai_tpu.models.llama import init_params

        # Non-text backends have their own loaders (reference: the model
        # loader spawns a different gRPC backend binary per modality —
        # initializers.go:50-154; here each returns a resident engine).
        backend_loaders = {
            "whisper": self._load_whisper,
            "tts": self._load_tts,
            "vad": self._load_vad,
            "diffusion": self._load_diffusion,
            "diffusers": self._load_diffusion,
            "stablediffusion": self._load_diffusion,
            "detection": self._load_detection,
            "musicgen": self._load_musicgen,
            "soundgen": self._load_musicgen,
            "sound-generation": self._load_musicgen,
            "remote": self._load_remote,
            "subprocess": self._load_subprocess,
            "bert": self._load_bert,
        }
        vlm = cfg.backend in ("llava", "vlm", "multimodal")
        loader = backend_loaders.get(cfg.backend) if not vlm else None
        if loader is None and not vlm and cfg.backend == "llama" and (
            cfg.model in whisper_presets() or "whisper" in cfg.model
        ):
            loader = self._load_whisper
        if loader is not None:
            t0 = time.monotonic()
            lm = loader(cfg)
            log.info(
                "loaded model %s (backend=%s) in %.1fs",
                cfg.name, cfg.backend, time.monotonic() - t0,
            )
            return lm

        t0 = time.monotonic()

        ckpt_dir: Optional[str] = None
        gguf_params = None
        gguf_tok_dir = None
        if cfg.model.endswith(".gguf"):
            # GGUF ingestion (reference: gguf.go:15-60 introspection +
            # grpc-server.cpp GGUF serving). Quantized tensors keep their
            # bits via grouped repack — engine/gguf.py.
            from localai_tpu.engine.gguf import load_gguf_checkpoint

            path = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isfile(path):
                raise FileNotFoundError(f"model {cfg.name!r}: {path!r} not found")
            arch, gguf_params, gguf_tok_dir = load_gguf_checkpoint(path)
        elif cfg.model in PRESETS:
            arch = get_arch(cfg.model)
        else:
            ckpt_dir = cfg.model
            if not os.path.isabs(ckpt_dir):
                ckpt_dir = os.path.join(self.app_cfg.models_dir, ckpt_dir)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: checkpoint dir {ckpt_dir!r} not found "
                    f"and not an arch preset ({sorted(PRESETS)})"
                )
            from localai_tpu.engine.weights import arch_from_hf_config

            arch = arch_from_hf_config(ckpt_dir)

        arch = _apply_rope_overrides(_apply_deployment_share(arch, cfg), cfg)

        from localai_tpu.parallel import distributed
        from localai_tpu.parallel.sharding import max_valid_tp

        par = cfg.parallel
        engine_devices = None
        if distributed.is_multiprocess():
            # Multi-host replica (ISSUE 13): dp strides ACROSS hosts, tp
            # stays within this host's chips (collectives on ICI, not DCN).
            # The engine/manager see the process-local device view of the
            # global mesh; weights shard-load per process via sharded_put.
            topo = distributed.topology()
            n_local = jax.local_device_count()
            tp = cfg.tensor_parallel if cfg.tensor_parallel > 0 else par.tp
            avail = n_local // max(1, par.ep * par.sp)
            tp = tp or max_valid_tp(arch, max(1, avail))
            tp = min(max(1, tp), max(1, avail))
            plan = distributed.multihost_plan(
                topo.num_processes, n_local, tp=tp, ep=par.ep, sp=par.sp)
            engine_devices = distributed.serving_devices()
            log.info(
                "model %s: multi-host plan dp=%d (hosts) x tp=%d (local "
                "chips) — process %d/%d",
                cfg.name, plan.dp, plan.tp, topo.process_id,
                topo.num_processes,
            )
        else:
            n_devices = len(jax.devices())
            avail = n_devices // max(1, par.dp * par.ep * par.sp)
            # tensor_parallel (ISSUE 7): the flat YAML knob wins over the
            # nested parallel.tp; -1/"auto" and 0 both fall back to the auto
            # pick (all devices left after dp/ep/sp, degraded to
            # max_valid_tp).
            tp = cfg.tensor_parallel if cfg.tensor_parallel > 0 else par.tp
            tp = tp or max_valid_tp(arch, max(1, avail))
            tp = min(max(1, tp), max(1, avail))
            plan = MeshPlan(dp=par.dp, tp=tp, ep=par.ep, sp=par.sp)

        tok_path = cfg.tokenizer or gguf_tok_dir or (ckpt_dir if ckpt_dir else None)
        if (tok_path and tok_path != "synthetic-bytes"
                and not _has_tokenizer_files(tok_path)):
            tok_path = None
        tokenizer = load_tokenizer(tok_path, vocab_size=arch.vocab_size)
        tv = getattr(tokenizer, "vocab_size", None)
        if tv and tv != arch.vocab_size:
            log.warning(
                "model %s: tokenizer vocab (%d) != arch vocab (%d); "
                "ids beyond the tokenizer are masked from sampling",
                cfg.name, tv, arch.vocab_size,
            )

        if cfg.lora_adapters and ckpt_dir is None:
            # Adapters need bf16 base tensors to merge into; GGUF payloads
            # are already quantized and synthetic presets have no checkpoint.
            # Failing loudly beats silently serving the unmodified base.
            raise ValueError(
                f"model {cfg.name!r}: lora_adapters require an HF safetensors "
                "checkpoint (not GGUF or a synthetic preset)"
            )
        if gguf_params is not None:
            params = gguf_params
        elif ckpt_dir is not None:
            from localai_tpu.engine.weights import load_hf_checkpoint

            lora = self._parse_lora_entries(cfg)
            # Load-time host quantization: the bf16 tree never touches HBM,
            # so int8 checkpoints up to ~2x HBM serve from one chip. LoRA
            # deltas merge on the host in the same pass, before quantizing.
            put = None
            if plan.total > 1:
                # Sharded placement AS EACH TENSOR IS READ (ISSUE 7):
                # jax.device_put with the param's NamedSharding ships every
                # chip its shard only — the full tree never materializes
                # replicated in HBM. (Quantized leaves keep their own
                # placement path and are re-placed by the engine.)
                from localai_tpu.engine.weights import sharded_put
                from localai_tpu.parallel.mesh import build_mesh

                put = sharded_put(arch, build_mesh(plan, engine_devices))
            params = load_hf_checkpoint(
                arch, ckpt_dir, put=put, quantize=cfg.quantization,
                lora=lora or None,
            )
            for adir, w in lora:
                log.info("model %s: merged lora adapter %s (weight=%.2f)",
                         cfg.name, adir, w)
        elif cfg.quantization and cfg.quantization not in ("none",):
            # Synthetic preset + quantization: init leaf-wise into the
            # quantized form so big archs fit (same ~2x HBM envelope).
            from localai_tpu.models.quant import init_params_quantized

            params = init_params_quantized(
                arch, jax.random.key(0), mode=cfg.quantization
            )
        elif plan.total > 1:
            # Each chip generates its own shard: a whole-tree init on the
            # default device needs the full bf16 model in ONE chip's HBM
            # (mistral-7b is 14.5 GB of a 16 GB v5e) before the engine
            # could spread it.
            from localai_tpu.parallel.mesh import build_mesh
            from localai_tpu.parallel.sharding import param_shardings

            params = jax.jit(
                lambda k: init_params(arch, k),
                out_shardings=param_shardings(
                    arch, build_mesh(plan, engine_devices)),
            )(jax.random.key(0))
        else:
            params = jax.jit(lambda k: init_params(arch, k))(jax.random.key(0))

        draft_arch = None
        draft_params = None
        if cfg.draft_model and cfg.spec_mode in ("prompt_lookup",
                                                 "self_draft"):
            # Model-free spec (ISSUE 12): the draft checkpoint would sit
            # dead in HBM — the target's own weights / the host-visible
            # token streams do the drafting. Don't even load it.
            log.info(
                "model %s: spec_mode=%s is model-free — skipping draft "
                "checkpoint %s", cfg.name, cfg.spec_mode, cfg.draft_model,
            )
        elif cfg.draft_model:
            if cfg.draft_model in PRESETS:
                draft_arch = get_arch(cfg.draft_model)
                draft_params = jax.jit(lambda k: init_params(draft_arch, k))(
                    jax.random.key(1)
                )
            else:
                from localai_tpu.engine.weights import (
                    arch_from_hf_config,
                    load_hf_checkpoint,
                )

                dd = self._resolve_ckpt_dir(cfg.draft_model)
                draft_arch = arch_from_hf_config(dd)
                draft_params = load_hf_checkpoint(draft_arch, dd)

        engine = Engine(
            arch,
            params,
            tokenizer,
            mesh_plan=plan,
            devices=engine_devices,
            engine_cfg=EngineConfig(
                max_slots=cfg.max_slots, max_seq=cfg.context_size,
                tensor_parallel=cfg.tensor_parallel,
                kv_pages=cfg.kv_pages, kv_page_size=cfg.kv_page_size,
                kv_page_headroom=cfg.kv_page_headroom,
                kv_preempt=cfg.kv_preempt,
                kv_swap_bytes=cfg.kv_swap_bytes,
                kv_cache_dtype=cfg.kv_cache_dtype,
                paged_kernel=cfg.paged_kernel,
                quant_kernel=cfg.quant_kernel,
                lora_kernel=cfg.lora_kernel,
                adapter_cache_bytes=cfg.adapter_cache_bytes,
                kv_scale=cfg.kv_scale,
                prefill_chunk=cfg.prefill_chunk,
                attention_sink=cfg.attention_sink,
                attention_window=cfg.attention_window,
                kv_spill_bytes=cfg.kv_spill_bytes,
                kv_l1_span=cfg.kv_l1_span,
                sp_prefill=cfg.sp_prefill,
                fork_sampling=cfg.fork_sampling,
                max_pending=cfg.max_pending,
                queue_timeout_s=cfg.queue_timeout_s,
                deadline_s=cfg.deadline_s,
                trace_journal_events=cfg.trace_journal_events,
                postmortem_dir=self.app_cfg.postmortem_dir,
                spec_mode=cfg.spec_mode,
                self_draft_layers=cfg.self_draft_layers,
                spec_accept_ewma=cfg.spec_accept_ewma,
                spec_draft_buckets=tuple(cfg.spec_draft_buckets),
            ),
            draft_cfg=draft_arch,
            draft_params=draft_params,
            n_draft=cfg.n_draft,
            quantization=cfg.quantization,
        )
        engine.start()
        # Cluster fan-out (ISSUE 6, docs/CLUSTER.md): cluster_replicas >= 2
        # serves this model through N same-host engine replicas (shared
        # weight tree, per-replica KV pools/loops) behind the prefix-
        # affinity scheduler — the ClusterEngine facade keeps the Engine
        # surface, so every API/watchdog/metrics path is unchanged. Draft
        # and vision engines stay single-replica (their side state has no
        # transfer story yet).
        from localai_tpu.cluster.replica import parse_peers

        n_replicas = self.app_cfg.cluster_replicas
        peers = [] if (draft_arch is not None or vlm) else parse_peers(
            self.app_cfg.cluster_peers)
        if (n_replicas >= 2 or peers) and draft_arch is None and not vlm:
            from localai_tpu.cluster import (
                ClusterEngine,
                LocalReplica,
                RemoteReplica,
                parse_roles,
            )

            n_local = max(1, n_replicas)
            roles = parse_roles(n_local, self.app_cfg.cluster_role)
            replicas = [LocalReplica("r0", engine, role=roles[0])]
            # Replica i takes the i-th slice of plan.total devices when the
            # host has one for each (r0 already sits on the first slice —
            # build_mesh takes the leading devices); a host with fewer
            # devices stacks them on the first slice, sharing the weights.
            width = engine.plan.total
            host_devs = jax.devices()
            spread = (engine_devices is None
                      and n_local * width <= len(host_devs))
            for i in range(1, n_local):
                extra = Engine(
                    arch, params, tokenizer, mesh_plan=plan,
                    devices=(host_devs[i * width:(i + 1) * width] if spread
                             else engine_devices),
                    engine_cfg=engine.ecfg, quantization=cfg.quantization,
                )
                extra.start()
                replicas.append(LocalReplica(f"r{i}", extra, role=roles[i]))
            # Remote peers (ISSUE 13): workers on OTHER machines, reached
            # over HTTP. Roles come from their LocalAI-Cluster-Role header
            # at the first gauge refresh; the scheduler treats them as
            # prefill-handoff/affinity targets, never in-process dispatch.
            for pname, purl in peers:
                replicas.append(RemoteReplica(
                    pname, purl, model=cfg.name,
                    gauge_stale_s=self.app_cfg.cluster_gauge_stale_s,
                    chunk_bytes=self.app_cfg.transfer_chunk_bytes,
                    verify=self.app_cfg.transfer_checksum,
                    max_resumes=self.app_cfg.transfer_resumes,
                ))
            engine = ClusterEngine(
                replicas,
                transfer_max_bytes=self.app_cfg.transfer_max_bytes,
                affinity_spans=self.app_cfg.affinity_spans,
            )
            log.info(
                "model %s: fanned out to %d cluster replicas (roles=%s)"
                "%s",
                cfg.name, n_local, ",".join(roles),
                f" + {len(peers)} remote peer(s)" if peers else "",
            )
        evaluator = Evaluator(cfg, tokenizer)
        lm = LoadedModel(cfg, engine, evaluator)
        if vlm:
            # Multimodal: attach the vision tower; the chat handler injects
            # projected image tokens at admission. Two families —
            # llava-style (fixed-grid CLIP tower) and Qwen2-VL (native
            # resolution + m-rope; reference: vllm/backend.py:211-243).
            from localai_tpu.models import qwen2_vl as QV
            from localai_tpu.models import vision as V

            varch = cfg.options.get("vision", "")
            if ckpt_dir is not None and QV.is_qwen2_vl_dir(ckpt_dir):
                qcfg = QV.vision_config_from_hf(ckpt_dir)
                if qcfg.hidden_size != arch.hidden_size:
                    raise ValueError(
                        f"qwen2-vl merger dim {qcfg.hidden_size} != LLM "
                        f"hidden {arch.hidden_size}"
                    )
                lm.vision = QV.Qwen2VLVisionEncoder(
                    qcfg, QV.load_hf_qwen2_vl_vision(qcfg, ckpt_dir)
                )
            else:
                if varch in V.VISION_PRESETS:
                    vcfg = V.VISION_PRESETS[varch]
                    vparams = V.init_params(vcfg, jax.random.key(2))
                elif ckpt_dir is not None:
                    vcfg = V.vision_config_from_hf(ckpt_dir)
                    vparams = V.load_hf_vision(vcfg, ckpt_dir)
                else:
                    raise ValueError(
                        f"model {cfg.name!r}: vlm backend needs options.vision "
                        f"(preset) or a checkpoint with a vision tower"
                    )
                if vcfg.llm_dim != arch.hidden_size:
                    raise ValueError(
                        f"vision projector dim {vcfg.llm_dim} != LLM hidden "
                        f"{arch.hidden_size}"
                    )
                lm.vision = V.VisionEncoder(vcfg, vparams)
        log.info(
            "loaded model %s (arch=%s mesh=%s%s) in %.1fs",
            cfg.name, arch.name, plan, " +vision" if vlm else "",
            time.monotonic() - t0,
        )
        return lm

    # ------------------------------------------------------------------ #
    # Audio backends
    # ------------------------------------------------------------------ #

    def _load_whisper(self, cfg: ModelConfig) -> LoadedModel:
        import os

        import jax as _jax

        from localai_tpu.engine.audio_engine import WhisperEngine
        from localai_tpu.models import whisper as W

        if cfg.model in W.WHISPER_PRESETS:
            wcfg = W.WHISPER_PRESETS[cfg.model]
            params = W.init_params(wcfg, _jax.random.key(0))
            tokenizer = None
        else:
            ckpt_dir = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: whisper checkpoint {ckpt_dir!r} not found"
                )
            wcfg = W.whisper_config_from_hf(ckpt_dir)
            params = W.load_hf_whisper(wcfg, ckpt_dir)
            tokenizer = None
            if _has_tokenizer_files(ckpt_dir):
                from transformers import AutoTokenizer

                tokenizer = AutoTokenizer.from_pretrained(ckpt_dir)
        return LoadedModel(cfg, WhisperEngine(wcfg, params, tokenizer), None)

    def _load_tts(self, cfg: ModelConfig) -> LoadedModel:
        import os

        import jax as _jax

        from localai_tpu.engine.audio_engine import TTSEngine
        from localai_tpu.models import tts as T

        if cfg.model in T.TTS_PRESETS:
            tcfg = T.TTS_PRESETS[cfg.model]
            params = T.init_params(tcfg, _jax.random.key(0))
        else:
            ckpt_dir = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: tts checkpoint {ckpt_dir!r} not found"
                )
            from localai_tpu.models import musicgen as MG
            from localai_tpu.models import vits as V

            if MG.is_musicgen_dir(ckpt_dir):
                # A MusicGen checkpoint configured under the tts/soundgen
                # usecase — route to the sound-generation engine.
                return self._load_musicgen(cfg)
            if V.is_vits_dir(ckpt_dir):
                # Real published voice (facebook/mms-tts-*, vits-ljs) in the
                # HF VITS layout — the neural path; Griffin-Lim stays the
                # fallback for own-format checkpoints.
                from localai_tpu.engine.audio_engine import VitsEngine

                vcfg, vparams, vtok = V.load_vits(ckpt_dir)
                return LoadedModel(
                    cfg,
                    VitsEngine(vcfg, vparams, vtok, voices=cfg.options.get("voices")),
                    None,
                )
            tcfg, params = T.load_tts(ckpt_dir)
        return LoadedModel(cfg, TTSEngine(tcfg, params, voices=cfg.options.get("voices")), None)

    def _load_musicgen(self, cfg: ModelConfig) -> LoadedModel:
        """Text-to-music (SoundGeneration): published MusicGen checkpoints
        (reference: backend/python/transformers/backend.py:489-539)."""
        import os

        from localai_tpu.engine.audio_engine import MusicgenEngine
        from localai_tpu.models import musicgen as MG

        ckpt_dir = self._resolve_ckpt_dir(cfg.model)
        if not os.path.isdir(ckpt_dir):
            raise FileNotFoundError(
                f"model {cfg.name!r}: musicgen checkpoint {ckpt_dir!r} not found"
            )
        if not MG.is_musicgen_dir(ckpt_dir):
            raise ValueError(
                f"model {cfg.name!r}: {ckpt_dir!r} is not a musicgen checkpoint "
                "(config.json model_type must be 'musicgen')"
            )
        if not _has_tokenizer_files(ckpt_dir):
            raise FileNotFoundError(
                f"model {cfg.name!r}: musicgen checkpoint {ckpt_dir!r} has no "
                "text tokenizer files (tokenizer.json / tokenizer_config.json)"
            )
        from localai_tpu.engine.tokenizer import HFTokenizer

        mcfg, params = MG.load_musicgen(ckpt_dir)
        return LoadedModel(cfg, MusicgenEngine(mcfg, params, HFTokenizer(ckpt_dir)), None)

    def _load_vad(self, cfg: ModelConfig) -> LoadedModel:
        import os

        from localai_tpu.engine.audio_engine import VADEngine

        from localai_tpu.audio import learned_vad as _LV

        if cfg.model in ("", "builtin", "base", "vad-base", "silero"):
            # Default: the shipped pretrained net (assets/vad-base.safetensors,
            # trained offline on the formant-synthesis corpus — the silero
            # role, reference vad.go:13-33). `model: energy` still selects
            # the weightless detector explicitly.
            packaged = _LV.packaged_weights()
            if packaged is not None:
                params = _LV.load_params(packaged)
                return LoadedModel(
                    cfg, VADEngine(_LV.config_from_params(params), params), None
                )
        if cfg.model and cfg.model != "energy":
            # Any other configured checkpoint that can't be found is an
            # error, not a silent fall-through (same standard as the
            # tts/detection loaders above).
            ckpt_dir = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: vad checkpoint {ckpt_dir!r} not found"
                )
            from localai_tpu.audio import learned_vad as LV

            weights = LV.find_weights(ckpt_dir)
            if not weights:
                raise FileNotFoundError(
                    f"model {cfg.name!r}: no vad.safetensors/model.safetensors "
                    f"in {ckpt_dir!r}"
                )
            # Learned VAD net (silero role) from safetensors; the net shape
            # is recovered from the weights themselves.
            params = LV.load_params(weights)
            return LoadedModel(
                cfg, VADEngine(LV.config_from_params(params), params), None
            )
        return LoadedModel(cfg, VADEngine(), None)

    def _load_bert(self, cfg: ModelConfig) -> LoadedModel:
        import os

        import jax as _jax

        from localai_tpu.engine.bert_engine import BertEngine
        from localai_tpu.models import bert as B

        if cfg.model in B.BERT_PRESETS:
            bcfg = B.BERT_PRESETS[cfg.model]
            params = B.init_params(bcfg, _jax.random.key(0))
            tok_path = cfg.tokenizer or None
        else:
            ckpt_dir = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: bert checkpoint {ckpt_dir!r} not found"
                )
            bcfg = B.bert_config_from_hf(ckpt_dir)
            params = B.load_hf_bert(bcfg, ckpt_dir)
            tok_path = cfg.tokenizer or ckpt_dir
        if tok_path and not _has_tokenizer_files(tok_path):
            tok_path = None
        tokenizer = load_tokenizer(tok_path, vocab_size=bcfg.vocab_size)
        return LoadedModel(cfg, BertEngine(bcfg, params, tokenizer), None)

    def _load_remote(self, cfg: ModelConfig) -> LoadedModel:
        from localai_tpu.engine.remote import RemoteEngine

        url = cfg.options.get("url")
        if not url:
            raise ValueError(f"model {cfg.name!r}: backend remote needs options.url")
        eng = RemoteEngine(
            url,
            remote_model=cfg.options.get("remote_model", ""),
            api_key=cfg.options.get("api_key", ""),
        )
        return LoadedModel(cfg, eng, None)

    def _load_subprocess(self, cfg: ModelConfig) -> LoadedModel:
        import os

        from localai_tpu.engine.remote import SubprocessEngine

        child = dict(cfg.options.get("child") or {})
        if not child:
            child = {"model": cfg.model, "context_size": cfg.context_size,
                     "max_tokens": cfg.max_tokens, "max_slots": cfg.max_slots}
        eng = SubprocessEngine(
            cfg.name, child,
            workdir=os.path.join(self.app_cfg.models_dir, f".subprocess-{cfg.name}"),
            env_extra=cfg.options.get("env") or {},
        )
        return LoadedModel(cfg, eng, None)

    def _load_detection(self, cfg: ModelConfig) -> LoadedModel:
        import os

        import jax as _jax

        from localai_tpu.engine.image_engine import DetectionEngine
        from localai_tpu.models import detection as Det

        if cfg.model in Det.DETECTION_PRESETS:
            dcfg = Det.DETECTION_PRESETS[cfg.model]
            params = Det.init_params(dcfg, _jax.random.key(0))
        else:
            ckpt_dir = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: detection checkpoint {ckpt_dir!r} not found"
                )
            from localai_tpu.models import yolos as Y

            if Y.is_yolos_dir(ckpt_dir):
                # Real published detector (hustvl/yolos-*) in the HF layout.
                from localai_tpu.engine.image_engine import YolosEngine

                ycfg, yparams = Y.load_yolos(ckpt_dir)
                return LoadedModel(cfg, YolosEngine(ycfg, yparams), None)
            dcfg, params = Det.load_detection(ckpt_dir)
        return LoadedModel(cfg, DetectionEngine(dcfg, params), None)

    def _load_diffusion(self, cfg: ModelConfig) -> LoadedModel:
        import os

        import jax as _jax

        from localai_tpu.engine.image_engine import DiffusionEngine
        from localai_tpu.models import diffusion as D

        if cfg.model in D.DIFFUSION_PRESETS:
            if cfg.lora_adapters:
                # Failing loudly beats silently serving the unmodified base
                # (same contract as the LLM loader above).
                raise ValueError(
                    f"model {cfg.name!r}: lora_adapters need a diffusers-"
                    "layout SD/SDXL checkpoint (not a synthetic preset)"
                )
            dcfg = D.DIFFUSION_PRESETS[cfg.model]
            params = D.init_params(dcfg, _jax.random.key(0))
        else:
            ckpt_dir = self._resolve_ckpt_dir(cfg.model)
            if not os.path.isdir(ckpt_dir):
                raise FileNotFoundError(
                    f"model {cfg.name!r}: diffusion checkpoint {ckpt_dir!r} not found"
                )
            from localai_tpu.models import flux as FX
            from localai_tpu.models import latent_diffusion as LD

            if FX.is_flux_dir(ckpt_dir):
                # Flux.1-class rectified-flow checkpoint (reference:
                # diffusers backend.py:218-224, :594-603).
                from localai_tpu.engine.image_engine import FluxEngine

                if cfg.lora_adapters:
                    raise ValueError(
                        f"model {cfg.name!r}: lora_adapters target SD/SDXL "
                        "checkpoints (kohya format); Flux LoRA is unsupported"
                    )
                # bf16 by default (fp32 Flux.1-dev is ~68 GB — beyond any
                # single chip); the model YAML may override via
                # `options.dtype` like the LLM loader's quantization knob.
                import jax.numpy as _jnp

                dtypes = {
                    "bfloat16": _jnp.bfloat16, "bf16": _jnp.bfloat16,
                    "float32": _jnp.float32, "fp32": _jnp.float32,
                }
                opt = str(cfg.options.get("dtype", "bfloat16")).lower()
                if opt not in dtypes:
                    raise ValueError(
                        f"model {cfg.name!r}: options.dtype {opt!r} — use "
                        "bfloat16 or float32"
                    )
                fcfg, fparams, ftoks = FX.load_flux_pipeline(
                    ckpt_dir, dtype=dtypes[opt]
                )
                return LoadedModel(cfg, FluxEngine(fcfg, fparams, ftoks), None)
            if LD.is_diffusers_dir(ckpt_dir):
                # Real published checkpoint (SD-1.5-class diffusers layout) —
                # reference: backend/python/diffusers/backend.py:27-120.
                from localai_tpu.engine.image_engine import LatentDiffusionEngine

                ldcfg, ldparams, tok = LD.load_pipeline(ckpt_dir)
                # Civitai-style SD/SDXL LoRA (kohya format) merged at load
                # (reference: diffusers backend.py:456-533 load_lora_weights).
                for apath, w in self._parse_lora_entries(cfg):
                    n_merged = LD.load_diffusion_lora(apath, ldparams, w)
                    if n_merged == 0:
                        raise ValueError(
                            f"model {cfg.name!r}: lora adapter {apath!r} "
                            "matched no unet/text-encoder tensors"
                        )
                    log.info("model %s: merged %d lora tensors from %s "
                             "(weight=%.2f)", cfg.name, n_merged, apath, w)
                # AnimateDiff-class motion adapter: a `motion_adapter` dir in
                # the model YAML, or one bundled inside the checkpoint (the
                # diffusers AnimateDiffPipeline save layout) — /v1/videos
                # then runs a real temporal model instead of the latent sweep
                # (reference: diffusers backend.py:226-253 video pipelines).
                from localai_tpu.models import video_diffusion as VD

                motion = None
                mdir = cfg.options.get("motion_adapter") or ""
                if mdir:
                    mdir = self._resolve_ckpt_dir(str(mdir))
                elif VD.is_motion_adapter_dir(
                    os.path.join(ckpt_dir, "motion_adapter")
                ):
                    mdir = os.path.join(ckpt_dir, "motion_adapter")
                if mdir:
                    if not VD.is_motion_adapter_dir(mdir):
                        raise FileNotFoundError(
                            f"model {cfg.name!r}: motion_adapter {mdir!r} is "
                            "not a diffusers MotionAdapter dir"
                        )
                    motion = VD.load_motion_adapter(mdir)
                    log.info("model %s: motion adapter loaded from %s",
                             cfg.name, mdir)
                sched = str(cfg.options.get("scheduler", "ddim"))
                if sched not in LD.SUPPORTED_SCHEDULERS:
                    # Fail at LOAD, not at the first generation request.
                    raise ValueError(
                        f"model {cfg.name!r}: unknown scheduler {sched!r} "
                        f"(supported: {', '.join(sorted(LD.SUPPORTED_SCHEDULERS))})"
                    )
                eng = LatentDiffusionEngine(
                    ldcfg, ldparams, tok,
                    default_scheduler=sched,
                    motion=motion,
                )
                return LoadedModel(cfg, eng, None)
            if cfg.lora_adapters:
                raise ValueError(
                    f"model {cfg.name!r}: lora_adapters need a diffusers-"
                    "layout SD/SDXL checkpoint (this is an own-format "
                    "diffusion checkpoint)"
                )
            dcfg, params = D.load_diffusion(ckpt_dir)
        return LoadedModel(cfg, DiffusionEngine(dcfg, params), None)


def whisper_presets() -> dict:
    from localai_tpu.models.whisper import WHISPER_PRESETS

    return WHISPER_PRESETS


def _apply_deployment_share(arch, cfg):
    """The part of the model this process holds under the YAML's deployment
    keys (config/model_config.py): `expert_share`, `stage_layers`,
    `vocab_rows`. Nothing stands in for the absent experts, stages or rows."""
    import dataclasses as _dc

    updates = {}
    if cfg.expert_share:
        idx, of = (int(x) for x in cfg.expert_share)
        if not (arch.is_moe and of >= 1 and 0 <= idx < of
                and arch.num_experts % of == 0):
            raise ValueError(
                f"model {cfg.name!r}: expert_share {cfg.expert_share} needs a "
                f"MoE model whose {arch.num_experts} experts divide by `of`")
        updates["expert_share"] = (idx, of)
    if cfg.stage_layers:
        # Stage 0 of a pipeline: the model's first n layers.
        n = int(cfg.stage_layers)
        if not 0 < n <= arch.num_layers:
            raise ValueError(
                f"model {cfg.name!r}: stage_layers {n} of {arch.num_layers}")
        updates["num_layers"] = n
        updates["layer_kinds"] = tuple(arch.layer_kinds[:n])
    if cfg.vocab_rows:
        # A vocabulary-parallel head's rows [0, n): the ids this process
        # embeds and samples are its own rows'.
        n = int(cfg.vocab_rows)
        if not 0 < n <= arch.vocab_size:
            raise ValueError(
                f"model {cfg.name!r}: vocab_rows {n} of {arch.vocab_size}")
        updates["vocab_size"] = n
    return _dc.replace(arch, **updates) if updates else arch


def _apply_rope_overrides(arch, cfg):
    """YAML rope knobs override the checkpoint's (reference parity:
    model_config.go rope_scaling/rope_freq_base are user config, forwarded
    over the checkpoint's own values)."""
    import dataclasses as _dc

    updates = {}
    if cfg.rope_freq_base:
        updates["rope_theta"] = float(cfg.rope_freq_base)
    rs = cfg.rope_scaling
    if rs:
        stype = rs.get("rope_type") or rs.get("type")
        if stype == "su":
            stype = "longrope"
        if stype not in ("linear", "llama3", "yarn", "longrope"):
            # Fail at LOAD, not at first admission trace — and never let a
            # factor-only dict silently null the checkpoint's own scaling
            # while still lifting the window.
            raise ValueError(
                f"model {cfg.name!r}: rope_scaling needs rope_type in "
                f"linear/llama3/yarn/longrope (got {stype!r})"
            )
        updates["rope_scaling"] = stype
        if "factor" in rs:
            updates["rope_scaling_factor"] = float(rs["factor"])
        if "original_max_position_embeddings" in rs:
            updates["rope_original_max_position"] = int(
                rs["original_max_position_embeddings"]
            )
        if "low_freq_factor" in rs:
            updates["rope_low_freq_factor"] = float(rs["low_freq_factor"])
        if "high_freq_factor" in rs:
            updates["rope_high_freq_factor"] = float(rs["high_freq_factor"])
        if "beta_fast" in rs:
            updates["rope_beta_fast"] = float(rs["beta_fast"])
        if "beta_slow" in rs:
            updates["rope_beta_slow"] = float(rs["beta_slow"])
        if rs.get("long_factor"):
            updates["rope_long_factor"] = tuple(rs["long_factor"])
        if rs.get("short_factor"):
            updates["rope_short_factor"] = tuple(rs["short_factor"])
        if rs.get("attention_factor") is not None:
            updates["rope_attn_factor"] = float(rs["attention_factor"])
        # A scaled rope serves past the checkpoint's advertised window; lift
        # max_position to the deployment context so longrope's long/short
        # choice and prompt admission agree with the YAML.
        updates["max_position"] = max(arch.max_position, cfg.context_size)
    if not updates:
        return arch
    return _dc.replace(arch, **updates)


def _has_tokenizer_files(path: str) -> bool:
    import os

    return any(
        os.path.exists(os.path.join(path, f))
        for f in ("tokenizer.json", "tokenizer.model", "tokenizer_config.json")
    )
