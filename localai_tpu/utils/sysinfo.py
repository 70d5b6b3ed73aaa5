"""System/accelerator introspection driving mesh defaults.

Reference: pkg/xsysinfo (CPU caps, GPU VRAM via gonvml) feeds backend
selection and model-fit checks. The TPU equivalent reports chip kind/count,
HBM per chip from the XLA runtime, host RAM, and a recommended MeshPlan —
tp across the slice first (ICI-bound), matching parallel.mesh defaults.
"""

from __future__ import annotations

import os
from typing import Any, Optional


def _host_ram_bytes() -> Optional[int]:
    try:
        return os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES")
    except (ValueError, OSError, AttributeError):
        return None


def device_info() -> dict[str, Any]:
    """Per-device and aggregate accelerator info (safe on CPU-only hosts)."""
    import jax

    devs = jax.devices()
    out: dict[str, Any] = {
        "platform": jax.default_backend(),
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
        "devices": [],
        "host_ram_bytes": _host_ram_bytes(),
        "cpu_count": os.cpu_count(),
    }
    for d in devs:
        entry: dict[str, Any] = {
            "id": d.id,
            "kind": getattr(d, "device_kind", str(d)),
            "process": getattr(d, "process_index", 0),
        }
        try:
            stats = d.memory_stats() or {}
            entry["hbm_bytes"] = stats.get("bytes_limit")
            entry["hbm_in_use_bytes"] = stats.get("bytes_in_use")
            entry["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        except Exception:  # noqa: BLE001 — CPU devices have no memory_stats
            pass
        out["devices"].append(entry)
    hbm = [e.get("hbm_bytes") for e in out["devices"] if e.get("hbm_bytes")]
    out["total_hbm_bytes"] = sum(hbm) if hbm else None
    return out


def engine_placement(engine) -> Optional[dict[str, Any]]:
    """Where one loaded engine actually sits: the mesh plan it ended up
    with (after any tensor_parallel clamp or max_valid_tp degrade) and the
    ids of the devices that hold its parameter and KV shards. A cluster
    facade reports each same-host replica. None for engines without a mesh
    (bert, image, audio)."""
    import jax

    replicas = getattr(engine, "local_replicas", None)
    if replicas is not None:
        return {"replicas": {r.name: engine_placement(r.engine)
                             for r in replicas}}
    plan = getattr(engine, "plan", None)
    mesh = getattr(engine, "mesh", None)
    if plan is None or mesh is None:
        return None

    def device_ids(tree) -> list[int]:
        # .sharding survives buffer donation; .devices() on a donated
        # array does not.
        return sorted({
            d.id for leaf in jax.tree.leaves(tree)
            for d in leaf.sharding.device_set
        })

    return {
        "plan": {"dp": plan.dp, "tp": plan.tp, "ep": plan.ep, "sp": plan.sp},
        "mesh_devices": [int(d.id) for d in mesh.devices.flat],
        "param_devices": device_ids(engine.params),
        "kv_devices": device_ids(engine.cache),
    }


def recommend_mesh(n_devices: Optional[int] = None) -> dict[str, int]:
    """Default mesh sizes: all devices on tp (fastest interconnect gets the
    fastest-varying parallelism — the scaling-book recipe used by
    parallel.mesh.plan_for_devices)."""
    import jax

    n = n_devices if n_devices is not None else len(jax.devices())
    return {"dp": 1, "tp": n, "ep": 1, "sp": 1}


def model_fits(param_bytes: int, n_devices: Optional[int] = None,
               kv_budget_frac: float = 0.35) -> Optional[bool]:
    """Quick HBM-fit check: params must leave kv_budget_frac of total HBM
    free for KV cache + activations. None when HBM is unknown (CPU)."""
    info = device_info()
    total = info.get("total_hbm_bytes")
    if not total:
        return None
    return param_bytes <= total * (1.0 - kv_budget_frac)
