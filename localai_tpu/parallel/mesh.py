"""Device-mesh construction.

The reference configures parallelism per engine (`tensor_split`,
`TensorParallelSize` — backend/backend.proto:193,233); here a MeshPlan is the
single declaration: axis sizes over the available devices, validated against
the architecture, reused by every jitted program.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

AXES = ("dp", "tp", "ep", "sp")


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Sizes for each mesh axis; product must equal the device count in use."""

    dp: int = 1
    tp: int = 1
    ep: int = 1
    sp: int = 1

    @property
    def total(self) -> int:
        return self.dp * self.tp * self.ep * self.sp

    def axis_sizes(self) -> tuple[int, ...]:
        return (self.dp, self.tp, self.ep, self.sp)


def build_mesh(plan: MeshPlan, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if plan.total > len(devs):
        raise ValueError(f"mesh plan {plan} needs {plan.total} devices, have {len(devs)}")
    devs = devs[: plan.total]
    arr = np.array(devs).reshape(plan.axis_sizes())
    return Mesh(arr, AXES)


def plan_for_devices(n: int, tp: Optional[int] = None) -> MeshPlan:
    """Default plan: prefer tensor parallel within a slice (ICI-bound), data
    parallel over what's left. Matches the scaling-book recipe of putting the
    fastest-varying parallelism on the fastest interconnect."""
    if tp is None:
        tp = n
    if n % tp != 0:
        raise ValueError(f"{n} devices not divisible by tp={tp}")
    return MeshPlan(dp=n // tp, tp=tp)
