"""Elastic mesh planning: the largest grid a device count fills.

``serve/resilience.py`` re-plans its service mesh with ``plan_mesh``
when the supervisor fences replicas, so the shrunken fleet keeps a
well-formed (pod, data, model) grid.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: Tuple[int, ...]
    axes: Tuple[str, ...]
    note: str = ""

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def plan_mesh(
    n_devices: int,
    *,
    model_parallel: int = 16,
    want_pods: Optional[int] = None,
) -> MeshPlan:
    """Largest (pod, data, model) mesh that fits ``n_devices``.

    Keeps the model axis fixed (TP degree is architecture-determined) and
    gives the rest to data; a pod axis is split out when the count divides.
    Drops devices that don't fit the grid (reported in ``note``) — the
    shrink path after failures.
    """
    mp = model_parallel
    while mp > 1 and n_devices % mp != 0:
        mp //= 2
    rest = n_devices // mp
    if want_pods and rest % want_pods == 0 and want_pods > 1:
        plan = MeshPlan((want_pods, rest // want_pods, mp),
                        ("pod", "data", "model"))
    else:
        plan = MeshPlan((rest, mp), ("data", "model"))
    used = plan.n_devices
    note = "" if used == n_devices else f"dropping {n_devices - used} devices"
    return dataclasses.replace(plan, note=note)

