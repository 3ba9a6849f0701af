"""TPU chip allocation across component processes on one host.

Equivalent of the reference's GPU allocator (reference:
sdk cli/allocator.py:54-251 ResourceAllocator.assign_gpus setting
CUDA_VISIBLE_DEVICES) for TPU: each worker process gets a disjoint set of
chip indices via TPU_VISIBLE_DEVICES (honored by libtpu) plus
JAX_PLATFORMS passthrough; CPU-only components get JAX_PLATFORMS=cpu so
they never grab the chips.
"""

from __future__ import annotations

import os
import subprocess
import sys
from dataclasses import dataclass
from typing import Optional

# The supervisor must never initialise a jax backend itself: a chip
# belongs to one process at a time, and a parent that holds it starves
# every worker it then spawns. The probe runs in a child that exits (and
# lets go of the chip) before any worker starts.
_PROBE = (
    "import jax; d = jax.devices(); "
    "print(len(d) if d[0].platform == 'tpu' else 0)"
)


def detect_num_chips() -> int:
    """TPU chips on this host, found without touching jax in this
    process: `DYN_TPU_NUM_CHIPS`, else a short-lived child process that
    asks jax and exits (device files differ by TPU generation: the v5e
    host shows only /dev/vfio). A probe
    that FAILS (the chip is held by someone, libtpu cannot start) raises
    — it does not read as "this host has no chips"."""
    env = os.environ.get("DYN_TPU_NUM_CHIPS")
    if env:
        return int(env)
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return 0  # the operator pinned this tree to the CPU
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], capture_output=True, text=True,
        timeout=300,
    )
    if proc.returncode != 0:
        raise RuntimeError(
            "TPU chip probe failed (is another process holding the chip?): "
            + proc.stderr.strip()[-500:]
        )
    return int(proc.stdout.strip().splitlines()[-1])


@dataclass
class TpuAllocator:
    # None = detect on first use: a graph with no `tpu` resources never
    # probes at all
    total_chips: Optional[int] = None
    _next: int = 0

    def assign(self, num_chips: int) -> Optional[list[int]]:
        """A disjoint chip-id range, or None if the host is out of chips."""
        if num_chips == 0:
            return []
        if self.total_chips is None:
            self.total_chips = detect_num_chips()
        if self._next + num_chips > self.total_chips:
            return None
        ids = list(range(self._next, self._next + num_chips))
        self._next += num_chips
        return ids

    def release_all(self) -> None:
        self._next = 0

    @staticmethod
    def env_for(chip_ids: list[int]) -> dict[str, str]:
        if not chip_ids:
            # CPU-only component: keep it off the accelerators entirely
            return {"JAX_PLATFORMS": "cpu"}
        return {"TPU_VISIBLE_DEVICES": ",".join(str(i) for i in chip_ids)}
