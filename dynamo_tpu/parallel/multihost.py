"""Multi-host bootstrap: jax.distributed process groups.

The reference bootstraps multi-node engines with Ray actors or MPI-style
launchers that set rank/world-size envs and let NCCL form the ring
(reference: lib/engines/vllm0_7/src/ray.rs spawn_vllm_workers,
lib/engines/sglang/sglang_inc.py:44-47 dist_init_addr/nnodes/node_rank,
launch/dynamo-run/src/lib.rs:232-276 --num-nodes/--node-rank plumbing).

The TPU-native equivalent is `jax.distributed.initialize`: one process
per host joins a coordinator, after which `jax.devices()` is the GLOBAL
device list and XLA collectives ride ICI within a slice and DCN across
hosts. Two serving topologies follow:

- **dp across hosts** (the common one): each host runs its own engine
  worker on its local chips and registers with the hub; routing spreads
  requests. No cross-host collectives on the serving path — this is the
  reference's multiple-workers-per-deployment shape and works today via
  the SDK/runtime.
- **model sharded across hosts** (tp spanning DCN): every process
  executes the same jitted step SPMD-style over a global mesh
  (multi-controller). `global_mesh` builds that mesh; the serving loop
  must then run lockstep on every host (MaxText-style), which large-model
  deployments drive through the same `dynamo-run` entry with identical
  flags per host.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass
from typing import Optional

log = logging.getLogger("dynamo_tpu.parallel.multihost")


@dataclass
class MultiHostConfig:
    """CLI surface (reference: launch/dynamo-run/src/lib.rs:232-276)."""

    num_nodes: int = 1
    node_rank: int = 0
    coordinator: Optional[str] = None  # "host:port" of node 0

    @property
    def is_multi_node(self) -> bool:
        return self.num_nodes > 1

    @property
    def is_leader(self) -> bool:
        return self.node_rank == 0

    def validate(self) -> None:
        if not self.is_multi_node:
            return
        if not (0 <= self.node_rank < self.num_nodes):
            raise ValueError(
                f"node_rank {self.node_rank} outside [0, {self.num_nodes})"
            )
        if not self.coordinator:
            raise ValueError("--coordinator host:port required when num_nodes > 1")


def initialize(cfg: MultiHostConfig) -> None:
    """Join the process group (idempotent no-op for single node). After
    this, jax.devices() is global; jax.local_devices() stays host-local."""
    if not cfg.is_multi_node:
        return
    cfg.validate()
    import jax

    jax.distributed.initialize(
        coordinator_address=cfg.coordinator,
        num_processes=cfg.num_nodes,
        process_id=cfg.node_rank,
    )
    log.info(
        "multi-host up: rank %d/%d, %d local / %d global devices",
        cfg.node_rank, cfg.num_nodes,
        jax.local_device_count(), jax.device_count(),
    )


def shutdown() -> None:
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:  # noqa: BLE001 — already down / never initialized
        pass


def global_mesh(mesh_config, devices=None):
    """Mesh over ALL processes' devices (a cross-host tp axis rides DCN;
    lay the fastest-varying axis (tp) within a host so its collectives
    stay on ICI)."""
    import jax

    from dynamo_tpu.parallel.mesh import build_mesh

    return build_mesh(mesh_config, devices or jax.devices())


def local_devices():
    import jax

    return jax.local_devices()
