"""Parallelism: device meshes, shardings, and multi-host bootstrap.

The reference delegates intra-model parallelism to its engines (NCCL inside
vLLM/sglang; Ray/torch.distributed bootstrap — SURVEY.md §2.4). On TPU this
layer is first-class: TP/SP/EP/DP are axes of one `jax.sharding.Mesh`,
collectives are XLA's over ICI/DCN, and multi-host bootstrap is
`jax.distributed` per-host processes.
"""

from dynamo_tpu.parallel.mesh import (
    MeshConfig,
    build_mesh,
    kv_cache_sharding,
    param_shardings,
    shard_params,
)

__all__ = [
    "MeshConfig",
    "build_mesh",
    "param_shardings",
    "kv_cache_sharding",
    "shard_params",
]
