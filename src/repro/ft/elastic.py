"""Elastic scaling: checkpoint → different mesh.

Checkpoints are mesh-agnostic (full arrays per leaf; checkpoint/ckpt.py),
so scaling a job up or down is: stop, restore_for_mesh with the new
sharding tree, continue. The deterministic data pipeline (data/pipeline.py)
is keyed by (step, shard), so the new world size re-partitions batches
without skipping or repeating data.

This module adds the policy pieces: choosing a new mesh for a changed
device count and validating that every parameter still shards.
"""
from __future__ import annotations

from repro.configs.base import ArchConfig
from repro.launch.mesh import make_mesh
from repro.launch.sharding import MeshShardPolicy
from repro.models import schema as schema_api


def plan_mesh(n_devices: int, model_parallelism: int = 16):
    """Pick a (data, model) mesh for the available devices; shrink TP if
    the device count doesn't support it."""
    while n_devices % model_parallelism and model_parallelism > 1:
        model_parallelism //= 2
    return make_mesh((n_devices // model_parallelism, model_parallelism),
                     ("data", "model"))


def reshard_plan(cfg: ArchConfig, mesh, mode: str = "train"):
    """Sharding tree for restore_for_mesh on the new mesh."""
    policy = MeshShardPolicy.create(cfg, mesh, mode)
    return policy.param_sharding_tree(schema_api.param_schema(cfg))
