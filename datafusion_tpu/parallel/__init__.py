"""Partitioned / distributed execution over a TPU device mesh.

The reference planned (never built) a distributed mode: etcd membership
+ HTTP workers exchanging Arrow IPC (`scripts/smoketest.sh:30-66`,
`README.md:33-35`), shipping serialized plans (`logicalplan.rs:307`,
`physicalplan.rs:18-34`) and datasource descriptions
(`datasource.rs:70-85`) to workers.

The TPU-native equivalent implemented here:

- partitions of a table shard round-robin over a `jax.sharding.Mesh`;
- each device runs the *same* fused filter+aggregate kernel on its
  shard (partial aggregation), via `shard_map`;
- partials combine with XLA collectives (`psum`, all-gather +
  min/max) riding ICI — replacing Arrow-IPC-over-HTTP result exchange;
- plan fragments still travel as the JSON wire format the reference
  intended (`PlanFragment`), which is what the multi-host mode ships:
  `DistributedContext` sends fragments over TCP to worker processes
  (`python -m datafusion_tpu.worker`) and merges their partial
  aggregate states by key (coordinator.py).
"""

from datafusion_tpu.parallel.mesh import make_mesh, mesh_axis, initialize_distributed
from datafusion_tpu.parallel.physical import PhysicalPlan, PlanFragment
from datafusion_tpu.parallel.partition import (
    PartitionedContext,
    PartitionedDataSource,
    PartitionedAggregateRelation,
)
from datafusion_tpu.parallel.coordinator import DistributedContext, WorkerHandle

__all__ = [
    "make_mesh",
    "mesh_axis",
    "initialize_distributed",
    "PhysicalPlan",
    "PlanFragment",
    "PartitionedContext",
    "PartitionedDataSource",
    "PartitionedAggregateRelation",
    "DistributedContext",
    "WorkerHandle",
]
