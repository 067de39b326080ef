"""Distributed layer: cluster topology, slice placement, and the TPU
mesh execution path.

Two planes, mirroring SURVEY.md §2.4/§5:
  - host plane (`cluster`): node membership, jump-hash partition →
    replica placement, slice ownership — the scheduling metadata the
    executor uses to fan queries out (reference cluster.go).
  - device plane (`mesh`): slices sharded across TPU devices of a
    `jax.sharding.Mesh`; Count/TopN reductions ride ICI collectives
    (psum) instead of the reference's HTTP mapReduce merge.
"""

from .broadcast import (
    Broadcaster,
    HTTPBroadcaster,
    NodeSet,
    NopBroadcaster,
    StaticNodeSet,
)
from .gossip import GossipNodeSet
from .epochs import EpochTracker, ResultCache, fragment_key
from .cluster import (
    DEFAULT_PARTITION_N,
    DEFAULT_REPLICA_N,
    Cluster,
    ConstHasher,
    JmpHasher,
    ModHasher,
    Node,
    NODE_STATE_ACTIVE,
    NODE_STATE_DOWN,
    NODE_STATE_JOINING,
    NODE_STATE_LEAVING,
    NODE_STATE_UP,
    SERVING_STATES,
    new_test_cluster,
)
from .rebalance import Rebalancer, Transfer
# The mesh module pulls in jax; load it lazily so host-only paths
# (config, CLI utilities, pure-HTTP nodes) import fast.
_MESH_NAMES = (
    "SLICE_AXIS",
    "ShardedIndex",
    "build_sharded_index",
    "combine_count",
    "compile_mesh_apply_writes",
    "compile_mesh_count",
    "compile_mesh_step",
    "compile_mesh_topn",
    "compile_serve_apply_writes",
    "compile_serve_count",
    "compile_serve_count_batch_shared",
    "coarse_row_starts",
    "compile_serve_row_counts",
    "compile_serve_row_counts_src",
    "connect_distributed",
    "default_mesh",
    "pack_mutation_batches",
    "plan_writes",
    "resolve_row_indices",
    "sharded_index_from_holder",
)

_SERVE_NAMES = ("MeshManager", "StagedView")


def __getattr__(name):
    if name in _MESH_NAMES:
        from . import mesh
        return getattr(mesh, name)
    if name in _SERVE_NAMES:
        from . import serve
        return getattr(serve, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

__all__ = [
    "MeshManager",
    "StagedView",
    "SLICE_AXIS",
    "ShardedIndex",
    "build_sharded_index",
    "combine_count",
    "compile_serve_apply_writes",
    "compile_serve_count",
    "compile_serve_count_batch_shared",
    "coarse_row_starts",
    "compile_serve_row_counts",
    "compile_serve_row_counts_src",
    "pack_mutation_batches",
    "compile_mesh_apply_writes",
    "compile_mesh_count",
    "compile_mesh_step",
    "compile_mesh_topn",
    "connect_distributed",
    "default_mesh",
    "plan_writes",
    "sharded_index_from_holder",
    "Broadcaster",
    "GossipNodeSet",
    "HTTPBroadcaster",
    "NodeSet",
    "NopBroadcaster",
    "StaticNodeSet",
    "new_test_cluster",
    "DEFAULT_PARTITION_N",
    "DEFAULT_REPLICA_N",
    "Cluster",
    "ConstHasher",
    "JmpHasher",
    "ModHasher",
    "Node",
    "NODE_STATE_ACTIVE",
    "NODE_STATE_DOWN",
    "NODE_STATE_JOINING",
    "NODE_STATE_LEAVING",
    "NODE_STATE_UP",
    "SERVING_STATES",
    "Rebalancer",
    "Transfer",
    "EpochTracker",
    "ResultCache",
    "fragment_key",
]
