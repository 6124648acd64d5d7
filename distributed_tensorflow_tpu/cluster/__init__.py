"""Cluster definition, discovery, launch, and coordination (SURVEY.md §3.3)."""

from distributed_tensorflow_tpu.cluster.cluster_spec import (
    CHIEF,
    COMPUTE_JOBS,
    EVALUATOR,
    PS,
    WORKER,
    ClusterDeviceFilters,
    ClusterSpec,
)
from distributed_tensorflow_tpu.cluster.coordination import (
    assert_same_program,
    barrier,
    broadcast_from_coordinator,
    is_coordinator,
    process_count,
    process_index,
)
from distributed_tensorflow_tpu.cluster.resolver import (
    ClusterResolver,
    GCEClusterResolver,
    KubernetesClusterResolver,
    SimpleClusterResolver,
    SlurmClusterResolver,
    TFConfigClusterResolver,
    TPUClusterResolver,
    resolve,
)
from distributed_tensorflow_tpu.cluster.server import Server, initialize_runtime
from distributed_tensorflow_tpu.cluster.topology import (
    MESH_AXES,
    MeshConfig,
    Topology,
    build_hybrid_mesh,
    build_mesh,
    device_summary,
    single_axis_mesh,
)

__all__ = [
    "CHIEF",
    "COMPUTE_JOBS",
    "EVALUATOR",
    "PS",
    "WORKER",
    "ClusterDeviceFilters",
    "ClusterSpec",
    "ClusterResolver",
    "GCEClusterResolver",
    "KubernetesClusterResolver",
    "SimpleClusterResolver",
    "SlurmClusterResolver",
    "TFConfigClusterResolver",
    "TPUClusterResolver",
    "resolve",
    "Server",
    "initialize_runtime",
    "MESH_AXES",
    "MeshConfig",
    "Topology",
    "build_hybrid_mesh",
    "build_mesh",
    "device_summary",
    "single_axis_mesh",
    "assert_same_program",
    "barrier",
    "broadcast_from_coordinator",
    "is_coordinator",
    "process_count",
    "process_index",
]
