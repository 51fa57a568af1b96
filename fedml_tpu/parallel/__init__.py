"""Device-mesh parallelism: the TPU-native replacement for the reference's
process-per-worker MPI runtime (ref fedml_core/distributed/communication/mpi/ +
fedml_api/distributed/utils/gpu_mapping.py).

Instead of `mpirun -np N+1` processes exchanging JSON-serialized state dicts
(SURVEY §2h), clients are laid out along a mesh axis of a single SPMD program:
"broadcast" is parameter replication, "gather + aggregate" is a weighted `psum`
over ICI. The mesh spec replaces gpu_mapping.yaml."""

from fedml_tpu.parallel.mesh import make_mesh, pad_client_batch
from fedml_tpu.parallel.fedavg_sharded import (
    make_sharded_fedavg_round,
    DistributedFedAvgAPI,
    DistributedFedNovaAPI,
    DistributedDittoAPI,
    DistributedDPFedAvgAPI,
    DistributedScaffoldAPI,
    DistributedFedOptAPI,
    RobustDistributedFedAvgAPI,
)
from fedml_tpu.parallel.tensor_parallel import make_tp_train_step
from fedml_tpu.parallel.expert_parallel import make_ep_train_step
from fedml_tpu.parallel.pipeline import make_pp_train_step
from fedml_tpu.parallel.hierarchical_sharded import (
    HierarchicalShardedAPI,
    make_hierarchical_sharded_round,
)
from fedml_tpu.parallel.multihost import (
    hybrid_mesh,
    initialize_multihost,
    mesh_traffic_summary,
)
from fedml_tpu.parallel.decentralized_sharded import (
    make_sharded_decentralized_run,
)

__all__ = [
    "make_mesh",
    "pad_client_batch",
    "make_sharded_fedavg_round",
    "DistributedFedAvgAPI",
    "DistributedFedNovaAPI",
    "DistributedDittoAPI",
    "DistributedDPFedAvgAPI",
    "DistributedScaffoldAPI",
    "DistributedFedOptAPI",
    "RobustDistributedFedAvgAPI",
    "make_tp_train_step",
    "make_ep_train_step",
    "make_pp_train_step",
    "HierarchicalShardedAPI",
    "make_hierarchical_sharded_round",
    "hybrid_mesh",
    "initialize_multihost",
    "mesh_traffic_summary",
    "make_sharded_decentralized_run",
]
