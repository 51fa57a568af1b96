"""fedml_tpu — a TPU-native federated-learning framework.

A from-scratch re-design of the capabilities of FedML (reference: GabriJP/FedML)
on JAX/XLA. The reference's MPI/gRPC/MQTT actor runtime
(fedml_core/distributed/communication/) becomes, for the common intra-pod case,
a pure jit-compiled round function sharded over a `jax.sharding.Mesh`; its
PyTorch model zoo (fedml_api/model/) becomes flax modules; its standalone
sequential simulator (fedml_api/standalone/fedavg/fedavg_api.py:40-84) becomes
vmap-over-clients on one chip. A Message/Observer-shaped async transport is kept
for true cross-silo federation.

Subpackages
-----------
- ``config``      typed run configuration (ref: fedml_core/trainer/model_trainer.py:7-38)
- ``partition``   non-IID partitioners + topologies (ref: fedml_core/non_iid_partition/)
- ``data``        federated dataset containers and loaders (ref: fedml_api/data_preprocessing/)
- ``models``      flax model zoo (ref: fedml_api/model/)
- ``train``       jit-compiled local training / evaluation operators
- ``algorithms``  FL algorithms (ref: fedml_api/{distributed,standalone}/)
- ``parallel``    mesh runtime: sharded FedAvg, ring/Ulysses SP, TP, EP, PP
- ``core``        Message/Observer transport (gRPC/MQTT/shm/loopback)
- ``ops``         Pallas TPU kernels (flash attention)
- ``robustness``  defenses (clip/DP, Byzantine aggregators) + backdoor harness
- ``secagg``      field MPC + pairwise-mask secure aggregation
- ``utils``       metrics, checkpoint/resume, profiling
- ``native``      C++ fastpack host ops (ctypes)
"""

__version__ = "0.2.0"

# NOTE: this file deliberately imports nothing. `import fedml_tpu` (and in
# particular `import fedml_tpu.telemetry`, which is jax-free by contract)
# must not pay the jax import. The supported jax is the one pyproject.toml
# pins (0.9.x); there are no compatibility shims.
