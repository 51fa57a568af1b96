"""Multi-host runtime: jax.distributed bootstrap + hybrid DCN×ICI meshes.

The reference scales across hosts with mpirun + NCCL/MPI process groups
(run_fedavg_distributed_pytorch.sh:16-35, fedml_experiments/centralized/
main.py:54-67); every cross-host exchange is an explicit P2P send. The TPU
equivalent is SPMD over a GLOBAL mesh: each host runs the same jitted
program over its local chips, `jax.distributed.initialize` forms the global
device set, and XLA routes collectives over ICI within a slice and DCN
across slices. Nothing else in the framework changes — the sharded round
functions (parallel/fedavg_sharded.py, hierarchical_sharded.py) are written
against mesh axis *names*, so the same code runs on 1 chip, an 8-chip
slice, or a multi-slice pod; only the mesh handed to them differs.

Axis-layout rule (scaling-book recipe): put the axis with the most traffic
innermost (ICI), the rare-sync axis outermost (DCN). For federated
learning that is: per-round client aggregation → ICI; hierarchical FL's
cross-group (cloud) sync every ``group_comm_round`` rounds → DCN.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


def initialize_multihost(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
    auto: bool = False,
) -> bool:
    """Bring this process into the global device set.

    Thin guard around ``jax.distributed.initialize``: no-op (returns False)
    when the run is single-process — nothing is explicitly configured (no
    args, no JAX_COORDINATOR_ADDRESS) and ``auto`` is off — or when
    num_processes == 1, so drivers can call it unconditionally. Replaces
    the reference's ``MPI.COMM_WORLD`` rank/size bootstrap
    (FedAvgAPI.py:14-18) and ``init_process_group("nccl")``.

    ``auto=True`` additionally hands control to jax's cluster auto-detection
    (Cloud TPU pod metadata, SLURM, …) with no explicit arguments, treating
    a detection failure as "single process". It is opt-in rather than the
    default because auto-detection probes environment services — in an
    air-gapped or test environment that probe is wasted work (and this
    container has no egress at all).

    CRITICAL ORDERING: nothing here may touch the XLA backend before
    ``initialize`` — ``jax.devices()`` / ``jax.process_count()`` would
    initialize it, after which ``jax.distributed.initialize`` raises (the
    same init-order pitfall as the dryrun device bootstrap).
    ``jax.distributed.is_initialized()`` is backend-free.
    """
    if _distributed_initialized():
        # label even when someone else did the initialize — the telemetry
        # track name should reflect host rank whenever a cluster exists
        _label_telemetry()
        return True
    env_addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    explicit = not (
        coordinator_address is None and env_addr is None and num_processes is None
    )
    if not explicit:
        if not auto:
            return False
        try:
            jax.distributed.initialize()  # cluster auto-detection
        except (RuntimeError, ValueError):
            return False  # no detectable cluster → single process
        return True
    if num_processes == 1:
        return False
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    _label_telemetry()
    return True


def _distributed_initialized() -> bool:
    """``jax.distributed.is_initialized()`` with a fallback for older jax
    (0.4.x has no such function): the coordination-service client in the
    private global state is the same signal the public API reads. Both
    paths are backend-free (see the CRITICAL ORDERING note above)."""
    fn = getattr(jax.distributed, "is_initialized", None)
    if fn is not None:
        return bool(fn())
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client is not None
    except Exception:  # noqa: BLE001 — private-API drift ⇒ assume uninitialized
        return False


def _label_telemetry() -> None:
    """Name this process's telemetry track after its host rank, so the
    per-host Chrome traces from a multi-host run can be merged in Perfetto
    and still read as host0/host1/… (each host writes its own file into the
    shared --telemetry_dir; span timestamps are epoch-anchored, so the
    merged view lines up on wall clock)."""
    from fedml_tpu.telemetry import get_tracer

    get_tracer().process_label = (
        f"fedml_tpu host{jax.process_index()}/{jax.process_count()}"
    )


def devices_by_host(devices: Optional[Sequence] = None) -> np.ndarray:
    """[n_hosts, devices_per_host] device array, hosts ordered by
    process_index and devices by id within each host. Raises if hosts are
    unevenly populated (a hybrid mesh needs a rectangle)."""
    devs = list(devices if devices is not None else jax.devices())
    hosts: dict = {}
    for d in devs:
        hosts.setdefault(d.process_index, []).append(d)
    counts = {len(v) for v in hosts.values()}
    if len(counts) != 1:
        raise ValueError(
            f"uneven devices per host: { {k: len(v) for k, v in hosts.items()} }"
        )
    rows = [
        sorted(hosts[p], key=lambda d: d.id) for p in sorted(hosts)
    ]
    return np.array(rows)


def hybrid_mesh(
    dcn_axis: str = "groups",
    ici_axis: str = "clients",
    devices: Optional[Sequence] = None,
    dcn_size: Optional[int] = None,
) -> Mesh:
    """2-D mesh with the slow (cross-host DCN) axis outermost and the fast
    (intra-host ICI) axis innermost.

    Multi-process: rows = hosts (process_index), so collectives over
    ``ici_axis`` stay inside a host/slice and only ``dcn_axis`` collectives
    cross DCN. Single-process (simulation, virtual CPU farm): the flat
    device list is folded into ``dcn_size`` rows (default: number of
    distinct process indices, else 1) so the same program shape can be
    exercised without a cluster — pass ``dcn_size`` explicitly to emulate
    an N-slice layout on the 8-device CPU mesh."""
    devs = list(devices if devices is not None else jax.devices())
    if dcn_size is None:
        grid = devices_by_host(devs)
    else:
        if len(devs) % dcn_size:
            raise ValueError(
                f"{len(devs)} devices not divisible into {dcn_size} rows"
            )
        grid = np.array(devs).reshape(dcn_size, len(devs) // dcn_size)
    return Mesh(grid, (dcn_axis, ici_axis))


def mesh_traffic_summary(mesh: Mesh) -> dict:
    """Which axes ride ICI vs DCN — a placement sanity check for drivers
    (the reference's analog is the gpu_mapping.yaml eyeball check). An axis
    crosses DCN iff its collectives span more than one process."""
    out = {}
    grid = mesh.devices
    for i, name in enumerate(mesh.axis_names):
        cols = np.moveaxis(grid, i, 0).reshape(grid.shape[i], -1)
        crosses = any(
            len({d.process_index for d in cols[:, j]}) > 1
            for j in range(cols.shape[1])
        )
        out[name] = "dcn" if crosses else "ici"
    return out
