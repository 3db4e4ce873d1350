"""Mesh and cluster topology (single host + `jax.distributed` tier).

A function, not a module-level constant: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).

The cluster tier (DESIGN.md §11): ``init_cluster`` brings a process into
a ``jax.distributed`` cluster (coordinator + process id from args or
``AMP_COORDINATOR`` / ``AMP_NUM_PROCESSES`` / ``AMP_PROCESS_ID`` env),
after which ``jax.devices()`` is the *global* device list.
``make_cluster_mesh`` then builds the widest serve mesh the backend
supports: a global mesh spanning every host's devices where cross-host
collectives exist (TPU/GPU), so processor-sharded large singles span
hosts — and a host-local mesh on backends without multi-process
computations (CPU: jaxlib rejects them), where data-parallel buckets and
proc-sharded singles stay host-local and the cluster router is the only
cross-host axis. ``supports_cross_host_collectives`` is the gate.
"""
from __future__ import annotations

import dataclasses
import os

import jax

from ..compat import make_mesh

__all__ = ["make_production_mesh", "make_host_mesh", "make_serve_mesh",
           "ClusterInfo", "init_cluster",
           "supports_cross_host_collectives", "make_cluster_mesh"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU examples)."""
    n = jax.device_count()
    data = data or (n // model)
    return make_mesh((data, model), ("data", "model"))


def make_serve_mesh(n_devices: int | None = None):
    """1-D 'data' mesh for the solve service's placement dispatcher.

    Both serving placements run over this one axis: data-parallel buckets
    shard the request batch across it, processor-sharded solves map the
    paper's P onto it (DESIGN.md §6). Defaults to every *local* device;
    pass ``n_devices`` to serve from a subset (e.g. to leave devices for a
    co-located training job).
    """
    n = n_devices or jax.local_device_count()
    # pin to local devices: under jax.distributed, jax.devices() is the
    # global list, but a host's serve mesh must stay host-local (the
    # cluster router, not the mesh, is the cross-host axis on CPU)
    return make_mesh((n,), ("data",), devices=jax.local_devices()[:n])


# -- cluster tier (DESIGN.md §11) -------------------------------------------

@dataclasses.dataclass(frozen=True)
class ClusterInfo:
    """This process's view of the cluster after ``init_cluster``."""

    process_index: int
    process_count: int
    local_devices: int
    global_devices: int
    coordinator: str | None

    @property
    def is_frontend(self) -> bool:
        """Process 0 hosts the cluster frontend/router by convention."""
        return self.process_index == 0


def init_cluster(coordinator_address: str | None = None,
                 num_processes: int | None = None,
                 process_id: int | None = None) -> ClusterInfo:
    """Join (or stand alone as) a ``jax.distributed`` cluster.

    Arguments fall back to ``AMP_COORDINATOR`` / ``AMP_NUM_PROCESSES`` /
    ``AMP_PROCESS_ID``; with no coordinator configured (or a process
    count of 1) this is a single-process no-op returning the local
    topology. Idempotent: a process already initialized (by a prior call
    or by the launcher) just reports the live topology.

    Call before any other jax API touches the backend — like mesh
    creation, distributed initialization must precede first device use.
    """
    coordinator_address = (coordinator_address
                           or os.environ.get("AMP_COORDINATOR"))
    if num_processes is None:
        env = os.environ.get("AMP_NUM_PROCESSES")
        num_processes = int(env) if env else None
    if process_id is None:
        env = os.environ.get("AMP_PROCESS_ID")
        process_id = int(env) if env else None

    # probe distributed state WITHOUT jax.process_count(): that call
    # instantiates the backend client, after which
    # jax.distributed.initialize refuses ("must be called before any JAX
    # computations are executed")
    if (coordinator_address and num_processes and num_processes > 1
            and not jax.distributed.is_initialized()):
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    return ClusterInfo(
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        local_devices=jax.local_device_count(),
        global_devices=jax.device_count(),
        coordinator=coordinator_address,
    )


def supports_cross_host_collectives() -> bool:
    """Whether XLA computations may span this cluster's processes.

    True trivially for a single process. Multi-process CPU clusters
    coordinate (device discovery, process ids) but jaxlib's CPU client
    rejects multi-process *computations* ("Multiprocess computations
    aren't implemented on the CPU backend"), so cross-host
    processor-sharded solves are TPU/GPU-only; CPU clusters route across
    hosts at the request level instead (serving.frontend).
    """
    if jax.process_count() <= 1:
        return True
    return jax.default_backend() != "cpu"


def make_cluster_mesh():
    """The widest 1-D serve mesh this process may dispatch onto:
    all-host global when cross-host collectives are supported (the mesh
    axis then spans every process's devices, so a processor-sharded
    large single maps the paper's P across hosts), else the host-local
    serve mesh (data-parallel buckets were host-local either way —
    request-level routing is the cross-host axis on CPU)."""
    if jax.process_count() > 1 and supports_cross_host_collectives():
        return make_mesh((jax.device_count(),), ("data",))
    return make_serve_mesh()
