"""Multi-host distributed backend: DCN-spanning meshes over XLA collectives.

The reference's "distributed communication backend" is OTP messaging +
Phoenix.PubSub on ONE BEAM node (SURVEY.md §2.9 — no NCCL/MPI anywhere);
scaling past one host there means nothing. Here multi-host IS first-class:
``init_process`` joins this process into a JAX distributed system (TPU
pods: ICI within a slice, DCN between hosts; CPU tests: Gloo over
localhost), and ``multihost_mesh`` lays the global device set out so the
heavy collectives stay on the fast network:

  * tp (tensor parallel)  — INNERMOST, always within one host's devices:
    per-layer psums ride ICI, never DCN;
  * dp (data parallel)    — OUTERMOST, across hosts: one grad all-reduce
    per step is the only DCN traffic (the scaling-book recipe);
  * sp (sequence parallel)— between the two: ring hops prefer neighbors.

Everything downstream is unchanged — param_specs/cache_spec/shard_map name
axes, never device counts, so the same serving and train steps jit over a
multihost mesh exactly as over a single-host one. tests/test_distributed.py
proves it by running a REAL two-process mesh (Gloo collectives across
process boundaries) on CPU: global train steps produce identical replicated
losses on every host.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence


@dataclasses.dataclass
class ProcessInfo:
    process_id: int
    num_processes: int
    local_devices: int
    global_devices: int

    @property
    def is_coordinator(self) -> bool:
        return self.process_id == 0


def _cluster_env_expects_peers() -> bool:
    """True when the environment says MULTIPLE processes should form a
    cluster — then an auto-init failure must surface, not degrade to a
    silent 1/N-of-the-pod run. Mere key PRESENCE is not enough: single-host
    TPU VMs routinely export TPU_WORKER_HOSTNAMES with one (or a garbage)
    entry, and crashing those would break every single-host serve."""
    import os
    if (os.environ.get("JAX_COORDINATOR_ADDRESS")
            or os.environ.get("COORDINATOR_ADDRESS")
            or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")):
        return True
    if "," in os.environ.get("TPU_WORKER_HOSTNAMES", ""):
        return True                              # >= 2 workers listed
    for key in ("OMPI_COMM_WORLD_SIZE", "SLURM_NTASKS"):
        try:
            if int(os.environ.get(key, "1")) > 1:
                return True
        except ValueError:
            pass
    return False


def init_process(coordinator_address: Optional[str] = None,
                 num_processes: Optional[int] = None,
                 process_id: Optional[int] = None) -> ProcessInfo:
    """Join the JAX distributed system. On TPU pods all three arguments are
    inferred from the environment (jax.distributed.initialize() with no
    args); CPU/GPU clusters pass them explicitly. With no arguments AND no
    cluster environment there is nothing to join and nothing is called:
    the no-argument form asks the cloud metadata server who the peers are,
    and a host without one (a sealed single-host machine) pays its
    connection timeouts at every start only to have the exception
    swallowed. When the environment says a cluster exists, an init
    failure raises: swallowing it would leave this process training on
    1/N of the pod or hanging in the first collective its peers enter
    without it."""
    import jax

    def _info() -> ProcessInfo:
        return ProcessInfo(
            process_id=jax.process_index(),
            num_processes=jax.process_count(),
            local_devices=jax.local_device_count(),
            global_devices=jax.device_count(),
        )

    if jax.distributed.is_initialized():
        # a second Runtime / repeated call in one process: the system is
        # up, just report it
        return _info()
    if process_id is not None and coordinator_address is None \
            and num_processes is None:
        # an explicit rank with nothing to join would silently degrade to
        # a single-process run with the rank dropped — the exact failure
        # mode this module exists to surface
        raise ValueError(
            "process_id given without coordinator_address/num_processes — "
            "pass all three for explicit clusters, or none for pod "
            "auto-detection")
    if coordinator_address is not None or num_processes is not None:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes, process_id=process_id)
    elif _cluster_env_expects_peers():
        jax.distributed.initialize()
    return _info()


def _hosts_of(devs: Sequence) -> list[list]:
    """Group devices by owning process, in process order, and require the
    groups to be even — the reshape below assumes a rectangular
    [hosts, local] layout."""
    by_proc: dict[int, list] = {}
    for d in devs:
        by_proc.setdefault(getattr(d, "process_index", 0), []).append(d)
    groups = [by_proc[p] for p in sorted(by_proc)]
    sizes = {len(g) for g in groups}
    if len(sizes) != 1:
        # ValueError (not assert): this guard must survive `python -O` —
        # a ragged host layout silently reshaped would misplace shards.
        raise ValueError(
            f"uneven devices per host: "
            f"{ {p: len(g) for p, g in by_proc.items()} }")
    return groups


def multihost_mesh(tp: Optional[int] = None, sp: int = 1,
                   devices: Optional[Sequence] = None):
    """Global dp×(sp×)tp mesh over every process's devices with tp packed
    inside a host. Host membership comes from each device's own
    ``process_index`` (never from list length), so explicit device lists —
    including cross-host ones — get the same tp-within-host guarantee:
    per-layer tp psums ride ICI, and only the dp axis crosses DCN. The
    mesh itself is built by make_mesh over the host-ordered device list
    (one reshape implementation for single- and multi-host)."""
    from quoracle_tpu.parallel.mesh import make_mesh
    import jax
    devs = list(devices) if devices is not None else jax.devices()
    hosts = _hosts_of(devs)
    n_local = len(hosts[0])
    tp = tp or 1
    if n_local % tp != 0:
        # ValueError (not assert): stripped asserts under `python -O` would
        # let a cross-host tp mesh build silently — the exact cross-DCN-psum
        # hang this module exists to prevent.
        raise ValueError(
            f"tp={tp} must divide the per-host device count {n_local} (tp "
            f"stays within one host so its collectives ride ICI, not DCN)")
    ordered = [d for g in hosts for d in g]
    return make_mesh(devices=ordered, tp=tp, sp=sp)


def host_local_batch(global_batch, mesh, spec):
    """Each host feeds its own shard of a dp-sharded batch: wraps
    multihost_utils.host_local_array_to_global_array so callers hand the
    PER-HOST numpy slice and get the global jax.Array laid out on the
    mesh. On a single process this is just device_put with the sharding."""
    import jax
    from jax.sharding import NamedSharding
    if jax.process_count() == 1:
        return jax.device_put(global_batch, NamedSharding(mesh, spec))
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(
        global_batch, mesh, spec)


def barrier(tag: str = "barrier") -> None:
    """Cross-host sync point (no-op single-process)."""
    import jax
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(tag)
