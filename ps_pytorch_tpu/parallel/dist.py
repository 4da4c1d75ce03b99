"""Multi-host wiring — the DCN control/bootstrap layer.

The reference bootstraps its world with ``mpirun --hostfile hosts_address``
(``run_pytorch.sh:1-16``) and OpenMPI's out-of-band TCP wire-up; every
subsequent cross-host byte rides hand-rolled MPI tags (SURVEY §2.3). Here
bootstrap is ``jax.distributed.initialize`` (gRPC coordination service over
DCN): the launcher (`ps_pytorch_tpu.tools.launch`) exports three environment
variables per host and each process calls :func:`initialize_from_env` before
touching any device. After that the data plane is pure XLA collectives over
the global mesh; the coordination-service KV doubles as the Coordinator's
control plane (runtime/coordinator.py DistributedKV).

Also home to the host-local -> global array assembly helpers: with more than
one process, a jitted function over a global mesh consumes *global* jax.Arrays
whose shards live on each host's addressable devices; ``globalize_batch``
builds them from each host's local batch (the data-locality contract —
workers never exchange raw examples, ``README.md:24``).
"""

import os
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Environment contract written by tools/launch.py (and usable by hand).
ENV_COORD = "PS_TPU_COORDINATOR"    # host:port of process 0
ENV_NPROC = "PS_TPU_NUM_PROCESSES"
ENV_PID = "PS_TPU_PROCESS_ID"


def initialize_from_env() -> bool:
    """Call jax.distributed.initialize from the launcher's env contract.

    Returns True if multi-process mode was initialized, False for the
    single-process case (no env set). Safe to call twice.
    """
    coord = os.environ.get(ENV_COORD)
    if not coord:
        return False
    if jax.distributed.is_initialized():
        return True
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ[ENV_NPROC]),
        process_id=int(os.environ[ENV_PID]),
    )
    return True


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def globalize_batch(mesh: Mesh, x_local: np.ndarray) -> jax.Array:
    """Host-local batch shard -> global jax.Array sharded over 'data'.

    Single-process this is a plain device_put; multi-process it assembles the
    global array from per-process local data (each host contributes the rows
    its mesh devices own).
    """
    sharding = NamedSharding(mesh, P("data"))
    if jax.process_count() == 1:
        return jax.device_put(x_local, sharding)
    return jax.make_array_from_process_local_data(sharding, x_local)


def globalize_replicated(mesh: Mesh, value: np.ndarray,
                         spec: Optional[P] = None) -> jax.Array:
    """Small host-identical array (e.g. the participation mask) -> global
    array with the given spec (default: sharded over 'data'). Every host must
    pass the same value."""
    spec = P("data") if spec is None else spec
    sharding = NamedSharding(mesh, spec)
    value = np.asarray(value)
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    return jax.make_array_from_callback(value.shape, sharding,
                                        lambda idx: value[idx])


def all_replicated(mesh: Mesh, tree: Any) -> Any:
    """Fetch a (possibly sharded) pytree of GLOBAL arrays to every host as
    host-local numpy in the logical (full) shapes — the collective gather
    behind LM checkpointing/eval when tp/pp/ep shard state across hosts.

    Per leaf: fully-replicated arrays are read from a local shard (no
    collective); sharded arrays are assembled with ``process_allgather
    (tiled=True)`` — the only mode that accepts global non-fully-
    addressable arrays (tiled=False raises; caught by the 2-process LM
    test). ALL hosts must call this (the sharded case is collective)."""
    if jax.process_count() == 1:
        return jax.device_get(tree)
    from jax.experimental import multihost_utils

    def fetch(x):
        if not isinstance(x, jax.Array):
            return x
        if x.is_fully_replicated:
            return np.asarray(x.addressable_data(0))
        if x.is_fully_addressable:
            # A host-LOCAL sharded array here would silently gather to
            # [nproc*d0, ...] (process_allgather's fully-addressable branch
            # concatenates per-process copies) — corrupt, not an error.
            raise ValueError(
                "all_replicated expects GLOBAL arrays placed on the shared "
                f"mesh; got a host-local sharded array {x.shape}")
        return multihost_utils.process_allgather(x, tiled=True)

    return jax.tree.map(fetch, tree)
