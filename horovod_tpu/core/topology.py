"""World topology: rank / size / local_rank / local_size / cross ranks.

The reference derives these from MPI communicators: ``MPI_COMM_WORLD`` rank
and size, a shared-memory split for the node-local communicator, and a
local-rank split for the cross-node communicator
(``horovod/common/operations.cc:1728-1797``). There is no MPI in this build;
the world is discovered from, in priority order:

1. Launcher environment (``HOROVOD_RANK``/``HOROVOD_SIZE``/...), set by
   ``horovodrun``/``horovod_tpu.runner`` — the analog of
   ``OMPI_COMM_WORLD_RANK`` et al. that mpirun exports.
2. The JAX multi-process runtime (``jax.process_index()``/``process_count()``)
   on a real TPU pod, where one process per host is the natural deployment.
3. Single-process default: rank 0 of a world of size 1 (the reference's
   "single-process MPI self-world" test fixture, SURVEY §4).

A rank is a *process*, exactly as in the reference (one process per
accelerator there; one process per TPU host here, owning
``jax.local_device_count()`` chips). ``num_devices()`` reports the total
data-parallel device count across the world, which is what examples use for
learning-rate scaling.
"""

from __future__ import annotations

import os
import socket
from dataclasses import dataclass
from typing import Optional

from . import config as _config


@dataclass(frozen=True)
class Topology:
    rank: int
    size: int
    local_rank: int
    local_size: int
    cross_rank: int
    cross_size: int
    # Number of accelerator devices owned by this process / by the world.
    local_device_count: int
    global_device_count: int
    hostname: str
    # Launcher-world coordinates. For a full world these equal rank/size;
    # for a subset world (``hvd.init(ranks=[...])``, reference
    # ``operations.cc:1728-1742`` MPI_Group_incl) rank/size describe the
    # subset communicator while world_rank/world_size keep the launcher
    # coordinates — world_rank 0 always hosts the controller service, since
    # that is the address the launcher advertised to every process.
    world_rank: int = -1
    world_size: int = -1
    # False for a process outside the subset: it gets a self-world of size
    # 1 (collectives work locally, nothing deadlocks) instead of the
    # reference's ill-defined MPI_COMM_WORLD fallback.
    is_member: bool = True
    # The subset composition (launcher ranks, in communicator order) for
    # ``init(ranks=[...])`` worlds; None for the full world. Defines the
    # world identity the controller protocol uses to keep co-scheduled
    # worlds on one port from cross-registering (core.status.WORLD_MISMATCH).
    members: Optional[tuple] = None

    def __post_init__(self):
        if self.world_rank < 0:
            object.__setattr__(self, "world_rank", self.rank)
            object.__setattr__(self, "world_size", self.size)

    @property
    def in_subset_world(self) -> bool:
        # A permuted full-size list (ranks=[1,0]) is also a subset world:
        # subset ranks no longer align with JAX process indices, so the
        # device plane (which assumes that alignment) must not be used.
        return (self.world_size != self.size or not self.is_member
                or self.rank != self.world_rank)

    @property
    def is_homogeneous(self) -> bool:
        """Reference: allgather of local sizes → is_homogeneous
        (``operations.cc:1760-1780``). Our worlds are homogeneous by
        construction (launcher enforces a uniform per-host process count);
        heterogeneous TPU slices are not a supported deployment."""
        return True


def _jax_counts():
    # Deferred import: topology must be resolvable before JAX spins up
    # (the launcher computes ranks without touching devices).
    import jax

    return (
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
    )


def discover(use_jax: bool = True, subset=None) -> Topology:
    """Resolve the world, preferring launcher env over the JAX runtime.

    ``subset`` is the rank list of ``hvd.init(ranks=[...])``: the subset
    forms the active communicator in list order (the reference's
    MPI_Group_incl semantics, ``operations.cc:1728-1742``); every launcher
    process must call init with the same list. Processes outside the list
    become self-worlds of size 1. Host-local splits (local_rank/size) keep
    their launcher values — the subset does not move processes between
    hosts (documented delta: the reference re-splits the subset comm by
    shared memory)."""
    full = _discover_full(use_jax=use_jax)
    if subset is None:
        return full
    subset = list(subset)
    if sorted(set(subset)) != sorted(subset) or not subset or \
            not all(isinstance(r, int) and 0 <= r < full.world_size
                    for r in subset):
        raise ValueError(
            f"init(ranks=...) must be a list of distinct ranks within "
            f"[0, {full.world_size}), got {subset!r}")
    if full.rank not in subset:
        return Topology(
            rank=0, size=1, local_rank=0, local_size=1, cross_rank=0,
            cross_size=1, local_device_count=full.local_device_count,
            global_device_count=full.local_device_count,
            hostname=full.hostname, world_rank=full.rank,
            world_size=full.size, is_member=False,
            members=tuple(subset))
    index = subset.index(full.rank)
    return Topology(
        rank=index, size=len(subset), local_rank=full.local_rank,
        local_size=full.local_size, cross_rank=full.cross_rank,
        cross_size=full.cross_size,
        local_device_count=full.local_device_count,
        global_device_count=full.local_device_count * len(subset),
        hostname=full.hostname, world_rank=full.rank,
        world_size=full.size, is_member=True, members=tuple(subset))


def _discover_full(use_jax: bool = True) -> Topology:
    env = os.environ
    hostname = socket.gethostname()
    if _config.HOROVOD_RANK in env and _config.HOROVOD_SIZE in env:
        rank = int(env[_config.HOROVOD_RANK])
        size = int(env[_config.HOROVOD_SIZE])
        local_rank = int(env.get(_config.HOROVOD_LOCAL_RANK, 0))
        local_size = int(env.get(_config.HOROVOD_LOCAL_SIZE, 1))
        cross_rank = int(env.get(_config.HOROVOD_CROSS_RANK, rank // max(local_size, 1)))
        cross_size = int(env.get(_config.HOROVOD_CROSS_SIZE, size // max(local_size, 1)))
        if use_jax and env.get(_config.HOROVOD_DATA_PLANE) != "host":
            import jax

            local_devices = jax.local_device_count()
        else:
            # Host-plane worlds (numpy-over-TCP; the torch/TF front-ends'
            # CPU deployment) never touch accelerators: one rank == one
            # device, and querying JAX here would needlessly initialize a
            # backend the job will not use.
            local_devices = 1
        return Topology(
            rank=rank,
            size=size,
            local_rank=local_rank,
            local_size=local_size,
            cross_rank=cross_rank,
            cross_size=cross_size,
            local_device_count=local_devices,
            global_device_count=local_devices * size,
            hostname=hostname,
        )
    if use_jax:
        pidx, pcount, local_devices, global_devices = _jax_counts()
        return Topology(
            rank=pidx,
            size=pcount,
            local_rank=0,
            local_size=1,
            cross_rank=pidx,
            cross_size=pcount,
            local_device_count=local_devices,
            global_device_count=global_devices,
            hostname=hostname,
        )
    return Topology(
        rank=0, size=1, local_rank=0, local_size=1, cross_rank=0,
        cross_size=1, local_device_count=1, global_device_count=1,
        hostname=hostname,
    )
