"""Process-level basics: init / shutdown / rank / size / local ranks.

Rebuild of ``HorovodBasics`` (``horovod/common/__init__.py:51-154``) and the
``extern "C"`` entry points it wraps (``horovod/common/operations.cc:2413-2468``).
Differences, by design (SURVEY §2.10):

* No MPI. The world comes from the launcher env or the JAX runtime
  (see ``core.topology``). ``init()`` therefore does not spawn a
  communication thread for the synchronous API — SPMD jit programs need no
  negotiation. The background controller for the *eager/async* named-tensor
  API is started lazily on first use (``ops.engine``).
* ``init(ranks=[...])`` (or ``comm=`` given as a rank list) forms a subset
  communicator over the launcher world in list order, matching the
  reference's ``MPI_Group_incl`` semantics; an mpi4py communicator object
  is rejected — there is no MPI in this build.
* ``mpi_threads_supported()`` exists for API parity and always returns False
  (there is no MPI to share with user code).
"""

from __future__ import annotations

import atexit
import threading
from typing import Optional

from .core import Config, LOG, NotInitializedError, Topology, discover


class _GlobalState:
    """Python analog of ``HorovodGlobalState`` (``operations.cc:115-249``).

    Holds everything that must be torn down on ``shutdown()``. Unlike the
    reference there is no background MPI thread to join for the sync path;
    the async engine registers its own shutdown hook here.
    """

    def __init__(self) -> None:
        self.lock = threading.RLock()
        self.initialized = False
        self.topology: Optional[Topology] = None
        self.config: Optional[Config] = None
        # Set by ops.engine when the eager controller starts; called on
        # shutdown (analog of joining BackgroundThreadLoop,
        # operations.cc:2425-2431).
        self.engine_shutdown_hooks = []


_global = _GlobalState()


def _state() -> _GlobalState:
    return _global


def init(ranks=None, comm=None) -> None:
    """Initialize the world. Idempotent, like ``InitializeHorovodOnce``
    (``operations.cc:2384-2399``): a second call while initialized is a
    no-op; after ``shutdown()`` re-initialization is allowed.

    ``ranks`` (or ``comm`` given as a rank list — the reference accepts
    both spellings, ``common/__init__.py:58-84``) forms a subset world:
    the listed launcher ranks become the active communicator in list
    order; every launcher process must call init with the same list.
    Processes outside the list get a self-world of size 1. An mpi4py
    communicator object is rejected — there is no MPI in this build."""
    with _global.lock:
        if _global.initialized:
            return
        if comm is not None and isinstance(comm, (list, tuple)):
            if ranks:
                raise ValueError("pass ranks either as ranks= or comm=, "
                                 "not both")
            ranks = list(comm)
            comm = None
        if ranks is not None and len(list(ranks)) == 0:
            raise ValueError(
                "init(ranks=[]) is an empty communicator; pass None (or "
                "omit) for the full world.")
        if comm is not None:
            raise ValueError(
                "horovod_tpu.init(comm=<mpi communicator>) requires MPI, "
                "which this build intentionally does not use; pass "
                "ranks=[...] for a subset world.")
        _global.config = Config.from_env()
        _global.topology = discover(subset=list(ranks) if ranks else None)
        _global.initialized = True
        if _global.config.timeline_all_ranks and \
                not _global.config.timeline_path:
            # the all-ranks knob only suffixes the base path; without one
            # there is nothing to record and the operator should hear
            # that rather than find an empty trace dir later
            LOG.warning(
                "HOROVOD_TIMELINE_ALL_RANKS=1 has no effect without "
                "HOROVOD_TIMELINE=<path>; set the base path to record "
                "per-rank traces (docs/tracing.md)")
        # Steps traced before init resolved the hierarchical knob from the
        # env and keep that routing baked in; warn if the pinned config now
        # disagrees (optimizers.check_build_time_resolutions).
        from . import optimizers as _optimizers

        _optimizers.check_build_time_resolutions(_global.config)
        topo = _global.topology
        if _global.config.jax_profile_dir and topo.rank == 0 \
                and topo.is_member:
            # is_member: subset-world NON-members also carry rank 0 (their
            # self-world), and several of them tracing into one directory
            # would collide on the hostname-keyed artifact
            # On-device twin of HOROVOD_TIMELINE (SURVEY §5.1): the host
            # timeline shows enqueue/negotiate/execute; XLA kernel time
            # lives in the profiler trace. Rank 0 only, like the timeline.
            try:
                import jax

                jax.profiler.start_trace(_global.config.jax_profile_dir)

                def _stop_trace() -> None:
                    jax.profiler.stop_trace()

                _global.engine_shutdown_hooks.append(_stop_trace)
            except Exception as exc:  # noqa: BLE001 - tracing is optional
                LOG.warning("HOROVOD_JAX_PROFILE: could not start the JAX "
                            "profiler trace: %s", exc)
        if topo.size > 1:
            # Multi-process worlds start the background engine eagerly, as
            # the reference spawns BackgroundThreadLoop inside init
            # (operations.cc:2394): every rank must participate in control
            # cycles from t0 or the coordinator cannot run negotiation,
            # stall detection, or shutdown for the ranks that did arrive.
            from .ops.engine import get_engine

            get_engine()
        elif ranks and len(ranks) > 1 and not topo.is_member \
                and topo.world_rank == 0:
            # Launcher world-rank 0 hosts the controller service even when
            # outside the subset: the launcher advertised ITS address to
            # every process, so the subset's cycles must rendezvous here.
            # (A single-member subset negotiates locally — no service, and
            # no shutdown cycle to wait for.)
            from .ops.engine import start_subset_service

            start_subset_service(list(ranks))
        epoch = world_epoch()
        # Observability plane (docs/metrics.md): world-identity gauges,
        # plus the opt-in HTTP exposition server on rank 0. Gauges are set
        # on every rank; the server only where the aggregated view lives.
        from .obs.registry import registry as _metrics_registry

        reg = _metrics_registry()
        reg.gauge("horovod_world_size",
                  "World size in processes").set(topo.size)
        reg.gauge("horovod_world_rank",
                  "This process's world rank").set(topo.rank)
        reg.gauge("horovod_elastic_world_epoch",
                  "Elastic world epoch (0 = first launch)").set(epoch)
        # Compile ledger (obs/compiles.py): the one listener behind
        # horovod_compiles_total and hvd.obs.compile_events(). It runs
        # only when JAX compiles; shutdown removes it and keeps its list.
        from .obs import compiles as _compiles

        _compiles.ledger().install()
        _global.engine_shutdown_hooks.append(_compiles.ledger().uninstall)
        if _global.config.metrics_port and topo.rank == 0 \
                and topo.is_member:
            from .obs import exposition as _expo, world_snapshot_provider

            try:
                server = _expo.serve(_global.config.metrics_port,
                                     world_snapshot_provider)
                _global.engine_shutdown_hooks.append(server.close)
                LOG.info("metrics exposition serving on "
                         "http://127.0.0.1:%d/metrics (and /metrics.json)",
                         server.port)
            except OSError as exc:
                # Observability must never take the job down: a taken
                # port degrades to no exposition, loudly.
                LOG.warning("HOROVOD_METRICS_PORT=%d: exposition server "
                            "failed to start (%s); metrics HTTP disabled "
                            "for this run", _global.config.metrics_port,
                            exc)
        if epoch > 0:
            # An elastic relaunch: say so at default verbosity — operators
            # reading a worker log must be able to tell attempt N from a
            # fresh start (the rank numbering may have changed).
            LOG.warning(
                "horovod_tpu initialized on elastic world epoch %d "
                "(relaunched world; ranks renumbered over surviving "
                "slots)", epoch)
        LOG.debug(
            "horovod_tpu initialized: rank=%d size=%d local_rank=%d "
            "local_size=%d devices=%d/%d",
            _global.topology.rank, _global.topology.size,
            _global.topology.local_rank, _global.topology.local_size,
            _global.topology.local_device_count,
            _global.topology.global_device_count)


def shutdown() -> None:
    """Tear down; mirrors ``horovod_shutdown`` (``operations.cc:2424-2431``)
    including the "re-init allowed afterwards" semantics."""
    with _global.lock:
        if not _global.initialized:
            return
        hooks, _global.engine_shutdown_hooks = _global.engine_shutdown_hooks, []
        # LIFO, like atexit: later-registered hooks depend on earlier state
        # (the engine registers after init's profiler hook; the engine must
        # drain and negotiate shutdown while the profiler is still tracing)
        hooks.reverse()
        for hook in hooks:
            try:
                hook()
            except Exception as exc:  # noqa: BLE001 - teardown must not raise
                LOG.warning("engine shutdown hook failed: %s", exc)
        _global.initialized = False
        _global.topology = None
        _global.config = None


atexit.register(shutdown)


def is_initialized() -> bool:
    return _global.initialized


def _topology() -> Topology:
    topo = _global.topology
    if topo is None:
        raise NotInitializedError()
    return topo


def config() -> Config:
    cfg = _global.config
    if cfg is None:
        raise NotInitializedError()
    return cfg


def rank() -> int:
    """World rank of this process (``horovod_rank``, ``operations.cc:2437``)."""
    return _topology().rank


def size() -> int:
    """World size in processes (``horovod_size``, ``operations.cc:2453``)."""
    return _topology().size


def local_rank() -> int:
    """Rank within this host (``horovod_local_rank``, ``operations.cc:2445``)."""
    return _topology().local_rank


def local_size() -> int:
    """Processes on this host (``horovod_local_size``, ``operations.cc:2461``)."""
    return _topology().local_size


def cross_rank() -> int:
    """Host index (split by local_rank in the reference,
    ``operations.cc:1781-1797``)."""
    return _topology().cross_rank


def cross_size() -> int:
    return _topology().cross_size


def local_device_count() -> int:
    """TPU chips owned by this process. No reference analog (there, one
    process drives exactly one GPU); on TPU a process drives a host's worth
    of chips and the SPMD data plane spans them."""
    return _topology().local_device_count


def num_devices() -> int:
    """Total data-parallel devices in the world = size() x chips/process.

    This is the factor examples use for linear LR scaling (the reference
    scales by ``hvd.size()`` because size == accelerator count there)."""
    return _topology().global_device_count


def mpi_threads_supported() -> bool:
    """API parity with ``horovod_mpi_threads_supported``
    (``operations.cc:2466``); always False — no MPI in this build."""
    if not _global.initialized:
        raise NotInitializedError()
    return False


def world_epoch() -> int:
    """Elastic world epoch: 0 for a first launch, bumped by
    ``runner.run_elastic`` on every relaunch (``HOROVOD_ELASTIC_EPOCH``).
    Readable before ``init()`` — the launcher env defines it, not the
    topology."""
    import os

    from .core import config as _config

    return int(os.environ.get(_config.HOROVOD_ELASTIC_EPOCH, "0"))
