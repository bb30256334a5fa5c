"""Eager collective engine: named-tensor async submission + cycle loop.

Rebuild of the worker half of ``horovod/common/operations.cc``: the
submission queue + tensor table of ``EnqueueTensorAllreduce/Allgather/
Broadcast`` (``operations.cc:2472-2591``), the background cycle loop
``RunLoopOnce`` (``:2030-2380``), op execution ``PerformOperation``
(``:768-1621``), and the torch-style handle manager
(``torch/handle_manager.{h,cc}``). Differences by design:

* Tensors are host numpy arrays OR device-resident ``jax.Array``s; device
  submissions fuse and reduce through on-chip programs (zero host
  transfers) and convert lazily only when a host wire needs bytes. The
  bulk-performance path on TPU remains the SPMD ``DistributedOptimizer``/
  jit route where XLA owns the collectives and none of this machinery runs
  (SURVEY §7 design stance).
* The multi-process data plane is the controller's host exchange (numpy over
  the authenticated TCP wire) — the CPU-world stand-in for MPI. On-device
  eager collectives across processes ride the same negotiated order; the
  identical ResponseList on every rank is what makes issuing the same XLA
  program legal (SURVEY §7 "hard parts").
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional

import numpy as np

from .. import basics
from ..analysis.witness import maybe_wrap as _witness_wrap
from ..core import config as _config
from ..core.logging import LOG
from ..core.status import SHUT_DOWN_ERROR, Status
from ..obs import TimelineBridge, flightrec as _flightrec, \
    registry as _obs_registry
from ..runner.network import default_secret
from ..utils.timeline import TRACE_META, Timeline, rank_timeline_path
from .autotuner import Autotuner
from .controller import (
    ControllerClient,
    ControllerService,
    make_negotiator,
)
from .messages import (
    OP_NAMES as _OP_NAMES,
    DataType,
    Request,
    RequestList,
    RequestType,
    Response,
    ResponseList,
    ResponseType,
    dtype_of,
)


def _is_sparse_codec(codec: str) -> bool:
    """Whether a negotiated codec tag names the top-k sparse wire
    (docs/compression.md §sparse) — the routing fork shared by the
    plain and apply-fused allreduce paths."""
    if codec == "none":
        return False
    from .compression import Compression

    return bool(getattr(Compression.lookup(codec), "sparse", False))

# Observability plane (docs/tracing.md): time spent turning negotiated
# responses into results — the "execute" half of the straggler report's
# negotiation-wait vs execute breakdown. Device-plane batches are
# asynchronous dispatches, so this measures dispatch + host-path data
# movement; device completion time lives in the JAX profiler.
_EXECUTE_SECONDS = _obs_registry().histogram(
    "horovod_execute_seconds",
    "Per-response execution time on the engine loop (dispatch + "
    "host-path data movement; device completion is asynchronous)")

# Generation-ordered sub-buffer flush (docs/tensor-fusion.md): the
# compute/collective overlap the pipeline actually ACHIEVED, measured —
# seconds the loop thread spent negotiating cycle k+1 while cycle k's
# flush was executing on the flush worker. The in-flight gauges make the
# ">= 2 cycles in flight" claim falsifiable.
_OVERLAP_SECONDS = _obs_registry().counter(
    "horovod_overlap_seconds_total",
    "Seconds of negotiation overlapped with an in-flight sub-buffer "
    "flush (the measured compute/collective overlap)")
_FLUSH_INFLIGHT = _obs_registry().gauge(
    "horovod_flush_inflight",
    "Sub-buffer flushes currently in flight (negotiated, not yet "
    "executed to completion)")
_FLUSH_INFLIGHT_PEAK = _obs_registry().gauge(
    "horovod_flush_inflight_peak",
    "Peak in-flight sub-buffer flush depth observed by this engine")
_SUBBUFFER_FLUSHES = _obs_registry().counter(
    "horovod_subbuffer_flushes_total",
    "Sub-buffer flushes dispatched through the overlap pipeline")

# Fused reduce+apply plane (docs/tensor-fusion.md §fused apply): batches
# that landed applied parameters, by execution strategy — "fused" is the
# single reduce+apply program, "split" the reduce-then-apply degrade
# (native controller wire, mixed batches, or the tuned knob) — plus the
# optimizer-apply dispatch count behind the dispatches-per-step story
# (fused: one per batch; split: one per leaf).
_REDUCE_APPLY_BATCHES = _obs_registry().counter(
    "horovod_reduce_apply_batches_total",
    "Allreduce batches that landed applied parameters from the engine",
    labels=("mode",))
_APPLY_DISPATCHES = _obs_registry().counter(
    "horovod_apply_dispatches_total",
    "Optimizer-apply program dispatches (standalone per-leaf programs "
    "on the two-dispatch/split routes; one combined program per batch "
    "when fused into the reduce)")


def cut_generations(entries: List["TensorTableEntry"],
                    n: int) -> List[List["TensorTableEntry"]]:
    """Cut one cycle tick's drained submissions into up to ``n``
    generation-ordered sub-buffers (docs/tensor-fusion.md).

    Chunks are CONTIGUOUS in arrival order — backprop produces gradients
    last-layer-first, so the earliest arrivals form the first sub-buffer
    and flush while later generations are still being produced (the
    T3-style overlap, arXiv 2401.16677). Boundaries fall where the
    cumulative payload crosses ``total * k / n`` so sub-buffers carry
    roughly equal bytes; every chunk is non-empty and the concatenation
    of the chunks is exactly the input (no reordering — the negotiated
    execution order stays the arrival order, which keeps sentry
    ordinals, consensus windows, and cache positions aligned)."""
    if not entries:
        return []
    n = max(1, min(int(n), len(entries)))
    if n == 1:
        return [list(entries)]
    sizes = [max(int(getattr(e.array, "nbytes", 0) or 0), 1)
             for e in entries]
    total = sum(sizes)
    out: List[List[TensorTableEntry]] = []
    cur: List[TensorTableEntry] = []
    acc = 0
    for i, (entry, size) in enumerate(zip(entries, sizes)):
        cur.append(entry)
        acc += size
        remaining_entries = len(entries) - i - 1
        remaining_chunks = n - len(out) - 1
        if remaining_chunks and (
                acc * n >= total * (len(out) + 1)
                or remaining_entries == remaining_chunks):
            out.append(cur)
            cur = []
    if cur:
        out.append(cur)
    return out


class _FlushClock:
    """Worker-busy accounting for the overlap measurement: the flush
    worker brackets every flush with ``mark_start``/``mark_end``, and the
    loop thread reads ``busy_seconds()`` before/after a negotiation — the
    delta is EXACTLY the worker-busy time inside that window (the single
    worker thread makes busy intervals disjoint), i.e. the achieved
    negotiate-while-flushing overlap."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._busy_since: Optional[float] = None
        self._busy_total = 0.0

    def mark_start(self) -> None:
        with self._lock:
            self._busy_since = time.monotonic()

    def mark_end(self) -> None:
        with self._lock:
            if self._busy_since is not None:
                self._busy_total += time.monotonic() - self._busy_since
                self._busy_since = None

    def busy_seconds(self) -> float:
        with self._lock:
            total = self._busy_total
            if self._busy_since is not None:
                total += time.monotonic() - self._busy_since
            return total


@dataclass
class ApplyContext:
    """Fused reduce+apply submission context (docs/tensor-fusion.md
    §fused apply): everything the engine needs to land this gradient's
    APPLIED parameter instead of the reduced gradient — the baked-in
    update rule, the current parameter and optimizer-slot leaves (the
    caller keeps them alive until ``apply_synchronize`` returns), and
    the already-incremented step count (Adam bias correction)."""

    rule: Any  # fused_apply.ApplyRule
    param: Any  # np.ndarray | jax.Array
    slots: tuple  # rule.nslots leaves, same shape as param — or, when
    # ``zero1`` is set, this rank's 1-D shard of each slot
    count: int
    average: bool = True
    # ZeRO-1 submission (docs/sharding.md): slots are this rank's shard
    # rows and the batch must run the reduce-scatter → shard-apply →
    # all-gather program. Rank-local routing state — it never rides the
    # wire (the negotiated fingerprint + the init-pinned exec flag keep
    # the fused/zero1 decision rank-identical), so no registry row.
    zero1: bool = False


class ApplyResult:
    """What an apply-capable response lands in the handle table: the
    applied parameter and the fresh optimizer slots (never the reduced
    gradient). Carries ``shape`` so the timeline's end-record contract
    for results holds unchanged."""

    __slots__ = ("param", "slots")

    def __init__(self, param, slots: tuple) -> None:
        self.param = param
        self.slots = tuple(slots)

    @property
    def shape(self):
        return self.param.shape


@dataclass
class TensorTableEntry:
    """In-flight named tensor (``common.h:77-98`` TensorTableEntry).

    ``array`` is a host numpy array OR a device-resident ``jax.Array`` —
    the TPU-native analog of the reference's device tensors staying on-GPU
    through the NCCL plane: jax submissions are fused/reduced by on-chip
    programs and only hit the host when a host wire needs the bytes."""

    name: str
    op: RequestType
    array: Any  # np.ndarray | jax.Array, per the docstring contract
    handle: int
    root_rank: int = -1
    codec: str = "none"  # negotiated wire-compression tag (messages.Request)
    # fused reduce+apply context, None for a plain collective
    apply: Optional[ApplyContext] = None


def _is_jax_array(a) -> bool:
    if isinstance(a, np.ndarray):
        return False
    try:
        import jax
    except Exception:  # noqa: BLE001 - no jax in this process
        return False
    return isinstance(a, jax.Array)


def _jax_multiprocess() -> bool:
    try:
        import jax

        return jax.process_count() > 1
    except Exception:  # noqa: BLE001 - no jax runtime yet
        return False


def _adopt_controller_fd(use_native: bool) -> Optional[int]:
    """Claim the launcher-inherited controller listener, if any.

    The launcher binds the controller socket itself and rank 0 inherits
    it (launcher._free_port TOCTOU fix) — consume the env marker so a
    re-init on the same process (``shutdown(); init()``) binds the port
    normally instead of adopting an fd the first service already closed.
    The native (C++) service binds its own socket, so there the inherited
    fd is closed to free the port for it — the backlogged early
    connections reset and the clients' connect retries re-dial."""
    fd_env = os.environ.pop(_config.HOROVOD_CONTROLLER_FD, None)
    if not fd_env:
        return None
    fd = int(fd_env)
    if use_native:
        try:
            os.close(fd)
        except OSError:
            pass
        return None
    return fd


# Handle ids are unique across engine generations (an engine can be torn
# down by shutdown and a fresh one started by re-init); ids must never
# collide in the API layer's handle→context map.
_handle_counter = itertools.count()


class HandleManager:
    """Async handles: allocate / mark done / poll / wait
    (``torch/handle_manager.cc:22-52``). Results carry the numpy output so
    ``synchronize`` can hand it back to the framework layer. Completed
    results remain readable after the engine stops — only never-completed
    entries get flushed with SHUT_DOWN_ERROR.

    Eviction contract: past ``MAX_RETAINED`` completed-but-unclaimed
    results, the oldest lose their PAYLOAD (the numpy array — the part
    that matters for memory) but keep a tombstone, so a late
    ``poll``/``wait`` gets a self-explanatory eviction error rather than
    ``unknown handle``. Tombstones are only dropped entirely past
    ``MAX_TOMBSTONES`` — at that point the caller abandoned >1M handles
    and ``unknown handle`` is accurate."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._done: Dict[int, threading.Event] = {}
        self._results: Dict[int, tuple] = {}
        self._evicted: Dict[int, None] = {}  # insertion-ordered set

    def allocate(self) -> int:
        with self._lock:
            handle = next(_handle_counter)
            self._done[handle] = threading.Event()
            return handle

    # Abandoned handles (fired-and-forgotten async ops) must not grow the
    # result table without bound in week-long jobs; evict oldest completed
    # payloads past this many outstanding results, oldest tombstones past
    # MAX_TOMBSTONES. Tombstoned handles share one pre-set Event (they are
    # all completed by construction) so a tombstone costs two dict slots,
    # not a live Event.
    MAX_RETAINED = 1 << 16
    MAX_TOMBSTONES = 1 << 18
    _TOMBSTONE_EVENT = threading.Event()
    _TOMBSTONE_EVENT.set()

    def mark_done(self, handle: int, status: Status,
                  result: Optional[np.ndarray]) -> None:
        with self._lock:
            self._results[handle] = (status, result)
            self._done[handle].set()
            while len(self._results) > self.MAX_RETAINED:
                oldest = next(iter(self._results))
                del self._results[oldest]
                self._evicted[oldest] = None
                self._done[oldest] = self._TOMBSTONE_EVENT
            while len(self._evicted) > self.MAX_TOMBSTONES:
                stale = next(iter(self._evicted))
                del self._evicted[stale]
                self._done.pop(stale, None)

    def poll(self, handle: int) -> bool:
        with self._lock:
            event = self._done.get(handle)
        if event is None:
            raise ValueError(f"unknown handle {handle}")
        return event.is_set()

    def wait(self, handle: int, timeout: Optional[float] = None):
        with self._lock:
            event = self._done.get(handle)
        if event is None:
            raise ValueError(f"unknown handle {handle}")
        if not event.wait(timeout):
            raise TimeoutError(f"collective handle {handle} did not complete")
        with self._lock:
            if handle in self._evicted:
                del self._evicted[handle]
                self._done.pop(handle, None)
                raise ValueError(
                    f"handle {handle}: result evicted — it completed but "
                    f"went unclaimed while > {self.MAX_RETAINED} newer "
                    f"results piled up; synchronize() or release() handles "
                    f"promptly")
            status, result = self._results.pop(handle)
            del self._done[handle]
        status.raise_if_error()
        return result


class _DevicePlaneWorker:
    """Sacrificial executor for device-plane collectives.

    A compiled XLA collective blocks until every participant issues it;
    Python cannot interrupt that execution. If a peer dies mid-collective
    the survivors would hang until the transport's own (long or absent)
    timeout — so the engine runs device-plane calls on this daemon thread
    and waits abortably: when the controller pushes a world abort (watch
    channel), the engine abandons the call and surfaces SHUT_DOWN_ERROR
    (reference semantics, ``operations.cc:1942-1957``). The abandoned
    thread may stay blocked in the dead collective; that is fine — the
    world is over and the process is about to exit, exactly like the
    reference's ranks after a NCCL comm abort.

    Single worker thread: collectives keep the engine's launch order."""

    def __init__(self) -> None:
        import queue

        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._thread = threading.Thread(
            target=self._run, name="horovod-device-plane", daemon=True)
        self._thread.start()

    def _run(self) -> None:
        while True:
            fn, args, fut = self._q.get()
            if fn is None:
                return
            if not fut.set_running_or_notify_cancel():
                continue
            try:
                fut.set_result(fn(*args))
            except BaseException as exc:  # noqa: BLE001 - ship to waiter
                fut.set_exception(exc)

    def submit(self, fn, *args):
        from concurrent.futures import Future

        fut = Future()
        self._q.put((fn, args, fut))
        return fut

    def stop(self, join_timeout_s: float = 0.0) -> None:
        """Queue the shutdown sentinel; with ``join_timeout_s`` > 0 also
        wait (bounded) for the thread to exit. Joining matters when the
        worker has RUN compiled XLA programs: a daemon thread frozen
        mid-C++ at interpreter finalization can leave jaxlib destructors
        facing a live thread ("terminate called without an active
        exception" aborts at exit). A worker parked in a dead collective
        never consumes the sentinel — the bounded join keeps teardown
        hang-free and the daemon flag keeps the abandonment safe."""
        self._q.put((None, None, None))
        if join_timeout_s > 0:
            self._thread.join(timeout=join_timeout_s)


class Engine:
    """One per process; owns the background cycle thread."""

    def __init__(self) -> None:
        topo = basics._topology()
        cfg = basics.config()
        self._rank = topo.rank
        self._size = topo.size
        self._cfg = cfg
        # lock witness (docs/analysis.md): under HOROVOD_LOCK_WITNESS=1
        # the engine lock joins the global held-before graph so tests
        # catch cross-module inversions the AST pass cannot see
        self._lock = _witness_wrap(threading.Lock(),
                                   "ops.engine.Engine._lock")
        self._submissions: List[TensorTableEntry] = []
        self._pending: Dict[str, TensorTableEntry] = {}
        self.handles = HandleManager()
        self._stop_requested = False
        self._stopped = threading.Event()
        self._wake = threading.Event()

        # Plain HOROVOD_TIMELINE stays rank-0-only (the reference
        # artifact, back-compat); HOROVOD_TIMELINE_ALL_RANKS=1 records on
        # EVERY member rank into rank-suffixed files that
        # tools/trace_merge.py folds into one clock-corrected world trace
        # (docs/tracing.md). Members only either way: subset-world
        # NON-members also carry rank 0 (their self-world) and would
        # clobber the member artifact.
        timeline_path = ""
        if cfg.timeline_path and topo.is_member:
            if cfg.timeline_all_ranks:
                timeline_path = rank_timeline_path(cfg.timeline_path,
                                                   topo.rank)
            elif topo.rank == 0:
                timeline_path = cfg.timeline_path
        self.timeline = Timeline(timeline_path, cfg.timeline_mark_cycles)
        if self.timeline.enabled:
            # identity record first: trace_merge must know whose lane
            # this file is even if the job dies before any span closes
            self.timeline.meta(TRACE_META, {
                "rank": topo.rank, "size": topo.size,
                "epoch": basics.world_epoch()})
        # Per-cycle span stamps (cycle ordinal + cache generation): set
        # each tick by _cycle_span_args, attached to NEGOTIATE end /
        # EXECUTE begin records so spans correlate across per-rank trace
        # files without a shared clock (docs/tracing.md).
        self._span_args: Optional[dict] = None
        self._local_cycle_no = 0
        # Observability plane (docs/metrics.md): registry deltas ride the
        # timeline as Chrome counter tracks (no-op when the timeline is
        # off); the publisher below feeds cross-rank aggregation.
        self._metrics_bridge = TimelineBridge(_obs_registry(), self.timeline)
        self._metrics_stop: Optional[threading.Event] = None
        self._metrics_thread: Optional[threading.Thread] = None
        self._metrics_interval_s = cfg.metrics_interval_s
        # Closed-loop tuning plane (docs/autotune.md): the last
        # extended-knob map this rank applied from a cycle response — the
        # change detector behind the timeline AUTOTUNE audit records.
        self._applied_knobs: dict = {}
        self._clock_sync = None

        self._service: Optional[ControllerService] = None
        # Hierarchical negotiation tree (docs/hierarchy.md): island heads
        # additionally host their sub-coordinator beside (not instead of)
        # anything else they run — rank 0 hosts BOTH the root service and
        # island 0's head. The planned successor hosts a STANDBY twin
        # (docs/recovery.md) that serves members only after they fail
        # over to it.
        self._subcoord = None
        self._standby_subcoord = None
        self._client: Optional[ControllerClient] = None
        self._negotiator = None
        self._native_controller = False  # set with use_native below
        self._autotuner: Optional[Autotuner] = None
        # The autotuner lives with the controller service — launcher
        # world-rank 0 (when a member; a non-member service host builds its
        # own in start_subset_service, and this engine's size-1 self-world
        # must not grow an orphan tuner beside it). The extended knob set
        # (cache capacity / codec / metrics interval) needs the Python
        # controller wire to apply; size-1 and native-controller worlds
        # tune the classic (fusion, cycle) pair only (docs/autotune.md).
        if cfg.autotune and topo.world_rank == 0 and topo.is_member:
            extended = False
            if self._size > 1:
                from .native_controller import native_controller_enabled

                extended = not native_controller_enabled(cfg)
            self._autotuner = Autotuner(cfg, extended=extended)
        self._plane = None
        if self._size == 1:
            self._negotiator = make_negotiator(1, cfg)
            if cfg.data_plane == "xla" and not _jax_multiprocess() \
                    and not topo.in_subset_world:
                # Explicit HOROVOD_DATA_PLANE=xla in a world of one: still
                # build the device plane so host tensors ride H2D → compiled
                # reduce on the accelerator → D2H. This is how the eager
                # front-ends (torch hooks → engine → XLA plane) get a
                # measured single-chip number; "auto" keeps the pure-host
                # short-circuit. Guarded like the size>1 branch: a size-1
                # self-world inside a multi-process JAX world (subset
                # non-member, or HOROVOD_DATA_PLANE=xla exported
                # pod-wide) must not touch the global device mesh —
                # XlaDataPlane requires one JAX process per rank.
                from .xla_plane import XlaDataPlane

                self._plane = XlaDataPlane(topo)
            elif cfg.data_plane == "xla":
                LOG.warning(
                    "HOROVOD_DATA_PLANE=xla ignored for this size-1 world: "
                    "the device plane spans all JAX processes, and this "
                    "world does not own them (multi-process JAX world or "
                    "subset non-member). Collectives short-circuit on "
                    "host.")
        else:
            if topo.in_subset_world:
                # The device plane spans the FULL jax process world; a
                # subset communicator must not issue collectives over it
                # (non-members would never participate). Host exchange only.
                if cfg.data_plane == "xla":
                    LOG.warning(
                        "subset world (init(ranks=...)): forcing the host "
                        "data plane — XLA collectives span the full device "
                        "mesh, not a rank subset.")
            elif cfg.data_plane == "xla" or (
                    cfg.data_plane == "auto" and _jax_multiprocess()):
                # The reference's NCCL/MPI split: the TCP controller below
                # stays the control plane; bytes move as compiled XLA
                # collectives over the global device mesh (ICI/DCN on pods,
                # gloo on CPU test worlds).
                from .xla_plane import XlaDataPlane

                self._plane = XlaDataPlane(topo)
            if topo.world_rank == 0:
                # at default verbosity: which plane carries the bytes is
                # the first thing to know about a multi-rank run, and
                # "auto" decides it from the JAX process world
                LOG.warning(
                    "eager data plane for %d ranks: %s "
                    "(HOROVOD_DATA_PLANE=%s)", self._size,
                    "xla — compiled collectives over the device mesh"
                    if self._plane is not None else
                    "host — numpy buffers over the controller's TCP wire",
                    cfg.data_plane)
            secret = default_secret()
            port = int(os.environ.get(_config.HOROVOD_CONTROLLER_PORT, "0"))
            addr = os.environ.get(_config.HOROVOD_CONTROLLER_ADDR, "127.0.0.1")
            if port == 0 and topo.world_rank != 0:
                raise RuntimeError(
                    "multi-process world but HOROVOD_CONTROLLER_PORT is not "
                    "set; the launcher (horovodrun / horovod_tpu.runner) "
                    "must export the coordinator address to every rank.")
            from .native_controller import (
                NativeControllerClient,
                NativeControllerService,
                native_controller_enabled,
            )

            # Native (C++) vs Python controller: one decision from config +
            # library availability, identical on every rank (the two speak
            # different wires).
            use_native = native_controller_enabled(cfg)
            self._native_controller = use_native
            from .controller import world_id_of

            world_id = world_id_of(topo.members, self._size)
            # Hierarchical negotiation tree (docs/hierarchy.md): resolve
            # the control-plane topology once, identically on every rank
            # (pure arithmetic over size/mode/cross_size — no extra
            # negotiation round). Every degrade below is DETERMINISTIC
            # and warned once — a silently-flat world would fake the
            # scaling the knob asked for, so only known-safe fallbacks
            # stay quiet on non-zero ranks.
            from .hierarchy import FLAT as _FLAT_HIER, plan_topology

            hier = _FLAT_HIER
            if cfg.hierarchy not in ("", "flat"):
                if use_native:
                    if topo.world_rank == 0:
                        LOG.warning(
                            "HOROVOD_HIERARCHY=%s degraded to flat: the "
                            "native C++ controller wire predates the "
                            "island RPCs; set HOROVOD_NATIVE_CONTROLLER=0 "
                            "for the negotiation tree.", cfg.hierarchy)
                elif topo.in_subset_world:
                    if topo.world_rank == 0:
                        LOG.warning(
                            "HOROVOD_HIERARCHY=%s degraded to flat for "
                            "this subset world: islands are planned over "
                            "the full launcher world only.", cfg.hierarchy)
                else:
                    # Head overrides (docs/recovery.md): the elastic
                    # driver's succession verdict after a head death.
                    # Parsed on EVERY rank from the same exported string
                    # so the plan stays rank-identical.
                    from .hierarchy import parse_head_overrides

                    hier = plan_topology(
                        self._size, cfg.hierarchy, topo.cross_size,
                        head_overrides=parse_head_overrides(
                            os.environ.get(
                                _config.HOROVOD_ISLAND_HEADS, "")))
                    if not hier.flat and not os.environ.get(
                            _config.HOROVOD_SUBCOORD_PORT):
                        if topo.world_rank == 0:
                            LOG.warning(
                                "HOROVOD_HIERARCHY=%s degraded to flat: "
                                "the launcher exported no island "
                                "sub-coordinator listener "
                                "(HOROVOD_SUBCOORD_PORT); launch via "
                                "horovod_tpu.runner for the tree.",
                                cfg.hierarchy)
                        hier = _FLAT_HIER
                    elif hier.flat and topo.world_rank == 0:
                        LOG.warning(
                            "HOROVOD_HIERARCHY=%s resolved to a single "
                            "island; keeping the flat star (a 1-island "
                            "tree is the star plus a pointless hop).",
                            cfg.hierarchy)
            if not hier.flat:
                from .hierarchy import HIER_ISLANDS

                HIER_ISLANDS.set(hier.n_islands)
            # Self-healing grace for dropped rank connections: host-
            # plane worlds only, unless the knob was set explicitly.
            # With the XLA data plane a dead peer's in-flight compiled
            # collective cannot be outlived safely — on the gloo CPU
            # backend it can even complete with GARBAGE buffers before
            # a delayed abort lands — so death attribution stays
            # immediate there by default. (Hoisted from the rank-0
            # branch: island heads apply the same window to their own
            # member connections.)
            window_s = cfg.reconnect_window_s if (
                self._plane is None or cfg.reconnect_window_explicit
            ) else 0.0
            if topo.world_rank == 0:
                # Controller duty follows the launcher's advertised address
                # (world rank 0), not the subset rank numbering.
                bind_host = os.environ.get(
                    _config.HOROVOD_CONTROLLER_BIND, "127.0.0.1")
                listen_fd = _adopt_controller_fd(use_native)
                if use_native:
                    if cfg.straggler_evict != "off":
                        LOG.warning(
                            "HOROVOD_STRAGGLER_EVICT=%s ignored: the "
                            "native controller keeps its arrival data in "
                            "C++; set HOROVOD_NATIVE_CONTROLLER=0 for "
                            "straggler mitigation.", cfg.straggler_evict)
                    self._service = NativeControllerService(
                        self._size, cfg, secret=secret, port=port,
                        bind_host=bind_host, autotuner=self._autotuner,
                        world_id=world_id)
                else:
                    negotiator = make_negotiator(self._size, cfg)
                    detector = None
                    if cfg.straggler_evict != "off":
                        # Persistent-straggler mitigation: fed from the
                        # coordinator's arrival attribution; construction
                        # validates the mode loudly (docs/autotune.md).
                        # The native service keeps its arrival data in
                        # C++, so the plane is Python-controller-only.
                        from ..tune.detector import StragglerDetector

                        detector = StragglerDetector.from_config(
                            cfg, self._size)
                    self._service = ControllerService(
                        self._size, negotiator, secret=secret, port=port,
                        bind_host=bind_host, autotuner=self._autotuner,
                        world_id=world_id,
                        stall_shutdown_s=cfg.stall_shutdown_time_s,
                        stall_warning_s=cfg.stall_warning_time_s,
                        listen_fd=listen_fd,
                        cache_capacity=cfg.cache_capacity,
                        fusion_threshold_bytes=cfg.fusion_threshold_bytes,
                        reconnect_window_s=window_s,
                        straggler_detector=detector,
                        codec_min_bytes=cfg.autotune_codec_min_bytes,
                        consensus_interval_steps=(
                            cfg.consensus_interval_steps),
                        islands=hier.islands or None)
                port = self._service.port
            if not hier.flat and hier.is_head(topo.world_rank):
                # This rank heads its island: host the sub-coordinator
                # BEFORE dialing any client — members may dial the head
                # the moment its launcher-bound listener is served, and
                # rank 0 heads island 0 BESIDE the root service it just
                # started (its head dials the freshly-bound root port).
                from .hierarchy import SubCoordinatorService

                sub_fd_env = os.environ.pop(
                    _config.HOROVOD_SUBCOORD_FD, None)
                island = hier.island_of[topo.world_rank]
                root_addrs = [a.strip() for a in addr.split(",")
                              if a.strip()]
                self._subcoord = SubCoordinatorService(
                    island, hier.islands[island],
                    upstream_addr={a: (a, port) for a in root_addrs},
                    secret=secret,
                    port=int(os.environ.get(
                        _config.HOROVOD_SUBCOORD_PORT, "0")),
                    world_id=world_id,
                    listen_fd=int(sub_fd_env) if sub_fd_env else None,
                    reconnect_window_s=window_s,
                    # After a succession the serving head may not be the
                    # lowest member — its upstream hello must carry ITS
                    # rank so the root's head map tracks reality.
                    head_rank=topo.world_rank)
            if not hier.flat and not hier.is_head(topo.world_rank) and (
                    hier.successor_of(hier.island_of[topo.world_rank])
                    == topo.world_rank):
                # Planned standby head (docs/recovery.md): host a dormant
                # twin of the island service on the standby listener the
                # launcher pre-bound. It holds NO upstream channels until
                # the first member request lands — a failover that never
                # happens costs one idle listener and nothing else.
                from .hierarchy import SubCoordinatorService

                standby_fd_env = os.environ.pop(
                    _config.HOROVOD_SUBCOORD_STANDBY_FD, None)
                standby_port_env = os.environ.get(
                    _config.HOROVOD_SUBCOORD_STANDBY_PORT)
                if standby_fd_env or standby_port_env:
                    island = hier.island_of[topo.world_rank]
                    root_addrs = [a.strip() for a in addr.split(",")
                                  if a.strip()]
                    self._standby_subcoord = SubCoordinatorService(
                        island, hier.islands[island],
                        upstream_addr={a: (a, port) for a in root_addrs},
                        secret=secret,
                        port=int(standby_port_env or "0"),
                        world_id=world_id,
                        listen_fd=(int(standby_fd_env)
                                   if standby_fd_env else None),
                        reconnect_window_s=window_s,
                        head_rank=topo.world_rank,
                        standby=True)
            # The launcher may advertise several controller addresses
            # (comma-separated: every NIC of the controller host); the
            # client probes them and uses the first routable one.
            addr_list = [a.strip() for a in addr.split(",") if a.strip()]
            if not addr_list:
                raise RuntimeError(
                    f"HOROVOD_CONTROLLER_ADDR is set but empty ({addr!r}); "
                    f"the launcher must export the controller address.")
            client_cls = (NativeControllerClient if use_native
                          else ControllerClient)
            addr_map = {a: (a, port) for a in addr_list}
            client_fallback = None
            if not hier.flat:
                # Every rank's control-plane connection — cycle/payload/
                # sentry client, metrics publisher, clock sync, flight-
                # recorder push, watch — dials its ISLAND HEAD instead of
                # the root; the head aggregates or relays. This address
                # swap IS the tree from a member's point of view: no
                # other rank-side code has a hierarchy branch, which is
                # what keeps the member wire (and so the negotiated
                # bytes) identical with flat.
                sub_addrs = [s.strip() for s in os.environ.get(
                    _config.HOROVOD_SUBCOORD_ADDR, "127.0.0.1"
                ).split(",") if s.strip()] or ["127.0.0.1"]
                sub_port = (self._subcoord.port
                            if self._subcoord is not None else
                            int(os.environ.get(
                                _config.HOROVOD_SUBCOORD_PORT, "0")))
                addr_map = {a: (a, sub_port) for a in sub_addrs}
                # Head succession (docs/recovery.md): every island rank —
                # the head included, whose own service a headstop drill
                # kills under it — arms the island's planned STANDBY
                # listener as the cycle client's fallback candidate.
                # Tried only once every reconnect round against the
                # primary fails, so a live head never loses a member to
                # it. Cycle/payload/sentry wire only: the metrics
                # publisher, clock sync, and flightrec push channels stay
                # primary-only (their loss is a documented degrade, not a
                # correctness hazard).
                standby_port = (
                    self._standby_subcoord.port
                    if self._standby_subcoord is not None else
                    int(os.environ.get(
                        _config.HOROVOD_SUBCOORD_STANDBY_PORT, "0")
                        or 0))
                if standby_port and standby_port != sub_port:
                    client_fallback = {
                        a: (a, standby_port) for a in sub_addrs}
            self._client = client_cls(
                addr_map, secret=secret,
                timeout_s=None, rank=self._rank, world_id=world_id,
                **({"log_stalls": self._rank == 0,
                    "stall_shutdown_s": cfg.stall_shutdown_time_s,
                    "stall_warning_s": cfg.stall_warning_time_s}
                   if use_native else
                   {"fallback": client_fallback}))
            if not use_native:
                # Metrics publisher (docs/metrics.md): pushes this rank's
                # registry snapshot to the coordinator's store on an
                # interval, over its own ANONYMOUS connection — never the
                # cycle client, whose strict request/response sequencing a
                # metrics push would corrupt. Python controller wire only:
                # the native service's fixed binary protocol predates the
                # metrics RPC (same pattern as the cache-bit and codec
                # fields).
                self._start_metrics_publisher(addr_map, secret, world_id)
            # Clock alignment (docs/tracing.md): offset-to-coordinator
            # estimation where something consumes it; degrades
            # deterministically on the native wire (clock_sync_supported).
            self._maybe_start_clock_sync(addr_map, secret, world_id)
            # Flight recorder (docs/blackbox.md): arm this rank's dump
            # context — on any world abort the event tail ships to the
            # coordinator's incident collector over the anonymous
            # "flightrec" RPC; the native wire predates the RPC and
            # degrades to a rank-local dump (warned once at dump time).
            _flightrec.arm_push(
                addr_map, secret, world_id, self._rank,
                basics.world_epoch(), snapshot_fn=self.state_snapshot,
                local_only=not getattr(self._client,
                                       "flightrec_supported", False))

        self._host_fallback_warned = set()

        # Steady-state negotiation bypass (docs/response-cache.md): the
        # rank-side response cache, mirrored by the coordinator. Python
        # controller wire only — the native controller's fixed binary wire
        # predates the cache-bit field, so it deterministically keeps the
        # full-RequestList cycle on every rank (the same pattern PR 1
        # applies to quantized codecs there). Size-1 worlds negotiate
        # in-process; there is no metadata round trip to bypass.
        self._response_cache = None
        if self._client is not None and cfg.cache_capacity > 0:
            if self._native_controller:
                LOG.debug(
                    "response cache disabled: the native controller wire "
                    "predates the cache-bit field; set "
                    "HOROVOD_NATIVE_CONTROLLER=0 to enable the "
                    "steady-state negotiation bypass.")
            else:
                from .response_cache import ResponseCache

                self._response_cache = ResponseCache(cfg.cache_capacity)
        # The bypass arms only after the coordinator's first full response
        # CONFIRMS it carries a cache (cache_generation is not None): the
        # loop idles from init, and an unconfirmed cache-bit tick against
        # a capacity-0 coordinator (env divergence) would abort the world
        # where this handshake instead degrades deterministically.
        self._cache_confirmed = False

        # Data-plane integrity plane (docs/integrity.md): the gradient
        # sentry screens every reduced allreduce batch; the consensus
        # accumulator digests post-allreduce bytes every
        # HOROVOD_CONSENSUS_INTERVAL_STEPS batches for the coordinator to
        # compare; the data-chaos injector poisons host-side fused
        # buffers deterministically (the plane's verifiable ground
        # truth). All three default off and cost nothing disarmed.
        self._sentry = None
        self._consensus_acc = None
        self._data_chaos = None
        if cfg.grad_sentry != "off":
            from ..integrity.sentry import GradSentry

            exchange = None
            if self._client is not None:
                if getattr(self._client, "sentry_exchange_supported",
                           False):
                    exchange = self._sentry_exchange
                else:
                    LOG.warning(
                        "HOROVOD_GRAD_SENTRY=%s: the native controller "
                        "wire predates the verdict-exchange RPC; sentry "
                        "verdicts are LOCAL-ONLY on this world (a NaN "
                        "still propagates through the sum, so collective "
                        "faults are caught; set "
                        "HOROVOD_NATIVE_CONTROLLER=0 for collective "
                        "verdicts).", cfg.grad_sentry)
            self._sentry = GradSentry(
                cfg.grad_sentry, exchange=exchange,
                on_trip=self._on_sentry_trip,
                # device-resident results screen on-device (two scalars
                # synced, not a full D2H) via the plane's census program
                probe=(self._plane.nonfinite_counts
                       if self._plane is not None else None))
        if cfg.consensus_interval_steps > 0 and self._client is not None:
            if self._native_controller:
                LOG.warning(
                    "HOROVOD_CONSENSUS_INTERVAL_STEPS=%d ignored: the "
                    "native controller wire predates the digest field; "
                    "set HOROVOD_NATIVE_CONTROLLER=0 for cross-rank "
                    "consensus verification.",
                    cfg.consensus_interval_steps)
            else:
                from ..integrity.consensus import DigestAccumulator

                self._consensus_acc = DigestAccumulator(
                    cfg.consensus_interval_steps)
        from ..chaos import injector_from_env

        injector = injector_from_env(self._rank)
        if injector is not None and injector.has_data_rules():
            self._data_chaos = injector

        # Gradient numerics observatory (docs/tensorwatch.md): sampled
        # per-tensor telemetry over reduced allreduce batches — norm²/
        # absmax/nnz/log₂ histogram/top-k mass, plus decode-error SNR
        # for quantized codecs in play or consented. Disabled (interval
        # 0) = no object at all: the hot path pays one `is not None`
        # check and zero allocations (the flightrec bar, pinned by the
        # tracemalloc test). Device-resident batches measure through
        # the plane's compiled collective-free probes (scalars synced,
        # no buffer D2H — the PR 8 census pattern).
        from ..obs import tensorwatch as _tensorwatch

        self._tensorwatch = _tensorwatch.from_config(
            cfg, size=self._size, rank=self._rank,
            probe=(self._plane.tensorwatch_stats
                   if self._plane is not None else None),
            snr_probe=(self._plane.codec_snr
                       if self._plane is not None else None),
            norm2_probe=(self._plane.tensorwatch_norm2
                         if self._plane is not None else None),
            timeline=self.timeline)

        # Generation-ordered sub-buffer flush (docs/tensor-fusion.md):
        # with HOROVOD_FUSION_SUBBUFFERS >= 2 the loop cuts each tick's
        # pending queue into arrival-ordered sub-buffers and keeps up to
        # that many negotiate/execute cycles in flight — cycle k+1's
        # negotiation (a cheap cache-bit vector in steady state) overlaps
        # cycle k's allreduce on the flush worker. 1 (default) keeps
        # today's single-flush barrier byte-identically: no worker, no
        # data channel, the untouched loop body.
        # Sparse top-k error-feedback residuals (docs/compression.md
        # §sparse): the dropped (non-top-k) mass of every sparse batch,
        # carried per tensor name so it re-enters the next step's
        # selection. Stamped with the elastic world epoch — a relaunch
        # restarts from committed state, so pre-relaunch residuals must
        # never replay into it (pinned by tests/test_zzsparse.py). The
        # fraction key is validated loudly at init, not at first batch.
        from .compression import TopKCompressor

        TopKCompressor.set_fraction_key(cfg.sparse_topk)
        self._sparse_residuals: Dict[str, Any] = {}
        self._sparse_epoch = basics.world_epoch()
        self._sparse_error_feedback = cfg.sparse_error_feedback

        self._subbuffers = cfg.fusion_subbuffers
        # Fused reduce+apply plane (docs/tensor-fusion.md §fused apply):
        # execution strategy for apply-capable batches — True runs the
        # single reduce+apply program, False the reduce-then-apply split.
        # Numerics-exact either way (the shared ApplyRule math), so the
        # tuning plane may flip it live via the `fused_apply` tuned knob
        # without a consent gate — on the HOST wire only, where the
        # reduce exchange is byte-identical in both strategies; on the
        # XLA device plane the strategies issue different compiled
        # collective programs, so the value is pinned at init
        # (_apply_tuned_knobs ignores the retune there, warned once).
        self._fused_apply_exec = True
        # ZeRO-1 execution capability (docs/sharding.md): init-pinned
        # like the device plane's fused_apply strategy — the sharded and
        # replicated programs issue DIFFERENT compiled collectives, so
        # the decision must be rank-identical for the life of the world.
        # Requires the XLA device plane (the reduce-scatter/all-gather
        # pair is a compiled program, not a TCP exchange) and a world
        # big enough to shard; the front-end consults this through
        # ops.zero1_active() before localizing any state, so an unarmed
        # world simply keeps replicated slots.
        self._zero1_exec = bool(cfg.zero1) and self._plane is not None \
            and self._size > 1
        if cfg.zero1 and not self._zero1_exec:
            LOG.warning(
                "HOROVOD_ZERO=1 requested but not armed (%s): optimizer "
                "state stays replicated; applied parameters are "
                "identical either way.",
                "world of one" if self._size <= 1
                else "host data plane — ZeRO-1 needs the XLA device "
                     "plane")
        self._apply_counts = {"fused": 0, "split": 0, "dispatches": 0,
                              "zero1": 0}
        self._flush_worker: Optional[_DevicePlaneWorker] = None
        self._flush_clock: Optional[_FlushClock] = None
        self._inflight: "deque" = deque()
        self._inflight_peak = 0
        self._flush_count = 0
        self._overlap_seconds = 0.0
        self._pipeline_warned = False
        if self._subbuffers > 1:
            self._arm_flush_pipeline()

        # XLA-plane failure propagation: a rank blocked inside a compiled
        # collective is beyond the reach of a poisoned control-plane
        # response, so subscribe to the controller's abort push channel and
        # run device collectives on an abandonable worker thread.
        self._abort_event = threading.Event()
        self._abort_reason: Optional[str] = None
        self._device_worker: Optional[_DevicePlaneWorker] = None
        self._finalizer_q = None
        self._crashed = False
        self._shutdown_reason: Optional[str] = None
        if self._plane is not None and self._client is not None:
            import queue

            self._device_worker = _DevicePlaneWorker()
            self._client.watch(self._on_world_abort)
            # Completion signalling, the reference's CUDA-event-queue +
            # finalizer-thread design (``operations.cc`` event_queue): XLA
            # dispatch is asynchronous, so a just-dispatched collective is
            # NOT done — handles must complete only when the device work
            # does. The finalizer waits (abortably, on its own sacrificial
            # worker) and then marks the handles, keeping the cycle loop
            # free to negotiate the next batch while this one executes.
            self._completion_worker = _DevicePlaneWorker()
            self._finalizer_q = queue.SimpleQueue()
            self._finalizer = threading.Thread(
                target=self._finalize_loop, name="horovod-finalizer",
                daemon=True)
            self._finalizer.start()

        self._thread = threading.Thread(
            target=self._loop, name="horovod-background", daemon=True)
        self._thread.start()

    def _start_metrics_publisher(self, addr, secret,
                                 world_id: str = "") -> None:
        """Cross-rank metrics aggregation feed: a daemon thread pushes
        this process's registry snapshot to the coordinator every
        ``HOROVOD_METRICS_INTERVAL_S`` (<= 0 disables). Faults drop the
        sample and redial next tick — the controller restarting or gone
        means the world is ending and a lost metrics push is noise. The
        push rides ``BasicClient.request``, so a frame lost in transit
        heals by the wire's dedup/reconnect machinery like any other
        control message; no chaos injector is attached (chaos ordinals
        target the CYCLE channel, and a second injected stream would
        desynchronize replay determinism)."""
        interval = self._cfg.metrics_interval_s
        if interval <= 0:
            return
        if not self._cfg.metrics_port and \
                not self._cfg.metrics_interval_explicit:
            # as opt-in as the exposition server: no port and no explicit
            # interval means nothing consumes the pushes — spawn no
            # thread, dial no connection
            return
        # Live knob: the tuning plane may retune the interval mid-run
        # (_apply_tuned_knobs); the loop re-reads it each tick.
        self._metrics_interval_s = interval
        self._metrics_stop = threading.Event()
        stop = self._metrics_stop
        rank = self._rank
        from ..runner.network import BasicClient

        def _push_loop() -> None:
            failures = 0  # consecutive; a single lost push is noise, a
            # persistent streak (wrong world on a shared port, bad secret)
            # must degrade LOUDLY like every other plane here
            client = None
            try:
                # Eager dial (final-flush contract): the connection must
                # exist BEFORE a negotiated shutdown closes the
                # coordinator's listener — an ESTABLISHED connection's
                # handler thread outlives service.shutdown(), so the final
                # push below still lands, while a first-ever dial at that
                # point would find the listener gone and silently lose the
                # whole final interval.
                client = BasicClient(addr, secret=secret,
                                     timeout_s=5.0, attempts=3)
            except Exception:  # noqa: BLE001 - the first tick retries
                client = None
            try:
                while True:
                    # stop.wait returning True is the engine's teardown
                    # signal: push ONE final snapshot (the last partial
                    # interval must not be silently lost), then exit. The
                    # engine's bounded join is the time cap — best-effort
                    # by contract, the wire may already be gone.
                    stopping = stop.wait(
                        max(self._metrics_interval_s, 0.05))
                    try:
                        if client is None:
                            client = BasicClient(addr, secret=secret,
                                                 timeout_s=5.0, attempts=3)
                        # world_id rides along so a co-located different
                        # world's service (shared port) refuses the push
                        # instead of storing it
                        client.request(("metrics", rank,
                                        _obs_registry().snapshot(),
                                        world_id))
                        failures = 0
                    except Exception as exc:  # noqa: BLE001 - drop, redial
                        failures += 1
                        if failures == 3 and not stop.is_set():
                            LOG.warning(
                                "metrics publisher: %d consecutive push "
                                "failures (last: %s); world snapshots will "
                                "miss rank %d until the feed recovers",
                                failures, exc, rank)
                        if client is not None:
                            try:
                                client.close()
                            except Exception:  # noqa: BLE001
                                pass
                            client = None
                    if stopping:
                        return
            finally:
                if client is not None:
                    try:
                        client.close()
                    except Exception:  # noqa: BLE001
                        pass

        self._metrics_thread = threading.Thread(
            target=_push_loop, name="horovod-metrics-publisher",
            daemon=True)
        self._metrics_thread.start()

    def _maybe_start_clock_sync(self, addr, secret,
                                world_id: str = "") -> None:
        """Clock alignment (docs/tracing.md): runs only where something
        consumes the offset — a recording timeline on this rank, or the
        metrics plane opted in (the gauges then ride the snapshot wire).
        The coordinator-hosting rank IS the reference timebase (offset 0
        by definition, no probes); the native controller wire predates
        the clock_probe RPC and degrades deterministically."""
        if self._client is None or not getattr(
                self._client, "clock_sync_supported", False):
            return
        if not (self.timeline.enabled or self._cfg.metrics_port or
                self._cfg.metrics_interval_explicit):
            return
        from ..obs.tracing import ClockSync, set_reference_clock

        if self._service is not None:
            set_reference_clock(self._rank, self.timeline)
            return
        self._clock_sync = ClockSync(
            addr, secret, world_id=world_id, rank=self._rank,
            timeline=self.timeline,
            interval_s=self._cfg.clock_sync_interval_s)
        self._clock_sync.start()

    # -- sub-buffer flush pipeline (docs/tensor-fusion.md) --------------------

    def _arm_flush_pipeline(self) -> None:
        """Build the overlap machinery (idempotent): a serial flush
        worker — execution keeps the negotiated order, the legality
        invariant — plus the controller client's dedicated data channel,
        so a flush parked in a payload/sentry rendezvous never holds the
        cycle connection (the two-channel deadlock). Degrades
        deterministically (warned once) where the pipeline cannot run:
        size-1 worlds negotiate in-process (nothing to overlap) and the
        native controller's binary wire predates the data-channel hello
        — the same degrade pattern as the cache-bit and metrics RPCs."""
        if self._flush_worker is not None:
            return
        if self._client is None or self._native_controller:
            if not self._pipeline_warned:
                self._pipeline_warned = True
                LOG.warning(
                    "HOROVOD_FUSION_SUBBUFFERS=%d ignored: sub-buffer "
                    "flush pipelining needs the Python controller wire in "
                    "a multi-process world (size-1 worlds negotiate "
                    "in-process; set HOROVOD_NATIVE_CONTROLLER=0 "
                    "otherwise). Keeping the single-flush path.",
                    self._subbuffers)
            self._subbuffers = 1
            return
        self._client.open_data_channel()
        self._flush_clock = _FlushClock()
        self._flush_worker = _DevicePlaneWorker()
        self._flush_worker._thread.name = "horovod-flush-pipeline"

    def _execute_flush(self, responses: List[Response], span_args,
                       cycle_no: int) -> None:
        """Flush-worker body: execute one negotiated sub-buffer's
        responses in order, bracketing the busy clock the loop thread
        reads the overlap off."""
        self._flush_clock.mark_start()
        try:
            for idx, resp in enumerate(responses):
                t_exec = time.monotonic()
                self._execute(idx, resp, span_args=span_args,
                              cycle_no=cycle_no)
                _EXECUTE_SECONDS.observe(time.monotonic() - t_exec)
        finally:
            self._flush_clock.mark_end()
            # flight recorder (docs/blackbox.md): flush lifecycle end,
            # keyed by the cycle the sub-buffer was negotiated under
            _flightrec.record(_flightrec.EV_FLUSH_END, cycle_no)

    # The coordinator retains a cycle's ResponseList (the payload
    # exchange's lookup table) for a 16-cycle sliding window
    # (ControllerService history). A slow in-flight flush — e.g. an
    # apply-fused batch compiling a fresh bucket program — must not let
    # the loop thread negotiate idle cycles past that window, or the
    # flush's own payload exchange KeyErrors on an expired cycle. Half
    # the window keeps a wide safety margin; throttling is symmetric
    # (cycles are a world rendezvous, so one throttled rank simply slows
    # the world's cycle count until its flush completes).
    _MAX_FLUSH_CYCLE_LAG = 8

    def _reap_flushes(self, block: bool = False) -> None:
        """Retire completed in-flight flushes in order; ``block=True``
        waits (abortably, like ``_device_call``) for the oldest one — the
        depth-cap path. A flush whose body raised re-raises HERE, on the
        loop thread, so the loop's crash path owns the teardown."""
        from concurrent.futures import TimeoutError as _FutTimeout

        while self._inflight:
            _, fut = self._inflight[0]
            if not fut.done() and not block:
                break
            if not fut.done():
                if self._abort_event.is_set():
                    raise RuntimeError(
                        self._abort_reason or SHUT_DOWN_ERROR)
                try:
                    fut.result(timeout=0.25)
                except _FutTimeout:
                    continue
            self._inflight.popleft()
            block = False
            fut.result()  # re-raise a failed flush into the loop
        _FLUSH_INFLIGHT.set(len(self._inflight))

    def _abandon_flushes(self, timeout_s: float = 15.0) -> None:
        """Teardown drain: give in-flight flushes a bounded window to
        finish (their handles must be marked by the worker, not
        double-flushed), then abandon — the worker is a daemon and the
        world is over."""
        deadline = time.monotonic() + timeout_s
        while self._inflight:
            _, fut = self._inflight.popleft()
            try:
                fut.result(timeout=max(0.0, deadline - time.monotonic()))
            except Exception:  # noqa: BLE001 - teardown: best effort
                pass
        _FLUSH_INFLIGHT.set(0)

    def overlap_stats(self) -> Dict[str, Any]:
        """Sub-buffer flush pipeline counters for tests, the dryrun
        certification, and bench reporting (zeros when single-flush)."""
        busy = self._flush_clock.busy_seconds() \
            if self._flush_clock is not None else 0.0
        return {
            "subbuffers": self._subbuffers,
            "pipelined": self._flush_worker is not None,
            "flushes": self._flush_count,
            "overlap_seconds": self._overlap_seconds,
            "execute_busy_seconds": busy,
            "inflight_peak": self._inflight_peak,
        }

    def _downgrade_codec(self, entry: TensorTableEntry, codec: str) -> str:
        """One rule for quantized-wire eligibility on the eager plane
        (shared by the plain and apply-fused allreduce paths): the
        decision reads only NEGOTIATED metadata (codec + dtype) and
        world-uniform state (plane presence), so every rank downgrades
        identically and compiled programs stay launch-order
        compatible."""
        if codec == "none":
            return codec
        if _is_sparse_codec(codec):
            # The sparse indices+values wire is float32-only by layout,
            # but unlike the quantized wire it has a REAL host-plane
            # transport (the coordinator's reference allgather combine),
            # so a plane-less world keeps the codec; only a non-f32
            # batch degrades — still decided from negotiated metadata.
            if dtype_of(entry.array) == DataType.FLOAT32:
                return codec
            if ("codec", codec) not in self._host_fallback_warned:
                self._host_fallback_warned.add(("codec", codec))
                LOG.warning(
                    "sparse allreduce (%s) requested for a non-float32 "
                    "batch; reducing dense at full precision (the "
                    "sparse wire's value block is float32 by layout).",
                    codec)
            return "none"
        if self._plane is not None and self._plane.supports_quantized(
                dtype_of(entry.array)):
            return codec
        if self._plane is None and \
                ("codec", codec) not in self._host_fallback_warned:
            self._host_fallback_warned.add(("codec", codec))
            LOG.warning(
                "quantized allreduce (%s) requested but the host "
                "TCP data plane is active; reducing at full "
                "precision (set HOROVOD_DATA_PLANE=xla for the "
                "quantized device wire).", codec)
        return "none"

    def _warn_host_fallback(self, op_name: str, tensor_name: str,
                            array: np.ndarray) -> None:
        """The device plane is active but this dtype must ride the host TCP
        plane — at pod scale that is orders of magnitude slower, so say so
        once per (op, dtype) instead of silently degrading."""
        key = (op_name, str(array.dtype))
        if key in self._host_fallback_warned:
            return
        self._host_fallback_warned.add(key)
        LOG.warning(
            "%s of %r (dtype %s) has no device-collective wire; falling "
            "back to the host TCP data plane, which is far slower at scale. "
            "Cast the tensor (e.g. to float32/int32) to keep it on-device.",
            op_name, tensor_name, array.dtype)

    def _on_world_abort(self, reason: str) -> None:
        """Watch-channel callback (daemon thread): record the reason and
        wake any device call parked in ``_device_call``. Fires on clean
        controller stop too — harmless, nothing is in a collective then."""
        self._abort_reason = reason
        self._abort_event.set()
        if reason and "stopping" not in reason:
            # flight recorder (docs/blackbox.md): a pushed world abort —
            # a rank parked inside a compiled collective may never reach
            # the loop's own teardown trigger, so ship the tail from
            # here too (idempotent once-flag in trigger_dump)
            _flightrec.trigger_dump(reason)

    def _device_call(self, fn, *args, worker=None):
        """Run a device-plane call abortably (see ``_DevicePlaneWorker``).
        Without a watch channel (size-1 worlds, host plane) it runs
        inline."""
        worker = worker or self._device_worker
        if worker is None:
            return fn(*args)
        if self._abort_event.is_set():
            raise RuntimeError(self._abort_reason or SHUT_DOWN_ERROR)
        from concurrent.futures import TimeoutError as _FutTimeout

        fut = worker.submit(fn, *args)
        while True:
            try:
                return fut.result(timeout=0.25)
            except _FutTimeout:
                if self._abort_event.is_set():
                    raise RuntimeError(
                        self._abort_reason or SHUT_DOWN_ERROR) from None

    def _finalize_loop(self) -> None:
        """Mark device-path handles done only when the dispatched XLA
        collective actually completed (reference completion semantics:
        CUDA events + finalizer thread). A peer death leaves the wait
        blocked forever on the sacrificial worker; the watch-channel abort
        unparks this loop, which fails the handles with SHUT_DOWN_ERROR."""
        import queue as _queue

        import jax

        while True:
            item = self._finalizer_q.get()
            if item is None:
                self._completion_worker.stop()
                return
            # Drain everything already queued and wait on the UNION: the
            # batches all executed concurrently under XLA's async dispatch,
            # so k sequential per-batch waits would add k completion
            # round-trips of pure latency (a measured 2.3x on the fusion
            # bench) for work that finishes together anyway.
            batch = [item]
            while True:
                try:
                    nxt = self._finalizer_q.get_nowait()
                except _queue.Empty:
                    break
                if nxt is None:  # keep the sentinel AFTER the drain
                    self._finalizer_q.put(None)
                    break
                batch.append(nxt)
            try:
                self._device_call(
                    jax.block_until_ready,
                    [r for _, res in batch for r in res],
                    worker=self._completion_worker)
                failed_union = False
            except Exception:  # noqa: BLE001 - isolate below
                # One bad computation must not poison sibling batches that
                # completed fine: fall back to per-batch waits so each
                # batch gets its own ok/error. (A world abort re-raises
                # immediately per batch — _device_call checks the abort
                # flag at entry — so the fallback stays fast then too.)
                failed_union = True
            for entries, results in batch:
                status = None
                if failed_union:
                    try:
                        self._device_call(jax.block_until_ready, results,
                                          worker=self._completion_worker)
                    except Exception as exc:  # noqa: BLE001
                        status = Status.unknown_error(str(exc))
                if status is not None:
                    for entry in entries:
                        try:
                            self.timeline.end(entry.name)
                        except Exception:  # noqa: BLE001
                            pass
                        self.handles.mark_done(entry.handle, status, None)
                    continue
                for entry, result in zip(entries, results):
                    # mark_done is the load-bearing call: a timeline hiccup
                    # must never leave a completed handle unmarked (a
                    # waiter would hang forever on it)
                    try:
                        self.timeline.end(entry.name, shape=result.shape)
                    except Exception:  # noqa: BLE001
                        pass
                    self.handles.mark_done(entry.handle, Status.ok(),
                                           result)

    # -- submission (API threads) --------------------------------------------

    def enqueue(self, op: RequestType, array: np.ndarray, name: str,
                root_rank: int = -1, codec: str = "none",
                apply: Optional[ApplyContext] = None) -> int:
        """EnqueueTensor* (``operations.cc:2472-2591``): duplicate names are
        rejected while the previous submission is still in flight, as the
        reference's tensor_table emplace does."""
        dtype_of(array)  # validate wire dtype early
        if codec != "none" and self._native_controller:
            # The native controller's fixed binary wire has no codec slot,
            # so quantized negotiation metadata cannot reach the
            # coordinator. Deterministic on every rank (the native
            # decision is config-driven and rank-identical): fall back to
            # the full-precision wire rather than risk divergent batches.
            if codec not in self._host_fallback_warned:
                self._host_fallback_warned.add(codec)
                LOG.warning(
                    "compressed allreduce (%s) is not carried by the "
                    "native controller wire; reducing dense at full "
                    "precision. Set HOROVOD_NATIVE_CONTROLLER=0 to use "
                    "the compressed eager data plane.", codec)
            codec = "none"
        with self._lock:
            if self._stop_requested:
                raise RuntimeError(SHUT_DOWN_ERROR)
            in_flight = {e.name for e in self._submissions} | set(self._pending)
            if name in in_flight:
                raise ValueError(
                    f"Requested to {_OP_NAMES[op]} a tensor with the same "
                    f"name as another tensor that is currently being "
                    f"processed: {name}. Synchronize the outstanding handle "
                    f"first or pass a unique name.")
            handle = self.handles.allocate()
            entry = TensorTableEntry(name=name, op=op, array=array,
                                     handle=handle, root_rank=root_rank,
                                     codec=codec, apply=apply)
            self._submissions.append(entry)
        # flight recorder (docs/blackbox.md): submission lifecycle start
        _flightrec.record(_flightrec.EV_ENQUEUE, detail=name)
        self.timeline.negotiate_start(name, _OP_NAMES[op])
        # No wake: submissions ride the next cycle tick, preserving the
        # reference's fusion window (HOROVOD_CYCLE_TIME batches arrivals,
        # ``operations.cc:2030-2060``). Only shutdown wakes the loop early.
        return handle

    # -- background loop ------------------------------------------------------

    def _loop(self) -> None:
        cycle_s = max(self._cfg.cycle_time_ms, 0.1) / 1000.0
        try:
            while True:
                self._wake.wait(timeout=cycle_s)
                self._wake.clear()
                self.timeline.mark_cycle_start()
                cycle_t0 = time.monotonic()
                stop = self._stop_requested
                with self._lock:
                    new_entries, self._submissions = self._submissions, []
                    for entry in new_entries:
                        self._pending[entry.name] = entry
                if self._flush_worker is not None:
                    if not stop:
                        cycle_s, stop_loop = self._pipelined_tick(
                            new_entries, cycle_s)
                        if stop_loop:
                            break
                        continue
                    # The shutdown cycle takes the single-flush path
                    # below; drain the pipeline first so its payload
                    # exchanges complete before the drain negotiation
                    # reaches the coordinator (and so a failed flush
                    # surfaces through the crash path, not silently).
                    while self._inflight:
                        self._reap_flushes(block=True)
                requests = [self._request_of(e) for e in new_entries]
                request_list = RequestList(
                    rank=self._rank, requests=requests, shutdown=stop)
                if self._negotiator is not None:
                    self._negotiator.add_request_list(request_list)
                    response_list = self._negotiator.construct_response_list()
                    self._local_cycle_no += 1
                else:
                    assert self._client is not None
                    response_list = self._cycle_with_cache(
                        request_list, requests, stop)
                self._span_args = self._cycle_span_args(response_list)
                for idx, resp in enumerate(response_list.responses):
                    t_exec = time.monotonic()
                    self._execute(idx, resp)
                    _EXECUTE_SECONDS.observe(time.monotonic() - t_exec)
                # registry deltas as timeline counter tracks (no-op when
                # the timeline is disabled — one attribute check)
                self._metrics_bridge.emit()
                # autotune: local worlds score here; multi-process worlds
                # score on the coordinator and ship cycle time back
                if self._negotiator is not None and self._autotuner is not None:
                    active_us = (time.monotonic() - cycle_t0) * 1e6
                    tuned = self._autotuner.observe_cycle(
                        response_list, active_us=active_us)
                    if tuned is not None:
                        self._negotiator.set_fusion_threshold(
                            int(tuned.config["fusion_threshold_bytes"]))
                        cycle_s = max(
                            float(tuned.config["cycle_time_ms"]),
                            0.1) / 1000.0
                        self._audit_knobs(dict(
                            tuned.config, action=tuned.action))
                elif response_list.tuned_cycle_ms is not None:
                    new_cycle_s = max(response_list.tuned_cycle_ms,
                                      0.1) / 1000.0
                    if new_cycle_s != cycle_s:
                        self._audit_knobs({"cycle_time_ms":
                                           response_list.tuned_cycle_ms})
                    cycle_s = new_cycle_s
                if response_list.shutdown:
                    if response_list.abort_reason:
                        # Escalated shutdown (stall deadline): flush with
                        # the structured reason so waiters raise
                        # RanksAbortedError naming the missing ranks.
                        self._shutdown_reason = response_list.abort_reason
                    break
        except Exception as exc:  # noqa: BLE001 - propagate to handles
            LOG.error("background loop failed: %s", exc)
            # A dead control plane (coordinator gone, peer died and the
            # abort raced teardown) IS a world shutdown: surface the
            # reference's SHUT_DOWN_ERROR semantics, keeping the transport
            # detail as the cause (``operations.cc:1942-1957``).
            reason = str(exc)
            if "shut down" not in reason:
                reason = f"{SHUT_DOWN_ERROR} (cause: {reason})"
            self._stop_requested = True  # before the flush: an enqueue
            # racing it must be rejected, not parked on a dead loop
            self._crashed = True  # teardown ordering differs, see finally
            if self._shutdown_reason is None:
                # post-mortem ops (get_engine on the stopped singleton)
                # surface this same structured reason
                self._shutdown_reason = reason
            # In-flight sub-buffer flushes first (bounded): their entries
            # must be marked by the worker OR by the outstanding flush
            # below, never raced between the two.
            self._abandon_flushes()
            self._flush_outstanding(Status.unknown_error(reason))
        finally:
            self._stop_requested = True
            if self._shutdown_reason:
                # Flight recorder (docs/blackbox.md): an escalated
                # shutdown or loop crash — ship this rank's black-box
                # tail while the coordinator is still reachable (the
                # service teardown below). Clean negotiated shutdowns
                # leave _shutdown_reason None and dump nothing.
                _flightrec.trigger_dump(self._shutdown_reason)
            self._abandon_flushes()
            if self._clock_sync is not None:
                self._clock_sync.stop()
            if self._metrics_stop is not None:
                self._metrics_stop.set()  # publisher drains before teardown
            self._flush_outstanding(Status.unknown_error(
                self._shutdown_reason or SHUT_DOWN_ERROR))
            crashed = getattr(self, "_crashed", False)
            if not crashed and self._finalizer_q is not None:
                # Clean shutdown: drain still-completing device batches
                # BEFORE the control plane goes away. (FIFO: the sentinel
                # lands behind them; the finalizer stops its own worker —
                # stopping it here could strand an unsubmitted batch.)
                self._finalizer_q.put(None)
                self._finalizer.join(timeout=15.0)
            if self._metrics_thread is not None:
                # Final-flush rendezvous (docs/metrics.md): the stop event
                # wakes the publisher, which pushes one last snapshot so
                # the final partial interval isn't silently lost. Join
                # BEFORE the client/service teardown below — the bounded
                # timeout is what keeps the flush best-effort rather than
                # a shutdown hazard (the thread is a daemon; an overrun
                # push is abandoned, never waited out).
                self._metrics_thread.join(timeout=3.0)
            if self._client is not None:
                # Never a clean detach: after a negotiated shutdown the
                # controller ignores the drop anyway, and on the crash path
                # the drop is precisely what tells it this rank died.
                self._client.close(detach=False)
            if self._subcoord is not None:
                # Island head duty: before the root service (rank 0 hosts
                # both) so the head's upstream farewell can still land.
                self._subcoord.shutdown()
            if self._standby_subcoord is not None:
                # A never-activated standby farewells nothing (it holds
                # no upstream channels); an activated one farewells like
                # the primary it replaced.
                self._standby_subcoord.shutdown()
            if self._service is not None:
                self._service.shutdown()
            if self._autotuner is not None:
                self._autotuner.close()
            timeline_safe = True
            if self._finalizer_q is not None:
                if crashed:
                    # Crash path: teardown first (the client drop IS the
                    # death signal to peers — a 15 s drain would delay the
                    # world abort), then drain; the watch-channel abort
                    # unparks a finalizer stuck in a dead collective.
                    self._finalizer_q.put(None)
                    self._finalizer.join(timeout=15.0)
                # Close the timeline only once the finalizer is done: it
                # emits timeline events, and the native writer's close
                # frees the C++ handle (a later write is a use-after-free).
                timeline_safe = not self._finalizer.is_alive()
            if self._device_worker is not None:
                # best-effort: a worker blocked in a dead collective never
                # consumes the sentinel, but it is a daemon thread
                self._device_worker.stop()
            if self._flush_worker is not None:
                # joined bounded (unlike the device worker): the flush
                # worker runs compiled apply programs on the host plane,
                # and leaving it frozen mid-C++ at interpreter exit
                # aborts in jaxlib teardown (see _DevicePlaneWorker.stop)
                self._flush_worker.stop(join_timeout_s=3.0)
            if timeline_safe:
                self.timeline.close()
            else:
                LOG.warning(
                    "finalizer still completing at shutdown; leaving the "
                    "timeline writer open to avoid a write-after-free")
            # after the trigger above: a clean world's later structured
            # raises (tests constructing errors) must not dump against a
            # stale context
            _flightrec.disarm_push()
            self._stopped.set()

    def _pipelined_tick(self, new_entries: List[TensorTableEntry],
                        cycle_s: float):
        """One wake tick under the sub-buffer flush pipeline
        (docs/tensor-fusion.md): cut the drained queue into
        generation-ordered sub-buffers, negotiate each as its own cycle,
        and hand execution to the flush worker — so the NEXT sub-buffer's
        negotiation (a cache-bit vector in steady state) runs while the
        previous one's allreduce is still in flight. Depth is capped at
        the sub-buffer count; an idle tick still negotiates one empty
        cycle (the heartbeat every rank owes every cycle). Returns
        ``(cycle_s, stop_loop)``."""
        batches = cut_generations(new_entries, self._subbuffers) or [[]]
        response_list = None
        for sub in batches:
            self._reap_flushes()  # fail fast on a crashed flush
            while len(self._inflight) >= self._subbuffers or (
                    self._inflight and
                    self._client.last_cycle - self._inflight[0][0]
                    >= self._MAX_FLUSH_CYCLE_LAG):
                self._reap_flushes(block=True)
            requests = [self._request_of(e) for e in sub]
            request_list = RequestList(rank=self._rank, requests=requests,
                                       shutdown=False)
            busy0 = self._flush_clock.busy_seconds()
            response_list = self._cycle_with_cache(request_list, requests,
                                                   False)
            # the achieved overlap: flush-worker busy seconds inside this
            # negotiation's wall window (exact — busy intervals are
            # disjoint on the single worker thread)
            overlap = self._flush_clock.busy_seconds() - busy0
            if overlap > 0:
                _OVERLAP_SECONDS.inc(overlap)
                self._overlap_seconds += overlap
            span_args = self._cycle_span_args(response_list)
            self._span_args = span_args
            if response_list.responses:
                cycle_no = self._client.last_cycle
                fut = self._flush_worker.submit(
                    self._execute_flush, list(response_list.responses),
                    span_args, cycle_no)
                self._inflight.append((cycle_no, fut))
                self._flush_count += 1
                _SUBBUFFER_FLUSHES.inc()
                depth = len(self._inflight)
                # flight recorder (docs/blackbox.md): flush dispatch with
                # its cycle ordinal + the in-flight depth it joined
                _flightrec.record(_flightrec.EV_FLUSH_START, cycle_no,
                                  aux=depth)
                _FLUSH_INFLIGHT.set(depth)
                if depth > self._inflight_peak:
                    self._inflight_peak = depth
                    _FLUSH_INFLIGHT_PEAK.set(depth)
                if self.timeline.enabled:
                    self.timeline.counter("flush_inflight",
                                          {"inflight": depth})
            if response_list.shutdown:
                break
        self._metrics_bridge.emit()
        if response_list.tuned_cycle_ms is not None:
            new_cycle_s = max(response_list.tuned_cycle_ms, 0.1) / 1000.0
            if new_cycle_s != cycle_s:
                self._audit_knobs({"cycle_time_ms":
                                   response_list.tuned_cycle_ms})
            cycle_s = new_cycle_s
        if response_list.shutdown:
            if response_list.abort_reason:
                self._shutdown_reason = response_list.abort_reason
            self._abandon_flushes()
            return cycle_s, True
        return cycle_s, False

    def _cycle_span_args(self, response_list) -> Optional[dict]:
        """Cross-rank correlation stamps for this cycle's span records
        (docs/tracing.md): the cycle ordinal — every rank participates in
        every negotiation cycle exactly once and in order, so ordinal N
        names the SAME rendezvous in every per-rank trace file — plus the
        response-cache generation, which distinguishes replayed-layout
        cycles from renegotiated ones when reading a merged trace."""
        if not self.timeline.enabled:
            return None
        if self._client is not None:
            ordinal = self._client.last_cycle
        else:
            ordinal = self._local_cycle_no - 1
        args = {"cycle": ordinal}
        generation = getattr(response_list, "cache_generation", None)
        if generation is not None:
            args["cache_generation"] = generation
        return args

    def _cycle_with_cache(self, request_list: RequestList,
                          requests: List[Request], stop: bool):
        """One controller round trip, through the steady-state bypass when
        the whole cycle hits the response cache (docs/response-cache.md):
        ship a fixed-size cache-bit vector instead of the RequestList and,
        on an all-ranks hit, replay the cached fused responses from the
        coordinator's compact ack. A shutdown cycle always takes the full
        path — the drain negotiation must reach the coordinator as-is."""
        from .messages import CacheHitAck, CacheRequest
        from .response_cache import bits_of

        cache = self._response_cache
        positions = None
        if cache is not None and self._cache_confirmed and not stop:
            positions = cache.plan_cycle(requests)
        # consensus digests ride whichever message actually ships this
        # cycle — the warm steady state must keep verifying too
        # (docs/integrity.md)
        digests = self._drain_digests()
        if positions is not None:
            out = self._client.cycle(self._rank, CacheRequest(
                rank=self._rank, bits=bits_of(positions, cache.capacity),
                generation=cache.generation, integrity_digest=digests))
        else:
            request_list.integrity_digest = digests
            out = self._client.cycle(self._rank, request_list)
        if isinstance(out, CacheHitAck):
            response_list = ResponseList(
                responses=cache.accept_ack(out),
                tuned_cycle_ms=out.tuned_cycle_ms,
                stall_warnings=out.stall_warnings,
                stall_check=out.stall_check,
                # carried for the span stamps (_cycle_span_args): an
                # all-hit cycle's trace must still say which cache
                # generation it replayed under
                cache_generation=out.generation)
        else:
            response_list = out
            if cache is not None:
                if getattr(response_list, "cache_generation", None) is None:
                    # The coordinator runs without a cache (capacity knob
                    # diverged, or a pre-cache service): planning bypasses
                    # against it could only fail loudly later — disable.
                    LOG.warning(
                        "coordinator carries no response-cache generation; "
                        "disabling the rank-side cache "
                        "(HOROVOD_CACHE_CAPACITY should resolve "
                        "identically on every rank).")
                    self._response_cache = None
                else:
                    self._cache_confirmed = True
                    with self._lock:
                        in_flight = {name: self._request_of(e)
                                     for name, e in self._pending.items()}
                    cache.accept_response_list(response_list, in_flight)
        self._apply_tuned_knobs(out)  # list or ack: both carry the map
        self._emit_cache_counters()
        return response_list

    def _apply_tuned_knobs(self, msg) -> None:
        """Apply the coordinator's piggybacked extended-knob map
        (docs/autotune.md). Runs on the engine loop thread AFTER the
        cycle's cache processing: a capacity retune always arrives
        alongside the generation bump that cleared the cache, so resizing
        here can never orphan live positions — the next cycle plans its
        bitvector under the same capacity the coordinator now holds.
        Idempotent per value; audited on change via timeline AUTOTUNE
        metadata."""
        knobs = getattr(msg, "tuned_knobs", None)
        if not knobs:
            return
        changed = {}
        capacity = knobs.get("cache_capacity")
        if capacity is not None and self._response_cache is not None and \
                int(capacity) != self._response_cache.capacity:
            self._response_cache.capacity = int(capacity)
            changed["cache_capacity"] = int(capacity)
        interval = knobs.get("metrics_interval_s")
        if interval is not None and \
                float(interval) != self._metrics_interval_s:
            self._metrics_interval_s = float(interval)
            changed["metrics_interval_s"] = float(interval)
        subbuffers = knobs.get("fusion_subbuffers")
        if subbuffers is not None and int(subbuffers) != self._subbuffers:
            # the overlap knob (docs/tensor-fusion.md): arms the pipeline
            # on first use (flush worker + data channel); the next tick
            # cuts by the new count. Arming runs on the loop thread —
            # exactly where a retune lands — so no in-flight flush can
            # observe a half-built pipeline.
            self._subbuffers = max(int(subbuffers), 1)
            if self._subbuffers > 1:
                self._arm_flush_pipeline()
            changed["fusion_subbuffers"] = self._subbuffers
        fused_apply = knobs.get("fused_apply")
        if fused_apply is not None and \
                bool(int(fused_apply)) != self._fused_apply_exec:
            if self._plane is not None:
                # On the XLA device plane the two strategies issue
                # DIFFERENT compiled collective programs (psum+apply vs
                # plain psum) for the same negotiated batch; a retune
                # lands on each rank's loop thread at its own moment, so
                # a mid-stream flip could desynchronize launch order
                # (the plane's byte-identical-programs invariant). The
                # strategy stays pinned at its init value there.
                self._warn_apply_once(
                    "tuned-exec-plane",
                    "fused_apply retune ignored on the XLA device "
                    "plane: the execution strategy changes the compiled "
                    "collective program and cannot flip mid-stream; "
                    "pin HOROVOD_FUSED_APPLY instead.")
            else:
                # Host TCP wire: the reduce exchange is byte-identical
                # in both strategies (the apply is rank-local compute),
                # so the flip is safe at any moment — numerics-exact by
                # the shared ApplyRule math; in-flight batches finish
                # under whichever mode they started.
                self._fused_apply_exec = bool(int(fused_apply))
                changed["fused_apply"] = int(fused_apply)
        codec = knobs.get("codec")
        if codec is not None and \
                codec != (self._applied_knobs.get("codec") or "none"):
            # audit only: the codec applies as a coordinator-side response
            # rewrite, never a rank-side request rule (ops/controller.py).
            # Never-seen == the "none" baseline, so the first extended map
            # does not fake a codec-change record in every rank's trace.
            changed["codec"] = codec
        if changed:
            self._applied_knobs.update(changed)
            self._audit_knobs(changed)

    def _audit_knobs(self, record: dict) -> None:
        """Timeline half of the decision audit (the registry half lives
        with the policy): one AUTOTUNE metadata record per change."""
        if self.timeline.enabled:
            from ..utils.timeline import AUTOTUNE

            try:
                self.timeline.meta(AUTOTUNE, dict(record))
            except Exception:  # noqa: BLE001 - audit must never kill a cycle
                pass

    def _emit_cache_counters(self) -> None:
        """Per-cycle bypass observability on the rank-0 timeline: hit/miss
        cycle totals and this cycle's negotiation wire bytes, as a Chrome
        counter track (satellite of docs/response-cache.md)."""
        cache = self._response_cache
        if cache is None or not self.timeline.enabled:
            return
        self.timeline.counter("response_cache", {
            "hit_cycles": cache.hit_cycles,
            "miss_cycles": cache.miss_cycles,
            "negotiation_tx_bytes": self._client.last_cycle_tx_bytes,
            "negotiation_rx_bytes": self._client.last_cycle_rx_bytes,
        })

    # -- data-plane integrity (docs/integrity.md) -----------------------------

    def _sentry_exchange(self, ordinal: int, bits: bytes) -> bytes:
        """Collective verdict fold: OR this batch's per-tensor finite
        bits across every rank through the controller rendezvous."""
        return self._client.sentry(self._rank, ordinal, bits)

    def _on_sentry_trip(self, record: dict) -> None:
        """Timeline half of the sentry audit (the registry half lives
        with the sentry): one INTEGRITY metadata record per trip."""
        if self.timeline.enabled:
            from ..utils.timeline import INTEGRITY

            try:
                self.timeline.meta(INTEGRITY, dict(record))
            except Exception:  # noqa: BLE001 - audit must not kill a batch
                pass

    def _screen_reduced(self, entries: List[TensorTableEntry],
                        results: List) -> List:
        """Integrity pipeline over one reduced allreduce batch: consensus
        digest FIRST (the bytes as received — a sentry rewrite is
        collective and identical on every rank, so digesting after it
        would mask exactly the divergence consensus exists to catch),
        then the sentry screen (which may zero the batch or raise
        ``NonFiniteGradError``)."""
        names = [e.name for e in entries]
        if self._consensus_acc is not None:
            self._consensus_acc.observe_batch(names, results)
        if self._sentry is not None:
            results = self._sentry.screen_batch(names, results)
        return results

    def _drain_digests(self):
        """Completed consensus windows for the next cycle message."""
        if self._consensus_acc is None:
            return None
        return self._consensus_acc.drain()

    def integrity_stats(self) -> Dict[str, Any]:
        """Sentry / consensus / data-chaos state for tests, the dryrun
        certification, and bench reporting (zeros when disarmed)."""
        return {
            "sentry": self._sentry.stats() if self._sentry else None,
            "consensus_windows": (self._consensus_acc.windows_emitted
                                  if self._consensus_acc else 0),
            "data_chaos_events": (list(self._data_chaos.events)
                                  if self._data_chaos else []),
        }

    def state_snapshot(self) -> Dict[str, Any]:
        """Engine state for the black-box incident dump and
        ``hvd.health_report()`` — one definition (docs/blackbox.md): the
        in-flight flush table, pending submissions, cache/apply/overlap
        counters, and the last tuned-knob map this rank applied. Safe to
        call from any thread at any time (a live poke must never perturb
        the loop): collections are copied under the engine lock where one
        exists, best-effort elsewhere."""
        with self._lock:
            pending = sorted(self._pending)
            queued = len(self._submissions)
        try:
            inflight = [cycle_no for cycle_no, _ in list(self._inflight)]
        except RuntimeError:  # deque mutated mid-copy: retry once, coarse
            inflight = [cycle_no for cycle_no, _ in list(self._inflight)]
        client = self._client
        return {
            "rank": self._rank,
            "size": self._size,
            "stopped": self._stopped.is_set(),
            "stop_requested": self._stop_requested,
            "crashed": getattr(self, "_crashed", False),
            "shutdown_reason": self._shutdown_reason,
            "abort_reason": self._abort_reason,
            "last_cycle": (client.last_cycle if client is not None
                           else max(self._local_cycle_no - 1, 0)),
            "pending_tensors": pending,
            "queued_submissions": queued,
            "inflight_flushes": inflight,
            "subbuffers": self._subbuffers,
            "cache": self.cache_stats(),
            "apply": self.apply_stats(),
            "overlap": self.overlap_stats(),
            "tensorwatch": (self._tensorwatch.stats()
                            if self._tensorwatch is not None else None),
            "applied_knobs": dict(self._applied_knobs),
            "native_controller": self._native_controller,
        }

    def cache_stats(self) -> Dict[str, int]:
        """Rank-side response-cache counters (zeros when disabled)."""
        if self._response_cache is None:
            return {"entries": 0, "capacity": 0, "generation": 0,
                    "hit_cycles": 0, "miss_cycles": 0}
        return self._response_cache.stats()

    def _request_of(self, entry: TensorTableEntry) -> Request:
        return Request(
            request_rank=self._rank,
            request_type=entry.op,
            tensor_name=entry.name,
            tensor_type=dtype_of(entry.array),
            tensor_shape=tuple(entry.array.shape),
            root_rank=entry.root_rank,
            codec=entry.codec,
            # negotiated like the codec; the native controller's binary
            # wire predates the field and simply drops it (the engine
            # then runs the split execution off its rank-side contexts)
            apply_fingerprint=(entry.apply.rule.fingerprint
                               if entry.apply is not None else ""),
        )

    def _flush_outstanding(self, status: Status) -> None:
        """All outstanding callbacks error out on shutdown
        (``operations.cc:1942-1957``)."""
        with self._lock:
            entries = list(self._pending.values()) + self._submissions
            self._pending.clear()
            self._submissions = []
        for entry in entries:
            self.handles.mark_done(entry.handle, status, None)

    # -- execution ------------------------------------------------------------

    def _execute(self, idx: int, resp: Response,
                 span_args: Optional[dict] = None,
                 cycle_no: Optional[int] = None) -> None:
        """PerformOperation (``operations.cc:768-1621``) for one response,
        possibly a fused allreduce batch.

        ``span_args``/``cycle_no`` are captured at negotiation time by the
        flush pipeline — executing on the worker thread, the client's
        "most recent cycle" may already be a LATER one, so the payload
        exchange and trace stamps must use the ordinal this response was
        negotiated under. The single-flush path leaves them None (the
        live values are correct there, execution being serialized behind
        negotiation)."""
        if span_args is None:
            span_args = self._span_args
        with self._lock:
            if resp.response_type == ResponseType.ERROR:
                # An escalated stall ERROR targets a tensor only SOME
                # ranks submitted (that is what a stall is); ranks
                # without a pending entry for it have nothing to mark.
                entries = [e for e in (self._pending.pop(n, None)
                                       for n in resp.tensor_names)
                           if e is not None]
            else:
                # Data responses keep the strict invariant: a batch
                # naming a tensor this rank never submitted is a
                # coordinator bug and must fail loudly here, not as a
                # short-handed payload rendezvous later.
                entries = [self._pending.pop(n) for n in resp.tensor_names]
        if not entries:
            return
        tl = self.timeline
        for entry in entries:
            # cycle-ordinal + cache-generation stamps: how the same
            # span is found across per-rank trace files (docs/tracing.md)
            tl.negotiate_end(entry.name, args=span_args)

        if resp.response_type == ResponseType.ERROR:
            status = Status.precondition_error(resp.error_message)
            for entry in entries:
                self.handles.mark_done(entry.handle, status, None)
            return

        op_name = _OP_NAMES[entries[0].op]
        for entry in entries:
            tl.start(entry.name, op_name, args=span_args)
        try:
            if resp.response_type == ResponseType.ALLREDUCE:
                if any(e.apply is not None for e in entries):
                    # apply-capable batch: land applied parameters and
                    # fresh optimizer slots, not gradients
                    # (docs/tensor-fusion.md §fused apply); the path
                    # owns its own consensus/sentry interplay
                    results = self._run_reduce_apply(idx, entries, resp,
                                                     cycle_no=cycle_no)
                else:
                    results = self._run_allreduce(
                        idx, entries,
                        getattr(resp, "tensor_codec", "none"),
                        cycle_no=cycle_no)
                    if self._sentry is not None or \
                            self._consensus_acc is not None:
                        results = self._screen_reduced(entries, results)
            elif resp.response_type == ResponseType.ALLGATHER:
                results = self._run_allgather(idx, entries[0], resp,
                                              cycle_no=cycle_no)
            else:
                results = self._run_broadcast(idx, entries[0], resp,
                                              cycle_no=cycle_no)
            if self._finalizer_q is not None and any(
                    _is_jax_array(r) for r in results):
                # Device results are asynchronous dispatches, not completed
                # collectives: the finalizer marks these handles when the
                # device work finishes (or the world aborts).
                self._finalizer_q.put((entries, results))
            else:
                for entry, result in zip(entries, results):
                    tl.end(entry.name, shape=result.shape)
                    self.handles.mark_done(entry.handle, Status.ok(), result)
        except Exception as exc:  # noqa: BLE001
            from ..runner.network import WireError

            reason = str(exc)
            if isinstance(exc, (WireError, OSError)) and \
                    "shut down" not in reason:
                # Control-plane loss mid-exchange == world shutdown (see
                # the equivalent mapping in _loop); genuine op errors keep
                # their own message.
                reason = f"{SHUT_DOWN_ERROR} (cause: {reason})"
            for entry in entries:
                tl.end(entry.name)
                self.handles.mark_done(
                    entry.handle, Status.unknown_error(reason), None)

    def _run_allreduce(self, idx: int, entries: List[TensorTableEntry],
                       codec: str = "none",
                       cycle_no: Optional[int] = None) -> List[np.ndarray]:
        fused = len(entries) > 1
        tl = self.timeline
        chaos = self._data_chaos
        if chaos is not None:
            # data-plane fault ordinals count allreduce BATCHES in
            # negotiated execution order — identical on every rank, so
            # nan@rankN:msgK replays bit-identically (docs/integrity.md).
            # Armed once per batch regardless of which path runs it; the
            # device-resident (onchip) path carries no host-side buffer
            # boundary and injects nothing, but still advances the
            # ordinal so mixed-path worlds stay aligned.
            chaos.begin_batch()
        watch = self._tensorwatch
        if watch is not None:
            # numerics observatory (docs/tensorwatch.md): the sampling
            # ordinal advances per allreduce batch in negotiated
            # execution order — rank-identical, like the sentry's
            watch.begin_batch()
        # Quantized wire eligibility is decided from NEGOTIATED batch
        # metadata (codec + dtype), identical on every rank, so the
        # compiled collective programs stay launch-order compatible.
        # Ineligible dtypes and plane-less (host TCP) worlds deterministically
        # ride the full-precision wire.
        codec = self._downgrade_codec(entries[0], codec)
        if _is_sparse_codec(codec):
            # Top-k sparse wire (docs/compression.md §sparse): its own
            # select → gather → scatter-decode route; the branch reads
            # only the negotiated codec, identical on every rank.
            return self._run_sparse_allreduce(idx, entries, codec,
                                              cycle_no=cycle_no)
        device_in = all(_is_jax_array(e.array) for e in entries)
        if device_in and self._client is None:
            # World of one, device tensors: sum over a single rank without
            # leaving the device. entry.array is already a private
            # on-device snapshot (see ops._submit), so returning it cannot
            # alias — or be invalidated by — any caller buffer.
            results = []
            for e in entries:
                tl.activity_start(e.name, "EXECUTE")
                results.append(e.array)
                tl.activity_end(e.name)
            if watch is not None and watch.sampling:
                watch.observe_batch([e.name for e in entries],
                                    [e.array for e in entries],
                                    results, codec)
            return results
        if device_in and self._plane is not None and \
                self._plane.supports(dtype_of(entries[0].array)):
            # All-device batch on the XLA plane: pack → psum → unpack with
            # zero host transfers (the analog of the reference's tensors
            # staying on-GPU through the NCCL fusion buffer).
            for e in entries:
                tl.activity_start(e.name, "EXECUTE")
            results = self._device_call(self._plane.allreduce_onchip,
                                        [e.array for e in entries], codec)
            for e in entries:
                tl.activity_end(e.name)
            if watch is not None and watch.sampling:
                # device route: the observatory's compiled probes sync
                # scalars off these arrays, no buffer D2H
                watch.observe_batch([e.name for e in entries],
                                    [e.array for e in entries],
                                    results, codec)
            return results
        if fused:
            for e in entries:
                tl.activity_start(e.name, "MEMCPY_IN_FUSION_BUFFER")
            # np.asarray is the lazy D2H for any jax entries mixed into a
            # host-path batch
            buf = np.concatenate([np.asarray(e.array).ravel()
                                  for e in entries])
            for e in entries:
                tl.activity_end(e.name)
        else:
            buf = np.asarray(entries[0].array).ravel()
        if chaos is not None:
            # the host-side fused-buffer boundary (docs/integrity.md):
            # nan faults poison a COPY of the local input here, before
            # the reduce — never the caller's array
            buf = chaos.on_reduce_input(buf)
        for e in entries:
            tl.activity_start(e.name, "EXECUTE")
        if self._plane is not None and self._plane.supports(dtype_of(buf)):
            # Preferred whenever a device plane exists — including the
            # explicit size-1 plane, where the single-rank psum is how the
            # eager path's bytes actually traverse the chip.
            out = self._device_call(self._plane.allreduce,
                                    np.ascontiguousarray(buf), codec)
        elif self._client is None:
            # world of one: sum over a single rank. Copy so results never
            # alias the caller's input array.
            out = np.array(buf, copy=True)
        else:
            if self._plane is not None:
                self._warn_host_fallback("allreduce", entries[0].name, buf)
            raw = self._client.payload(self._rank, idx,
                                       np.ascontiguousarray(buf).tobytes(),
                                       cycle_no=cycle_no)
            out = np.frombuffer(raw, dtype=buf.dtype).copy()  # writable
        if chaos is not None:
            # flipbits faults corrupt THIS rank's received reduced buffer
            # — the silent single-rank divergence consensus digests exist
            # to catch (docs/integrity.md)
            out = chaos.on_reduce_output(out)
        for e in entries:
            tl.activity_end(e.name)
        results = []
        offset = 0
        if fused:
            for e in entries:
                tl.activity_start(e.name, "MEMCPY_OUT_FUSION_BUFFER")
        for e in entries:
            n = e.array.size
            results.append(out[offset:offset + n].reshape(e.array.shape))
            offset += n
        if fused:
            for e in entries:
                tl.activity_end(e.name)
        if watch is not None and watch.sampling:
            # observed as RECEIVED, pre-sentry (the consensus framing):
            # a sentry rewrite is downstream of this measurement
            watch.observe_batch([e.name for e in entries],
                                [e.array for e in entries], results,
                                codec)
        return results

    def _run_sparse_allreduce(self, idx: int,
                              entries: List[TensorTableEntry],
                              codec: str,
                              cycle_no: Optional[int] = None) -> List:
        """Fused allreduce over the top-k sparse indices+values wire
        (docs/compression.md §sparse): per-tensor top-k selection of
        this rank's contribution (+ carried error-feedback residual),
        the pairs shipped over the reference allgather shape — the
        coordinator concatenates equal-K rank payloads; the XLA plane
        runs two tiled all_gathers per entry — and scatter-added back
        to the dense SUM on every rank.  Dropped mass lands in the
        per-tensor residual (``self._sparse_residuals``) and re-enters
        the next step's selection, which is what preserves convergence.

        Consensus digests the DECODED DENSE result: the rank side via
        ``_screen_reduced`` over these results, the coordinator side
        via the same ``sparse_wire.decode_sum`` over the combined
        payload — bit-identical float scatter order by construction."""
        import math as _math

        from . import sparse_wire
        from .compression import Compression

        tl = self.timeline
        chaos = self._data_chaos
        watch = self._tensorwatch
        comp = Compression.lookup(codec)
        feedback = self._sparse_error_feedback
        epoch = basics.world_epoch()
        if epoch != self._sparse_epoch:
            # elastic relaunch: the restored world restarted from
            # committed state, so replaying pre-relaunch residuals would
            # double-count the mass they carry
            self._sparse_residuals.clear()
            self._sparse_epoch = epoch
        fused = len(entries) > 1
        names = [e.name for e in entries]
        if self._plane is not None:
            # Device plane (host-fed entries ride it too, like the dense
            # path): compiled per-entry select/decode around the shared
            # tiled all_gather program — no full-buffer D2H, residuals
            # stay device-resident. Plane presence is world-uniform
            # (the XLA plane requires one JAX process per rank), so
            # every rank issues the same collective sequence.
            for e in entries:
                tl.activity_start(e.name, "EXECUTE")
            residuals = [self._sparse_residuals.get(e.name)
                         if feedback else None for e in entries]
            results, new_res, stats = self._device_call(
                self._plane.sparse_allreduce_onchip,
                [e.array for e in entries], residuals, comp, feedback)
            if feedback:
                for e, r in zip(entries, new_res):
                    self._sparse_residuals[e.name] = r
            sparse_wire.account_batch(
                stats["selected"], stats["dropped"], stats["wire_bytes"],
                _math.sqrt(stats["residual_norm2"]), "onchip")
            for e in entries:
                tl.activity_end(e.name)
            if watch is not None and watch.sampling:
                watch.observe_batch(names, [e.array for e in entries],
                                    results, codec)
            return results
        # Host path: numpy select over the fused corrected buffer, the
        # wire over the coordinator's payload exchange (or local for a
        # world of one — still lossy, the codec's semantics don't change
        # with world size).
        spans, off = [], 0
        for e in entries:
            n = int(e.array.size)
            spans.append((off, n))
            off += n
        n_dense = off
        if fused:
            for e in entries:
                tl.activity_start(e.name, "MEMCPY_IN_FUSION_BUFFER")
        parts = []
        for e, (start, n) in zip(entries, spans):
            flat = np.asarray(e.array).ravel().astype(np.float32,
                                                      copy=False)
            if feedback:
                r = self._sparse_residuals.get(e.name)
                if r is not None:
                    flat = flat + r
            parts.append(flat)
        buf = np.concatenate(parts) if fused \
            else np.ascontiguousarray(parts[0])
        if fused:
            for e in entries:
                tl.activity_end(e.name)
        if chaos is not None:
            # nan faults poison a COPY of the local input pre-selection,
            # the same boundary as the dense path (docs/integrity.md)
            buf = chaos.on_reduce_input(buf)
        for e in entries:
            tl.activity_start(e.name, "EXECUTE")
        idx_parts, val_parts = [], []
        new_res: Dict[str, np.ndarray] = {}
        k_total = 0
        res_norm2 = 0.0
        for e, (start, n) in zip(entries, spans):
            seg = buf[start:start + n]
            k = comp.k_of(n)
            sidx, svals = sparse_wire.topk_select(seg, k)
            idx_parts.append(
                (sidx.astype(np.int64) + start).astype(np.int32))
            val_parts.append(svals)
            if feedback:
                r = np.array(seg, dtype=np.float32, copy=True)
                r[sidx] = 0.0
                new_res[e.name] = r
                res_norm2 += float(np.dot(r, r))
            k_total += k
        payload = sparse_wire.pack_pairs(np.concatenate(idx_parts),
                                         np.concatenate(val_parts))
        if self._client is None:
            combined, size = payload, 1
        else:
            combined = self._client.payload(self._rank, idx, payload,
                                            cycle_no=cycle_no)
            size = self._size
        g_idx, g_vals = sparse_wire.unpack_wire(combined, size)
        if chaos is not None:
            # flipbits faults corrupt THIS rank's received sparse INDEX
            # stream — a flipped index lands mass on the wrong row, the
            # decoded-dense divergence the consensus digests exist to
            # catch (docs/integrity.md; residual bookkeeping above used
            # the ORIGINAL selected indices, never the flipped ones)
            g_idx = chaos.on_sparse_indices(g_idx)
        out = sparse_wire.scatter_sum(g_idx, g_vals, n_dense)
        if feedback:
            # commit only after a successful exchange: a wire failure
            # must not half-advance the residual state
            self._sparse_residuals.update(new_res)
        sparse_wire.account_batch(k_total, n_dense - k_total,
                                  len(payload), _math.sqrt(res_norm2),
                                  "host")
        for e in entries:
            tl.activity_end(e.name)
        results = []
        if fused:
            for e in entries:
                tl.activity_start(e.name, "MEMCPY_OUT_FUSION_BUFFER")
        for e, (start, n) in zip(entries, spans):
            results.append(out[start:start + n].reshape(e.array.shape))
        if fused:
            for e in entries:
                tl.activity_end(e.name)
        if watch is not None and watch.sampling:
            watch.observe_batch(names, [e.array for e in entries],
                                results, codec)
        return results

    # -- fused reduce+apply (docs/tensor-fusion.md §fused apply) --------------

    def _warn_apply_once(self, key: str, msg: str, *args) -> None:
        if ("apply", key) in self._host_fallback_warned:
            return
        self._host_fallback_warned.add(("apply", key))
        LOG.warning(msg, *args)

    def _apply_leaf(self, ctx: ApplyContext, reduced) -> ApplyResult:
        """Split-path per-leaf apply: ONE jitted program per leaf — the
        same ``bucket_apply_fn`` family the fused route compiles over
        the whole bucket, so split and fused are bit-identical by
        construction (the update is elementwise; XLA's within-program
        op fusion is shape-independent, pinned by the twin tests). The
        average divide rides in-program (``denom``), gate off: the
        sentry already screened the reduced batch at full tensor
        granularity on this route."""
        from .fused_apply import bucket_apply_fn

        denom = self._size if ctx.average and self._size > 1 else 1
        out = bucket_apply_fn(ctx.rule, False, denom)(
            reduced, ctx.param, np.int32(ctx.count), *ctx.slots)
        self._apply_counts["dispatches"] += 1
        _APPLY_DISPATCHES.inc()
        return ApplyResult(out[0], tuple(out[3:]))

    def _run_reduce_apply(self, idx: int, entries: List[TensorTableEntry],
                          resp: Response,
                          cycle_no: Optional[int] = None) -> List:
        """Execute one apply-capable allreduce batch: the flush lands
        APPLIED parameters and fresh optimizer slots (``ApplyResult``)
        instead of reduced gradients.

        Two strategies, numerics-identical by the shared ``ApplyRule``
        math:

        * **fused** — ONE compiled reduce+apply dispatch per batch: on
          the device plane the psum (or quantized decode), loss-scale
          unscale, nonfinite census, and leaf update compile into a
          single donated program (``XlaDataPlane.reduce_apply``); on the
          host plane the TCP exchange reduces and one bucket program
          applies. Requires the negotiated ``Response.fused_apply``
          kind — the Python controller's guarantee that the batch is
          rule-uniform on every rank.
        * **split** — the reduce exactly as a plain batch (full sentry
          tensor granularity included), then one jitted apply per leaf:
          the degrade for the native controller wire (which predates
          the fingerprint field), mixed batches, non-uniform step
          counts, and the ``fused_apply`` tuned knob's 0 position.

        Consensus digests the reduced bytes PRE-apply on both routes;
        the sentry's verdict exchange runs per batch on both routes, at
        batch granularity under fused (the in-program census gate
        already made a poisoned step a collective no-op)."""
        codec = getattr(resp, "tensor_codec", "none")
        ctxs = [e.apply for e in entries]
        fingerprint = getattr(resp, "fused_apply", "")
        # rank-identical by construction: apply contexts are a
        # deterministic function of replicated front-end state (same
        # tensors, same step counts, same average flag on every rank),
        # the fingerprint rides the negotiated response, and the exec
        # flag is init-pinned on the device plane — so every rank takes
        # the same fused/split branch for the same batch
        uniform = all(c is not None for c in ctxs) and len(
            {(c.rule.fingerprint, c.count, c.average)
             for c in ctxs if c is not None}) == 1
        # ZeRO-1 batch (docs/sharding.md): every context carries shard
        # slots and the init-pinned capability is armed. A MIXED batch
        # (some shard, some full) is a submission bug — shard slots
        # cannot take the split path (their shapes are 1/N of the leaf),
        # so it must fail loudly, never degrade.
        zero1 = self._zero1_exec and uniform and \
            all(c.zero1 for c in ctxs)
        if not zero1 and any(c is not None and c.zero1 for c in ctxs):
            raise RuntimeError(
                f"ZeRO-1 batch cannot execute: zero1 submissions mixed "
                f"with non-zero1 contexts or the capability is unarmed "
                f"(exec={self._zero1_exec}) for batch "
                f"{[e.name for e in entries]}")
        fused = bool(fingerprint) and uniform and self._fused_apply_exec
        if zero1 and not fused and uniform and self._fused_apply_exec:
            # the native controller wire predates the fingerprint field,
            # so its responses cannot negotiate the fused kind — but a
            # zero1 batch has no split fallback (shard slots), and the
            # rank-side uniformity decision is deterministic and
            # rank-identical (same replicated front-end state, same
            # init-pinned flags), so arming fused here is safe on every
            # rank at once.
            self._warn_apply_once(
                "zero1-wire",
                "ZeRO-1 batch on a controller wire without the apply "
                "fingerprint field: arming the fused route from "
                "rank-side uniformity (deterministic on every rank).")
            fused = True
        if fused and _is_sparse_codec(
                getattr(resp, "tensor_codec", "none")):
            if zero1:
                # shard slots cannot take the split path the sparse
                # downgrade needs; apply_step's fusable gate keeps
                # sparse codecs off the zero1 route, so reaching here
                # means a mid-run codec change — fail loudly.
                raise RuntimeError(
                    "ZeRO-1 batches cannot ride a sparse (top-k) codec; "
                    "keep HOROVOD_ZERO=1 runs on a dense or quantized "
                    "compression")
            # Sparse batches downgrade to the two-dispatch split (the
            # existing _downgrade_codec composition rule): the sparse
            # decode is a gather+scatter, not a psum, so it cannot ride
            # the donated reduce+apply program. Negotiated-codec
            # decision — every rank splits the same batches.
            self._warn_apply_once(
                "sparse-split",
                "fused reduce+apply degrades to the split "
                "reduce-then-apply execution for sparse (top-k) "
                "batches; applied parameters still land.")
            fused = False
        # flight recorder (docs/blackbox.md): the negotiated fused-apply
        # strategy and fingerprint for this batch — the evidence a
        # postmortem needs when one rank applied and another reduced.
        # Enabled check BEFORE building the detail string: the disabled
        # path must stay allocation-free (the HOROVOD_FLIGHTREC=0
        # contract pinned by the tracemalloc test).
        if _flightrec.recorder().enabled:
            _flightrec.record(
                _flightrec.EV_FUSED_APPLY,
                ordinal=-1 if cycle_no is None else cycle_no,
                detail=("fused:" if fused else "split:") + fingerprint[:16])
        if fused and fingerprint and \
                fingerprint != ctxs[0].rule.fingerprint:
            # the coordinator negotiated a different apply program than
            # this rank submitted — a bug, never a silent divergence
            raise RuntimeError(
                f"fused-apply desync: response negotiated rule "
                f"{fingerprint!r} but rank {self._rank} submitted "
                f"{ctxs[0].rule.fingerprint!r} for batch "
                f"{[e.name for e in entries]}")
        if not fused:
            if not fingerprint and uniform and self._fused_apply_exec:
                self._warn_apply_once(
                    "split-wire",
                    "fused reduce+apply degrades to the split "
                    "reduce-then-apply execution: this controller wire "
                    "predates the apply fingerprint field (set "
                    "HOROVOD_NATIVE_CONTROLLER=0 for single-dispatch "
                    "apply batches). Applied parameters still land.")
            reduced = self._run_allreduce(idx, entries, codec,
                                          cycle_no=cycle_no)
            if self._sentry is not None or self._consensus_acc is not None:
                reduced = self._screen_reduced(entries, reduced)
            self._apply_counts["split"] += 1
            _REDUCE_APPLY_BATCHES.labels(mode="split").inc()
            return [r if e.apply is None else self._apply_leaf(e.apply, r)
                    for e, r in zip(entries, reduced)]

        from .fused_apply import bucket_apply_fn
        from .xla_plane import _next_bucket

        tl = self.timeline
        chaos = self._data_chaos
        if chaos is not None:
            chaos.begin_batch()  # same ordinal domain as plain batches
        watch = self._tensorwatch
        if watch is not None:
            watch.begin_batch()  # same ordinal domain as plain batches
        rule, count = ctxs[0].rule, ctxs[0].count
        denom = self._size if ctxs[0].average and self._size > 1 else 1
        # census gate: for skip/zero/abort the program must not land a
        # poisoned update (abort tears the world down right after, but
        # the params a restore reads must be the ungated ones); warn/off
        # hand values through like the two-dispatch path would
        gate = self._sentry is not None and \
            self._sentry.policy in ("skip", "zero", "abort")
        if gate and self._sentry.policy == "zero" and \
                len(entries) > 1:
            self._warn_apply_once(
                "zero-granularity",
                "HOROVOD_GRAD_SENTRY=zero applies at BATCH granularity "
                "under fused reduce+apply (the in-program census gate "
                "zeroes the whole batch, i.e. skip semantics); use the "
                "split execution for per-tensor nulling.")
        shapes = [tuple(int(s) for s in e.array.shape) for e in entries]
        sizes = [int(np.prod(s, dtype=np.int64)) if s else 1
                 for s in shapes]
        total = int(sum(sizes))
        bucket = _next_bucket(total)
        codec = self._downgrade_codec(entries[0], codec)
        for e in entries:
            tl.activity_start(e.name, "EXECUTE")
        # the observatory measures the reduced gradients pre-apply, so a
        # sampled apply-fused batch needs the host views too (one D2H on
        # the device route, sampled steps only — documented in
        # docs/tensorwatch.md; the plain route keeps the scalar probes)
        need_views = self._consensus_acc is not None or \
            self._sentry is not None or \
            (watch is not None and watch.sampling)
        if zero1:
            # ZeRO-1 device route (docs/sharding.md): shard-major
            # packing, then ONE compiled reduce-scatter → shard-apply →
            # all-gather dispatch with donated param/slot buckets. The
            # host-side interleave forces a D2H per leaf — acceptable on
            # the proof surface; device-resident packing is the
            # follow-on optimization the layout was designed for.
            from ..sharding import zero1 as _z1

            sh_lens = [_z1.shard_len(n, self._size) for n in sizes]
            sbucket = _next_bucket(int(sum(sh_lens)))
            grad_rows = _z1.pack_rows(
                [np.asarray(e.array) for e in entries],
                self._size, sbucket)
            param_full = _z1.pack_rows(
                [c.param for c in ctxs], self._size, sbucket)
            slot_rows = [
                _z1.pack_shard_row([c.slots[k] for c in ctxs], sbucket)
                for k in range(rule.nslots)]
            red_rows, newp_rows, nan, inf, slot_out_rows = \
                self._device_call(
                    self._plane.reduce_scatter_apply, grad_rows,
                    param_full, count, slot_rows, rule, codec, gate,
                    denom)
            new_p_leaves = _z1.unpack_rows(
                np.asarray(newp_rows), shapes, self._size, sbucket)
            _z1.record_imbalance(grad_rows, np.asarray(red_rows),
                                 self._size)
            slot_shards = [_z1.split_shard_row(np.asarray(r), sh_lens)
                           for r in slot_out_rows]
            red_leaves = _z1.unpack_rows(
                np.asarray(red_rows), shapes, self._size, sbucket) \
                if need_views else None
        elif self._plane is not None and self._plane.supports(
                dtype_of(entries[0].array)):
            # device route: pack grad/param/slot buckets, ONE compiled
            # psum+apply dispatch with donated buckets
            write = self._plane._write_fn(np.dtype(np.float32),
                                          np.dtype(np.float32))
            zeros = self._plane._zeros_fn(bucket, np.dtype(np.float32))

            def pack(leaves):
                buf, off = zeros(), 0
                for leaf, n in zip(leaves, sizes):
                    buf = write(buf, leaf, off)
                    off += n
                return buf

            grad_buf = pack([e.array for e in entries])
            param_buf = pack([c.param for c in ctxs])
            slot_bufs = [pack([c.slots[k] for c in ctxs])
                         for k in range(rule.nslots)]
            self._plane._account_allreduce(
                "apply", total, np.dtype(np.float32).itemsize,
                np.float32, codec)
            reduced, new_p, nan, inf, new_slots = self._device_call(
                self._plane.reduce_apply, grad_buf, param_buf, count,
                slot_bufs, rule, codec, gate, denom)
            read = lambda buf, shape, n, off: self._plane._read_fn(  # noqa: E731
                shape, n, np.dtype(np.float32), np.dtype(np.float32),
                bucket)(buf, off)
            red_host = np.asarray(reduced) if need_views else None
        else:
            # host route: the TCP exchange reduces (the same unpadded
            # concat bytes a plain batch would ship), then one bucket
            # program applies (census+gate+divide+update in a single
            # dispatch)
            buf = np.empty((total,), np.float32)
            off = 0
            for e, n in zip(entries, sizes):
                buf[off:off + n] = np.asarray(e.array).ravel()
                off += n
            if chaos is not None:
                buf = chaos.on_reduce_input(buf)
            if self._client is None:
                out = np.array(buf, copy=True)  # world of one
            else:
                raw = self._client.payload(
                    self._rank, idx,
                    np.ascontiguousarray(buf).tobytes(),
                    cycle_no=cycle_no)
                out = np.frombuffer(raw, dtype=np.float32).copy()
            if chaos is not None:
                out = chaos.on_reduce_output(out)
            # np.empty + explicit tail zero: the pad region only needs
            # deterministic FINITE values (the census reads g; params
            # and slots are never read back past ``total``), and
            # zero-filling whole power-of-two buckets was measurable on
            # the bench at fusion-buffer sizes
            gpad = np.empty((bucket,), np.float32)
            gpad[:total] = out[:total]
            gpad[total:] = 0.0
            ppad = np.empty((bucket,), np.float32)
            ppad[total:] = 0.0
            spads = [np.empty((bucket,), np.float32)
                     for _ in range(rule.nslots)]
            off = 0
            for c, n in zip(ctxs, sizes):
                ppad[off:off + n] = np.asarray(c.param).ravel()
                for k in range(rule.nslots):
                    spads[k][off:off + n] = np.asarray(c.slots[k]).ravel()
                off += n
            for k in range(rule.nslots):
                spads[k][total:] = 0.0
            fused_out = bucket_apply_fn(rule, gate, denom)(
                gpad, ppad, np.int32(count), *spads)
            new_p = np.asarray(fused_out[0])  # one D2H per bucket
            nan, inf = int(fused_out[1]), int(fused_out[2])
            new_slots = [np.asarray(s) for s in fused_out[3:]]
            red_host = gpad if need_views else None
            read = lambda buf, shape, n, off: \
                buf[off:off + n].reshape(shape)  # noqa: E731
        self._apply_counts["fused"] += 1
        if zero1:
            self._apply_counts["zero1"] += 1
        self._apply_counts["dispatches"] += 1
        _REDUCE_APPLY_BATCHES.labels(
            mode="zero1" if zero1 else "fused").inc()
        _APPLY_DISPATCHES.inc()
        names = [e.name for e in entries]
        if need_views:
            if zero1:
                # the program all-gathers the raw reduced bucket, so
                # every rank digests identical PRE-apply bytes — the
                # same consensus framing as the replicated routes
                views = red_leaves
            else:
                views, off = [], 0
                for shape, n in zip(shapes, sizes):
                    views.append(red_host[off:off + n].reshape(shape))
                    off += n
            if watch is not None and watch.sampling:
                # numerics observatory: the reduced gradients as
                # received, PRE-apply (the consensus framing)
                watch.observe_batch(names,
                                    [e.array for e in entries], views,
                                    codec)
            # consensus FIRST, on the raw reduced bytes (pre-apply, the
            # docs/integrity.md contract), then the sentry's collective
            # verdict off the in-program two-scalar census
            if self._consensus_acc is not None:
                self._consensus_acc.observe_batch(names, views)
            if self._sentry is not None:
                trips_before = len(self._sentry.trips)
                self._sentry.screen_batch(names, views,
                                          precomputed=(int(nan),
                                                       int(inf)))
                if gate and int(nan) + int(inf) == 0 and \
                        len(self._sentry.trips) > trips_before:
                    if zero1:
                        # the sharded program's census is already
                        # GLOBAL (shard counts psum-med in-program), so
                        # every rank's gate fired on the same collective
                        # verdict — a trip with a clean global census
                        # means the sentry's exchange saw something the
                        # census cannot express; consensus names the
                        # divergence, and a collective-free local
                        # rewrite is impossible without peer slot
                        # shards, so keep the landed result.
                        self._warn_apply_once(
                            "zero1-trip",
                            "sentry tripped on a ZeRO-1 batch with a "
                            "clean global census; keeping the landed "
                            "update (the in-program gate verdict is "
                            "already collective under ZeRO-1).")
                    else:
                        # The COLLECTIVE verdict says bad but this
                        # rank's local census was clean — a
                        # peer-divergent reduced buffer (the sentry's
                        # "peer" kind): the in-program gate fired on
                        # the bad rank but not here, so the full update
                        # already landed locally. Recompute the
                        # zero-gradient step from the UNTOUCHED
                        # submission contexts (collective-free — never
                        # a psum re-run) so every rank converges on the
                        # identical no-op update the gated rank
                        # applied.
                        new_p, new_slots = self._zero_grad_apply(
                            rule, ctxs, sizes, total, bucket, count,
                            denom)
                        read = lambda buf, shape, n, off: \
                            buf[off:off + n].reshape(shape)  # noqa: E731
        if zero1:
            results = [
                ApplyResult(new_p_leaves[i],
                            tuple(slot_shards[k][i]
                                  for k in range(rule.nslots)))
                for i in range(len(entries))]
        else:
            results, off = [], 0
            for shape, n in zip(shapes, sizes):
                results.append(ApplyResult(
                    read(new_p, shape, n, off),
                    tuple(read(s, shape, n, off) for s in new_slots)))
                off += n
        for e in entries:
            tl.activity_end(e.name)
        return results

    def _zero_grad_apply(self, rule, ctxs, sizes, total: int,
                         bucket: int, count: int, denom: int):
        """The collective sentry rewrite for an apply-fused batch whose
        LOCAL census was clean: re-run the bucket apply with a zeroed
        gradient over the original param/slot leaves — the exact step
        the census gate computed on the rank that saw the fault (the
        gate zeroes the gradient before the divide), so the world
        converges. Host buckets are bit-identical (same gated program,
        same shapes); a device-plane batch recomputes through the host
        program, within 1 ulp of the peer's in-program chain — in a
        scenario where the reduced bytes already diverged, which armed
        consensus names loudly regardless."""
        from .fused_apply import bucket_apply_fn

        gpad = np.zeros((bucket,), np.float32)
        ppad = np.empty((bucket,), np.float32)
        ppad[total:] = 0.0
        spads = [np.empty((bucket,), np.float32)
                 for _ in range(rule.nslots)]
        off = 0
        for c, n in zip(ctxs, sizes):
            ppad[off:off + n] = np.asarray(c.param).ravel()
            for k in range(rule.nslots):
                spads[k][off:off + n] = np.asarray(c.slots[k]).ravel()
            off += n
        for k in range(rule.nslots):
            spads[k][total:] = 0.0
        out = bucket_apply_fn(rule, True, denom)(
            gpad, ppad, np.int32(count), *spads)
        return np.asarray(out[0]), [np.asarray(s) for s in out[3:]]

    def apply_stats(self) -> Dict[str, Any]:
        """Fused reduce+apply counters for tests, the dryrun
        certification, and bench provenance (zeros when the plane never
        ran)."""
        return {
            "exec_fused": self._fused_apply_exec,
            "exec_zero1": self._zero1_exec,
            "fused_batches": self._apply_counts["fused"],
            "split_batches": self._apply_counts["split"],
            "zero1_batches": self._apply_counts["zero1"],
            "apply_dispatches": self._apply_counts["dispatches"],
        }

    def _run_allgather(self, idx: int, entry: TensorTableEntry,
                       resp: Response,
                       cycle_no: Optional[int] = None) -> List[np.ndarray]:
        if _is_jax_array(entry.array):
            if self._client is None:
                # size-1 concat == the (private, snapshot) array itself
                return [entry.array]
            if self._plane is not None and self._plane.supports_move(
                    dtype_of(entry.array)):
                return [self._device_call(self._plane.allgather_onchip,
                                          entry.array, resp.tensor_sizes)]
        arr = np.asarray(entry.array)  # lazy D2H for device submissions
        if self._client is None:
            return [arr.copy()]
        if self._plane is not None and self._plane.supports_move(
                dtype_of(arr)):
            return [self._device_call(self._plane.allgather,
                                      np.ascontiguousarray(arr),
                                      resp.tensor_sizes)]
        if self._plane is not None:
            self._warn_host_fallback("allgather", entry.name, arr)
        raw = self._client.payload(
            self._rank, idx, np.ascontiguousarray(arr).tobytes(),
            cycle_no=cycle_no)
        total_first = sum(resp.tensor_sizes)
        shape = (total_first,) + tuple(arr.shape[1:])
        return [np.frombuffer(raw, dtype=arr.dtype)
                .reshape(shape).copy()]

    def _run_broadcast(self, idx: int, entry: TensorTableEntry,
                       resp: Response,
                       cycle_no: Optional[int] = None) -> List[np.ndarray]:
        root = resp.tensor_sizes[0]
        if _is_jax_array(entry.array):
            if self._client is None:
                # size-1 broadcast == the (private, snapshot) array itself
                return [entry.array]
            if self._plane is not None and self._plane.supports_move(
                    dtype_of(entry.array)):
                return [self._device_call(self._plane.broadcast_onchip,
                                          entry.array, root)]
        arr = np.asarray(entry.array)  # lazy D2H for device submissions
        if self._client is None:
            return [arr.copy()]
        if self._plane is not None and self._plane.supports_move(
                dtype_of(arr)):
            return [self._device_call(self._plane.broadcast,
                                      np.ascontiguousarray(arr), root)]
        if self._plane is not None:
            self._warn_host_fallback("broadcast", entry.name, arr)
        payload = np.ascontiguousarray(arr).tobytes() \
            if self._rank == root else b""
        raw = self._client.payload(self._rank, idx, payload,
                                   cycle_no=cycle_no)
        return [np.frombuffer(raw, dtype=arr.dtype)
                .reshape(arr.shape).copy()]

    # -- shutdown -------------------------------------------------------------

    def stop(self, timeout: float = 10.0) -> None:
        """Coordinated shutdown: the next cycle carries shutdown=True, the
        coordinator re-broadcasts it, every rank drains
        (``operations.cc:2065,2125-2128,2150,2374-2376``)."""
        self._stop_requested = True
        self._wake.set()
        self._stopped.wait(timeout)


def start_subset_service(subset_ranks) -> None:
    """Host the controller service for a subset world this process is NOT
    a member of (launcher world-rank 0 outside ``init(ranks=...)``): the
    launcher advertised this host's address, so the subset's control
    cycles and host-plane exchanges must rendezvous here. No engine, no
    client — pure service duty, torn down by ``hvd.shutdown``."""
    from .native_controller import (
        NativeControllerService,
        native_controller_enabled,
    )

    from .controller import world_id_of

    cfg = basics.config()
    subset_ranks = list(subset_ranks)
    subset_size = len(subset_ranks)
    # the SAME identity the members compute from their topology
    world_id = world_id_of(tuple(subset_ranks), subset_size)
    port = int(os.environ.get(_config.HOROVOD_CONTROLLER_PORT, "0"))
    bind_host = os.environ.get(_config.HOROVOD_CONTROLLER_BIND,
                               "127.0.0.1")
    use_native = native_controller_enabled(cfg)
    # local_observatory=False: this host runs NO engine, so nothing in
    # this process could ever feed the numerics observatory's evidence
    # gate — armed gating here would block the consented codec forever
    # (docs/tensorwatch.md); it degrades to consent-only, warned once.
    autotuner = Autotuner(cfg, extended=not use_native,
                          local_observatory=False) \
        if cfg.autotune else None
    listen_fd = _adopt_controller_fd(use_native)
    if use_native:  # same decision the members make
        service = NativeControllerService(
            subset_size, cfg, secret=default_secret(), port=port,
            bind_host=bind_host, autotuner=autotuner, world_id=world_id)
    else:
        detector = None
        if cfg.straggler_evict != "off":
            from ..tune.detector import StragglerDetector

            detector = StragglerDetector.from_config(cfg, subset_size)
        service = ControllerService(
            subset_size, make_negotiator(subset_size, cfg),
            secret=default_secret(), port=port, bind_host=bind_host,
            autotuner=autotuner, world_id=world_id,
            stall_shutdown_s=cfg.stall_shutdown_time_s,
            stall_warning_s=cfg.stall_warning_time_s,
            listen_fd=listen_fd,
            cache_capacity=cfg.cache_capacity,
            fusion_threshold_bytes=cfg.fusion_threshold_bytes,
            straggler_detector=detector,
            codec_min_bytes=cfg.autotune_codec_min_bytes,
            consensus_interval_steps=cfg.consensus_interval_steps,
            # Same gating as the member-hosted service above: the subset's
            # members resolve their own data plane from this same config,
            # so only a definitely-host-plane world gets the grace window
            # by default ("auto" may resolve to XLA on the members, where
            # death attribution must stay immediate).
            reconnect_window_s=cfg.reconnect_window_s if (
                cfg.data_plane == "host" or cfg.reconnect_window_explicit
            ) else 0.0)

    def _teardown() -> None:
        # Grace period: the host's own shutdown (often atexit) must not
        # yank the controller from a subset that is still mid-job.
        if not service.wait_world_shutdown(30.0):
            LOG.warning("subset-service host exiting before the subset "
                        "negotiated shutdown; tearing the controller down")
        service.shutdown()
        if autotuner is not None:
            autotuner.close()

    basics._state().engine_shutdown_hooks.append(_teardown)


_engine_lock = threading.Lock()
_engine: Optional[Engine] = None


def get_engine() -> Engine:
    """Lazy singleton start; registers teardown with ``basics.shutdown``."""
    global _engine
    with _engine_lock:
        if _engine is not None and _engine._stopped.is_set():
            # The engine stopped WITHOUT a local ``hvd.shutdown()`` (which
            # clears the singleton through _shutdown_engine): the world
            # ended underneath this process — a peer's negotiated
            # shutdown, or an escalated abort. Surface the reference's
            # shut-down semantics with the structured reason
            # (RanksAbortedError parses out of it); silently building a
            # replacement engine here raced the dying controller and
            # turned the abort into a bare "connection refused".
            Status.unknown_error(
                _engine._shutdown_reason or SHUT_DOWN_ERROR
            ).raise_if_error()
        if _engine is None:
            basics._topology()  # raises NotInitializedError when appropriate
            engine = Engine()
            basics._state().engine_shutdown_hooks.append(
                lambda: _shutdown_engine(engine))
            _engine = engine
        return _engine


def _shutdown_engine(engine: Engine) -> None:
    global _engine
    engine.stop()
    with _engine_lock:
        if _engine is engine:
            _engine = None
