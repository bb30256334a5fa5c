"""Runtime configuration knobs, all environment variables.

The reference has no config files and no CLI parser in the library: every
runtime knob is an env var read in ``BackgroundThreadLoop``
(``horovod/common/operations.cc:1707,1825-1909``; names declared at
``operations.h:57-66``). We keep the exact same names (HOROVOD_*) so that
operational muscle memory and docs transfer, and add a small number of
TPU-specific knobs (controller address, virtual world description) needed
because our control plane is TCP rather than MPI.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

# --- reference knob names (operations.h:57-66) -------------------------------
HOROVOD_FUSION_THRESHOLD = "HOROVOD_FUSION_THRESHOLD"
HOROVOD_CYCLE_TIME = "HOROVOD_CYCLE_TIME"
HOROVOD_TIMELINE = "HOROVOD_TIMELINE"
HOROVOD_TIMELINE_MARK_CYCLES = "HOROVOD_TIMELINE_MARK_CYCLES"
# Distributed tracing (docs/tracing.md; ours): plain HOROVOD_TIMELINE
# stays rank-0-only for back-compat with the reference artifact; setting
# this to 1 makes EVERY member rank record spans into a rank-suffixed
# file (<path>.rankN.json) that tools/trace_merge.py folds into one
# clock-corrected Chrome trace with a process lane per rank.
HOROVOD_TIMELINE_ALL_RANKS = "HOROVOD_TIMELINE_ALL_RANKS"
# Seconds between clock-alignment handshakes against the coordinator
# (min-RTT-filtered ping battery; obs/tracing.py). <= 0 disables the
# periodic re-sync (the init-time sync still runs where the plane is
# active at all).
HOROVOD_CLOCK_SYNC_INTERVAL = "HOROVOD_CLOCK_SYNC_INTERVAL_S"
# TPU-side twin of the timeline (SURVEY §5.1 mapping): the host timeline
# records enqueue/negotiate/execute; on-device time lives in the XLA
# profiler. This knob brackets init→shutdown with a jax.profiler trace on
# rank 0, so both artifacts land side by side.
HOROVOD_JAX_PROFILE = "HOROVOD_JAX_PROFILE"
HOROVOD_STALL_CHECK_DISABLE = "HOROVOD_STALL_CHECK_DISABLE"
# Extension: the reference hardcodes 60s (STALL_WARNING_TIME,
# operations.cc:258); configurable here, same default.
HOROVOD_STALL_WARNING_TIME = "HOROVOD_STALL_WARNING_TIME"
# Fault-tolerance escalation (horovod_tpu.elastic): a stall that outlives
# this many seconds is converted from a warning into a structured world
# abort — every healthy rank raises RanksAbortedError naming the missing
# ranks instead of blocking forever. 0 (default) keeps the reference's
# warn-and-wait behavior; upstream Horovod later grew the same knob as
# HOROVOD_STALL_SHUTDOWN_TIME_SECONDS.
HOROVOD_STALL_SHUTDOWN_TIME = "HOROVOD_STALL_SHUTDOWN_TIME_S"
HOROVOD_HIERARCHICAL_ALLREDUCE = "HOROVOD_HIERARCHICAL_ALLREDUCE"
HOROVOD_HIERARCHICAL_ALLGATHER = "HOROVOD_HIERARCHICAL_ALLGATHER"
# Default gradient-compression codec for DistributedOptimizer /
# allreduce_gradients when the caller does not pass compression=
# explicitly: none (default) / fp16 / bf16 / int8 / fp8. Extension beyond
# the reference (which only has the per-call Compression argument): the
# quantized wire (EQuARX int8/fp8) is an operational knob one wants to
# flip fleet-wide without touching training code. docs/compression.md.
HOROVOD_COMPRESSION = "HOROVOD_COMPRESSION"
# Steady-state negotiation bypass (docs/response-cache.md): max cached
# fused responses per rank/coordinator; 0 disables the cache-bit fast
# path. Upstream Horovod later grew the same knob as HOROVOD_CACHE_CAPACITY.
# Must resolve identically on every rank (the launcher's env export does
# this): cache coherence is deterministic replay of identical transitions,
# and capacity participates in eviction choices.
HOROVOD_CACHE_CAPACITY = "HOROVOD_CACHE_CAPACITY"
HOROVOD_AUTOTUNE = "HOROVOD_AUTOTUNE"
HOROVOD_AUTOTUNE_LOG = "HOROVOD_AUTOTUNE_LOG"
# --- closed-loop tuning plane (horovod_tpu.tune; ours, docs/autotune.md) -----
# Optimizer backend behind HOROVOD_AUTOTUNE=1: "policy" (default) is the
# pure-Python coordinate-descent/hill-climb loop — no native core needed;
# "native" opts back into the C++ GP/Bayesian parameter manager
# (cc/autotune.cc), which tunes only the classic (fusion, cycle) pair.
HOROVOD_AUTOTUNE_BACKEND = "HOROVOD_AUTOTUNE_BACKEND"
# Scored cycles folded (median) into one measurement window (default 5,
# the reference's median-of-5), and cycles discarded after each knob move
# before measurement resumes (default 5) — a just-applied knob reaches
# every rank one response later, so the first post-move cycles mix
# configurations and must not score.
HOROVOD_AUTOTUNE_WINDOW = "HOROVOD_AUTOTUNE_WINDOW"
HOROVOD_AUTOTUNE_COOLDOWN = "HOROVOD_AUTOTUNE_COOLDOWN"
# Relative score-regression tolerance of the revert guard (default 0.05):
# a measured window worse than best_known * (1 - tolerance) rolls the
# move back to the best-known config.
HOROVOD_AUTOTUNE_TOLERANCE = "HOROVOD_AUTOTUNE_TOLERANCE"
# JSONL decision audit log (one line per retune/revert; rendered by
# tools/tune_report.py). Distinct from HOROVOD_AUTOTUNE_LOG, the per-cycle
# CSV sample log.
HOROVOD_AUTOTUNE_DECISIONS = "HOROVOD_AUTOTUNE_DECISIONS"
# Opt-in codec ladder for the codec knob, e.g. "int8,fp8". EMPTY (the
# default) pins the codec: quantized wires are lossy, so the tuner may
# only explore them when the operator explicitly consents. Only
# codec=="none" allreduce batches at least CODEC_MIN_BYTES big (the
# "large gradient" tensor class, default 4096) are rewritten; explicitly
# quantized traffic is never touched.
HOROVOD_AUTOTUNE_CODECS = "HOROVOD_AUTOTUNE_CODECS"
HOROVOD_AUTOTUNE_CODEC_MIN_BYTES = "HOROVOD_AUTOTUNE_CODEC_MIN_BYTES"
# Deterministic test hook (the HOROVOD_ELASTIC_FAULT pattern):
# "regress@N" scales every score observed after the Nth accepted retune
# so the next measured window regresses and the revert guard must fire
# exactly once (the fault clears itself on the first revert).
HOROVOD_AUTOTUNE_FAULT = "HOROVOD_AUTOTUNE_FAULT"
# Persistent-straggler mitigation (docs/autotune.md): "off" (default) /
# "advisory" (detector verdicts are counted, logged, and pushed to the
# elastic driver, which records them) / "enforce" (the elastic driver
# additionally blacklists the named slot and relaunches through the
# elastic path). Unknown values fail loudly at detector construction.
HOROVOD_STRAGGLER_EVICT = "HOROVOD_STRAGGLER_EVICT"
# Sliding window the detector folds blame-seconds over (default 30 s)
# and the minimum attributed cycles inside it before any verdict
# (default 20) — a handful of cycles must never name a straggler.
HOROVOD_STRAGGLER_WINDOW = "HOROVOD_STRAGGLER_WINDOW_S"
HOROVOD_STRAGGLER_MIN_CYCLES = "HOROVOD_STRAGGLER_MIN_CYCLES"
HOROVOD_LOG_LEVEL = "HOROVOD_LOG_LEVEL"
HOROVOD_LOG_HIDE_TIME = "HOROVOD_LOG_HIDE_TIME"

# --- launcher / control-plane knobs (ours; role of mpirun's env in the ref) --
HOROVOD_RANK = "HOROVOD_RANK"
HOROVOD_SIZE = "HOROVOD_SIZE"
HOROVOD_LOCAL_RANK = "HOROVOD_LOCAL_RANK"
HOROVOD_LOCAL_SIZE = "HOROVOD_LOCAL_SIZE"
HOROVOD_CROSS_RANK = "HOROVOD_CROSS_RANK"
HOROVOD_CROSS_SIZE = "HOROVOD_CROSS_SIZE"
HOROVOD_CONTROLLER_ADDR = "HOROVOD_CONTROLLER_ADDR"
HOROVOD_CONTROLLER_PORT = "HOROVOD_CONTROLLER_PORT"
# Single-host launches: the launcher binds the controller listener itself
# (port 0) and rank 0 inherits the LIVE socket via this fd — closing the
# probe-then-rebind TOCTOU window where another process could steal the
# advertised port between the launcher's probe and rank 0's bind.
HOROVOD_CONTROLLER_FD = "HOROVOD_CONTROLLER_FD"
# Hierarchical negotiation tree (docs/hierarchy.md): "flat" (default)
# keeps the rank-0 coordinator star; "auto" derives one island per host
# from the launcher's cross_size; "islands:N" forces N islands. Any
# resolved 1-island split, size-1 world, or native-controller world
# degrades deterministically to flat (warned once).
HOROVOD_HIERARCHY = "HOROVOD_HIERARCHY"
# Launcher -> rank plumbing for the negotiation tree (never set by hand;
# the launcher derives them from HOROVOD_HIERARCHY): the rank's island
# id, and the island sub-coordinator's address/port every member dials
# instead of the root. Island heads additionally inherit their
# pre-bound listener via HOROVOD_SUBCOORD_FD (same TOCTOU-closing
# pattern as HOROVOD_CONTROLLER_FD above).
HOROVOD_ISLAND = "HOROVOD_ISLAND"
HOROVOD_SUBCOORD_ADDR = "HOROVOD_SUBCOORD_ADDR"
HOROVOD_SUBCOORD_PORT = "HOROVOD_SUBCOORD_PORT"
HOROVOD_SUBCOORD_FD = "HOROVOD_SUBCOORD_FD"
HOROVOD_SECRET_KEY = "HOROVOD_SECRET_KEY"
HOROVOD_START_TIMEOUT = "HOROVOD_START_TIMEOUT"
# Force the JAX platform ("cpu", "tpu", ...) before any backend starts:
# ``import horovod_tpu`` applies it via jax.config, which also works after
# jax itself was imported (JAX_PLATFORMS is only read at that import). The
# debug analog of the reference running an MPI job with
# CUDA_VISIBLE_DEVICES= hidden: the same launcher command line can be
# steered onto CPU for debugging (docs/running.md).
HOROVOD_PLATFORM = "HOROVOD_PLATFORM"
# Launcher: set to "0" to stop the launcher from pinning one TPU chip per
# local rank (TPU_VISIBLE_DEVICES et al.) when a host runs several slots.
HOROVOD_LAUNCHER_PIN_DEVICES = "HOROVOD_LAUNCHER_PIN_DEVICES"
# Data plane selection for eager cross-process collectives:
#   "auto" — XLA collectives over the global device mesh when a multi-process
#            JAX runtime is initialized; TCP/host reduction otherwise.
#   "xla"  — force device collectives.
#   "host" — force host (numpy-over-TCP) reduction; used by CPU launcher tests.
HOROVOD_DATA_PLANE = "HOROVOD_DATA_PLANE"

# --- elastic fault-tolerance plane (horovod_tpu.elastic; ours) ---------------
# World epoch: 0 for the first launch, bumped by the elastic driver on every
# relaunch so workers (and elastic.State) can tell a restart from a fresh
# start.
HOROVOD_ELASTIC_EPOCH = "HOROVOD_ELASTIC_EPOCH"
# Address/port of the elastic driver's health-and-state service (heartbeats
# from every rank; committed-state store for elastic.State). Exported by
# runner.run_elastic; absent for non-elastic jobs.
HOROVOD_ELASTIC_ADDR = "HOROVOD_ELASTIC_ADDR"
HOROVOD_ELASTIC_PORT = "HOROVOD_ELASTIC_PORT"
# Seconds between worker heartbeats to the elastic driver.
HOROVOD_HEARTBEAT_INTERVAL = "HOROVOD_HEARTBEAT_INTERVAL"
# Fault-injection hook for recovery tests: "rank:commit[:epoch]" kills that
# rank with os._exit right before it persists its Nth commit (epoch
# defaults to 0 so the fault does not re-fire after the relaunch). See
# docs/elastic.md.
HOROVOD_ELASTIC_FAULT = "HOROVOD_ELASTIC_FAULT"

# --- surgical recovery plane (ours; docs/recovery.md) ------------------------
# "1" (default) arms warm-survivor relaunch: on a world fault, surviving
# worker processes park in the driver's recovery barrier instead of
# exiting, re-enter the next epoch in-process (keeping the process, its
# devices, and its compiled-program caches), and only dead slots are
# cold-forked. "0" restores the SIGTERM-everything cold relaunch.
# Degrades to cold (warned once) under the native controller and for
# rank-shifted survivors (a warm process cannot re-pin devices).
HOROVOD_RECOVERY_WARM = "HOROVOD_RECOVERY_WARM"
# Seconds the driver waits for survivors of a failed epoch to park in
# the recovery barrier before giving up on reusing them (a survivor that
# never parks is terminated and its slot cold-forked).
HOROVOD_RECOVERY_WINDOW_S = "HOROVOD_RECOVERY_WINDOW_S"
# Slot-blacklist forgiveness (docs/recovery.md): seconds after which a
# failure strike against a slot decays and the slot re-enters the pool.
# 0 (default) keeps the historical life sentence. A StragglerEvictError
# VERDICT is never forgiven regardless of this knob — eviction is a
# measured judgment, not a transient fault.
HOROVOD_BLACKLIST_FORGIVE_S = "HOROVOD_BLACKLIST_FORGIVE_S"
# Island head-rank overrides ("island:rank,island:rank"): planned
# successors published by the elastic driver's warm path when a head
# rank died, so the relaunched island rejoins under its planned
# successor instead of re-electing min(members). Never set by hand.
HOROVOD_ISLAND_HEADS = "HOROVOD_ISLAND_HEADS"
# Launcher -> successor plumbing for live head succession: the standby
# listener every island member fails over to when the head's service
# dies but its rank survives (bound by the launcher beside the primary;
# the planned successor adopts it via HOROVOD_SUBCOORD_STANDBY_FD).
HOROVOD_SUBCOORD_STANDBY_PORT = "HOROVOD_SUBCOORD_STANDBY_PORT"
HOROVOD_SUBCOORD_STANDBY_FD = "HOROVOD_SUBCOORD_STANDBY_FD"
# Deterministic fault hook for the succession drill ("headstop@cycleK"):
# the primary island head stops its sub-coordinator SERVICE (process and
# rank survive as an ordinary member) right before forwarding its Kth
# upstream island cycle — the service-death-without-rank-death shape
# live succession exists for. Epoch-0 only, the ELASTIC_FAULT convention.
HOROVOD_RECOVERY_FAULT = "HOROVOD_RECOVERY_FAULT"

# --- checkpoint plane (horovod_tpu.ckpt; ours, docs/checkpoint.md) -----------
# Per-request timeout (seconds) of elastic.State's commit push / fetch
# client. The seed hard-coded 60 s because one synchronous commit frame
# carried the whole model; the chunked async pipeline makes a generous
# whole-model timeout both wrong and a silent-hang window, so the bound
# is a declared knob (default keeps the historical 60 s for the legacy
# synchronous path).
HOROVOD_CKPT_PUSH_TIMEOUT_S = "HOROVOD_CKPT_PUSH_TIMEOUT_S"
# "1" arms the async commit pipeline: every rank hands its committed
# tree to a background streaming thread (its OWN identified connection —
# the PR-9 second-connection pattern) that ships chunked frames to the
# elastic driver's seal ledger while training keeps stepping; commit
# stall becomes O(snapshot), independent of state size. Unset/"0"
# (default) keeps the synchronous rank-0 whole-tree push bit-exactly.
HOROVOD_CKPT_ASYNC = "HOROVOD_CKPT_ASYNC"
# Chunk size (bytes) of the async commit stream (default 1 MiB): bounds
# the largest single frame a parked commit stream can occupy the wire
# with, and is the granularity the kill-between-chunks fault keys on.
HOROVOD_CKPT_CHUNK_BYTES = "HOROVOD_CKPT_CHUNK_BYTES"
# Fault-injection hook for the async pipeline: "rank:ckpt[:chunk]" kills
# that rank with os._exit right BEFORE its streaming thread sends chunk
# number `chunk` (0-based, default 0) of commit `ckpt` — the
# kill-between-chunks drill. Epoch-0 only, so the fault never re-fires
# after the relaunch (the HOROVOD_ELASTIC_FAULT convention).
HOROVOD_CKPT_FAULT = "HOROVOD_CKPT_FAULT"
# Directory the driver's seal ledger spills sealed epochs and the
# gateway's ticket journal into. Unset (default) keeps both in driver
# memory — they then survive world relaunches but not a driver restart;
# set, a restarted driver reloads the last sealed epoch (bytes-digest
# verified) and resumes journaled in-flight requests.
HOROVOD_CKPT_DIR = "HOROVOD_CKPT_DIR"
# Commit cadence of State.maybe_commit(): commit every Nth call
# (default 1 = every call). Also the checkpoint plane's knob on the
# autotune ladder (tune.policy.ckpt_interval_knob); an explicitly-set
# env pins it, per the standard pin rule.
HOROVOD_CKPT_INTERVAL_STEPS = "HOROVOD_CKPT_INTERVAL_STEPS"

# --- chaos plane + self-healing control plane (ours; docs/chaos.md) ----------
# Deterministic fault-injection spec for the controller wire, e.g.
# "drop@rank1:msg12,delay@rank0:50ms:every7,seed:7" (grammar in
# horovod_tpu.chaos). Empty = no injection. Malformed specs fail loudly at
# client construction.
HOROVOD_CHAOS = "HOROVOD_CHAOS"
# Seconds a rank-bound controller connection that dropped may reconnect
# and supersede before the drop is declared a rank death (the self-healing
# grace window). 0 restores abort-on-first-drop. Python controller service
# only; the native (C++) service keeps immediate attribution.
HOROVOD_RECONNECT_WINDOW = "HOROVOD_RECONNECT_WINDOW_S"
# Client-side transparent-reconnect budget: attempts and the initial /
# maximum exponential backoff between them. Read by
# ``runner.network.ReconnectPolicy.from_env`` at client construction, not
# through Config (clients are built in places that never see a Config).
HOROVOD_RECONNECT_ATTEMPTS = "HOROVOD_RECONNECT_ATTEMPTS"
HOROVOD_RECONNECT_BACKOFF = "HOROVOD_RECONNECT_BACKOFF_S"
HOROVOD_RECONNECT_MAX_BACKOFF = "HOROVOD_RECONNECT_MAX_BACKOFF_S"

# --- data-plane integrity plane (horovod_tpu.integrity; ours) ----------------
# Collective numerical-health sentry over reduced gradients
# (docs/integrity.md): off (default) / warn / skip / zero / abort. The
# verdict is itself collective (a one-element finite-bit exchange over the
# controller wire), so skip/zero decisions are bit-identical on every rank
# and can never desync the world. Unknown values fail loudly at engine
# construction.
HOROVOD_GRAD_SENTRY = "HOROVOD_GRAD_SENTRY"
# Cross-rank consensus verification cadence: every N fused allreduce
# batches each rank digests its post-allreduce gradients and piggybacks
# the digest on the next negotiation message; the coordinator compares
# and a mismatch escalates as a structured ConsensusError instead of
# training on silently diverged state. 0 (default) disables.
HOROVOD_CONSENSUS_INTERVAL = "HOROVOD_CONSENSUS_INTERVAL_STEPS"

# --- flight recorder (horovod_tpu.obs.flightrec; ours, docs/blackbox.md) -----
# Always-on per-rank black-box event ring: every control- and data-plane
# transition (negotiation cycles, flushes, sentry verdicts, consensus
# seals, reconnects, chaos injections, elastic commits, serving batches)
# lands in a fixed-capacity ring buffer, and any world abort dumps a
# cross-rank `blackbox-<world>-<epoch>.json` incident file for
# tools/blackbox_report.py. "0" disables (the hot path then records
# nothing and allocates nothing).
HOROVOD_FLIGHTREC = "HOROVOD_FLIGHTREC"
# Ring capacity in events (default 4096; preallocated slots, O(1)
# append — older events are overwritten, counted as dropped).
HOROVOD_FLIGHTREC_EVENTS = "HOROVOD_FLIGHTREC_EVENTS"
# Seconds the coordinator's incident collector waits for per-rank event
# tails before writing the dump with whatever arrived (best-effort,
# time-bounded by contract — a dead rank never pushes).
HOROVOD_FLIGHTREC_DUMP_TIMEOUT = "HOROVOD_FLIGHTREC_DUMP_TIMEOUT_S"
# Incident-file directory; default: beside the timeline artifact when
# HOROVOD_TIMELINE is set, else the working directory.
HOROVOD_FLIGHTREC_DIR = "HOROVOD_FLIGHTREC_DIR"
# Seconds the launcher lets SURVIVING ranks drain after a rank dies hard
# (nonzero exit) before terminating them — the window in which the
# coordinator's incident collector lands the dump that the teardown
# SIGTERM would otherwise destroy. Default: reconnect window + dump
# timeout + 1, capped at 15; "0" restores immediate fail-fast teardown.
# Only a bound on the FAILURE path: survivors that exit on their own end
# the wait early, and clean worlds never enter it.
HOROVOD_FLIGHTREC_LAUNCH_GRACE = "HOROVOD_FLIGHTREC_LAUNCH_GRACE_S"

# --- gradient numerics observatory (horovod_tpu.obs.tensorwatch; ours,
# docs/tensorwatch.md) --------------------------------------------------------
# Sampled per-tensor gradient telemetry on the eager data plane: every N
# allreduce batches the engine measures norm², max|g|, nonzero count, a
# coarse log₂-magnitude occupancy histogram, the top-k mass-coverage
# curve (sparse-readiness), and — for quantized codecs in play or
# consented via HOROVOD_AUTOTUNE_CODECS — the decode-error SNR of this
# rank's local contribution. 0 (default) disables: no observatory
# object, zero allocations on the hot path (the flightrec bar).
HOROVOD_TENSORWATCH_INTERVAL = "HOROVOD_TENSORWATCH_INTERVAL_STEPS"
# Decode-SNR floor (dB) of the evidence gate: the autotuner's lossy
# codec move is only proposed once HOROVOD_TENSORWATCH_SNR_WINDOW
# consecutive sampled SNRs certify above this floor, and a sampled SNR
# falling below it while the codec is applied triggers a revert through
# the best-known-config guard (decision-log audited).
HOROVOD_TENSORWATCH_SNR_FLOOR = "HOROVOD_TENSORWATCH_SNR_FLOOR_DB"
HOROVOD_TENSORWATCH_SNR_WINDOW = "HOROVOD_TENSORWATCH_SNR_WINDOW"
# Cardinality cap of the labeled horovod_tensor_* families: only the K
# worst tensors (lowest SNR, else largest norm) carry labels on the
# registry; the FULL per-tensor table is hvd.tensor_report() /
# GET /v1/tensors (label values must stay low-cardinality by the
# registry's contract — never one per tensor of a large model).
HOROVOD_TENSORWATCH_WORST = "HOROVOD_TENSORWATCH_WORST_K"

# --- observability plane (horovod_tpu.obs; ours, docs/metrics.md) ------------
# HTTP exposition of the metrics registry on rank 0: Prometheus text at
# /metrics, JSON snapshot at /metrics.json, loopback-bound. 0 or unset =
# no server, no thread (strictly opt-in).
HOROVOD_METRICS_PORT = "HOROVOD_METRICS_PORT"
# Seconds between each rank's registry-snapshot pushes to the coordinator
# (the cross-rank aggregation feed, an anonymous control-wire channel).
# The publisher is as opt-in as the server: it runs only when
# HOROVOD_METRICS_PORT is set or this interval is set explicitly (a job
# with neither spawns no thread and no connection); <= 0 disables it
# outright, and world snapshots then carry the calling rank only.
HOROVOD_METRICS_INTERVAL = "HOROVOD_METRICS_INTERVAL_S"

# --- inference serving plane (horovod_tpu.serving; ours, docs/serving.md) ----
# The driver-resident ServingPlane exports its coordinator RPC endpoint to
# the worker ranks through these (run_elastic merges plane.env() into every
# attempt's environment; see serving/plane.py). The secret rides the env
# exactly like HOROVOD_SECRET_KEY does from the launcher.
HOROVOD_SERVING_ADDR = "HOROVOD_SERVING_ADDR"
HOROVOD_SERVING_PORT = "HOROVOD_SERVING_PORT"
HOROVOD_SERVING_SECRET = "HOROVOD_SERVING_SECRET"
# Gateway defaults (driver-side; constructor args win over env): max live
# requests admitted to the queue, the SLO budget admission rejects past
# (429 + Retry-After), and the per-request completion deadline (503 once
# exceeded — never a hang).
HOROVOD_SERVING_QUEUE_MAX = "HOROVOD_SERVING_QUEUE_MAX"
HOROVOD_SERVING_SLO_MS = "HOROVOD_SERVING_SLO_MS"
HOROVOD_SERVING_DEADLINE_MS = "HOROVOD_SERVING_DEADLINE_MS"
# Micro-batcher knobs, both on the autotune ladder (docs/serving.md):
# largest packed batch, and either an explicit comma-separated list of
# padding-bucket edges (pins the edges knob) or the default geometric
# ladder derived from HOROVOD_SERVING_EDGE_RATIO (default 2).
HOROVOD_SERVING_BATCH_MAX = "HOROVOD_SERVING_BATCH_MAX"
HOROVOD_SERVING_BUCKET_EDGES = "HOROVOD_SERVING_BUCKET_EDGES"
HOROVOD_SERVING_EDGE_RATIO = "HOROVOD_SERVING_EDGE_RATIO"
# Closed-loop tuning of the two batcher knobs (numerics-neutral — padding
# and packing never change any request's row values — so no consent gate
# like the codec's). Off by default.
HOROVOD_SERVING_AUTOTUNE = "HOROVOD_SERVING_AUTOTUNE"
# Deterministic fault injection for the serving wire (docs/chaos.md): the
# control-wire chaos grammar (drop/delay/corrupt/close/refuse), keyed by
# the serving worker's request ordinals — its own injection domain, so
# serving faults never perturb HOROVOD_CHAOS replay on the cycle channel.
HOROVOD_SERVING_CHAOS = "HOROVOD_SERVING_CHAOS"
# Kill-mid-batch hook ("kill@rankN:batchM[@epochE]"): the named rank
# os._exits right before reporting its Mth batch result in epoch E
# (default 0) — the serving twin of HOROVOD_ELASTIC_FAULT.
HOROVOD_SERVING_FAULT = "HOROVOD_SERVING_FAULT"

# --- sparse top-k gradient wire (ops/sparse_wire.py; ours, docs/compression.md) ---
# Top-k fraction of the "topk" sparse codec, as a PERCENT key matching the
# tensorwatch sparse-readiness curve: "0.1" / "1" / "10" (default "1") —
# each fused allreduce entry ships its k = ceil(f * n) largest-magnitude
# entries as (index, value) pairs over the reference allgather shape and
# every rank decodes the dense mean locally. Unknown keys fail loudly at
# codec construction (ops/sparse_wire.py), never silently rescale.
HOROVOD_SPARSE_TOPK = "HOROVOD_SPARSE_TOPK"
# Evidence floor of the sparse codec's gate: the fraction (0..1) of
# gradient energy the top-k selection must certifiably cover (the
# horovod_tensorwatch_topk_mass curve, energy-weighted per batch) for
# HOROVOD_TENSORWATCH_SNR_WINDOW consecutive samples before the autotuner
# may propose the "topk" codec; a sampled coverage below the floor while
# the codec is applied triggers the audited collapse revert.
HOROVOD_SPARSE_COVERAGE_FLOOR = "HOROVOD_SPARSE_COVERAGE_FLOOR"
# Error feedback (residual accumulation): "1" (default) keeps the dropped
# (non-top-k) mass in a persistent per-rank residual buffer that re-enters
# the next step's selection — the convergence-preserving memory of the
# sparse wire. "0" disables it (each step's dropped mass is lost), which
# demonstrably breaks convergence parity; exposed so that claim is
# testable, not as an operational mode.
HOROVOD_SPARSE_ERROR_FEEDBACK = "HOROVOD_SPARSE_ERROR_FEEDBACK"

# Generation-ordered sub-buffer flush (docs/tensor-fusion.md; ours, the
# T3-style compute/collective overlap on the eager plane): cut each cycle
# tick's pending queue into up to N arrival-ordered sub-buffers that
# negotiate and flush independently, keeping >=2 negotiate/execute cycles
# in flight so cycle k+1's negotiation overlaps cycle k's allreduce.
# 1 (default) keeps the single-flush barrier bit-exactly; >=2 requires the
# Python controller wire (the cache-bit / metrics-RPC degrade pattern).
HOROVOD_FUSION_SUBBUFFERS = "HOROVOD_FUSION_SUBBUFFERS"

# Fused reduce+apply data plane (docs/tensor-fusion.md §fused apply;
# ours, the PAPERS 2305.06942 fused computation-collective design): "1"
# makes ``hvd.apply_step`` submit apply-capable allreduces — the engine
# lands APPLIED parameters and fresh optimizer slots from one compiled
# reduce+apply program per fused batch (psum/quantized decode, loss-scale
# unscale, nonfinite census, SGD/momentum/Adam leaf update) instead of
# handing gradients back for a separate optimizer dispatch. Unset/"0"
# (default) keeps the two-dispatch path bit-exactly. The execution
# strategy within the armed plane (fused single program vs reduce-then-
# apply) additionally sits on the autotune ladder as ``fused_apply``
# (numerics-exact, so never pinned by this env; docs/autotune.md).
HOROVOD_FUSED_APPLY = "HOROVOD_FUSED_APPLY"

# --- sharding plane (ours; docs/sharding.md) ---------------------------------
# Mesh grammar for the 2-D GSPMD planner (sharding/meshplan.py):
# "batch" (default) keeps the flat 1-D data-parallel world byte-
# identically; "batch,model:K" grows a K-way named model axis (K must
# divide the device count). The planner validates the spec loudly at
# plan time — a typo never silently falls back to an unsharded mesh.
HOROVOD_MESH = "HOROVOD_MESH"
# ZeRO stage-1 partitioned optimizer state (sharding/zero1.py): "1"
# makes apply-capable batches run reduce-scatter → local shard apply →
# all-gather as ONE donated compiled program on the XLA device plane,
# with each rank holding only its 1/N shard of the optimizer slots.
# Applied parameters are bit-exact vs the replicated fused plane (the
# single-definition ApplyRule math over a slice). Requires
# HOROVOD_FUSED_APPLY=1 to have any effect; degrades loudly to
# replicated execution on the host plane and in worlds of one.
HOROVOD_ZERO = "HOROVOD_ZERO"

# --- implementation selection + developer knobs (ours) -----------------------
# Negotiation-core selection: "0" forces the pure-Python negotiator;
# anything else prefers the C++ core where built (make_negotiator in
# ops/controller.py; also gates the native timeline writer). Availability
# is per-host — heterogeneous deployments pin it explicitly.
HOROVOD_NATIVE_CORE = "HOROVOD_NATIVE_CORE"
# Controller-service selection (ops/native_controller.py): "auto"
# (default) uses the C++ service where built, "0"/"1" force Python/C++.
HOROVOD_NATIVE_CONTROLLER = "HOROVOD_NATIVE_CONTROLLER"
# Interface the rank-0 controller service binds (default loopback);
# multi-host worlds set the DCN-reachable address (docs/running.md).
HOROVOD_CONTROLLER_BIND = "HOROVOD_CONTROLLER_BIND"
# Runtime lock witness (docs/analysis.md): "1" wraps the engine's /
# controller's / registry's locks so tests record the ACTUAL acquisition
# order into a global held-before graph and raise LockInversionError on
# inversions the AST lock-order pass (tools/hvdlint.py) cannot see.
# Strictly opt-in: unset means the raw locks, zero overhead.
HOROVOD_LOCK_WITNESS = "HOROVOD_LOCK_WITNESS"

DEFAULT_FUSION_THRESHOLD_BYTES = 64 * 1024 * 1024  # operations.cc:1838
DEFAULT_CACHE_CAPACITY = 1024  # upstream response_cache.cc default
DEFAULT_CYCLE_TIME_MS = 5.0  # operations.cc:1846
DEFAULT_START_TIMEOUT_S = 30.0
STALL_WARNING_TIME_S = 60.0  # operations.cc:258


def _env_bool(name: str) -> bool:
    return os.environ.get(name, "").strip().lower() not in ("", "0", "false")


def _env_float(name: str, default: float) -> float:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return float(raw)
    except ValueError:
        return default


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name)
    if raw is None or raw == "":
        return default
    try:
        return int(raw)
    except ValueError:
        return default


@dataclass
class Config:
    """Snapshot of all runtime knobs, taken once at ``init()`` time.

    The reference reads these in the background thread right after MPI init
    (``operations.cc:1825-1909``); we read them in ``hvd.init()``.
    """

    fusion_threshold_bytes: int = DEFAULT_FUSION_THRESHOLD_BYTES
    cycle_time_ms: float = DEFAULT_CYCLE_TIME_MS
    # generation-ordered sub-buffer flush (docs/tensor-fusion.md): 1 keeps
    # the single-flush barrier; explicit values pin the autotune knob
    fusion_subbuffers: int = 1
    fusion_subbuffers_explicit: bool = False
    # fused reduce+apply plane (docs/tensor-fusion.md §fused apply): the
    # front-end opt-in; the fused-vs-split execution strategy inside the
    # armed plane belongs to the autotune ladder, not this env
    fused_apply: bool = False
    # sharding plane (docs/sharding.md): the 2-D mesh grammar and the
    # ZeRO-1 partitioned-optimizer opt-in
    mesh: str = "batch"
    zero1: bool = False
    timeline_path: str = ""
    timeline_mark_cycles: bool = False
    timeline_all_ranks: bool = False
    clock_sync_interval_s: float = 30.0
    jax_profile_dir: str = ""
    stall_check_disable: bool = False
    stall_warning_time_s: float = STALL_WARNING_TIME_S
    stall_shutdown_time_s: float = 0.0  # 0 = warn forever, never abort
    heartbeat_interval_s: float = 1.0
    # hierarchical negotiation tree (docs/hierarchy.md): control-plane
    # topology — "flat", "auto", or "islands:N" (validated at init)
    hierarchy: str = "flat"
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    compression: str = "none"
    cache_capacity: int = DEFAULT_CACHE_CAPACITY
    autotune: bool = False
    autotune_log: str = ""
    # closed-loop tuning plane (docs/autotune.md)
    autotune_backend: str = "policy"
    autotune_window: int = 5
    autotune_cooldown: int = 5
    autotune_tolerance: float = 0.05
    autotune_decisions: str = ""
    autotune_codecs: tuple = ()
    autotune_codec_min_bytes: int = 4096
    autotune_fault: str = ""
    straggler_evict: str = "off"
    straggler_window_s: float = 30.0
    straggler_min_cycles: int = 20
    # data-plane integrity plane (docs/integrity.md)
    grad_sentry: str = "off"
    consensus_interval_steps: int = 0
    # gradient numerics observatory (docs/tensorwatch.md)
    tensorwatch_interval_steps: int = 0
    tensorwatch_snr_floor_db: float = 20.0
    tensorwatch_snr_window: int = 5
    tensorwatch_worst_k: int = 8
    # sparse top-k gradient wire (docs/compression.md §sparse)
    sparse_topk: str = "1"
    sparse_coverage_floor: float = 0.95
    sparse_error_feedback: bool = True
    # checkpoint plane (docs/checkpoint.md)
    ckpt_push_timeout_s: float = 60.0
    ckpt_async: bool = False
    ckpt_chunk_bytes: int = 1 << 20
    ckpt_interval_steps: int = 1
    ckpt_interval_explicit: bool = False
    ckpt_dir: str = ""
    # True when HOROVOD_CACHE_CAPACITY was set explicitly: the tuner then
    # treats the capacity knob as pinned (same contract as
    # fusion_threshold_explicit below).
    cache_capacity_explicit: bool = False
    start_timeout_s: float = DEFAULT_START_TIMEOUT_S
    data_plane: str = "auto"
    metrics_port: int = 0
    metrics_interval_s: float = 2.0
    # True when HOROVOD_METRICS_INTERVAL_S was set explicitly: the
    # publisher runs iff the port or the interval was asked for (same
    # pattern as reconnect_window_explicit)
    metrics_interval_explicit: bool = False
    chaos_spec: str = ""
    reconnect_window_s: float = 5.0
    # True when HOROVOD_RECONNECT_WINDOW_S was set explicitly: the engine
    # then applies it even to XLA-data-plane worlds, which otherwise keep
    # immediate death attribution (a compiled collective cannot outlive a
    # dead peer, and on the gloo CPU test backend it can complete with
    # GARBAGE before a delayed abort lands — see ops/engine.py).
    reconnect_window_explicit: bool = False
    # An explicitly-set env knob is pinned: the autotuner treats it as fixed
    # (reference SetValue(..., fixed=true), ``parameter_manager.cc:329-336``).
    fusion_threshold_explicit: bool = False
    cycle_time_explicit: bool = False

    @staticmethod
    def from_env() -> "Config":
        return Config(
            fusion_threshold_explicit=bool(
                os.environ.get(HOROVOD_FUSION_THRESHOLD)),
            cycle_time_explicit=bool(os.environ.get(HOROVOD_CYCLE_TIME)),
            fusion_threshold_bytes=_env_int(
                HOROVOD_FUSION_THRESHOLD, DEFAULT_FUSION_THRESHOLD_BYTES),
            cycle_time_ms=_env_float(HOROVOD_CYCLE_TIME, DEFAULT_CYCLE_TIME_MS),
            fusion_subbuffers=max(
                _env_int(HOROVOD_FUSION_SUBBUFFERS, 1), 1),
            fusion_subbuffers_explicit=bool(
                os.environ.get(HOROVOD_FUSION_SUBBUFFERS)),
            fused_apply=_env_bool(HOROVOD_FUSED_APPLY),
            mesh=os.environ.get(HOROVOD_MESH, "batch"),
            zero1=_env_bool(HOROVOD_ZERO),
            timeline_path=os.environ.get(HOROVOD_TIMELINE, ""),
            timeline_mark_cycles=_env_bool(HOROVOD_TIMELINE_MARK_CYCLES),
            timeline_all_ranks=_env_bool(HOROVOD_TIMELINE_ALL_RANKS),
            clock_sync_interval_s=_env_float(HOROVOD_CLOCK_SYNC_INTERVAL,
                                             30.0),
            jax_profile_dir=os.environ.get(HOROVOD_JAX_PROFILE, ""),
            stall_check_disable=_env_bool(HOROVOD_STALL_CHECK_DISABLE),
            stall_warning_time_s=_env_float(HOROVOD_STALL_WARNING_TIME,
                                            STALL_WARNING_TIME_S),
            stall_shutdown_time_s=_env_float(HOROVOD_STALL_SHUTDOWN_TIME,
                                             0.0),
            heartbeat_interval_s=_env_float(HOROVOD_HEARTBEAT_INTERVAL, 1.0),
            hierarchy=(os.environ.get(HOROVOD_HIERARCHY, "flat")
                       .strip().lower() or "flat"),
            hierarchical_allreduce=_env_bool(HOROVOD_HIERARCHICAL_ALLREDUCE),
            hierarchical_allgather=_env_bool(HOROVOD_HIERARCHICAL_ALLGATHER),
            compression=(os.environ.get(HOROVOD_COMPRESSION, "none")
                         .strip().lower() or "none"),
            cache_capacity=max(_env_int(HOROVOD_CACHE_CAPACITY,
                                        DEFAULT_CACHE_CAPACITY), 0),
            autotune=_env_bool(HOROVOD_AUTOTUNE),
            autotune_log=os.environ.get(HOROVOD_AUTOTUNE_LOG, ""),
            autotune_backend=(os.environ.get(HOROVOD_AUTOTUNE_BACKEND,
                                             "policy").strip().lower()
                              or "policy"),
            autotune_window=max(_env_int(HOROVOD_AUTOTUNE_WINDOW, 5), 1),
            autotune_cooldown=max(_env_int(HOROVOD_AUTOTUNE_COOLDOWN, 5), 0),
            autotune_tolerance=_env_float(HOROVOD_AUTOTUNE_TOLERANCE, 0.05),
            autotune_decisions=os.environ.get(HOROVOD_AUTOTUNE_DECISIONS,
                                              ""),
            autotune_codecs=tuple(
                c.strip().lower() for c in
                os.environ.get(HOROVOD_AUTOTUNE_CODECS, "").split(",")
                if c.strip()),
            autotune_codec_min_bytes=max(
                _env_int(HOROVOD_AUTOTUNE_CODEC_MIN_BYTES, 4096), 0),
            autotune_fault=os.environ.get(HOROVOD_AUTOTUNE_FAULT, ""),
            straggler_evict=(os.environ.get(HOROVOD_STRAGGLER_EVICT, "off")
                             .strip().lower() or "off"),
            straggler_window_s=_env_float(HOROVOD_STRAGGLER_WINDOW, 30.0),
            straggler_min_cycles=max(
                _env_int(HOROVOD_STRAGGLER_MIN_CYCLES, 20), 1),
            grad_sentry=(os.environ.get(HOROVOD_GRAD_SENTRY, "off")
                         .strip().lower() or "off"),
            consensus_interval_steps=max(
                _env_int(HOROVOD_CONSENSUS_INTERVAL, 0), 0),
            tensorwatch_interval_steps=max(
                _env_int(HOROVOD_TENSORWATCH_INTERVAL, 0), 0),
            tensorwatch_snr_floor_db=_env_float(
                HOROVOD_TENSORWATCH_SNR_FLOOR, 20.0),
            tensorwatch_snr_window=max(
                _env_int(HOROVOD_TENSORWATCH_SNR_WINDOW, 5), 1),
            tensorwatch_worst_k=max(
                _env_int(HOROVOD_TENSORWATCH_WORST, 8), 1),
            sparse_topk=(os.environ.get(HOROVOD_SPARSE_TOPK, "1")
                         .strip() or "1"),
            sparse_coverage_floor=_env_float(
                HOROVOD_SPARSE_COVERAGE_FLOOR, 0.95),
            sparse_error_feedback=os.environ.get(
                HOROVOD_SPARSE_ERROR_FEEDBACK, "1").strip().lower()
            not in ("0", "false"),
            ckpt_push_timeout_s=_env_float(HOROVOD_CKPT_PUSH_TIMEOUT_S, 60.0),
            ckpt_async=_env_bool(HOROVOD_CKPT_ASYNC),
            ckpt_chunk_bytes=max(
                _env_int(HOROVOD_CKPT_CHUNK_BYTES, 1 << 20), 1),
            ckpt_interval_steps=max(
                _env_int(HOROVOD_CKPT_INTERVAL_STEPS, 1), 1),
            ckpt_interval_explicit=bool(
                os.environ.get(HOROVOD_CKPT_INTERVAL_STEPS)),
            ckpt_dir=os.environ.get(HOROVOD_CKPT_DIR, ""),
            cache_capacity_explicit=bool(
                os.environ.get(HOROVOD_CACHE_CAPACITY)),
            start_timeout_s=_env_float(
                HOROVOD_START_TIMEOUT, DEFAULT_START_TIMEOUT_S),
            data_plane=os.environ.get(HOROVOD_DATA_PLANE, "auto"),
            metrics_port=max(_env_int(HOROVOD_METRICS_PORT, 0), 0),
            metrics_interval_s=_env_float(HOROVOD_METRICS_INTERVAL, 2.0),
            metrics_interval_explicit=bool(
                os.environ.get(HOROVOD_METRICS_INTERVAL)),
            chaos_spec=os.environ.get(HOROVOD_CHAOS, ""),
            reconnect_window_s=_env_float(HOROVOD_RECONNECT_WINDOW, 5.0),
            reconnect_window_explicit=bool(
                os.environ.get(HOROVOD_RECONNECT_WINDOW)),
        )
