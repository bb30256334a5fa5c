#!/usr/bin/env python
"""Synthetic benchmark, reproducing the reference measurement protocol.

Reference: ``examples/pytorch_synthetic_benchmark.py:24-110`` — ResNet-50,
batch 32/device, SGD 0.01, synthetic ImageNet data; 10 warmup batches, then
``num_iters`` x ``num_batches_per_iter`` timed batches; report img/sec mean
± 1.96 sigma. Here the training step is the framework's product path: flax
ResNet-50 (bf16 compute / f32 params), ``hvd.DistributedOptimizer`` over the
data axis of the device mesh, jit-compiled so gradient averaging is an XLA
collective on ICI.

One process, on the device it measures: it finds a TPU or exits non-zero
(``HOROVOD_BENCH_PLATFORM=cpu`` asks for a CPU run explicitly). Prints ONE
JSON line, stamped with where it was measured:
  {"metric": ..., "value": N, "unit": "img/s", "vs_baseline": R,
   "platform": ..., "device_kind": ..., "n_devices": N, ...}

vs_baseline: the reference publishes exactly one absolute throughput figure
— 1656.82 total img/s for ResNet-101, batch 64/GPU, on 16 Pascal P100s
(``docs/benchmarks.md:19-38``), i.e. 103.55 img/s per device. That per-device
figure is the only anchor available (BASELINE.md), so vs_baseline =
our img/s/device ÷ 103.55 (note: ResNet-50 here vs ResNet-101 there).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Optional

import numpy as np

REFERENCE_PER_DEVICE_IMG_S = 1656.82 / 16  # docs/benchmarks.md:19-38


def _log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# Per-chip bf16 peak TFLOP/s for the MFU line, keyed by the device_kind JAX
# reports. The measured step runs bf16 on the MXU (models/_common dtype
# policy), so the bf16 number is the right denominator. A kind that is not
# here is an error, never a default: add it with its source.
_PEAK_TFLOPS_BY_KIND = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": 197.0,
}


def _peak_tflops(device) -> Optional[float]:
    """bf16 peak of ``device`` in TFLOP/s; None on the CPU backend, where
    MFU is meaningless and the field is skipped."""
    if device.platform == "cpu":
        return None
    try:
        return _PEAK_TFLOPS_BY_KIND[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak TFLOP/s on record for device_kind "
            f"{device.device_kind!r} (known: {sorted(_PEAK_TFLOPS_BY_KIND)}); "
            f"add it to _PEAK_TFLOPS_BY_KIND with its source") from None


def _bench_device():
    """The first device of the backend this run measures on.

    A measurement finds a TPU or fails: it never falls back to the host.
    ``HOROVOD_BENCH_PLATFORM=cpu`` is the explicit request for a CPU run
    (tests and tiny validation runs). Shared by bench.py and
    benchmarks/lm_bench.py."""
    import jax

    pin = os.environ.get("HOROVOD_BENCH_PLATFORM", "").strip().lower()
    if pin:
        jax.config.update("jax_platforms", pin)
    device = jax.devices()[0]
    if device.platform not in ("tpu", pin):
        _log(f"no TPU: JAX found platform {device.platform!r} "
             f"({device.device_kind}). This benchmark measures the chip and "
             f"does not fall back; set HOROVOD_BENCH_PLATFORM=cpu to ask "
             f"for a CPU run.")
        sys.exit(1)
    return device


def _device_stamp(device, n_devices: int) -> dict:
    """Where a result was measured; goes into every result line."""
    return {"platform": device.platform, "device_kind": device.device_kind,
            "n_devices": n_devices}


def _git_head() -> Optional[str]:
    """Short HEAD sha of the repo this script lives in (shared helper:
    ``horovod_tpu.core.provenance``), stamped into every result line.
    None where the tree is not a git checkout."""
    from horovod_tpu.core.provenance import git_head_sha

    return git_head_sha(os.path.dirname(os.path.abspath(__file__)))


def _step_flops_of(compiled, log) -> Optional[float]:
    """XLA's own FLOP count for one compiled step (per-device SPMD
    program) — what MFU should be computed from; an analytic 2*MACs
    estimate would miss rematerialization and the optimizer/BN work XLA
    actually runs. Best-effort: None when the backend has no cost model."""
    try:
        return float(compiled.cost_analysis().get("flops", 0.0)) or None
    except Exception as exc:  # noqa: BLE001 - cost model is best-effort
        log(f"cost_analysis unavailable: {exc!r}")
        return None


def _add_mfu_fields(result: dict, step_flops: Optional[float],
                    steps_per_s: float, device, log) -> None:
    """Attach achieved TFLOP/s (+ mfu_pct on recognized accelerators)."""
    if not step_flops:
        return
    achieved = step_flops * steps_per_s
    # 4 decimals: tiny CPU validation runs land around 1e-3 TFLOP/s
    # and must not round to a meaningless 0.0
    result["tflops_per_device"] = round(achieved / 1e12, 4)
    peak_tf = _peak_tflops(device)
    if peak_tf:
        result["mfu_pct"] = round(100.0 * achieved / (peak_tf * 1e12), 1)
        log(f"MFU: {result['mfu_pct']}% "
            f"({result['tflops_per_device']} of {peak_tf} TFLOP/s peak)")


def _maybe_dump_hlo(compiled, log) -> None:
    """HOROVOD_BENCH_DUMP_HLO=<path>: write the backend-optimized HLO
    (post AllReduceCombiner / fusion) — the artifact for auditing dtypes
    and host transfers on real hardware. Shared env contract for every
    benchmark script."""
    dump = os.environ.get("HOROVOD_BENCH_DUMP_HLO")
    if not dump:
        return
    try:
        with open(dump, "w") as f:
            f.write(compiled.as_text())
        log(f"compiled HLO written to {dump}")
    except Exception as exc:  # noqa: BLE001
        log(f"HLO dump failed: {exc!r}")


def _maybe_profile_one_batch(run_batch, wait_on, log) -> None:
    """HOROVOD_BENCH_PROFILE=<dir>: capture a device profile (XPlane, see
    tools/profile_summary.py) of ONE warm batch BEFORE the timed
    iterations, so trace overhead never pollutes the reported numbers.
    ``wait_on()`` must block until the dispatched batch completes. The
    trace is always stopped — a live trace across the timed loop would
    silently deflate every reported number."""
    profile_dir = os.environ.get("HOROVOD_BENCH_PROFILE")
    if not profile_dir:
        return
    import jax

    tracing = False
    try:
        jax.profiler.start_trace(profile_dir)
        tracing = True
        run_batch()
        wait_on()
        log(f"profile written to {profile_dir}")
    except Exception as exc:  # noqa: BLE001 - profiling is best-effort
        log(f"profile capture failed: {exc!r}")
    finally:
        if tracing:
            try:
                jax.profiler.stop_trace()
            except Exception as exc:  # noqa: BLE001
                log(f"stop_trace failed: {exc!r}")


def _parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("--model", default="resnet50",
                        choices=["resnet50", "resnet101", "vgg16",
                                 "inception3"],
                        help="resnet50 default; resnet101/vgg16/inception3 "
                             "complete the reference's benchmark trio "
                             "(docs/benchmarks.md:5-6)")
    parser.add_argument("--batch-size", type=int, default=32,
                        help="batch size per device (reference default 32)")
    parser.add_argument("--fp16-allreduce", action="store_true",
                        default=False,
                        help="gradient compression during allreduce "
                             "(reference flag; rides bf16 on TPU — the "
                             "MXU-native 16-bit format)")
    parser.add_argument("--int8-allreduce", action="store_true",
                        default=False,
                        help="EQuARX-style block-quantized int8 gradient "
                             "allreduce: ~4x fewer wire bytes than f32 at "
                             "a bounded block-relative error "
                             "(docs/compression.md)")
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--timeline-dir", default="",
                        help="capture per-rank Chrome-trace timeline "
                             "artifacts of the eager control plane into "
                             "this directory alongside the BENCH json "
                             "(sets HOROVOD_TIMELINE + "
                             "HOROVOD_TIMELINE_ALL_RANKS; merge with "
                             "tools/trace_merge.py — docs/tracing.md)")
    parser.add_argument("--autotune", action="store_true", default=False,
                        help="enable the closed-loop tuning plane for "
                             "this run (HOROVOD_AUTOTUNE=1) and capture "
                             "its JSONL decision log beside the BENCH "
                             "json (into --timeline-dir when set, else "
                             "the cwd; render with tools/tune_report.py "
                             "— docs/autotune.md). Governs the eager "
                             "control plane; SPMD steps have no cycles "
                             "to tune.")
    parser.add_argument("--subbuffers", type=int, default=0,
                        help="generation-ordered sub-buffer flush count "
                             "for the eager data plane "
                             "(HOROVOD_FUSION_SUBBUFFERS=N, "
                             "docs/tensor-fusion.md): >=2 overlaps "
                             "backprop compute with in-flight allreduce; "
                             "achieved overlap ratio lands in the BENCH "
                             "json. Governs the eager control plane; "
                             "SPMD steps overlap inside XLA.")
    parser.add_argument("--fused-apply", action="store_true",
                        default=False,
                        help="arm the fused reduce+apply plane for this "
                             "run (HOROVOD_FUSED_APPLY=1, "
                             "docs/tensor-fusion.md §fused apply): "
                             "hvd.apply_step lands applied parameters "
                             "from one reduce+apply program per batch; "
                             "apply-batch and dispatch provenance lands "
                             "in the BENCH json. Governs the eager "
                             "plane; SPMD steps fuse inside XLA.")
    parser.add_argument("--zero1", action="store_true",
                        default=False,
                        help="arm the ZeRO-1 partitioned-optimizer plane "
                             "for this run (HOROVOD_ZERO=1, "
                             "docs/sharding.md): hvd.apply_step shards "
                             "optimizer state across ranks and flushes "
                             "batches as one reduce-scatter+apply+"
                             "all-gather program; zero1 batch and "
                             "per-rank slot-residency provenance lands "
                             "in the BENCH json. Implies the fused "
                             "reduce+apply plane.")
    parser.add_argument("--grad-sentry", default="",
                        choices=["", "off", "warn", "skip", "zero",
                                 "abort"],
                        help="arm the gradient sentry for this run "
                             "(HOROVOD_GRAD_SENTRY=<policy>, "
                             "docs/integrity.md): reduced gradients are "
                             "screened for NaN/Inf on the eager plane and "
                             "guarded in the compiled SPMD step; trip "
                             "counters land in the BENCH json")
    parser.add_argument("--tensorwatch", type=int, default=0,
                        help="arm the gradient numerics observatory for "
                             "this run (HOROVOD_TENSORWATCH_INTERVAL_"
                             "STEPS=N, docs/tensorwatch.md): every Nth "
                             "eager allreduce batch is measured — "
                             "per-tensor norms, decode SNR, the top-k "
                             "sparse-readiness curve — and SNR/top-k "
                             "provenance lands in the BENCH json. "
                             "Governs the eager control plane; SPMD "
                             "steps have no engine batches to sample.")
    parser.add_argument("--hierarchy", default="",
                        help="arm the hierarchical negotiation tree for "
                             "this run (HOROVOD_HIERARCHY=auto|islands:N, "
                             "docs/hierarchy.md): island heads merge "
                             "their members' negotiation traffic and the "
                             "root absorbs one submission per island per "
                             "cycle; topology and root-message-count "
                             "provenance lands in the BENCH json off the "
                             "live registry. Needs the Python controller "
                             "wire (armed alongside); a world the "
                             "planner cannot split degrades to flat "
                             "with a warning and honest zero counters.")
    args = parser.parse_args(argv)
    if args.fp16_allreduce and args.int8_allreduce:
        parser.error("--fp16-allreduce and --int8-allreduce are "
                     "mutually exclusive")
    return args


def main() -> None:
    args = _parse_args()

    if args.timeline_dir:
        # Per-rank timeline capture (docs/tracing.md): BEFORE hvd.init()
        # reads the config. setdefault so an operator's explicit
        # HOROVOD_TIMELINE pins win; ALL_RANKS makes the artifacts
        # rank-suffixed and therefore merge-ready for trace_merge.py.
        os.makedirs(args.timeline_dir, exist_ok=True)
        os.environ.setdefault(
            "HOROVOD_TIMELINE",
            os.path.join(args.timeline_dir, f"{args.model}_timeline.json"))
        os.environ.setdefault("HOROVOD_TIMELINE_ALL_RANKS", "1")
        os.environ.setdefault("HOROVOD_TIMELINE_MARK_CYCLES", "1")
        _log(f"timeline capture -> {os.environ['HOROVOD_TIMELINE']} "
             f"(per-rank; merge with tools/trace_merge.py)")

    if args.grad_sentry:
        # Data-plane integrity plane (docs/integrity.md): like --autotune,
        # BEFORE hvd.init() reads the config (and before the SPMD step
        # traces — the in-program guard reads the policy at trace time);
        # setdefault so an operator's explicit pin wins.
        os.environ.setdefault("HOROVOD_GRAD_SENTRY", args.grad_sentry)
        _log(f"grad sentry armed: "
             f"HOROVOD_GRAD_SENTRY={os.environ['HOROVOD_GRAD_SENTRY']} "
             f"(trip counters land in the BENCH json)")

    if args.subbuffers:
        # Sub-buffer flush pipelining (docs/tensor-fusion.md): like
        # --grad-sentry, BEFORE hvd.init() reads the config; setdefault
        # so an operator's explicit pin wins.
        os.environ.setdefault("HOROVOD_FUSION_SUBBUFFERS",
                              str(args.subbuffers))
        _log(f"sub-buffer flush armed: HOROVOD_FUSION_SUBBUFFERS="
             f"{os.environ['HOROVOD_FUSION_SUBBUFFERS']} (overlap ratio "
             f"lands in the BENCH json)")

    if args.fused_apply:
        # Fused reduce+apply (docs/tensor-fusion.md §fused apply): like
        # --subbuffers, BEFORE hvd.init() reads the config; setdefault
        # so an operator's explicit pin wins.
        os.environ.setdefault("HOROVOD_FUSED_APPLY", "1")
        _log(f"fused reduce+apply armed: HOROVOD_FUSED_APPLY="
             f"{os.environ['HOROVOD_FUSED_APPLY']} (apply-batch and "
             f"dispatch provenance lands in the BENCH json)")

    if args.zero1:
        # ZeRO-1 partitioned optimizer state (docs/sharding.md): like
        # --fused-apply, BEFORE hvd.init() reads the config; setdefault
        # so an operator's explicit pin wins. The zero1 flush IS a
        # fused program, so the fused-apply plane is armed alongside.
        os.environ.setdefault("HOROVOD_ZERO", "1")
        os.environ.setdefault("HOROVOD_FUSED_APPLY", "1")
        _log(f"ZeRO-1 sharding armed: HOROVOD_ZERO="
             f"{os.environ['HOROVOD_ZERO']} (zero1 batch and slot-"
             f"residency provenance lands in the BENCH json)")

    if args.tensorwatch:
        # Gradient numerics observatory (docs/tensorwatch.md): like
        # --grad-sentry, BEFORE hvd.init() reads the config; setdefault
        # so an operator's explicit pin wins.
        os.environ.setdefault("HOROVOD_TENSORWATCH_INTERVAL_STEPS",
                              str(args.tensorwatch))
        _log(f"numerics observatory armed: "
             f"HOROVOD_TENSORWATCH_INTERVAL_STEPS="
             f"{os.environ['HOROVOD_TENSORWATCH_INTERVAL_STEPS']} "
             f"(SNR/top-k provenance lands in the BENCH json)")

    if args.hierarchy:
        # Negotiation tree (docs/hierarchy.md): like --grad-sentry,
        # BEFORE hvd.init() reads the config; setdefault so an
        # operator's explicit pins win. The island RPCs ride the Python
        # controller wire, so that is armed alongside — the native
        # controller would silently degrade the run to flat and the
        # capture would measure nothing tree-shaped.
        os.environ.setdefault("HOROVOD_HIERARCHY", args.hierarchy)
        os.environ.setdefault("HOROVOD_NATIVE_CONTROLLER", "0")
        _log(f"negotiation tree armed: HOROVOD_HIERARCHY="
             f"{os.environ['HOROVOD_HIERARCHY']} (topology and "
             f"root-message provenance lands in the BENCH json)")

    if args.autotune:
        # Closed-loop tuning plane (docs/autotune.md): like --timeline-dir,
        # BEFORE hvd.init() reads the config; setdefault so an operator's
        # explicit pins win. The decision log lands beside the other
        # artifacts so a capture round carries its own tuning audit.
        dest = args.timeline_dir or "."
        os.makedirs(dest, exist_ok=True)
        os.environ.setdefault("HOROVOD_AUTOTUNE", "1")
        os.environ.setdefault(
            "HOROVOD_AUTOTUNE_DECISIONS",
            os.path.join(dest, f"{args.model}_autotune_decisions.jsonl"))
        _log(f"autotune decision log -> "
             f"{os.environ['HOROVOD_AUTOTUNE_DECISIONS']} "
             f"(render with tools/tune_report.py)")

    import jax

    device = _bench_device()
    from horovod_tpu.core.platform import setup_compile_cache

    setup_compile_cache()
    import optax

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_dp_train_step, synthesize_image_job
    from horovod_tpu.models import InceptionV3, ResNet50, ResNet101, VGG16

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = mesh.size
    log = _log
    log(f"Model: {args.model}, batch {args.batch_size}/device, "
        f"devices: {n_dev} ({device.platform}, {device.device_kind})")

    model_cls = {"resnet50": ResNet50, "resnet101": ResNet101,
                 "vgg16": VGG16, "inception3": InceptionV3}[args.model]
    model = model_cls(num_classes=1000)
    side = 299 if args.model == "inception3" else 224
    global_batch = args.batch_size * n_dev

    # synthetic batch + model init, on the mesh the step will use
    images, labels, variables = synthesize_image_job(
        model, mesh, global_batch, side, num_classes=1000)
    log("model initialized")
    # vgg16 has no BatchNorm -> no batch_stats collection
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})

    # --fp16-allreduce maps to bf16 cast-compression on TPU (the format
    # the ICI collectives and MXU natively carry; fp16 would round-trip
    # through an alien dtype); --int8-allreduce rides the EQuARX
    # block-quantized wire; reference flag semantics otherwise
    # (mutual exclusion enforced in _parse_args)
    compression = (hvd.Compression.int8 if args.int8_allreduce
                   else hvd.Compression.bf16 if args.fp16_allreduce
                   else hvd.Compression.none)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01), axis_name="data",
                                   compression=compression)
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    step = make_dp_train_step(model, opt, mesh, axis_name="data")

    # AOT-compile once; _step_flops_of reads the executable's own cost
    # analysis for the MFU denominator's numerator.
    log("Compiling train step (AOT)...")
    compiled = step.lower(params, opt_state, batch_stats, images,
                          labels).compile()
    step_flops = _step_flops_of(compiled, log)
    _maybe_dump_hlo(compiled, log)

    loss = None

    def run_batch():
        nonlocal params, opt_state, batch_stats, loss
        params, opt_state, batch_stats, loss = compiled(
            params, opt_state, batch_stats, images, labels)

    log(f"Running {args.num_warmup_batches} warmup batches...")
    for _ in range(args.num_warmup_batches):
        run_batch()
    jax.block_until_ready(params)

    img_secs = []
    _maybe_profile_one_batch(run_batch,
                             lambda: jax.block_until_ready(params), log)

    # every result line says what was measured, where, and on which
    # revision
    provenance = {
        "metric": f"{args.model}_synthetic_train_images_per_sec_per_device",
        "unit": "img/s",
        "batch_size": args.batch_size,
        **_device_stamp(device, n_dev),
        "git_sha": _git_head(),
    }
    if args.fp16_allreduce:
        provenance["fp16_allreduce"] = True
    if args.int8_allreduce:
        provenance["int8_allreduce"] = True
    if args.grad_sentry:
        provenance["grad_sentry"] = args.grad_sentry
    if args.subbuffers:
        provenance["subbuffers"] = args.subbuffers
    if args.fused_apply:
        provenance["fused_apply"] = True
    if args.zero1:
        provenance["zero1"] = True
    if args.tensorwatch:
        provenance["tensorwatch"] = args.tensorwatch
    if args.hierarchy:
        provenance["hierarchy"] = args.hierarchy

    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            run_batch()
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        rate = global_batch * args.num_batches_per_iter / dt
        img_secs.append(rate)
        log(f"Iter #{i}: {rate:.1f} img/sec total")

    mean = float(np.mean(img_secs))
    conf = float(1.96 * np.std(img_secs))
    per_device = mean / n_dev
    log(f"Img/sec/device: {per_device:.1f} +- {conf / n_dev:.1f}")
    log(f"Total img/sec on {n_dev} device(s): {mean:.1f} +- {conf:.1f} "
        f"(loss {float(loss):.3f})")

    # the P100 anchor is a ResNet-101 figure; a cross-model ratio would be
    # meaningless for vgg16/inception3, so emit null there
    vs_baseline = (round(per_device / REFERENCE_PER_DEVICE_IMG_S, 3)
                   if args.model.startswith("resnet") else None)
    result = dict(provenance)
    result.update({
        "value": round(per_device, 2),
        "vs_baseline": vs_baseline,
    })
    if args.grad_sentry:
        # integrity-plane audit beside the number (docs/integrity.md):
        # eager-plane trips/checks plus the compiled step's guarded
        # lowerings, straight off the metrics registry
        snap = hvd.metrics_snapshot()

        def _total(family):
            fam = snap.get(family)
            return sum(s["value"] for s in fam["samples"]) if fam else 0

        result["sentry_trips"] = _total("horovod_sentry_trips_total")
        result["sentry_checks"] = _total("horovod_sentry_checks_total")
        result["sentry_spmd_guards"] = _total(
            "horovod_sentry_spmd_guards_total")
    if args.subbuffers:
        # overlap audit beside the number (docs/tensor-fusion.md): the
        # eager engine's achieved overlap ratio and pipeline depth. Read
        # off the LIVE engine only — the SPMD bench loop itself has no
        # eager cycles, and spinning an engine up just to report zeros
        # would be a side effect, not provenance.
        from horovod_tpu.ops import engine as _engine_mod

        eng = _engine_mod._engine
        ov = eng.overlap_stats() if eng is not None else {
            "flushes": 0, "inflight_peak": 0, "overlap_seconds": 0.0,
            "execute_busy_seconds": 0.0}
        busy = ov["execute_busy_seconds"]
        result["subbuffer_flushes"] = ov["flushes"]
        result["flush_inflight_peak"] = ov["inflight_peak"]
        result["overlap_seconds"] = round(ov["overlap_seconds"], 6)
        result["overlap_ratio"] = round(
            ov["overlap_seconds"] / busy, 4) if busy > 0 else 0.0
    if args.fused_apply:
        # apply-fused audit beside the number (docs/tensor-fusion.md
        # §fused apply): apply-capable batches by execution strategy and
        # the dispatches-per-step story, read off the LIVE engine only
        # (the --subbuffers pattern: the SPMD bench loop has no eager
        # cycles, and a side-effect engine would be fake provenance).
        from horovod_tpu.ops import engine as _engine_mod

        eng = _engine_mod._engine
        ap = eng.apply_stats() if eng is not None else {
            "exec_fused": False, "fused_batches": 0, "split_batches": 0,
            "apply_dispatches": 0}
        result["apply_fused_batches"] = ap["fused_batches"]
        result["apply_split_batches"] = ap["split_batches"]
        result["apply_dispatches"] = ap["apply_dispatches"]
        batches = ap["fused_batches"] + ap["split_batches"]
        result["apply_dispatches_per_batch"] = round(
            ap["apply_dispatches"] / batches, 3) if batches else 0.0
    if args.zero1:
        # zero1 audit beside the number (docs/sharding.md): batches that
        # flushed as one reduce-scatter+apply+all-gather program and this
        # rank's resident slot bytes, read off the LIVE engine and the
        # sharding-plane gauges (the --fused-apply pattern).
        from horovod_tpu.obs.registry import registry as _reg
        from horovod_tpu.ops import engine as _engine_mod

        eng = _engine_mod._engine
        ap = eng.apply_stats() if eng is not None else {
            "exec_zero1": False, "zero1_batches": 0}
        result["zero1_exec"] = bool(ap.get("exec_zero1"))
        result["zero1_batches"] = ap.get("zero1_batches", 0)
        fams = _reg().snapshot()
        slot_fam = fams.get("horovod_shard_slot_bytes") or {}
        samples = slot_fam.get("samples") or [{}]
        result["zero1_slot_bytes"] = samples[0].get("value", 0)
    if args.tensorwatch:
        # numerics-observatory audit beside the number
        # (docs/tensorwatch.md): sampled-batch count off the LIVE
        # engine's watch (the --subbuffers pattern — no side-effect
        # engine), worst decode SNR and the sparse-readiness curve off
        # the registry gauges the observatory maintains.
        from horovod_tpu.obs.tensorwatch import (
            FAMILY_CODEC_SNR,
            FAMILY_TOPK,
            _labeled_values,
        )
        from horovod_tpu.ops import engine as _engine_mod

        eng = _engine_mod._engine
        watch = getattr(eng, "_tensorwatch", None) \
            if eng is not None else None
        tw = watch.stats() if watch is not None else {
            "batches": 0, "samples": 0, "tensors": 0}
        result["tensorwatch_samples"] = tw["samples"]
        result["tensorwatch_tensors"] = tw["tensors"]
        snap = hvd.metrics_snapshot()

        def _labeled(family, label):
            # the report fold's one definition of the labeled-samples
            # extraction (obs.tensorwatch), not a local re-implementation
            return _labeled_values(snap, family, label)

        snrs = _labeled(FAMILY_CODEC_SNR, "codec")
        if snrs:
            result["tensorwatch_worst_snr_db"] = round(
                min(snrs.values()), 2)
            result["tensorwatch_snr_by_codec"] = {
                c: round(v, 2) for c, v in sorted(snrs.items())}
        topk = _labeled(FAMILY_TOPK, "k")
        if topk:
            result["tensorwatch_topk_mass"] = {
                k: round(v, 4) for k, v in sorted(topk.items())}
    if args.hierarchy:
        # tree-plane audit beside the number (docs/hierarchy.md): the
        # resolved topology and the root's absorbed message count off
        # the LIVE registry — a degraded-to-flat run reports islands 0
        # and zero root messages, never a guessed topology.
        snap = hvd.metrics_snapshot()

        def _hier_total(family):
            fam = snap.get(family)
            return sum(s["value"] for s in fam["samples"]) if fam else 0

        result["hier_islands"] = int(
            _hier_total("horovod_hier_islands"))
        result["hier_root_messages"] = int(
            _hier_total("horovod_hier_root_messages_total"))
        result["hier_merged_cycles"] = int(
            _hier_total("horovod_hier_merged_cycles_total"))
        result["hier_raw_cycles"] = int(
            _hier_total("horovod_hier_raw_cycles_total"))
    # cost_analysis() reports the per-device SPMD program's flops for one
    # batch, so the rate to multiply by is batches/s
    _add_mfu_fields(result, step_flops, mean / global_batch, device, log)
    print(json.dumps(result))
    hvd.shutdown()


if __name__ == "__main__":
    main()
