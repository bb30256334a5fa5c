"""Run one cell of the benchmark once, in one process.

    python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Sets up (seeded weights and batches made on the device, the program's
``DistributedOptimizer`` step compiled ahead of time, its first steps
driven and read for ``correct``, warm-up), measures for ``--seconds``,
then checks ``correct`` against the plain reference and prints the
contract's one JSON line last. It refuses anything but a TPU whose
``device_kind`` is in ``peaks.json``. ``--rehearse-cpu`` is the explicit
request for a run on the CPU backend at a toy size: it drives every phase
and prints no device metric.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, near enough: before any import

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

from chipbench import cell as cells  # noqa: E402
from chipbench import check, timing, trace_reduce  # noqa: E402

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACED_STEPS = 11       # the first follows a drained device and is dropped
WARMUP_STEPS = 4        # after the three read for ``correct``
TRACE_DIR = os.path.join(cells.ROOT, ".chipbench_trace")


def say(message: str) -> None:
    print(f"[chipbench] {message}", flush=True)


class Refused(SystemExit):
    """The run cannot be made here; nothing is printed as a result."""

    def __init__(self, message: str):
        print(f"chipbench: {message}", file=sys.stderr, flush=True)
        super().__init__(2)


@contextlib.contextmanager
def count_compiles():
    """Counts the programs JAX hands to the compiler inside the block,
    persistent-cache hits included (the event wraps the cache lookup)."""
    from jax import monitoring

    seen = [0]

    def on_event(event, duration_secs, **kwargs):
        del duration_secs, kwargs
        if event == _COMPILE_EVENT:
            seen[0] += 1

    monitoring.register_event_duration_secs_listener(on_event)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(on_event)


def allreduce_group_sizes(hlo: str) -> list:
    """Replica-group size of every all-reduce in compiled HLO text; 0 for
    the empty group list, which means every device."""
    import re

    sizes = []
    for line in hlo.splitlines():
        if not re.search(r"\ball-reduce(-start)?\(", line):
            continue
        explicit = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
        iota = re.search(r"replica_groups=\[\d+,(\d+)\]<=\[", line)
        if explicit:
            sizes.append(len(explicit.group(1).split(",")))
        elif iota:
            sizes.append(int(iota.group(1)))
        elif "replica_groups={}" in line:
            sizes.append(0)
        else:
            sizes.append(-1)  # unreadable: fails the placement check
    return sizes


def device_peak_bytes(stats: dict) -> int:
    """The most one device held at once, from ``memory_stats()``. On the
    TPU runtime ``peak_bytes_in_use`` counts live buffers (state, batches,
    code) but not the region it reserves for the loaded programs'
    temporaries, which it reports apart as ``peak_bytes_reserved`` (my chip
    run, PR 23: 5.28 GB reserved beside a step whose compiler account says
    5.46 GB of temporaries); the device holds both at once."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def place_compile_cache() -> str:
    """The program's own placement of JAX's persistent cache
    (``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/
    .jax_bench_cache``), with every program kept whatever its size or
    compile time, so that a second run compiles nothing."""
    import jax

    from horovod_tpu.core.platform import setup_compile_cache

    path = setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def take_devices(cell, rehearse_cpu: bool):
    """The devices the cell runs on, or a refusal."""
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if rehearse_cpu:
        if platform != "cpu":
            raise Refused(f"--rehearse-cpu asks for the CPU backend, JAX "
                          f"found {platform!r}")
    elif platform != "tpu":
        raise Refused(f"needs a TPU, but JAX found platform {platform!r} "
                      f"({kind}, {len(devices)} device(s)); nothing was run")
    if len(devices) < cell.chips:
        raise Refused(f"cell {cell.name} asks for {cell.chips} chip(s), JAX "
                      f"found {len(devices)}")
    peaks = None if rehearse_cpu else cells.peaks_of(kind)
    return devices[:cell.chips], peaks


class Trainer:
    """The compiled step with its state and its feed: built once in
    set-up, driven through its first steps there, handed to the window."""

    def __init__(self, cell, mesh, seed: int):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import horovod_tpu as hvd

        self.cell, self.mesh = cell, mesh
        family, config, traffic = cell.family, cell.config, cell.traffic
        self.keys = cells.seed_keys(seed, 2)
        replicated = NamedSharding(mesh, P())
        self.times = {}

        t = time.perf_counter()
        self._init = jax.jit(
            functools.partial(family.init_model_state, config),
            out_shardings=replicated)
        model_state = self._init(self.keys[0])
        specs = family.data_spec("data")
        self.pool = jax.jit(
            functools.partial(family.make_pool, config, traffic),
            out_shardings=[tuple(NamedSharding(mesh, s) for s in specs)]
            * traffic["pool"])(self.keys[1])
        model = family.build(config)
        opt = hvd.DistributedOptimizer(family.optimizer(config),
                                       axis_name="data")
        opt_state = jax.jit(opt.init)(model_state[0])
        jax.block_until_ready((model_state, self.pool, opt_state))
        self.times["seeded_init_s"] = time.perf_counter() - t

        t = time.perf_counter()
        model_state = (hvd.broadcast_parameters(model_state[0], root_rank=0),
                       *model_state[1:])
        jax.block_until_ready(model_state)
        self.times["broadcast_parameters_s"] = time.perf_counter() - t

        self.state = family.assemble(model_state, opt_state)
        t = time.perf_counter()
        self.compiled = family.make_step(model, opt, mesh).lower(
            *self.state, *self.pool[0]).compile()
        self.times["compile_s"] = time.perf_counter() - t
        self.hlo = self.compiled.as_text()
        self.compiler_account = self.compiled.memory_analysis()
        self.dispatched = 0

    def step(self):
        """Dispatch one training step on the next batch of the pool;
        returns its loss, not yet ready."""
        batch = self.pool[self.dispatched % len(self.pool)]
        *state, loss = self.compiled(*self.state, *batch)
        self.state = tuple(state)
        self.dispatched += 1
        return loss

    def first_steps(self) -> dict:
        """Drive the first ``check.STEPS`` steps and read what ``correct``
        compares: each loss, the first gradient's norms out of the
        optimizer's state after one step, the parameters' change after
        all of them (against the seeded weights, made again)."""
        from chipbench import numerics

        family, config = self.cell.family, self.cell.config
        losses, grad_norms = [], None
        for i in range(check.STEPS):
            losses.append(float(self.step()))
            if i == 0:
                grad_norms = numerics.leaf_norms(
                    family.first_gradient(self.state[1], config))
        seeded = self._init(self.keys[0])[0]
        update_norms = numerics.difference_norms(self.state[0], seeded)
        del seeded
        return {"losses": losses, "grad_norms": grad_norms,
                "update_norms": update_norms}

    def replicas_identical(self) -> bool:
        """Every parameter leaf bit-identical on all the mesh's devices."""
        import jax
        import jax.numpy as jnp
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        def same(leaves):
            out = []
            for x in leaves:
                bits = jax.lax.bitcast_convert_type(
                    x, jnp.uint32 if x.dtype.itemsize == 4 else jnp.uint16)
                out.append(jnp.all(jax.lax.pmax(bits, "data")
                                   == jax.lax.pmin(bits, "data")))
            return jnp.all(jnp.stack(out))

        leaves = jax.tree_util.tree_leaves(self.state[0])
        return bool(jax.jit(shard_map(
            same, mesh=self.mesh, in_specs=(P(),), out_specs=P(),
            check_vma=False))(leaves))

    def free(self) -> None:
        import jax

        for leaf in jax.tree_util.tree_leaves((self.state, self.pool)):
            leaf.delete()
        self.state = self.pool = self.compiled = None


def placement_ok(trainer: Trainer) -> bool:
    """Several chips: parameters identical everywhere after the window and
    every all-reduce of the compiled step spanning the whole mesh."""
    n = trainer.mesh.size
    if n == 1:
        return True
    sizes = allreduce_group_sizes(trainer.hlo)
    spans = bool(sizes) and all(s in (0, n) for s in sizes)
    say(f"correct: {len(sizes)} all-reduce(s) in the compiled step, replica "
        f"group sizes {sorted(set(sizes))} (0 = every device), mesh of {n}: "
        f"{'ok' if spans else 'NOT spanning the mesh'}")
    identical = trainer.replicas_identical()
    say(f"correct: parameters bit-identical on all {n} devices after the "
        f"window: {identical}")
    return spans and identical


def traced_stretch(trainer: Trainer, directory: str) -> dict:
    """Trace ``TRACED_STEPS`` steady steps and reduce the trace."""
    import jax

    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)

    def step():
        with jax.profiler.TraceAnnotation("chipbench.dispatch"):
            return trainer.step()

    def read(loss):
        with jax.profiler.TraceAnnotation("chipbench.read_loss"):
            return float(loss)

    jax.block_until_ready(trainer.state)
    jax.profiler.start_trace(directory)
    try:
        timing.run_window(step, 0.0, read=read, max_steps=TRACED_STEPS)
        jax.block_until_ready(trainer.state)
    finally:
        jax.profiler.stop_trace()
    return trace_reduce.reduce_trace(directory, chips=trainer.mesh.size)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--benchmark", default=None, metavar="FILE",
                        help="a file of BENCHMARK.json's shape (tests)")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    cell = cells.Spec(args.benchmark).cell(args.workload)
    devices, peaks = take_devices(cell, args.rehearse_cpu)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"cell {cell.name}: config {cell.config_name}, traffic "
        f"{cell.traffic_name}, platform={device['platform']} device_kind="
        f"{device['kind']!r} devices={device['count']} seed={args.seed}")

    import jax

    import horovod_tpu as hvd

    say(f"compile cache: {place_compile_cache()}")
    imports_s = time.perf_counter() - _T0

    family, config, traffic = cell.family, cell.config, cell.traffic
    limits = cell.limits()
    hvd.init()
    try:
        mesh = hvd.parallel.data_parallel_mesh(devices)
        with count_compiles() as setup_compiles:
            trainer = Trainer(cell, mesh, args.seed)
            mosaic_calls = trainer.hlo.count(trace_reduce.MOSAIC_TARGET)
            t = time.perf_counter()
            program = trainer.first_steps()
            first_steps_s = time.perf_counter() - t
            t = time.perf_counter()
            timing.run_window(trainer.step, 0.0, max_steps=WARMUP_STEPS)
            jax.block_until_ready(trainer.state)
            warmup_s = time.perf_counter() - t

        with count_compiles() as window_compiles:
            window = timing.run_window(trainer.step, args.seconds)
            jax.block_until_ready(trainer.state)
        setup_s = window.opened_at - _T0
        memory = [d.memory_stats() or {} for d in devices]
        peak_bytes = max(map(device_peak_bytes, memory), default=0)
        say(f"memory of device 0 after the window: {memory[0]}; the "
            f"compiled step by the compiler's account: "
            f"{trainer.compiler_account}")
        say(f"set-up {setup_s:.3f} s: imports and device "
            f"{imports_s:.3f}, " + ", ".join(
                f"{k[:-2]} {v:.3f}" for k, v in trainer.times.items())
            + f", first steps {first_steps_s:.3f}, warm-up {warmup_s:.3f}; "
            f"{setup_compiles[0]} program(s) compiled or fetched")

        trace = None
        if args.trace and not args.rehearse_cpu:
            trace = traced_stretch(
                trainer, os.path.join(TRACE_DIR, cell.name))
        placed = placement_ok(trainer)
        hlo = trainer.hlo
        trainer.free()

        t = time.perf_counter()
        reference = family.reference_run(config, traffic, trainer.keys,
                                         check.STEPS)
        say(f"reference: {check.STEPS} steps of the plain float32 trainer "
            f"in {time.perf_counter() - t:.3f} s, of which "
            f"{reference['compile_s']:.3f} compiling or fetching its "
            f"gradient program (outside set-up and window)")
    finally:
        hvd.shutdown()

    per_chip = cell.per_chip_batch
    e2e = timing.end_to_end(
        window, traffic["global_batch"], cell.chips,
        traffic["steps_per_timing_sample"],
        family.flops_per_sample(config, traffic),
        peaks["bf16_flops_per_s"] if peaks else math.nan)
    facts = e2e.pop("facts")
    e2e["setup_s"] = setup_s
    say("window: " + ", ".join(f"{k}={v:.6g}" if isinstance(v, float)
                               else f"{k}={v}" for k, v in facts.items()))
    say(f"losses: first steps {program['losses']} (reference "
        f"{reference['losses']}), window {window.losses[0]:.6g} .. "
        f"{window.losses[-1]:.6g}")

    correct = check.verdict(check.compare(program, reference), limits, say)
    finite = window.failed == 0 and all(
        math.isfinite(x) for x in program["losses"])
    say(f"correct: {window.failed} of {window.steps} window steps had a "
        f"non-finite loss; {window_compiles[0]} compilation(s) in the window "
        f"(must be 0)")
    kernel_work = family.kernel_work(config, traffic, per_chip)
    wants_kernel = bool(kernel_work)
    kernel_ok = (mosaic_calls > 0) == wants_kernel or args.rehearse_cpu
    if not kernel_ok:
        say(f"correct: {mosaic_calls} Mosaic custom call(s) in the compiled "
            f"step, but the configuration "
            f"{'needs' if wants_kernel else 'has no'} kernel")
    correct = (correct and finite and placed and kernel_ok
               and window_compiles[0] == 0)

    run = {"cell": cell, "window": window, "facts": facts, "peaks": peaks,
           "compile_s": trainer.times["compile_s"],
           "compiles_in_window": window_compiles[0], "hlo": hlo,
           "peak_bytes": peak_bytes, "trace": trace,
           "kernel_work": kernel_work}
    if args.rehearse_cpu:
        metrics = {}
    elif args.trace:
        metrics = {}
        for m in cell.per_layer:
            value = cell.spec.reader(m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    device["memory_peak_bytes"] = peak_bytes
    line = {"correct": bool(correct), "attempted": window.steps,
            "failed": window.failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = statistics.fmean(
            d["busy_s"] for d in trace["devices"])
        device["window_s"] = trace["devices"][0]["window_s"]
        line["breakdown"] = trace_reduce.breakdown(trace)
    if args.rehearse_cpu:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
