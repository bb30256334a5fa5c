#!/usr/bin/env python3
"""Chip smoke: the data-parallel trainer's main path, once, on a TPU.

    python3 chip_smoke.py

One process, over every device JAX reports, through the entry points a user
calls (``hvd.init``, ``hvd.parallel.data_parallel_mesh``,
``hvd.DistributedOptimizer(axis_name="data")``, ``hvd.broadcast_parameters``
and the jitted ``shard_map`` steps of ``benchmarks/_dp_step.py``):

* trains ResNet-50 at full width (bs 32/chip, SGD) and the Transformer LM at
  GPT-2-small width with the Pallas flash kernel (bs 8/chip, seq 1024,
  AdamW) for a few steps each on one fixed synthetic batch — loss finite at
  every step and lower at the last than the first, no compilation after the
  warm-up step, parameters replicated on every device, the batch split
  evenly, and on several devices every all-reduce spanning all of them;
* checks the flash kernel's output and three gradients against dense
  attention at ``highest`` matmul precision at the LM's shape, and that the
  compiled LM step carries the Mosaic custom call (an interpreted kernel
  cannot pass).

It refuses to start unless ``jax.devices()[0].platform == "tpu"``; any failed
check raises, so the only way to exit 0 is for every phase to pass. Set-up,
compile and per-step wall seconds are printed as plain facts, not metrics.
The last line of standard output is
``{"ok": true, "device": {"platform", "kind", "count"}}``; the facts also
land in ``chiprun_out/chip_smoke/report.json``.

The phase functions take their sizes as arguments, so
``tests/test_chip_smoke.py`` drives the same code at toy sizes on the CPU.
"""

from __future__ import annotations

import contextlib
import functools
import json
import math
import os
import re
import sys
import time

_ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(_ROOT, "chiprun_out", "chip_smoke")

RESNET50_BS32 = dict(batch_per_device=32, image_side=224, num_classes=1000,
                     steps=6)
GPT2_SMALL_FLASH = dict(num_layers=12, num_heads=12, d_model=768, d_ff=3072,
                        vocab_size=32768, seq_len=1024, batch_per_device=8,
                        steps=6)

_MOSAIC_CALL = 'custom_call_target="tpu_custom_call"'


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(ok: bool, message: str) -> None:
    if not ok:
        raise SmokeFailure(message)


def say(message: str) -> None:
    print(f"[chip_smoke] {message}", flush=True)


def require_tpu():
    """``jax.devices()`` when they are TPUs; otherwise exit non-zero naming
    what was found, before anything is trained or printed as a result."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{devices[0].platform!r} ({devices[0].device_kind}, "
                 f"{len(devices)} device(s)); nothing was run.")
    return devices


@contextlib.contextmanager
def count_compiles():
    """Counts the programs JAX hands to the compiler inside the block,
    persistent-cache hits included: a reading of the program's own compile
    ledger (``horovod_tpu/obs/compiles.py``; ``hvd.init()`` installs it,
    and outside an initialized world this block does for its duration).
    The count is in ``seen[0]`` once the block has ended."""
    from horovod_tpu.obs import compiles

    mine = compiles.ledger().install()
    before = compiles.compiles_total()
    seen = [0]
    try:
        yield seen
    finally:
        seen[0] = compiles.compiles_total() - before
        if mine:
            compiles.ledger().uninstall()


def check_replicated(tree, mesh, what: str) -> None:
    """Every leaf holds its full value on every device of ``mesh``."""
    import jax

    devices = set(mesh.devices.flat)
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        shards = leaf.addressable_shards
        check(leaf.sharding.is_fully_replicated
              and {s.device for s in shards} == devices
              and all(s.data.shape == leaf.shape for s in shards),
              f"{what}{jax.tree_util.keystr(path)} is not replicated on all "
              f"{len(devices)} device(s): {leaf.sharding}")


def check_batch_split(batch, mesh, what: str) -> None:
    """Each device of ``mesh`` holds its own 1/n of the leading axis."""
    n = mesh.size
    rows = batch.shape[0] // n
    spans = sorted((s.index[0].start or 0, s.data.shape[0])
                   for s in batch.addressable_shards)
    check({s.device for s in batch.addressable_shards}
          == set(mesh.devices.flat)
          and spans == [(i * rows, rows) for i in range(n)],
          f"{what} {batch.shape} is not split {n} ways over the mesh: "
          f"(start, rows) per shard = {spans}")


def allreduce_group_sizes(hlo: str) -> list:
    """Replica-group size of every all-reduce in compiled HLO text; 0 for
    the empty group list, which means every device."""
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
        else:
            check("replica_groups={}" in line,
                  f"cannot read the replica groups of: {line.strip()}")
            sizes.append(0)
    return sizes


def check_allreduce_spans_mesh(hlo: str, mesh, what: str) -> int:
    """On several devices the step must carry all-reduces, each over the
    whole mesh. Returns how many there are."""
    sizes = allreduce_group_sizes(hlo)
    if mesh.size > 1:
        check(bool(sizes), f"{what}: no all-reduce in the compiled step on "
                           f"{mesh.size} devices")
        check(all(s in (0, mesh.size) for s in sizes),
              f"{what}: all-reduce replica groups of sizes {sorted(set(sizes))}"
              f", expected {mesh.size}")
    return len(sizes)


def run_steps(compiled, state, data, steps: int):
    """Run ``steps`` calls of ``compiled(*state, *data) -> (*state, loss)``.
    The first call is the warm-up; any compilation after it is a failure."""
    import jax

    def one_step(state):
        t0 = time.perf_counter()
        *state, loss = compiled(*state, *data)
        loss = float(loss)  # waits for the step
        return state, loss, time.perf_counter() - t0

    state, loss, seconds = one_step(state)
    losses, step_seconds = [loss], [seconds]
    with count_compiles() as compiles:
        for _ in range(steps - 1):
            state, loss, seconds = one_step(state)
            losses.append(loss)
            step_seconds.append(seconds)
        jax.block_until_ready(state)
    check(all(math.isfinite(x) for x in losses),
          f"non-finite loss among {losses}")
    check(losses[-1] < losses[0],
          f"loss did not go down over {steps} steps: {losses}")
    check(compiles[0] == 0,
          f"{compiles[0]} compilation(s) after the warm-up step")
    return state, {"losses": [round(x, 4) for x in losses],
                   "step_seconds": [round(x, 4) for x in step_seconds],
                   "compiles_after_warmup": compiles[0]}


def train_resnet(model, mesh, *, batch_per_device: int, image_side: int,
                 num_classes: int, steps: int) -> dict:
    """A few SGD steps of ``model`` on one synthetic batch, data-parallel
    over ``mesh`` through ``make_dp_train_step``."""
    import jax
    import optax

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_dp_train_step, synthesize_image_job

    global_batch = batch_per_device * mesh.size
    t0 = time.perf_counter()
    images, labels, variables = synthesize_image_job(
        model, mesh, global_batch, image_side, num_classes)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    opt = hvd.DistributedOptimizer(optax.sgd(0.01), axis_name="data")
    opt_state = jax.jit(opt.init)(params)
    params = hvd.broadcast_parameters(params, root_rank=0)
    jax.block_until_ready((params, images, labels))
    check_replicated(params, mesh, "params after broadcast_parameters")
    check_batch_split(images, mesh, "images")
    setup_seconds = time.perf_counter() - t0

    step = make_dp_train_step(model, opt, mesh, axis_name="data")
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, batch_stats, images,
                          labels).compile()
    compile_seconds = time.perf_counter() - t0
    hlo = compiled.as_text()
    n_allreduce = check_allreduce_spans_mesh(hlo, mesh, "ResNet step")
    _, n_async = hvd.obs.record_exchange_collectives("resnet_step", hlo)

    (params, _, _), facts = run_steps(
        compiled, (params, opt_state, batch_stats), (images, labels), steps)
    check_replicated(params, mesh, "params after training")
    return {"global_batch": global_batch, "all_reduces": n_allreduce,
            "async_collectives": n_async,
            "setup_seconds": round(setup_seconds, 2),
            "compile_seconds": round(compile_seconds, 2), **facts}


def train_lm(mesh, *, num_layers: int, num_heads: int, d_model: int,
             d_ff: int, vocab_size: int, seq_len: int, batch_per_device: int,
             steps: int, require_mosaic: bool) -> dict:
    """A few AdamW steps of the flash-attention Transformer LM on one
    synthetic batch, data-parallel over ``mesh`` through
    ``make_lm_train_step``. ``require_mosaic``: the compiled step must
    contain the Mosaic custom call, i.e. the kernel was not interpreted."""
    import jax
    import optax

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_lm_train_step, synthesize_lm_job
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(
        vocab_size=vocab_size, num_layers=num_layers, num_heads=num_heads,
        d_model=d_model, d_ff=d_ff, max_seq_len=seq_len, attention="flash")
    global_batch = batch_per_device * mesh.size
    t0 = time.perf_counter()
    tokens, variables = synthesize_lm_job(model, mesh, global_batch, seq_len)
    params = variables["params"]
    opt = hvd.DistributedOptimizer(
        optax.adamw(3e-4, weight_decay=0.01), axis_name="data")
    opt_state = jax.jit(opt.init)(params)
    params = hvd.broadcast_parameters(params, root_rank=0)
    jax.block_until_ready((params, tokens))
    check_replicated(params, mesh, "params after broadcast_parameters")
    check_batch_split(tokens, mesh, "tokens")
    setup_seconds = time.perf_counter() - t0

    step = make_lm_train_step(model, opt, mesh, axis_name="data")
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, tokens).compile()
    compile_seconds = time.perf_counter() - t0
    hlo = compiled.as_text()
    mosaic_calls = hlo.count(_MOSAIC_CALL)
    if require_mosaic:
        check(mosaic_calls > 0,
              "the compiled LM step has no Mosaic custom call: the flash "
              "kernel did not compile for the chip")
    n_allreduce = check_allreduce_spans_mesh(hlo, mesh, "LM step")
    _, n_async = hvd.obs.record_exchange_collectives("lm_step", hlo)

    (params, _), facts = run_steps(compiled, (params, opt_state), (tokens,),
                                   steps)
    check_replicated(params, mesh, "params after training")
    return {"global_batch": global_batch, "mosaic_custom_calls": mosaic_calls,
            "all_reduces": n_allreduce, "async_collectives": n_async,
            "setup_seconds": round(setup_seconds, 2),
            "compile_seconds": round(compile_seconds, 2), **facts}


def check_flash_vs_dense(*, batch: int, seq_len: int, num_heads: int,
                         head_dim: int, dtype, interpret: bool,
                         tolerance: float) -> dict:
    """Causal flash attention against dense attention at ``highest`` matmul
    precision: output and the gradients of q, k, v for one random
    cotangent. Errors are max |difference| over max |reference|."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops.pallas_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import dense_attention

    shape = (batch, seq_len, num_heads, head_dim)
    q, k, v, cotangent = (
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key in jax.random.split(jax.random.PRNGKey(2), 4))

    def output_and_grads(attention, q, k, v, cotangent):
        out, vjp = jax.vjp(attention, q, k, v)
        return (out, *vjp(cotangent))

    got = jax.jit(functools.partial(output_and_grads, functools.partial(
        flash_attention, causal=True, interpret=interpret)))(
            q, k, v, cotangent)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(functools.partial(output_and_grads, functools.partial(
            dense_attention, causal=True)))(q, k, v, cotangent)

    errors = {}
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        check(g.shape == shape and bool(jnp.isfinite(g).all()),
              f"flash {name}: shape {g.shape}, or non-finite values")
        errors[name] = float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
    check(max(errors.values()) <= tolerance,
          f"flash vs dense ({jnp.dtype(dtype).name}) beyond {tolerance}: "
          f"{errors}")
    return {"dtype": jnp.dtype(dtype).name, "tolerance": tolerance,
            "max_error_over_max_reference":
                {name: float(f"{err:.3g}") for name, err in errors.items()}}


def main() -> None:
    devices = require_tpu()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    say(f"platform={device['platform']} device_kind={device['kind']!r} "
        f"devices={device['count']}")

    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu import cc
    from horovod_tpu.core.config import HOROVOD_FLIGHTREC_DIR
    from horovod_tpu.core.platform import setup_compile_cache
    from horovod_tpu.models import ResNet50

    os.makedirs(OUT_DIR, exist_ok=True)
    # an aborting world dumps its flight recorder here, not into the checkout
    os.environ[HOROVOD_FLIGHTREC_DIR] = OUT_DIR
    report = {"device": device, "compile_cache": setup_compile_cache(),
              "native_core_loaded": cc.available()}
    say(f"compile cache: {report['compile_cache']}")
    say(f"native core loaded: {report['native_core_loaded']}"
        + ("" if report["native_core_loaded"] else f" ({cc.load_error()})"))

    hvd.init()
    try:
        mesh = hvd.parallel.data_parallel_mesh()
        check(mesh.size == len(devices),
              f"mesh of {mesh.size} over {len(devices)} devices")

        lm_shape = dict(batch=GPT2_SMALL_FLASH["batch_per_device"],
                        seq_len=GPT2_SMALL_FLASH["seq_len"],
                        num_heads=GPT2_SMALL_FLASH["num_heads"],
                        head_dim=(GPT2_SMALL_FLASH["d_model"]
                                  // GPT2_SMALL_FLASH["num_heads"]))
        report["flash_vs_dense"] = [
            check_flash_vs_dense(**lm_shape, dtype=dtype, interpret=False,
                                 tolerance=tolerance)
            for dtype, tolerance in ((jnp.float32, 2e-2),
                                     (jnp.bfloat16, 2e-2))]
        say(f"flash vs dense at {lm_shape}: {report['flash_vs_dense']}")

        report["resnet50"] = train_resnet(
            ResNet50(num_classes=RESNET50_BS32["num_classes"]), mesh,
            **RESNET50_BS32)
        say(f"ResNet-50 bs{RESNET50_BS32['batch_per_device']}/chip: "
            f"{report['resnet50']}")

        report["transformer_lm"] = train_lm(mesh, **GPT2_SMALL_FLASH,
                                            require_mosaic=True)
        say(f"GPT-2-small flash LM bs"
            f"{GPT2_SMALL_FLASH['batch_per_device']}/chip: "
            f"{report['transformer_lm']}")
    finally:
        hvd.shutdown()

    with open(os.path.join(OUT_DIR, "report.json"), "w",
              encoding="utf-8") as fh:
        json.dump(report, fh, indent=1)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
