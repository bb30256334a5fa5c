#!/usr/bin/env python
"""Scaling-curve harness: DP throughput and efficiency vs device count.

The reference's headline result is a scaling chart — img/sec at 1..512
GPUs with ~90% efficiency for ResNet-101/Inception V3
(``docs/benchmarks.md:5-6`` there); BASELINE.md's north star for this
build is the same curve on a TPU pod (>=90% at v5e-256). This harness
produces that curve for whatever devices are visible:

* on a TPU pod slice: real chips over ICI — the production measurement;
* on this dev box: N virtual CPU XLA devices — validates the harness and
  the sharded step end-to-end (CPU img/s is NOT a TPU prediction).

Each device count runs in a fresh subprocess (XLA device count is fixed at
backend init). Per point: the same global batch PER DEVICE (weak scaling,
the reference's protocol), mean img/s over timed iters, efficiency =
(img/s at n) / (n * img/s at 1).

Usage: python benchmarks/scaling_bench.py [--devices 1,2,4,8]
         [--model tiny|resnet50] [--platform cpu|native]
         [--batch-size 32] [--iters 5] [--batches-per-iter 3]
Prints one JSON line per point and a final efficiency table.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys


def _measure() -> None:
    """Subprocess body: one scaling point on n virtual/real devices."""
    n = int(os.environ["SCALING_N_DEVICES"])
    platform = os.environ["SCALING_PLATFORM"]
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    if platform == "cpu":
        from horovod_tpu.core.platform import pin_cpu_platform

        pin_cpu_platform(n)
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_dp_train_step
    from horovod_tpu.core.platform import setup_compile_cache

    setup_compile_cache()
    model_name = os.environ["SCALING_MODEL"]
    batch = int(os.environ["SCALING_BATCH"])
    iters = int(os.environ["SCALING_ITERS"])
    bpi = int(os.environ["SCALING_BPI"])

    hvd.init()
    available = jax.devices()
    if len(available) < n:
        raise RuntimeError(
            f"scaling point n={n} requested but only {len(available)} "
            f"{available[0].platform} device(s) are visible — the point "
            f"would silently measure a smaller mesh.")
    devices = available[:n]
    mesh = Mesh(np.asarray(devices), ("data",))

    if model_name == "resnet50":
        from horovod_tpu.models import ResNet50

        model, side, num_classes = ResNet50(num_classes=1000), 224, 1000
    else:  # tiny: harness validation on CPU in seconds, same code path
        from horovod_tpu.models import ResNet
        from horovod_tpu.models.resnet import ResNetBlock

        model = ResNet(stage_sizes=[1], num_filters=8, num_classes=10,
                       block_cls=ResNetBlock, dtype=jnp.float32)
        side, num_classes = 32, 10

    global_batch = batch * n
    rng = jax.random.PRNGKey(0)
    x = jax.random.normal(rng, (global_batch, side, side, 3), jnp.float32)
    # label range follows the model's class count so this script measures
    # the identical protocol as bench.py (labels 0..999 for resnet50)
    y = jax.random.randint(rng, (global_batch,), 0, num_classes)
    variables = model.init(jax.random.PRNGKey(1), x[:2])
    params, batch_stats = variables["params"], variables.get(
        "batch_stats", {})
    opt = hvd.DistributedOptimizer(optax.sgd(0.01), axis_name="data")
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    step = make_dp_train_step(model, opt, mesh, axis_name="data")

    for _ in range(2):  # warmup / compile
        params, opt_state, batch_stats, _ = step(params, opt_state,
                                                 batch_stats, x, y)
    jax.block_until_ready(params)
    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        for _ in range(bpi):
            params, opt_state, batch_stats, _ = step(
                params, opt_state, batch_stats, x, y)
        jax.block_until_ready(params)
        rates.append(global_batch * bpi / (time.perf_counter() - t0))
    print(json.dumps({"devices": n, "img_per_s": float(np.mean(rates))}))
    hvd.shutdown()


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--devices", default="1,2,4,8",
                        help="comma list of device counts to measure")
    parser.add_argument("--model", default="tiny",
                        choices=["tiny", "resnet50"])
    parser.add_argument("--platform", default="cpu",
                        choices=["cpu", "native"],
                        help="cpu = virtual XLA CPU devices (harness "
                             "validation); native = whatever jax.devices() "
                             "exposes (the pod measurement)")
    parser.add_argument("--batch-size", type=int, default=32)
    parser.add_argument("--iters", type=int, default=5)
    parser.add_argument("--batches-per-iter", type=int, default=3)
    args = parser.parse_args()

    counts = [int(c) for c in args.devices.split(",")]
    points = []
    for n in counts:
        env = dict(os.environ)
        env.update({
            "SCALING_WORKER": "1",
            "SCALING_N_DEVICES": str(n),
            "SCALING_PLATFORM": args.platform,
            "SCALING_MODEL": args.model,
            "SCALING_BATCH": str(args.batch_size),
            "SCALING_ITERS": str(args.iters),
            "SCALING_BPI": str(args.batches_per_iter),
        })
        if args.platform == "cpu":
            env.pop("JAX_PLATFORMS", None)
        out = subprocess.run([sys.executable, os.path.abspath(__file__)],
                             env=env, capture_output=True, text=True,
                             timeout=1800)
        if out.returncode != 0:
            raise RuntimeError(f"point n={n} failed:\n{out.stderr}")
        points.append(json.loads(out.stdout.strip().splitlines()[-1]))
        print(json.dumps(points[-1]), flush=True)

    # Efficiency is defined against the single-device point (BASELINE.md's
    # ">=90% at 256 chips" is relative to n=1); without one, fall back to
    # the smallest measured point and say so.
    one = next((p for p in points if p["devices"] == 1), None)
    ref = one or min(points, key=lambda p: p["devices"])
    base = ref["img_per_s"] / ref["devices"]
    suffix = "" if one else f" (relative to n={ref['devices']}, no n=1 run)"
    print(f"\n{'devices':>8} {'img/s':>10} {'per-dev':>9} "
          f"{'efficiency':>11}{suffix}")
    for p in points:
        per_dev = p["img_per_s"] / p["devices"]
        print(f"{p['devices']:>8} {p['img_per_s']:>10.1f} {per_dev:>9.1f} "
              f"{per_dev / base:>10.1%}")


if __name__ == "__main__":
    if os.environ.get("SCALING_WORKER"):
        _measure()
    else:
        main()
