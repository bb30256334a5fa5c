#!/usr/bin/env python
"""Transformer-LM synthetic benchmark: tokens/s/device + MFU.

The reference's benchmark family is conv nets (its 2019 vintage predates
LM training at scale); this is the framework's second flagship workload —
matmul-dominated, so it shows what the MXU can actually sustain where
ResNet-50 at bs32 is bandwidth-bound (docs/benchmarks.md "Why bs32
caps"). Same measurement protocol as ``bench.py``
(``examples/pytorch_synthetic_benchmark.py:24-110``): synthetic data,
10 warmup batches, ``--num-iters`` x ``--num-batches-per-iter`` timed
batches, mean ± 1.96σ; the step is the framework's product path
(``hvd.DistributedOptimizer`` over the data axis, jit + shard_map,
donated buffers, AOT-compiled).

Defaults are GPT-2-small-shaped (12 layers, 12 heads, d_model 768,
d_ff 3072, seq 1024, vocab 32768) with the Pallas flash-attention kernel
(``--attention dense`` for the XLA-fused baseline; the kernel runs in the
Pallas interpreter on the CPU backend so CPU CI drives the identical code
path).

One process, on the device it measures, like bench.py: it finds a TPU or
exits non-zero unless ``HOROVOD_BENCH_PLATFORM=cpu`` asks for a CPU run.
Prints ONE JSON line, metric ``transformer_lm_tokens_per_sec_per_device``
(vs_baseline null — the reference publishes no LM figure), stamped with
``platform`` / ``device_kind`` / ``n_devices``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np


def _parse_args(argv=None):
    parser = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    parser.add_argument("--num-layers", type=int, default=12)
    parser.add_argument("--num-heads", type=int, default=12)
    parser.add_argument("--d-model", type=int, default=768)
    parser.add_argument("--d-ff", type=int, default=3072)
    parser.add_argument("--vocab-size", type=int, default=32768)
    parser.add_argument("--seq-len", type=int, default=1024)
    parser.add_argument("--batch-size", type=int, default=8,
                        help="sequences per device")
    parser.add_argument("--attention", default="flash",
                        choices=["dense", "flash"])
    parser.add_argument("--remat", action="store_true",
                        help="jax.checkpoint each block (long-seq memory)")
    parser.add_argument("--num-warmup-batches", type=int, default=10)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    return parser.parse_args(argv)


def main() -> None:
    args = _parse_args()

    from bench import (
        _add_mfu_fields,
        _bench_device,
        _device_stamp,
        _git_head as _git_sha,
        _log as log,
        _maybe_dump_hlo,
        _maybe_profile_one_batch,
        _step_flops_of,
    )

    device = _bench_device()
    import jax
    import optax

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_lm_train_step, synthesize_lm_job
    from horovod_tpu.core.platform import setup_compile_cache
    from horovod_tpu.models import TransformerLM

    setup_compile_cache()
    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = mesh.size
    log(f"TransformerLM: {args.num_layers}L/{args.num_heads}H/"
        f"d{args.d_model}/ff{args.d_ff}, vocab {args.vocab_size}, "
        f"seq {args.seq_len}, batch {args.batch_size}/device, "
        f"attention={args.attention}, devices: {n_dev} "
        f"({device.platform}, {device.device_kind})")

    model = TransformerLM(
        vocab_size=args.vocab_size, num_layers=args.num_layers,
        num_heads=args.num_heads, d_model=args.d_model, d_ff=args.d_ff,
        max_seq_len=args.seq_len, attention=args.attention,
        remat=args.remat)
    global_batch = args.batch_size * n_dev

    # synthetic tokens + model init, on the mesh the step will use
    tokens, variables = synthesize_lm_job(model, mesh, global_batch,
                                          args.seq_len)
    params = variables["params"]
    log("model initialized")

    opt = hvd.DistributedOptimizer(
        optax.adamw(3e-4, weight_decay=0.01), axis_name="data")
    opt_state = jax.jit(opt.init)(params)
    params = hvd.broadcast_parameters(params, root_rank=0)
    step = make_lm_train_step(model, opt, mesh, axis_name="data")

    log("Compiling LM train step (AOT)...")
    compiled = step.lower(params, opt_state, tokens).compile()
    step_flops = _step_flops_of(compiled, log)
    _maybe_dump_hlo(compiled, log)

    loss = None

    def run_batch():
        nonlocal params, opt_state, loss
        params, opt_state, loss = compiled(params, opt_state, tokens)

    log(f"Running {args.num_warmup_batches} warmup batches...")
    for _ in range(args.num_warmup_batches):
        run_batch()
    jax.block_until_ready(params)

    _maybe_profile_one_batch(run_batch,
                             lambda: jax.block_until_ready(params), log)

    tok_secs = []
    tokens_per_batch = global_batch * args.seq_len
    for i in range(args.num_iters):
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            run_batch()
        jax.block_until_ready(params)
        dt = time.perf_counter() - t0
        rate = tokens_per_batch * args.num_batches_per_iter / dt
        tok_secs.append(rate)
        log(f"Iter #{i}: {rate:.0f} tokens/sec total")

    mean = float(np.mean(tok_secs))
    conf = float(1.96 * np.std(tok_secs))
    per_device = mean / n_dev
    log(f"Tokens/sec/device: {per_device:.0f} +- {conf / n_dev:.0f} "
        f"(loss {float(loss):.3f})")

    result = {
        "metric": "transformer_lm_tokens_per_sec_per_device",
        "value": round(per_device, 1),
        "unit": "tokens/s",
        "vs_baseline": None,  # the reference publishes no LM figure
        "attention": args.attention,
        "seq_len": args.seq_len,
        "batch_size": args.batch_size,
        **_device_stamp(device, n_dev),
        "git_sha": _git_sha(),
    }
    # steps/s, not tokens/s: step_flops is the whole per-device step
    _add_mfu_fields(result, step_flops, mean / tokens_per_batch,
                    device, log)
    print(json.dumps(result))
    hvd.shutdown()


if __name__ == "__main__":
    main()
