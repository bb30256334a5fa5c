"""Eager-API MNIST — the ``tensorflow_mnist_eager.py`` analog: no jit-SPMD
step; gradients are computed per process and averaged through the *eager*
named-tensor allreduce (the ``DistributedGradientTape`` pattern,
``tensorflow/__init__.py:252-326``). Each named gradient is submitted
async, the engine fuses whatever lands in the same cycle
(HOROVOD_CYCLE_TIME) into one buffer, and ``synchronize`` hands back the
world-averaged result — the reference's enqueue→negotiate→fuse→execute
pipeline end to end.

This is the parity path, not the performance path: for throughput use the
jit/shard_map route (``examples/jax_mnist.py``) where XLA owns the
collectives.

Run single-process: python examples/jax_mnist_eager.py
Run multi-process:  python -m horovod_tpu.runner -np 2 --host-data-plane \
                        python examples/jax_mnist_eager.py
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import MnistCNN


def synthetic_mnist(n: int, seed: int):
    # one labeling function shared by EVERY seed (class prototypes from a
    # fixed generator): ranks see different samples of the SAME task, so
    # the world-averaged gradient actually converges — a per-seed
    # labeling would hand each rank a conflicting task
    proto = np.random.default_rng(0).standard_normal(
        (10, 28, 28, 1)).astype(np.float32)
    rng = np.random.default_rng(seed)
    y = rng.integers(0, 10, n).astype(np.int32)
    noise = rng.standard_normal((n, 28, 28, 1)).astype(np.float32)
    x = 0.1 * (proto[y] + noise)
    return jnp.asarray(x), jnp.asarray(y)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=40)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=0.02)
    parser.add_argument("--compression", type=str, default="none",
                        help="gradient wire codec: none/fp16/bf16/int8/"
                             "fp8/topk (docs/compression.md; topk is the "
                             "sparse wire — HOROVOD_SPARSE_TOPK picks k, "
                             "HOROVOD_SPARSE_ERROR_FEEDBACK=0 ablates "
                             "the residual)")
    args = parser.parse_args()
    compression = hvd.Compression.lookup(args.compression)

    hvd.init()
    print(f"rank {hvd.rank()}/{hvd.size()} on {jax.devices()}", flush=True)

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    opt = optax.sgd(args.lr * hvd.size(), momentum=0.9)
    opt_state = opt.init(params)

    # consistent start (reference step 6)
    params = hvd.broadcast_parameters(params, root_rank=0)

    @jax.jit
    def local_grads(params, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        return jax.value_and_grad(loss_fn)(params)

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    # each rank sees different data — the allreduce is what keeps replicas
    # identical
    x_all, y_all = synthetic_mnist(args.batch_size * args.steps,
                                   seed=1000 + hvd.rank())

    leaves, treedef = jax.tree_util.tree_flatten(params)
    names = [f"grad.{i}" for i in range(len(leaves))]

    for step in range(args.steps):
        lo = step * args.batch_size
        x, y = x_all[lo:lo + args.batch_size], y_all[lo:lo + args.batch_size]
        loss, grads = local_grads(params, x, y)

        # DistributedGradientTape: submit every named gradient async, let
        # the cycle fuse them, then synchronize in order. The sparse wire
        # needs step-stable names: its error-feedback residual is keyed by
        # tensor name, and a per-step suffix would orphan the carried mass
        # (safe here — every handle is synchronized before resubmission).
        sparse = getattr(compression, "sparse", False)
        grad_leaves = jax.tree_util.tree_leaves(grads)
        handles = [
            hvd.allreduce_async(np.asarray(g), average=True,
                                name=name if sparse else f"{name}.s{step}",
                                compression=compression)
            for name, g in zip(names, grad_leaves)
        ]
        averaged = [jnp.asarray(hvd.synchronize(h)) for h in handles]
        grads = jax.tree_util.tree_unflatten(treedef, averaged)

        params, opt_state = apply(params, opt_state, grads)
        if hvd.rank() == 0 and step % 10 == 0:
            print(f"step {step}: loss={float(loss):.4f}", flush=True)

    # deterministic final eval on this rank's training prefix (each seed
    # carries its OWN labeling function, so a fresh seed would measure an
    # unlearnable task): the machine-readable line the convergence-parity
    # certification (__graft_entry__.dryrun_sparse) compares across codecs
    final_loss, _ = local_grads(params, x_all[:256], y_all[:256])
    if hvd.rank() == 0:
        print(f"final_loss={float(final_loss):.6f}", flush=True)
        print("done", flush=True)
    hvd.shutdown()


if __name__ == "__main__":
    main()
