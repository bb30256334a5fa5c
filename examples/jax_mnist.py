"""Data-parallel MNIST training in JAX — the ``examples/pytorch_mnist.py``
equivalent for the TPU-native framework.

Follows the reference README's canonical steps: init → scale LR by the
device count → wrap the optimizer → broadcast initial state from rank 0 →
train, checkpointing on rank 0 only. Data is synthetic (no dataset
downloads in the benchmark environment); swap ``synthetic_mnist`` for a real
loader to train for accuracy.

Run single-host:   python examples/jax_mnist.py
Run multi-process: python -m horovod_tpu.runner -np 2 --host-data-plane \
                       python examples/jax_mnist.py
"""

import argparse

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import MnistCNN


def synthetic_mnist(n: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 28, 28, 1)).astype(np.float32)
    y = rng.integers(0, 10, size=(n,), dtype=np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-device batch size")
    parser.add_argument("--lr", type=float, default=0.01)
    parser.add_argument("--checkpoint-dir", default=None)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = hvd.local_device_count()

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(42),
                        jnp.zeros((1, 28, 28, 1)))

    # Reference README step 3: scale LR by the number of workers.
    opt = hvd.DistributedOptimizer(
        optax.sgd(args.lr * hvd.num_devices(), momentum=0.9),
        axis_name="data")
    opt_state = opt.init(params)

    # Step 4: rank-0-consistent start.
    params = hvd.broadcast_parameters(params, root_rank=0)
    opt_state = hvd.broadcast_optimizer_state(opt_state, root_rank=0)

    def loss_fn(p, x, y):
        logits = model.apply(p, x)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def train_step(p, s, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        updates, s = opt.update(grads, s, p)
        return optax.apply_updates(p, updates), s, jax.lax.pmean(loss, "data")

    step = hvd.parallel.data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P()))

    global_batch = args.batch_size * n_dev
    steps_per_epoch = 20
    for epoch in range(args.epochs):
        for i in range(steps_per_epoch):
            x, y = synthetic_mnist(global_batch, seed=epoch * 1000 + i)
            params, opt_state, loss = step(params, opt_state, x, y)
        # metric averaging across ranks (MetricAverageCallback pattern)
        logs = {"loss": float(loss)}
        hvd.callbacks.MetricAverageCallback().on_epoch_end(
            epoch, hvd.callbacks.TrainLoop(), logs)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={logs['loss']:.4f}")
            if args.checkpoint_dir:
                # Step 6: checkpoint on rank 0 only.
                hvd.checkpoint.save(
                    f"{args.checkpoint_dir}/epoch{epoch}",
                    {"params": params, "opt_state": opt_state})
    hvd.shutdown()


if __name__ == "__main__":
    main()
