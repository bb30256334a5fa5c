"""dm-haiku front-end example: the same DistributedOptimizer wraps any
optax-based framework — flax (``jax_mnist.py``), haiku (here), or raw JAX.
Mirrors the reference's pattern of one optimizer wrapper serving many
front-ends (SURVEY §2.2-2.5).

Run: python examples/haiku_mnist.py
"""

import argparse

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import haiku as hk
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd


def net_fn(x):
    return hk.Sequential([
        hk.Conv2D(32, 3), jax.nn.relu,
        hk.MaxPool(2, 2, "VALID"),
        hk.Flatten(),
        hk.Linear(128), jax.nn.relu,
        hk.Linear(10),
    ])(x)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch-size", type=int, default=32)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = hvd.local_device_count()

    net = hk.without_apply_rng(hk.transform(net_fn))
    params = net.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    # adaptive optimizers don't linear-scale with world size (the Goyal
    # rule is for SGD); keep the base LR
    opt = hvd.DistributedOptimizer(optax.adam(1e-3), axis_name="data")
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    def loss_fn(p, x, y):
        logits = net.apply(p, x)
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

    rng = np.random.default_rng(0)
    global_batch = args.batch_size * n_dev
    # small fixed synthetic dataset so the loss visibly decreases
    dataset = [
        (jnp.asarray(rng.standard_normal(
            (global_batch, 28, 28, 1)).astype(np.float32)),
         jnp.asarray(rng.integers(0, 10, size=(global_batch,))))
        for _ in range(4)
    ]
    for i in range(args.steps):
        x, y = dataset[i % len(dataset)]
        params, opt_state, loss = step(params, opt_state, x, y)
    if hvd.rank() == 0:
        print(f"final loss: {float(loss):.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
