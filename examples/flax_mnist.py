"""MNIST via the Flax front-end — the ``keras_mnist.py`` analog (reference
``examples/keras_mnist.py``): build a model, wrap the optimizer with the
front-end's ``DistributedTrainState`` (the ``hvd.DistributedOptimizer``
Keras wrap), broadcast initial state, train data-parallel, checkpoint on
rank 0, and prove resume via ``load_model``.

Run single-host:   python examples/flax_mnist.py
Run multi-process: python -m horovod_tpu.runner -np 2 --host-data-plane \
                       python examples/flax_mnist.py
"""

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
import horovod_tpu.flax as hvd_flax
from horovod_tpu.models import MnistCNN


def synthetic_mnist(n: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, 28, 28, 1)).astype(np.float32) * 0.1
    w = rng.standard_normal((28 * 28, 10)).astype(np.float32)
    y = np.argmax(x.reshape(n, -1) @ w, axis=1).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=2)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--lr", type=float, default=0.01)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = hvd.local_device_count()
    global_batch = args.batch_size * n_dev

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))

    # Scale LR by world size (reference keras_mnist.py: lr * hvd.size()) and
    # wrap via the front-end; axis_name routes averaging onto the mesh.
    def make_state():
        return hvd_flax.DistributedTrainState.create(
            apply_fn=model.apply, params=params,
            tx=optax.sgd(args.lr * hvd.num_devices(), momentum=0.9),
            axis_name=hvd.parallel.DATA_AXIS)

    state = hvd_flax.broadcast_train_state(make_state(), root_rank=0)

    def train_step(state, x, y):
        def loss_fn(p):
            logits = state.apply_fn(p, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(state.params)
        loss = jax.lax.pmean(loss, hvd.parallel.DATA_AXIS)
        return state.apply_gradients(grads=grads), loss

    step = hvd.parallel.data_parallel_step(
        train_step, state.tx, mesh,
        in_specs=(P(), P(hvd.parallel.DATA_AXIS), P(hvd.parallel.DATA_AXIS)),
        out_specs=(P(), P()))

    x_all, y_all = synthetic_mnist(global_batch * 10, seed=1000 + hvd.rank())
    for epoch in range(args.epochs):
        losses = []
        for b in range(x_all.shape[0] // global_batch):
            sl = slice(b * global_batch, (b + 1) * global_batch)
            state, loss = step(state, x_all[sl], y_all[sl])
            losses.append(float(jnp.mean(loss)))
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss {np.mean(losses):.4f}")

    # Rank-0 checkpoint + load_model resume (test_keras.py:62-246 pattern).
    # The path is rank-0's and shared (restore is collective: every rank
    # loads, then root's copy is broadcast — checkpoint.restore contract).
    ckpt = os.path.join(tempfile.mkdtemp(), "flax_mnist_ckpt")
    hvd_flax.save_model(ckpt, state)
    # Broadcasting rank-0's path doubles as the write barrier: no rank can
    # learn the path (and start reading) before rank 0 finished saving.
    ckpt = hvd.broadcast_object(ckpt, 0)
    restored = hvd_flax.load_model(ckpt, make_state())
    assert int(restored.step) == int(state.step)
    if hvd.rank() == 0:
        print(f"restored at step {int(restored.step)}: OK")
    hvd.shutdown()


if __name__ == "__main__":
    main()
