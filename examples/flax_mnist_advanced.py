"""Callbacks-driven MNIST training — the ``keras_mnist_advanced.py`` analog
(reference ``examples/keras_mnist_advanced.py``): broadcast-at-start,
gradual LR warmup (Goyal et al.), per-epoch metric averaging across ranks,
and rank-0-only checkpointing, all expressed through the callback surface
(``hvd.callbacks``) that mirrors the reference's Keras callbacks.

The LR-mutating callbacks need the optimizer built with
``optax.inject_hyperparams`` so ``learning_rate`` is a mutable leaf of the
optimizer state — the analog of Keras's mutable ``optimizer.lr``.

Run single-host:   python examples/flax_mnist_advanced.py
Run multi-process: python -m horovod_tpu.runner -np 2 --host-data-plane \
                       python examples/flax_mnist_advanced.py
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
    x = rng.standard_normal((n, 28, 28, 1)).astype(np.float32) * 0.1
    w = rng.standard_normal((28 * 28, 10)).astype(np.float32)
    # learnable structure so accuracy visibly improves
    y = np.argmax(x.reshape(n, -1) @ w, axis=1).astype(np.int32)
    return jnp.asarray(x), jnp.asarray(y)


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=4)
    parser.add_argument("--batch-size", type=int, default=64)
    parser.add_argument("--base-lr", type=float, default=0.01)
    parser.add_argument("--warmup-epochs", type=int, default=2)
    parser.add_argument("--checkpoint-dir", default=None)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = hvd.local_device_count()
    global_batch = args.batch_size * n_dev

    model = MnistCNN()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))

    # inject_hyperparams makes learning_rate a state leaf the LR callbacks
    # can poke between batches (keras_mnist_advanced sets optimizer.lr).
    opt = hvd.DistributedOptimizer(
        optax.inject_hyperparams(optax.sgd)(
            learning_rate=args.base_lr, momentum=0.9),
        axis_name="data")
    opt_state = opt.init(params)

    def train_step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply(p, x)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, jnp.argmax(logits, -1)

        (loss, pred), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        acc = jnp.mean((pred == y).astype(jnp.float32))
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, "data"), jax.lax.pmean(acc, "data"))

    step = hvd.parallel.data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()))

    x_all, y_all = synthetic_mnist(global_batch * 12, seed=1000 + hvd.rank())
    steps_per_epoch = x_all.shape[0] // global_batch

    state = hvd.callbacks.TrainLoop(params=params, opt_state=opt_state,
                                    learning_rate=args.base_lr)
    callbacks = hvd.callbacks.CallbackList([
        # keras_mnist_advanced callback stack, one-for-one:
        hvd.callbacks.BroadcastGlobalVariablesCallback(root_rank=0),
        hvd.callbacks.LearningRateWarmupCallback(
            initial_lr=args.base_lr, warmup_epochs=args.warmup_epochs,
            steps_per_epoch=steps_per_epoch),
        hvd.callbacks.MetricAverageCallback(),
    ])

    callbacks.on_train_begin(state)
    for epoch in range(args.epochs):
        callbacks.on_epoch_begin(epoch, state)
        losses, accs = [], []
        for b in range(steps_per_epoch):
            callbacks.on_batch_begin(b, state)
            lo = b * global_batch
            x, y = x_all[lo:lo + global_batch], y_all[lo:lo + global_batch]
            state.params, state.opt_state, loss, acc = step(
                state.params, state.opt_state, x, y)
            losses.append(float(loss))
            accs.append(float(acc))
        logs = {"loss": float(np.mean(losses)),
                "accuracy": float(np.mean(accs))}
        callbacks.on_epoch_end(epoch, state, logs)  # world-averaged in place
        if hvd.rank() == 0:
            print(f"epoch {epoch}: lr={state.learning_rate:.4f} "
                  f"loss={logs['loss']:.4f} acc={logs['accuracy']:.3f}",
                  flush=True)
            if args.checkpoint_dir:
                # rank-0-only checkpointing (README Usage step 6)
                hvd.checkpoint.save(
                    os.path.join(args.checkpoint_dir, f"epoch{epoch}"),
                    {"params": state.params, "opt_state": state.opt_state})
    hvd.shutdown()


if __name__ == "__main__":
    main()
