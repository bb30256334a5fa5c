"""ResNet-50 ImageNet-style training — analog of the reference's
``examples/keras_imagenet_resnet50.py`` / ``pytorch_imagenet_resnet50.py``:
LR = base * num_devices with gradual warmup (Goyal et al.), staircase decay
at epochs 30/60/80, bf16 compression on the gradient allreduce, checkpoint
on rank 0. Data is synthetic unless a loader is plugged in.

Run: python examples/jax_imagenet_resnet50.py --epochs 1 --steps-per-epoch 5 \
         --batch-size 8 --image-size 64   (smoke settings)
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
from horovod_tpu.callbacks import warmup_schedule
from horovod_tpu.models import ResNet50


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--epochs", type=int, default=90)
    parser.add_argument("--steps-per-epoch", type=int, default=100)
    parser.add_argument("--batch-size", type=int, default=32,
                        help="per-device batch size")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--base-lr", type=float, default=0.0125,
                        help="per-device LR (reference keras example)")
    parser.add_argument("--warmup-epochs", type=int, default=5)
    parser.add_argument("--checkpoint-dir", default=None)
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    n_dev = hvd.local_device_count()

    model = ResNet50(num_classes=1000)
    params_vars = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((2, args.image_size, args.image_size, 3)))
    params, batch_stats = params_vars["params"], params_vars["batch_stats"]

    # Warmup to base_lr * num_devices over warmup_epochs, then staircase
    # decay (reference LearningRateScheduleCallback stack at 30/60/80).
    def decay(step):
        epoch = step // args.steps_per_epoch + args.warmup_epochs
        scale = jnp.where(epoch >= 80, 1e-3,
                          jnp.where(epoch >= 60, 1e-2,
                                    jnp.where(epoch >= 30, 1e-1, 1.0)))
        return args.base_lr * hvd.num_devices() * scale

    schedule = warmup_schedule(args.base_lr, args.steps_per_epoch,
                               warmup_epochs=args.warmup_epochs, after=decay)
    opt = hvd.DistributedOptimizer(
        optax.sgd(schedule, momentum=0.9, nesterov=True),
        axis_name="data", compression=hvd.Compression.bf16)
    opt_state = opt.init(params)
    params = hvd.broadcast_parameters(params, root_rank=0)

    def loss_fn(p, stats, x, y):
        logits, updated = model.apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()
        return loss, updated["batch_stats"]

    def train_step(p, s, stats, x, y):
        (loss, stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, stats, x, y)
        updates, s = opt.update(grads, s, p)
        stats = jax.tree_util.tree_map(
            lambda v: jax.lax.pmean(v, "data"), stats)
        return (optax.apply_updates(p, updates), s, stats,
                jax.lax.pmean(loss, "data"))

    step = hvd.parallel.data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), P(), P("data"), P("data")),
        out_specs=(P(), P(), P(), P()))

    global_batch = args.batch_size * n_dev
    rng = np.random.default_rng(hvd.rank())
    for epoch in range(args.epochs):
        for _ in range(args.steps_per_epoch):
            x = jnp.asarray(rng.standard_normal(
                (global_batch, args.image_size, args.image_size, 3),
                dtype=np.float32))
            y = jnp.asarray(rng.integers(0, 1000, size=(global_batch,)))
            params, opt_state, batch_stats, loss = step(
                params, opt_state, batch_stats, x, y)
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss={float(loss):.4f}")
            if args.checkpoint_dir:
                hvd.checkpoint.save(f"{args.checkpoint_dir}/epoch{epoch}",
                                    {"params": params,
                                     "batch_stats": batch_stats})
    hvd.shutdown()


if __name__ == "__main__":
    main()
