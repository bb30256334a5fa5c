"""Transformer LM training: data-parallel and sequence-parallel modes.

Beyond-parity example (the reference predates attention entirely, SURVEY
§5.7): one model (``horovod_tpu.models.TransformerLM``), three launch modes
on the same device mesh —

* ``--mode dp``      data-parallel batch sharding (the reference's product)
* ``--mode ring``    ring-attention sequence parallelism: the *sequence*
                     dimension is sharded; K/V blocks rotate over the axis
* ``--mode ulysses`` all_to_all head re-sharding sequence parallelism

Run:  python examples/jax_transformer_lm.py --mode ring --seq-len 512
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
from horovod_tpu.models import TransformerLM, lm_loss

VOCAB = 128


def synthetic_text(n_seq: int, seq_len: int, seed: int):
    """Repeating n-gram structure so the LM has something to learn."""
    rng = np.random.default_rng(seed)
    base = np.tile(np.arange(16), (n_seq, seq_len // 16 + 1))[:, :seq_len]
    noise = rng.integers(0, 4, (n_seq, seq_len))
    return jnp.asarray(((base * 7 + noise) % VOCAB).astype(np.int32))


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode", default="dp",
                        choices=["dp", "ring", "ulysses"])
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch-size", type=int, default=8,
                        help="global batch (dp shards it; sp replicates it)")
    parser.add_argument("--seq-len", type=int, default=256)
    parser.add_argument("--lr", type=float, default=1e-2)
    parser.add_argument("--remat", action="store_true",
                        help="jax.checkpoint each block: recompute "
                             "activations in backward (memory for FLOPs — "
                             "the lever for longer sequences per chip)")
    args = parser.parse_args()

    hvd.init()
    mesh = hvd.parallel.data_parallel_mesh()
    axis = hvd.parallel.DATA_AXIS
    n_dev = hvd.num_devices()  # mesh spans ALL devices in the world
    seq_parallel = args.mode != "dp"
    if seq_parallel and args.seq_len % n_dev:
        raise SystemExit(f"--seq-len must divide by {n_dev} devices")
    if not seq_parallel and args.batch_size % n_dev:
        raise SystemExit(f"--batch-size must divide by {n_dev} devices")

    model = TransformerLM(
        vocab_size=VOCAB, num_layers=2, num_heads=8, d_model=128, d_ff=512,
        max_seq_len=args.seq_len, dtype=jnp.float32,
        attention={"dp": "dense", "ring": "ring",
                   "ulysses": "ulysses"}[args.mode],
        seq_axis=axis if seq_parallel else None, remat=args.remat)
    # dense twin for init: same structure/params, no axis requirement
    init_model = model.clone(attention="dense", seq_axis=None)
    tokens = synthetic_text(args.batch_size, args.seq_len,
                            seed=1000 + (0 if seq_parallel else hvd.rank()))
    variables = init_model.init(jax.random.PRNGKey(0), tokens[:1, :8])
    variables = hvd.broadcast_parameters(variables, root_rank=0)

    opt = hvd.DistributedOptimizer(optax.adam(args.lr), axis_name=axis)
    opt_state = opt.init(variables)
    positions = jnp.broadcast_to(jnp.arange(args.seq_len), tokens.shape)

    def train_step(variables, opt_state, tokens, positions):
        # loss_fn stays LOCAL in both modes: dp shards the batch, sp shards
        # the sequence (each shard scores its next-token slice; the target
        # of a shard's last position lives on the next shard and is skipped
        # — a 1/seq_local margin). The DistributedOptimizer averages the
        # pre-summed replicated-param gradients over the axis, which IS the
        # gradient of the pmean'd global loss — adding a pmean inside
        # loss_fn would divide the gradients by the axis size twice.
        def loss_fn(v):
            return lm_loss(model.apply(v, tokens, positions), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(variables)
        updates, opt_state = opt.update(grads, opt_state, variables)
        new_vars = optax.apply_updates(variables, updates)
        return new_vars, opt_state, jax.lax.pmean(loss, axis)

    data_spec = P(None, axis) if seq_parallel else P(axis)
    step = hvd.parallel.data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), data_spec, data_spec),
        out_specs=(P(), P(), P()))

    for i in range(args.steps):
        variables, opt_state, loss = step(variables, opt_state, tokens,
                                          positions)
        if hvd.rank() == 0 and (i % 10 == 0 or i == args.steps - 1):
            print(f"step {i}: loss={float(loss):.4f} mode={args.mode}")
    if hvd.rank() == 0:
        print("done")
    hvd.shutdown()


if __name__ == "__main__":
    main()
