"""The benchmark's two step bodies and their synthetic jobs.

What every cell of ``chipbench`` (and ``bench.py``, ``chip_smoke.py``, the
``benchmarks/*_bench.py`` scripts) measures: an image step (model apply +
loss + grad + ``DistributedOptimizer`` update + BatchNorm statistics) and
a language-model step, each written once as the per-shard function and
compiled by ``hvd.parallel.data_parallel_step``, which owns how a
data-parallel step is traced and compiled.

Both bodies name the phases of the step with ``jax.named_scope`` —
``hvd.loss`` (forward; its transpose is the backward pass),
``hvd.apply_updates``, ``hvd.sync_stats`` — beside the ``hvd.exchange`` and
``hvd.optimizer`` that ``DistributedOptimizer`` brings; the names reach the
compiled HLO's ``op_name`` and are documented in docs/tracing.md.
"""

from __future__ import annotations


def _init_on_mesh(make, mesh, *specs):
    """Run ``make()`` as ONE jitted program on ``mesh``'s devices (an
    un-jitted flax init is hundreds of small compiles on a TPU), its
    outputs laid out by ``specs`` — i.e. already as the step wants them."""
    import jax
    from jax.sharding import NamedSharding

    return jax.jit(make, out_shardings=tuple(
        NamedSharding(mesh, spec) for spec in specs))()


def synthesize_image_job(model, mesh, global_batch: int, side: int,
                         num_classes: int, axis_name: str = "data"):
    """``(images, labels, variables)`` for ``make_dp_train_step``: one
    fixed synthetic batch split on ``axis_name`` and ``model``'s freshly
    initialised variables replicated, all from fixed seeds."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def make():
        rng = jax.random.PRNGKey(0)
        return (jax.random.normal(rng, (global_batch, side, side, 3),
                                  jnp.float32),
                jax.random.randint(rng, (global_batch,), 0, num_classes),
                model.init(jax.random.PRNGKey(1),
                           jnp.zeros((2, side, side, 3), jnp.float32)))

    return _init_on_mesh(make, mesh, P(axis_name), P(axis_name), P())


def synthesize_lm_job(model, mesh, global_batch: int, seq_len: int,
                      axis_name: str = "data"):
    """``(tokens, variables)`` for ``make_lm_train_step``: one fixed batch
    of random tokens split on ``axis_name`` and ``model``'s variables
    replicated. Parameter shapes depend on neither the attention backend
    nor the sequence length, so the init runs dense attention on a short
    input."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    def make():
        tokens = jax.random.randint(
            jax.random.PRNGKey(0), (global_batch, seq_len), 0,
            model.vocab_size, dtype=jnp.int32)
        return tokens, model.clone(attention="dense").init(
            jax.random.PRNGKey(1), jnp.zeros((2, 8), jnp.int32))

    return _init_on_mesh(make, mesh, P(axis_name), P())


def make_dp_train_step(model, opt, mesh, axis_name: str = "data",
                       donate: bool = True):
    """Build the jitted DP train step over ``mesh``'s ``axis_name``.

    Returns ``step(params, opt_state, batch_stats, x, y) -> (params,
    opt_state, batch_stats, loss)`` with x/y sharded on the data axis and
    everything else replicated; ``loss`` is the cross-replica mean of the
    loss the update was computed from. Models without BatchNorm pass
    ``batch_stats={}`` through unchanged. ``opt`` is the
    ``hvd.DistributedOptimizer`` over ``axis_name``."""
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import data_parallel_step

    def loss_fn(params, batch_stats, x, y):
        with jax.named_scope("hvd.loss"):
            logits, updated = model.apply(
                {"params": params, "batch_stats": batch_stats}, x,
                train=True, mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        return loss, updated.get("batch_stats", {})

    def train_step(params, opt_state, batch_stats, x, y):
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch_stats, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        # cross-replica BN statistics averaging (per-replica stats would be
        # rank-varying; the reference averages metrics the same way)
        with jax.named_scope("hvd.sync_stats"):
            new_stats = jax.tree_util.tree_map(
                lambda s: jax.lax.pmean(s, axis_name), new_stats)
        with jax.named_scope("hvd.apply_updates"):
            params = optax.apply_updates(params, updates)
        with jax.named_scope("hvd.sync_stats"):
            loss = jax.lax.pmean(loss, axis_name)
        return params, opt_state, new_stats, loss

    return data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P(), P(), P()),
        donate_argnums=(0, 1, 2) if donate else ())


def lm_step_loss(model, params, tokens):
    """The language-model step's loss, under ``hvd.loss``: the model is
    asked for the loss itself and forms head, loss and their gradients a
    block of rows at a time (``models.transformer.lm_head_loss``): faster
    than ``lm_loss`` over the whole float32 logits on four of the five LM
    cells and level on the fifth (PERF.md, PR 41)."""
    import jax

    with jax.named_scope("hvd.loss"):
        return model.apply({"params": params}, tokens, loss_tokens=tokens)


def bd_step_loss(model, params, clean, noisy, weights):
    """The block-diffusion step's loss, under ``hvd.loss``: the model
    (``models.sdar.SdarMoeLM``) scores the noisy rows against the clean ids
    at the masked positions, each by its weight."""
    import jax

    with jax.named_scope("hvd.loss"):
        return model.apply({"params": params}, clean, noisy, weights=weights)


def _lm_update(opt, axis_name, params, opt_state, loss_of):
    """What a language-model step does once it has its loss as a function
    of the parameters: gradient, ``DistributedOptimizer`` update, the loss
    averaged over the replicas."""
    import jax
    import optax

    loss, grads = jax.value_and_grad(loss_of)(params)
    updates, opt_state = opt.update(grads, opt_state, params)
    with jax.named_scope("hvd.apply_updates"):
        params = optax.apply_updates(params, updates)
    with jax.named_scope("hvd.sync_stats"):
        loss = jax.lax.pmean(loss, axis_name)
    return params, opt_state, loss


def make_lm_train_step(model, opt, mesh, axis_name: str = "data"):
    """Build the jitted DP language-model train step over ``mesh``.

    Returns ``step(params, opt_state, tokens) -> (params, opt_state,
    loss)`` with tokens ``[global_batch, seq]`` sharded on the data axis,
    params and optimizer state replicated and donated, and ``loss`` the
    cross-replica mean next-token cross entropy."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import data_parallel_step

    def train_step(params, opt_state, tokens):
        return _lm_update(opt, axis_name, params, opt_state,
                          lambda p: lm_step_loss(model, p, tokens))

    return data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), P(axis_name)), out_specs=(P(), P(), P()),
        donate_argnums=(0, 1))


def make_bd_train_step(model, opt, mesh, axis_name: str = "data"):
    """The block-diffusion train step over ``mesh``: ``step(params,
    opt_state, clean, noisy, weights) -> (params, opt_state, loss)``, the
    three data arrays ``[global_batch, seq]`` each sharded on the data axis,
    the rest as ``make_lm_train_step``'s; the loss is
    ``models.sdar.SdarMoeLM``'s, the noisy rows scored against the clean ids
    at the masked positions, each by its weight."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import data_parallel_step

    def train_step(params, opt_state, clean, noisy, weights):
        return _lm_update(
            opt, axis_name, params, opt_state,
            lambda p: bd_step_loss(model, p, clean, noisy, weights))

    return data_parallel_step(
        train_step, opt, mesh,
        in_specs=(P(), P(), P(axis_name), P(axis_name), P(axis_name)),
        out_specs=(P(), P(), P()), donate_argnums=(0, 1))
