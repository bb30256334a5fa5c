"""Shared data-parallel train-step construction for the benchmark scripts.

One definition of each measured program (model apply + loss + grad +
DistributedOptimizer update, jitted as a shard_map over the data axis) so
`bench.py`, `benchmarks/scaling_bench.py`, `benchmarks/lm_bench.py` and
`chip_smoke.py` cannot drift apart — the reference keeps its protocol in
one script per framework for the same reason
(``examples/pytorch_synthetic_benchmark.py:37-110``).

Both builders name the phases of the step with ``jax.named_scope`` —
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
                       donate: bool = True, hierarchical=None,
                       scan_batches: int = 1, explicit_grad_reduce=None):
    """Build the jitted DP train step over ``mesh``'s ``axis_name``.

    Returns ``step(params, opt_state, batch_stats, x, y) -> (params,
    opt_state, batch_stats, loss)`` with x/y sharded on the data axis and
    everything else replicated; ``loss`` is the cross-replica mean of the
    loss the update was computed from (the last batch's, when scanning).
    Models without BatchNorm pass ``batch_stats={}`` through unchanged.

    ``scan_batches > 1`` wraps the step body in ``lax.scan`` so ONE
    dispatched call executes N batches back to back on device (same
    static batch — the synthetic-benchmark situation). Diagnostic, not
    protocol: comparing it against N separate dispatches isolates
    Python-dispatch / pipeline-drain overhead from true device time
    (docs/benchmarks.md "Why bs32 caps", item 2).

    ``hierarchical`` (default: follow ``HOROVOD_HIERARCHICAL_ALLREDUCE``
    via the optimizer's own resolution) selects the two-level factored
    gradient reduction over a (dcn, ici) ``axis_name`` pair. That mode
    traces with ``check_vma=False``: under vma tracking shard_map pre-sums
    replicated-param cotangents with a flat whole-mesh psum before the
    optimizer's transform runs, which would silently bypass the factored
    reduce_scatter/psum/all_gather route (``operations.cc:1284-1436``'s
    TPU analog in ``parallel/hierarchical.py``).

    ``explicit_grad_reduce`` (default: equals ``hierarchical``) forces the
    same ``check_vma=False`` tracing WITHOUT the factored route — needed
    whenever the optimizer's own reduction must carry the bytes, e.g.
    gradient compression: under vma tracking the auto-inserted psum runs
    in f32 BEFORE the compress hook, so the cast would be numerics-only
    and never shrink the collective's wire traffic.
    """
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.ops.spmd import overlap_compiler_options

    if hierarchical is None:
        from horovod_tpu.optimizers import _use_hierarchical

        hierarchical = _use_hierarchical(axis_name, None)
    if explicit_grad_reduce is None:
        explicit_grad_reduce = hierarchical

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

    if scan_batches > 1:
        single = train_step

        def train_step(params, opt_state, batch_stats, x, y):  # noqa: F811
            def body(carry, _):
                *carry, loss = single(*carry, x, y)
                return tuple(carry), loss

            carry, losses = jax.lax.scan(
                body, (params, opt_state, batch_stats), None,
                length=scan_batches)
            return (*carry, losses[-1])

    return jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(), P(axis_name), P(axis_name)),
                  out_specs=(P(), P(), P(), P()),
                  check_vma=not (hierarchical or explicit_grad_reduce)),
        donate_argnums=(0, 1, 2) if donate else (),
        compiler_options=overlap_compiler_options(mesh, axis_name))


def make_lm_train_step(model, opt, mesh, axis_name: str = "data"):
    """Build the jitted DP language-model train step over ``mesh``.

    Returns ``step(params, opt_state, tokens) -> (params, opt_state,
    loss)`` with tokens ``[global_batch, seq]`` sharded on the data axis,
    params and optimizer state replicated and donated, and ``loss`` the
    cross-replica mean next-token cross entropy."""
    import jax
    import optax
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.models import lm_loss
    from horovod_tpu.ops.spmd import overlap_compiler_options

    def train_step(params, opt_state, tokens):
        def loss_fn(p):
            with jax.named_scope("hvd.loss"):
                return lm_loss(model.apply({"params": p}, tokens), tokens)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = opt.update(grads, opt_state, params)
        with jax.named_scope("hvd.apply_updates"):
            params = optax.apply_updates(params, updates)
        with jax.named_scope("hvd.sync_stats"):
            loss = jax.lax.pmean(loss, axis_name)
        return params, opt_state, loss

    return jax.jit(
        shard_map(train_step, mesh=mesh,
                  in_specs=(P(), P(), P(axis_name)),
                  out_specs=(P(), P(), P())),
        donate_argnums=(0, 1),
        compiler_options=overlap_compiler_options(mesh, axis_name))
