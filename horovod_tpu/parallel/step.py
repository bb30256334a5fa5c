"""The one place a data-parallel train step is traced and compiled.

A per-shard ``train_step`` becomes ``jax.jit(shard_map(train_step))`` here
and nowhere else, because two things about that wrapping decide whether
the step is right and fast, and neither is the caller's to know:

* **the tracing mode.** Under ``shard_map``'s default (vma tracking) the
  gradient of a replicated parameter arrives at the optimizer already
  summed, by a full-precision all-reduce in the transpose of the loss. An
  optimizer whose exchange has to carry the bytes itself — a wire codec,
  or the factored (dcn, ici) route — would then act on nothing, silently;
  such a step is traced with ``check_vma=False``. Which it is follows
  from the ``DistributedOptimizer`` (``optimizers.exchange_route``).
* **the compile options** under which the TPU compiler runs the exchange
  beside other work, which follow from the mesh
  (``ops.spmd.overlap_compiler_options``).
"""

import jax
from jax import shard_map

from ..ops import spmd
from ..optimizers import exchange_route


def data_parallel_step(train_step, optimizer, mesh, in_specs, out_specs,
                       donate_argnums=()):
    """Compile the per-shard ``train_step`` as one program over ``mesh``.

    ``train_step`` is what one shard runs: it takes and returns what
    ``in_specs`` / ``out_specs`` lay out (``jax.shard_map``'s own
    arguments) and calls ``optimizer.update`` on its local gradients.
    ``optimizer`` is the ``hvd.DistributedOptimizer(..., axis_name=...)``
    it calls: its axis is the one the gradients cross, and its codec and
    route pick the tracing mode. Anything else is refused with a
    ``ValueError``: a plain optax transform exchanges nothing, and one
    without an axis reduces through the eager engine.

    Returns what ``jax.jit`` returns — call it, ``.lower()`` it,
    ``.compile()`` it; ``donate_argnums`` is ``jax.jit``'s — named after
    ``train_step``, so the program is ``jit(train_step)`` in profiles and
    compile logs."""
    axis_name, carries_bytes = exchange_route(optimizer)
    return jax.jit(
        shard_map(train_step, mesh=mesh, in_specs=in_specs,
                  out_specs=out_specs, check_vma=not carries_bytes),
        donate_argnums=donate_argnums,
        compiler_options=spmd.overlap_compiler_options(mesh, axis_name))
