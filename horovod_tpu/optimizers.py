"""DistributedOptimizer: gradient averaging injected into an optimizer.

Rebuild of the reference's framework optimizer wrappers:
``horovod/torch/__init__.py:65-198`` (``_DistributedOptimizer`` with
per-parameter hooks and ``backward_passes_per_step`` accumulation) and
``horovod/tensorflow/__init__.py:151-249`` (``compute_gradients`` override).
The JAX-native form is an ``optax.GradientTransformation`` wrapper: gradient
averaging happens at ``update()`` time, before the inner optimizer sees the
gradients.

Two modes, matching ``ops``:

* **SPMD** (``axis_name=...``): for train steps compiled with
  ``pjit``/``shard_map`` over a mesh — the averaging is a ``lax.pmean`` that
  XLA schedules and fuses on ICI. This is the TPU hot path; there is no
  engine, no host hop, and XLA's all-reduce combiner plays the role of the
  reference's fusion buffer (``HOROVOD_FUSION_THRESHOLD``).
* **Eager** (default): concrete per-process gradients are submitted to the
  background engine as named tensors — one async allreduce per leaf,
  synchronized together, which exercises the same fusion path the reference
  drives from its gradient hooks (``torch/__init__.py:95-130``).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax
from jax import lax

from . import basics, ops
from .core.logging import LOG
from .ops.compression import Compression

# Build-time knob resolutions made BEFORE hvd.init() (env reads).
# init() audits these against the pinned config: a step traced before init
# keeps its build-time routing/codec forever, so a divergence would
# otherwise be silent (see check_build_time_resolutions).
_prebuild_hierarchical_resolutions: list = []
_prebuild_compression_resolutions: list = []


def _use_hierarchical(axis_name, hierarchical) -> bool:
    if hierarchical is not None:
        return hierarchical
    if isinstance(axis_name, str) or axis_name is None or \
            len(tuple(axis_name)) != 2:
        return False
    # HOROVOD_HIERARCHICAL_ALLREDUCE knob, as in the reference
    # (operations.cc:1880-1890). Resolution must not depend on init order:
    # exchange_route consults this at BUILD time to pick check_vma, and
    # a step built before hvd.init() would otherwise silently lose the
    # factored route (vma tracking pre-psums the cotangents). Initialized
    # worlds use the pinned config; otherwise read the env directly.
    if basics.is_initialized():
        return basics.config().hierarchical_allreduce
    from .core.config import Config

    resolved = Config.from_env().hierarchical_allreduce
    _prebuild_hierarchical_resolutions.append(resolved)
    return resolved


def _resolve_compression(compression, record: bool = False):
    """``compression=None`` means "follow the HOROVOD_COMPRESSION knob"
    (``core.config``): initialized worlds use the pinned config; before
    ``hvd.init()`` the env is read directly — same build-time semantics
    as the hierarchical knob (a step traced before init keeps its
    build-time codec). An explicit ``Compression.*`` argument always
    wins. ``record=True`` registers a pre-init resolution for the
    ``check_build_time_resolutions`` audit — set only by the reduction
    sites that actually bake the codec into a traced step, so ad-hoc
    resolutions (tests, introspection) cannot trigger spurious
    stale-codec warnings at the next init."""
    if compression is not None:
        return compression
    if basics.is_initialized():
        name = basics.config().compression
    else:
        from .core.config import Config

        name = Config.from_env().compression
        if record:
            _prebuild_compression_resolutions.append(name)
    return Compression.lookup(name)


def check_build_time_resolutions(cfg) -> None:
    """Called by ``hvd.init()``: warn when a step traced before init
    resolved the hierarchical knob differently from the now-pinned config
    (env changed between build and init, or ``init(config=...)`` overrode
    it). The traced step silently keeps its build-time behavior — XLA has
    already baked the collective routing in — so the only honest remedy is
    to rebuild the step or align the config."""
    stale = {v for v in _prebuild_hierarchical_resolutions
             if v != cfg.hierarchical_allreduce}
    stale_codecs = {v for v in _prebuild_compression_resolutions
                    if v != cfg.compression}
    # Consume the audited entries: a later shutdown/re-init must only audit
    # steps built since THIS init, not re-warn about ones already reported
    # (which may have been rebuilt by then).
    _prebuild_hierarchical_resolutions.clear()
    _prebuild_compression_resolutions.clear()
    if stale:
        built = "ON" if True in stale else "off"
        pinned = "ON" if cfg.hierarchical_allreduce else "off"
        LOG.warning(
            "a train step was built before hvd.init() with hierarchical "
            "allreduce %s, but the initialized world pins it %s. Steps "
            "traced before init keep their build-time collective routing; "
            "rebuild them after init (or align "
            "HOROVOD_HIERARCHICAL_ALLREDUCE / init(config=...)) so the "
            "routing matches the pinned config.", built, pinned)
    if stale_codecs:
        LOG.warning(
            "a train step was built before hvd.init() with compression "
            "codec %s (HOROVOD_COMPRESSION), but the initialized world "
            "pins %r. Steps traced before init keep their build-time "
            "wire codec; rebuild them after init (or align the env / "
            "init(config=...)) so the wire matches the pinned config.",
            "/".join(sorted(stale_codecs)), cfg.compression)


def allreduce_gradients(grads: Any, axis_name=None, average: bool = True,
                        compression=None,
                        hierarchical: Optional[bool] = None) -> Any:
    """Average a gradient pytree across the world.

    The DistributedGradientTape analog
    (``tensorflow/__init__.py:252-326``): apply to any grads pytree before
    feeding an optimizer. With a two-axis ``axis_name`` (dcn, ici) and
    ``hierarchical`` (or ``HOROVOD_HIERARCHICAL_ALLREDUCE``), varying
    gradients take the factored reduce_scatter/allreduce/all_gather route
    of ``parallel.hierarchical``. ``compression=None`` follows the
    ``HOROVOD_COMPRESSION`` knob; a quantized codec (``Compression.int8``
    / ``.fp8``) moves the collective bytes as block-quantized wire — on
    the hierarchical route only the DCN hop is quantized (the EQuARX
    design point)."""
    compression = _resolve_compression(compression, record=True)
    quantized = bool(getattr(compression, "quantized", False))
    leaves, treedef = jax.tree_util.tree_flatten(grads)
    if axis_name is not None:
        if _use_hierarchical(axis_name, hierarchical):
            from .ops.spmd import _varies_over, _vma_tracking_active
            from .parallel.hierarchical import hierarchical_grad_allreduce

            dcn_axis, ici_axis = tuple(axis_name)
            # The factored route applies when the gradient still needs
            # cross-device summing: a varying cotangent under vma tracking,
            # or ANY cotangent under legacy tracing (check_vma=False, where
            # shard_map does not auto-psum transposes — the mode a
            # hierarchical step should be built in, because vma tracking
            # pre-sums replicated-param grads with a flat whole-mesh psum
            # before this transform ever sees them, silencing the knob).
            legacy = not _vma_tracking_active(axis_name)
            reduced = []
            factored_leaves = 0
            for g in leaves:
                comp, ctx = compression.compress(g)
                if legacy or _varies_over(comp, axis_name):
                    factored_leaves += 1
                    red = hierarchical_grad_allreduce(
                        comp, dcn_axis, ici_axis, average=average,
                        codec=compression if quantized else None)
                else:
                    # pre-summed cotangent (see ops.spmd.allreduce)
                    red = ops.spmd.allreduce(comp, axis_name, average=average)
                reduced.append(compression.decompress(red, ctx))
            if leaves and not factored_leaves:
                # The knob is ON but every cotangent arrived pre-summed by
                # vma tracking's flat whole-mesh psum — the factored
                # reduce_scatter/psum/all_gather route never fires. Runs at
                # trace time, so this warns once per trace, not per step.
                source = ("hierarchical=True" if hierarchical
                          else "HOROVOD_HIERARCHICAL_ALLREDUCE")
                LOG.warning(
                    "hierarchical allreduce is enabled (via %s) but every "
                    "gradient leaf arrived pre-summed (vma tracking inserts "
                    "a flat whole-mesh psum in the shard_map transpose), so "
                    "the factored hierarchical route is inert for this "
                    "step. Build the step with "
                    "hvd.parallel.data_parallel_step (by hand: shard_map("
                    "..., check_vma=False)) so cotangents reach the "
                    "optimizer unsummed.", source)
            return jax.tree_util.tree_unflatten(treedef, reduced)
        reduced = [
            ops.allreduce(g, average=average, compression=compression,
                          axis_name=axis_name)
            for g in leaves
        ]
        return jax.tree_util.tree_unflatten(treedef, reduced)
    # Eager: submit all leaves asynchronously first so the engine can fuse
    # them into buckets (the reference's gradient hooks achieve the same
    # arrival pattern), then synchronize in order.
    handles = [
        ops.allreduce_async(g, average=average,
                            name=f"DistributedOptimizer.grad.{i}",
                            compression=compression)
        for i, g in enumerate(leaves)
    ]
    reduced = [ops.synchronize(h) for h in handles]
    return jax.tree_util.tree_unflatten(treedef, reduced)


class DistributedOptState(NamedTuple):
    inner: Any
    accum: Any  # gradient accumulator (backward_passes_per_step > 1) or None
    counter: jnp.ndarray  # passes since last allreduce+apply


def DistributedOptimizer(optimizer: optax.GradientTransformation,
                         *,
                         axis_name=None,
                         compression=None,
                         average: bool = True,
                         backward_passes_per_step: int = 1,
                         hierarchical: Optional[bool] = None,
                         ) -> optax.GradientTransformation:
    """Wrap an optax optimizer so updates are computed from world-averaged
    gradients. ``backward_passes_per_step`` accumulates N passes locally
    before one allreduce + one inner update, exactly the delay-counter
    semantics of ``torch/__init__.py:71-73,114-130``.
    ``compression=None`` follows ``HOROVOD_COMPRESSION`` (resolved per
    reduction, so a step traced after ``hvd.init()`` sees the pinned
    config); pass ``hvd.Compression.int8`` (or fp16/bf16/fp8) to pin a
    codec explicitly."""
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    n_acc = backward_passes_per_step

    def init_fn(params):
        accum = None
        if n_acc > 1:
            accum = jax.tree_util.tree_map(jnp.zeros_like, params)
        return DistributedOptState(
            inner=optimizer.init(params),
            accum=accum,
            counter=jnp.zeros((), jnp.int32),
        )

    def _reduce(grads):
        # hvd.exchange / hvd.optimizer: the phase names a compiled step's
        # device time is read by (docs/tracing.md "Scopes in a compiled
        # step"); on the eager route a scope does nothing
        with jax.named_scope("hvd.exchange"):
            return allreduce_gradients(
                grads, axis_name=axis_name, average=average,
                compression=compression, hierarchical=hierarchical)

    def _inner_update(reduced, inner, params):
        with jax.named_scope("hvd.optimizer"):
            return optimizer.update(reduced, inner, params)

    def update_fn(grads, state, params=None):
        if n_acc == 1:
            reduced = _reduce(grads)
            updates, inner = _inner_update(reduced, state.inner, params)
            return updates, DistributedOptState(inner, None, state.counter)

        accum = jax.tree_util.tree_map(jnp.add, state.accum, grads)
        counter = state.counter + 1
        if axis_name is None:
            # Eager path: concrete values, python control flow.
            if int(counter) >= n_acc:
                reduced = _reduce(accum)
                updates, inner = _inner_update(reduced, state.inner, params)
                zeros = jax.tree_util.tree_map(jnp.zeros_like, accum)
                return updates, DistributedOptState(
                    inner, zeros, jnp.zeros((), jnp.int32))
            updates = jax.tree_util.tree_map(jnp.zeros_like, grads)
            return updates, DistributedOptState(state.inner, accum, counter)

        # SPMD path: compiled control flow.
        def sync_branch(operand):
            accum_, inner_, params_ = operand
            reduced = _reduce(accum_)
            updates, new_inner = _inner_update(reduced, inner_, params_)
            zeros = jax.tree_util.tree_map(jnp.zeros_like, accum_)
            return updates, new_inner, zeros, jnp.zeros((), jnp.int32)

        def accum_branch(operand):
            accum_, inner_, _params_ = operand
            updates = jax.tree_util.tree_map(jnp.zeros_like, accum_)
            return updates, inner_, accum_, counter

        updates, inner, accum, counter = lax.cond(
            counter >= n_acc, sync_branch, accum_branch,
            (accum, state.inner, params))
        return updates, DistributedOptState(inner, accum, counter)

    # Tag for is_distributed(): GradientTransformation is a plain NamedTuple
    # (no instance attributes), so the marker rides on the update function.
    update_fn._horovod_distributed = True
    # Fused reduce+apply threading (docs/tensor-fusion.md §fused apply):
    # when the inner optimizer is one of the fusable rules
    # (hvd.fused_sgd/fused_momentum/fused_adam), carry the rule and the
    # wrap's routing knobs so apply_step can hand the whole
    # reduce→unscale→update chain to the engine as ONE program under
    # HOROVOD_FUSED_APPLY=1.
    from .ops.fused_apply import rule_of as _rule_of

    update_fn._horovod_apply_rule = _rule_of(optimizer)
    update_fn._horovod_apply_meta = {
        "axis_name": axis_name, "average": average,
        "compression": compression, "n_acc": n_acc,
        "hierarchical": hierarchical,
    }
    return optax.GradientTransformation(init_fn, update_fn)


def is_distributed(tx: optax.GradientTransformation) -> bool:
    """True if ``tx`` was produced by :func:`DistributedOptimizer` (used by
    the front-ends to refuse double wrapping)."""
    return bool(getattr(tx.update, "_horovod_distributed", False))


def exchange_route(tx: optax.GradientTransformation) -> tuple:
    """``(axis_name, carries_bytes)`` of a compiled step that calls ``tx``,
    a :func:`DistributedOptimizer` over a mesh axis. ``carries_bytes``:
    the exchange itself has to move the gradients, because its codec is
    not ``Compression.none`` or its (dcn, ici) axis takes the factored
    route (what ``parallel.data_parallel_step`` does about it is said
    there). Both knobs resolve as they do when the step is traced, and a
    resolution made before ``hvd.init()`` is kept for
    :func:`check_build_time_resolutions`."""
    if not is_distributed(tx):
        raise ValueError(
            "a compiled data-parallel step needs a DistributedOptimizer-"
            "wrapped transform: a plain optax transform exchanges nothing")
    meta = tx.update._horovod_apply_meta
    axis_name = meta["axis_name"]
    if axis_name is None:
        raise ValueError(
            "a compiled data-parallel step needs DistributedOptimizer(..., "
            "axis_name=<the mesh's data axis>): without one the optimizer "
            "reduces through the eager engine, which a traced step cannot")
    codec = _resolve_compression(meta["compression"], record=True)
    return axis_name, (codec is not Compression.none or _use_hierarchical(
        axis_name, meta["hierarchical"]))


def _fused_apply_armed() -> bool:
    """The ``HOROVOD_FUSED_APPLY`` opt-in, resolved like the other
    build-time knobs: pinned config once initialized, env before."""
    if basics.is_initialized():
        return basics.config().fused_apply
    from .core.config import Config

    return Config.from_env().fused_apply


def _zero1_armed() -> bool:
    """The ``HOROVOD_ZERO`` opt-in (docs/sharding.md), resolved exactly
    like :func:`_fused_apply_armed`; capability (XLA plane, world > 1)
    is the engine's call via ``ops.zero1_active``."""
    from .sharding.zero1 import armed

    return armed()


def apply_step(tx: optax.GradientTransformation, grads: Any, state: Any,
               params: Any):
    """One distributed optimizer step that LANDS applied parameters:
    ``(new_params, new_state) = apply_step(tx, grads, state, params)``.

    ``tx`` must be a :func:`DistributedOptimizer`. Two routes, bit-exact
    to each other by the shared :mod:`ops.fused_apply` rule math
    (certified by ``dryrun_fused_apply``):

    * **two-dispatch** (default): the classic pair — allreduce the
      gradients through ``tx.update``, then ``optax.apply_updates`` —
      one reduce dispatch plus per-leaf apply dispatches.
    * **apply-fused** (``HOROVOD_FUSED_APPLY=1``, eager path, inner
      optimizer from :func:`~horovod_tpu.fused_sgd` /
      :func:`~horovod_tpu.fused_momentum` / :func:`~horovod_tpu.fused_adam`):
      each leaf rides an apply-capable allreduce and the engine's flush
      returns the applied parameter and fresh optimizer slots from one
      fused reduce+apply program per batch (docs/tensor-fusion.md
      §fused apply) — the reduce→apply device round trip is gone, and
      the PR 9 sub-buffer overlap window covers the update math too.

    The SPMD path (``axis_name=``) always takes the two-dispatch form
    here — inside jit XLA already fuses the chain; see
    :func:`ops.spmd.reduce_apply` for the explicit in-program fusion."""
    if not is_distributed(tx):
        raise ValueError(
            "apply_step needs a DistributedOptimizer-wrapped transform")
    meta = getattr(tx.update, "_horovod_apply_meta", None) or {}
    rule = getattr(tx.update, "_horovod_apply_rule", None)
    comp = _resolve_compression(meta.get("compression"))
    # cast codecs (fp16/bf16) change the wire dtype pre-submit — the
    # f32 apply bucket cannot carry them, so they keep the two-dispatch
    # path; quantized codecs decode INSIDE the fused program (EQuARX)
    quantized_ok = comp is Compression.none or \
        getattr(comp, "quantized", False)
    fusable = rule is not None and meta.get("axis_name") is None and \
        meta.get("n_acc", 1) == 1 and quantized_ok
    if fusable and _fused_apply_armed():
        from .ops import apply_synchronize, fused_apply_async, \
            zero1_active
        from .ops.fused_apply import FusedApplyState

        inner = state.inner
        count_next = int(inner.count) + 1
        leaves, treedef = jax.tree_util.tree_flatten(grads)
        p_leaves = jax.tree_util.tree_flatten(params)[0]
        # ZeRO-1 (docs/sharding.md): when the engine armed the sharded
        # plane, optimizer slots live as this rank's ShardLeaf shards —
        # localized lazily on the first armed step (init_fn still builds
        # full zeros; elastic restore re-cuts whatever world committed),
        # and the engine runs reduce-scatter → shard apply → all-gather
        # instead of the replicated reduce+apply. Parameters land fully
        # replicated and bit-exact either way.
        z1 = _zero1_armed() and zero1_active()
        slot_trees = inner.slots
        if z1:
            from .sharding import zero1 as _z1

            if slot_trees and not _z1.has_shards(slot_trees):
                slot_trees = tuple(
                    _z1.localize_tree(s, basics.size(), basics.rank())
                    for s in slot_trees)
            _z1.note_slot_residency(slot_trees)
            shard_cols = [jax.tree_util.tree_flatten(
                s, is_leaf=_z1.is_shard)[0] for s in slot_trees]
            slot_leaves = [[sl.data for sl in col]
                           for col in shard_cols]
        else:
            slot_leaves = [jax.tree_util.tree_flatten(s)[0]
                           for s in slot_trees]
        handles = [
            fused_apply_async(
                g, p_leaves[i], tuple(s[i] for s in slot_leaves), rule,
                count_next, name=f"DistributedOptimizer.apply.{i}",
                average=meta.get("average", True), compression=comp,
                zero1=z1)
            for i, g in enumerate(leaves)]
        outs = [apply_synchronize(h) for h in handles]
        unflatten = jax.tree_util.tree_unflatten
        new_params = unflatten(treedef, [o[0] for o in outs])
        if z1:
            import numpy as _np

            new_slots = tuple(
                unflatten(treedef, [
                    _z1.ShardLeaf(_np.asarray(o[1][k]),
                                  shard_cols[k][i].spec)
                    for i, o in enumerate(outs)])
                for k in range(rule.nslots))
        else:
            new_slots = tuple(
                unflatten(treedef, [o[1][k] for o in outs])
                for k in range(rule.nslots))
        new_inner = FusedApplyState(count=inner.count + 1,
                                    slots=new_slots)
        return new_params, DistributedOptState(
            inner=new_inner, accum=state.accum, counter=state.counter)
    if rule is not None:
        # replicated paths below cannot consume ZeRO-1 shard slots
        # (their shapes are 1/N of each leaf) — reaching them with a
        # sharded state means the knobs or codec changed mid-run
        from .sharding import zero1 as _z1guard

        if _z1guard.has_shards(getattr(state.inner, "slots", ())):
            raise RuntimeError(
                "ZeRO-1 sharded optimizer state cannot take the "
                "replicated two-dispatch path; keep HOROVOD_ZERO=1 "
                "runs on a fusable configuration (dense or quantized "
                "codec, HOROVOD_FUSED_APPLY=1)")
    if fusable:
        # the two-dispatch REFERENCE path: one reduce dispatch (summed
        # wire, the fused plane's exact input), then one jitted apply
        # program per leaf from the SAME bucket_apply_fn family the
        # engine compiles — average divide in-program — so fused vs
        # two-dispatch is bit-exact by construction (the
        # dryrun_fused_apply certification). The optax-compatible
        # tx.update surface below remains for generic inner optimizers;
        # its eager apply_updates add lands within 1 ulp of these
        # in-program chains (XLA fuses mul+add differently there).
        from .ops.engine import _APPLY_DISPATCHES
        from .ops.fused_apply import FusedApplyState, bucket_apply_fn

        reduced = allreduce_gradients(
            grads, axis_name=None, average=False, compression=comp)
        inner = state.inner
        count_next = int(inner.count) + 1
        denom = basics.size() if meta.get("average", True) else 1
        fn = bucket_apply_fn(rule, False, denom)
        leaves, treedef = jax.tree_util.tree_flatten(reduced)
        p_leaves = jax.tree_util.tree_flatten(params)[0]
        slot_leaves = [jax.tree_util.tree_flatten(s)[0]
                       for s in inner.slots]
        new_p, new_slot_cols = [], [[] for _ in range(rule.nslots)]
        import numpy as _np

        for i, g in enumerate(leaves):
            out = fn(g, p_leaves[i], _np.int32(count_next),
                     *(s[i] for s in slot_leaves))
            # one standalone apply dispatch per leaf — the cost the
            # fused plane folds into the reduce (the dispatches-per-step
            # story, docs/tensor-fusion.md §fused apply)
            _APPLY_DISPATCHES.inc()
            new_p.append(out[0])
            for k in range(rule.nslots):
                new_slot_cols[k].append(out[3 + k])
        unflatten = jax.tree_util.tree_unflatten
        new_params = unflatten(treedef, new_p)
        new_slots = tuple(unflatten(treedef, c) for c in new_slot_cols)
        new_inner = FusedApplyState(count=inner.count + 1,
                                    slots=new_slots)
        return new_params, DistributedOptState(
            inner=new_inner, accum=state.accum, counter=state.counter)
    updates, new_state = tx.update(grads, state, params)
    return optax.apply_updates(params, updates), new_state
