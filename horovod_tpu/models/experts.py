"""The routed expert layer of the sparse decoders (``ExpertLayer``;
docs/laguna.md), as one chip of an expert-parallel deployment holds it.

It routes every token over all ``num_experts``, keeps ``experts_per_token``
of them, and computes the part of the result that the experts it holds
give, plus the shared expert. What the absent experts would add is left
out, and nothing stands in for their chips or their traffic. No token is
dropped under any imbalance: the rows routed to held experts are sorted by
expert and multiplied as grouped matrix products (``ops.grouped_matmul``),
a small slice at a time in one loop whose trip count is the routing's: a
pass costs its rows, up to every row.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from .parts import INIT, GatedMLP

SCORINGS = {"sigmoid": nn.sigmoid,
            "softmax": functools.partial(jax.nn.softmax, axis=-1)}
GATES = {"silu": nn.silu, "relu": nn.relu}
# what the layer's backward pass takes of its routing — the experts selected
# and their scores, the slots and where each expert's tiles end (``route``,
# ``slot_layout``): a recomputed layer whose policy saves them
# (``parts.keep_policy``) sorts nothing again
KEPT_NAMES = ("moe.ids", "moe.top", "moe.slots", "moe.tile_ends")


@jax.custom_jvp
def _read(scores, top, ids):
    """``jnp.take_along_axis(scores, ids, axis=-1)`` where that is known to
    be ``top``: nothing is read, and the derivative is taken at ``ids``."""
    return top


@_read.defjvp
def _read_jvp(primals, tangents):
    _, top, ids = primals
    # a sum over all the experts in which one term is not zero: its
    # transpose is element-wise, where a gather's is a scatter into the
    # scores' gradient that the TPU applies an update at a time
    chosen = ids[..., None] == jnp.arange(tangents[0].shape[-1])
    return top, jnp.sum(
        jnp.where(chosen, tangents[0][..., None, :], 0), axis=-1)


def route(scores, experts_per_token: int, scaling: float):
    """``(ids, weights)`` [N, k]: the ``experts_per_token`` largest of the
    ``scores`` [N, E] (a sigmoid's, or a softmax's over all ``E``:
    ``SCORINGS``), their weights normalised to sum 1 over the selected and
    scaled. The selection's two results are named (``KEPT_NAMES``) and its
    derivative goes through the named ``ids``, so that what the backward
    pass needs of it is what a policy can save, not a second ``top_k``'s
    own result."""
    top, ids = map(checkpoint_name, jax.lax.top_k(
        jax.lax.stop_gradient(scores), experts_per_token), KEPT_NAMES[1::-1])
    top = _read(scores, top, ids)
    return ids, scaling * top / jnp.sum(top, axis=-1, keepdims=True)


def _slice(p, x, weights, w1, w3, w2, slots, tile_ends, size, tile, gate):
    """Slice ``p`` of the slots through the experts: ``(rows, token, weight,
    at, a, h, gated, y)``. ``slots`` holds, expert after expert, each one's
    rows padded to whole tiles, the index of an assignment (``rows``, of
    ``token``) or ``N * k``, past the arrays: a read there is clipped to the
    last row (finite; no gradient takes it), an update dropped. Expert
    ``e``'s tiles end at ``tile_ends[e]``, whence ``at``: each tile's group,
    the active tiles, ``tile``. ``h = gate(a w1) * (a w3)``, ``gate`` one of
    ``GATES``; ``y = h w2``."""
    from ..ops.grouped_matmul import grouped_matmul

    rows = jax.lax.dynamic_slice_in_dim(slots, p * size, size)
    tiles = p * (size // tile) + jnp.arange(size // tile)
    group = jnp.minimum(jnp.sum(tiles[:, None] >= tile_ends, axis=1),
                        w1.shape[0] - 1)
    at = (group, jnp.clip(tile_ends[-1] - tiles[0], 0, tiles.size), tile)
    token = rows // weights.shape[1]
    a = x.at[token].get(mode="clip")
    h, gated = jax.vjp(lambda h1, h3: GATES[gate](h1) * h3,
                       grouped_matmul(a, w1, *at), grouped_matmul(a, w3, *at))
    weight = weights.reshape(-1).at[rows].get(mode="clip")[:, None]
    return rows, token, weight, at, a, h, gated, grouped_matmul(h, w2, *at)


def _by_token(x):
    """The shape in which a float32 sum over the rows of ``x`` [N, d] is
    carried: a row cut into pieces of 128 where ``d`` allows it, which is
    what ``ops.grouped_matmul.moe_rows_add`` adds to; else ``x``'s own."""
    n, d = x.shape
    return (n, d // 128, 128) if d % 128 == 0 else (n, d)


def _add_by_token(total, rows, token, scale, at):
    """``total`` plus a slice's ``rows`` (times ``scale`` [size, 1] where
    given), each added in float32 to the row ``token`` names; a slot past
    an expert's rows (``token == N``) adds nothing."""
    from ..ops.grouped_matmul import moe_rows_add

    if total.ndim == 3:
        return moe_rows_add(total, rows, token, scale, *at[1:])
    rows = rows.astype(jnp.float32)
    return total.at[token].add(rows if scale is None else rows * scale,
                               mode="drop")


def _loop(tile_ends, size, tile, one, like, shapes):
    """``one(p, carry)`` over the slices in use, from f32 zeros of
    ``shapes``, typed as ``like`` varies."""
    from ..ops.spmd import vary_like

    return jax.lax.fori_loop(
        0, -(-tile_ends[-1] * tile // size), one,
        vary_like(like, *(jnp.zeros(shape, jnp.float32) for shape in shapes)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _expert_loop(x, weights, w1, w3, w2, slots, tile_ends, size, tile, gate):
    """The held experts' weighted outputs added up by token, float32 [N, d],
    a slice at a time into the carried sum (``_by_token``); backward the
    same loop, a slice recomputed and transposed at a time into gradients
    added to in place."""
    w1, w3, w2 = (w.astype(x.dtype) for w in (w1, w3, w2))

    def one(p, carry):
        _, token, weight, at, *_, y = _slice(p, x, weights, w1, w3, w2, slots,
                                             tile_ends, size, tile, gate)
        return (_add_by_token(carry[0], y, token, weight, at),)

    return _loop(tile_ends, size, tile, one, x,
                 (_by_token(x),))[0].reshape(x.shape)


def _loop_bwd(size, tile, gate, res, g):
    from ..ops.grouped_matmul import grouped_matmul_transposed

    x, weights, *matrices, slots, tile_ends = res
    w1, w3, w2 = (w.astype(x.dtype) for w in matrices)

    def one(p, grads):
        dx, dweights, dw1, dw3, dw2 = grads
        rows, token, weight, at, a, h, gated, y = _slice(
            p, x, weights, w1, w3, w2, slots, tile_ends, size, tile, gate)
        # zero for an empty slot, whose row and weight are some token's
        gy = g.at[token].get(mode="fill", fill_value=0)
        dweights = dweights.at[rows].add(
            jnp.sum(gy * y.astype(jnp.float32), axis=-1), mode="drop")
        dy = (gy * weight).astype(y.dtype)
        dh, dw2 = grouped_matmul_transposed(h, dy, w2, dw2, *at)
        dh1, dh3 = gated(dh)
        da1, dw1 = grouped_matmul_transposed(a, dh1, w1, dw1, *at)
        da3, dw3 = grouped_matmul_transposed(a, dh3, w3, dw3, *at)
        da = da1.astype(jnp.float32) + da3
        return (_add_by_token(dx, da, token, None, at), dweights, dw1, dw3,
                dw2)

    grads = _loop(tile_ends, size, tile, one, x, (
        _by_token(x), (weights.size,), *(w.shape for w in matrices)))
    return (*(d.reshape(a.shape).astype(a.dtype) for d, a in zip(grads, res)),
            None, None)


_expert_loop.defvjp(lambda *a: (_expert_loop(*a), a[:7]), _loop_bwd)


def _gate_zeros(x, weights, w1, w3, w2, slots, tile_ends, size, tile, gate):
    """How many elements of the gated hidden rows ``h`` are exactly zero,
    over the slots that hold an assignment: the loop's own slices, counted
    and not multiplied on (a gauge's number, traced only where asked for)."""
    w1, w3, w2 = (w.astype(x.dtype) for w in (w1, w3, w2))

    def one(p, carry):
        rows, *_, h, _, _ = _slice(p, x, weights, w1, w3, w2, slots,
                                   tile_ends, size, tile, gate)
        return (carry[0] + jnp.sum((h == 0) & (rows < weights.size)[:, None],
                                   dtype=jnp.float32),)

    return _loop(tile_ends, size, tile, one, x, ((),))[0]


def slice_slots(capacity: int, held: int, num_experts: int):
    """``(slots, tile)``: a slice, the tiles that hold an eighth of the rows
    an even router sends here, and a tile's rows (8 below a kernel tile)."""
    from ..ops.grouped_matmul import ROW_TILE

    eighth = capacity * held // (8 * num_experts)
    tile = ROW_TILE if eighth >= ROW_TILE else 8
    return max(eighth // tile, 1) * tile, tile


def slot_layout(key, held: int, size: int, tile: int):
    """``(slots, tile_ends, rows)`` of assignments ``key`` [capacity], each
    the held expert it goes to or ``held`` for none: ``slots``, the
    assignments sorted by expert, each expert's padded with ``capacity`` to
    whole tiles, on to a whole number of slices of ``size``; ``tile_ends``
    [held], the tile each expert's end at; ``rows``, how many are held.

    One stable sort lays the slots out: an expert's count is a comparison
    summed, so the slots its last tile has past its rows are known before
    the sort and go into it behind the assignments, under the expert's key
    and holding ``capacity``, as an assignment to no held expert does."""
    capacity = key.size
    counts = jnp.sum(key[:, None] == jnp.arange(held), axis=0,
                     dtype=jnp.int32)
    tiles_of = -(-counts // tile)
    spare = jnp.arange(-(-(capacity + held * tile) // size) * size - capacity)
    spare_key = jnp.sum(
        spare[:, None] >= jnp.cumsum(tiles_of * tile - counts), axis=1,
        dtype=jnp.int32)
    _, slots = jax.lax.sort(
        (jnp.concatenate([key, spare_key]), jnp.concatenate([
            jnp.where(key < held, jnp.arange(capacity, dtype=jnp.int32),
                      capacity),
            jnp.full(spare.shape, capacity, jnp.int32)])), num_keys=1)
    return slots, jnp.cumsum(tiles_of), jnp.sum(counts)


def held_expert_sum(x, ids, weights, w1, w3, w2, first: int,
                    num_experts: int, gate: str = "silu",
                    count_zeros: bool = False):
    """``sum over the held e among a token's experts of weight_e *
    expert_e(x)`` for tokens ``x`` [N, d], routed to ``ids`` [N, k] with
    ``weights`` [N, k]; the experts held are ``first .. first + len(w1)``,
    each ``(gate(x w1) * (x w3)) w2``, ``gate`` one of ``GATES``. Returns
    that sum, float32 [N, d], and the loop's ``slices`` run, ``slots`` in
    use, slots it ``ran`` and, of those, the slots ``summed`` by token in
    ``moe_rows_add``; with ``count_zeros`` also ``gate_zero_share``, the
    share of the elements of ``gate(x w1) * (x w3)`` that are exactly zero
    over the rows routed here (a pass of its own over the slices).

    The assignments to held experts are sorted by expert into slots, each
    expert's rows padded to whole tiles of the grouped-product kernel
    (``ops.grouped_matmul``). One loop from slot 0 takes a slice
    (``slice_slots``) at a time as far as the slots in use reach: the work
    follows the rows routed here and no row is ever dropped."""
    from ..ops.spmd import vary_like

    held = w1.shape[0]
    local = ids.reshape(-1) - first
    size, tile = slice_slots(ids.size, held, num_experts)
    slots, tile_ends, rows = slot_layout(
        jnp.where((local >= 0) & (local < held), local, held), held, size,
        tile)
    slots, tile_ends = map(checkpoint_name, (slots, tile_ends),
                           KEPT_NAMES[2:])
    # the trip count is a device's own: typed as varying like the tokens, the
    # replicated weights get their gradient summed over the axis outside it
    operands = vary_like(x, x, weights, w1, w3, w2, slots, tile_ends)
    slices = -(-tile_ends[-1] * tile // size)
    ran = slices * size
    loop = {"slices": slices, "slots": tile_ends[-1] * tile, "ran": ran,
            "summed": ran * (len(_by_token(x)) == 3)}
    if count_zeros:
        loop["gate_zero_share"] = _gate_zeros(
            *operands, size, tile, gate) / jnp.maximum(
                rows * w1.shape[-1], 1)
    return _expert_loop(*operands, size, tile, gate), loop


class ExpertLayer(nn.Module):
    """Routed experts, of which this chip holds ``experts_held = (first,
    count)``, plus one shared expert (none where ``shared_width`` is 0: no
    parameter, no product). Routes over all ``num_experts`` in float32 by
    ``scoring`` (one of ``SCORINGS``), keeps ``experts_per_token``, adds
    ``shared(x)`` and the held experts' weighted outputs; what absent
    experts would add is left out. An expert is ``(gate(x w1) * (x w3))
    w2``, ``gate`` one of ``GATES`` (the shared expert is ``parts.GatedMLP``,
    SiLU-gated whatever ``gate``). The router reads the tensor the experts
    multiply, or ``routed_by`` where the call gives one (a block's input
    from before its attention, say): its operations are under
    ``hvd.moe.route`` either way.

    Sows into the collection ``moe_stats`` (when the caller makes it
    mutable) what ``obs.moe.publish`` turns into gauges: ``assignments``
    [num_experts], how many of the ``N * k`` assignments each expert got,
    ``absent``, how many went to experts not held, and the loop's numbers
    (``gate_zero_share`` among them outside ``init``: a training step, which
    does not carry the collection, holds no operation for it)."""

    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    width: int
    shared_width: int
    scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    scoring: str = "sigmoid"
    gate: str = "silu"

    @nn.compact
    def __call__(self, x, routed_by=None):
        first, held = self.experts_held
        if not 0 <= first <= first + held <= self.num_experts or held < 1:
            raise ValueError(f"experts_held {self.experts_held} is no part "
                             f"of {self.num_experts} experts")
        if self.scoring not in SCORINGS:
            raise ValueError(f"scoring must be one of {sorted(SCORINGS)}, "
                             f"got {self.scoring!r}")
        if self.gate not in GATES:
            raise ValueError(f"gate must be one of {sorted(GATES)}, got "
                             f"{self.gate!r}")
        d = x.shape[-1]
        tokens = x.reshape(-1, d)
        read = tokens if routed_by is None else routed_by.reshape(-1, d)
        with jax.named_scope("hvd.moe"):
            with jax.named_scope("hvd.moe.route"):
                # float32 in earnest: without ``highest`` the TPU multiplies
                # float32 operands in one bfloat16 pass
                scores = SCORINGS[self.scoring](nn.Dense(
                    self.num_experts, use_bias=False, dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST, kernel_init=INIT,
                    name="router")(read.astype(jnp.float32)))
                ids, weights = route(scores, self.experts_per_token,
                                     self.scaling)
            counts = jnp.zeros((self.num_experts,), jnp.int32).at[
                ids.reshape(-1)].add(1)
            self.sow("moe_stats", "assignments", counts)
            self.sow("moe_stats", "absent",
                     ids.size - jnp.sum(counts[first:first + held]))
            with jax.named_scope("hvd.moe.experts"):
                w1, w3 = (self.param(name, INIT, (held, d, self.width))
                          for name in ("experts_w1", "experts_w3"))
                w2 = self.param("experts_w2", INIT, (held, self.width, d))
                routed, loop = held_expert_sum(
                    tokens, ids, weights, w1, w3, w2, first, self.num_experts,
                    self.gate, self.is_mutable_collection("moe_stats")
                    and not self.is_initializing())
                for name, value in loop.items():
                    self.sow("moe_stats", name, value)
                shared = GatedMLP(self.shared_width, self.dtype,
                                  name="shared")(tokens) \
                    if self.shared_width else None
            with jax.named_scope("hvd.moe.combine"):
                out = routed.astype(self.dtype)
                if shared is not None:
                    out = shared + out
        return out.reshape(x.shape)


def held_of(config: dict) -> Tuple[int, int]:
    """``experts_held`` ``(first, count)`` of a ``config.json``'s keys plus
    ``experts_held`` ``{"first": .., "count": ..}``: all ``num_experts``
    when absent."""
    held = config.get("experts_held",
                      {"first": 0, "count": config["num_experts"]})
    return held["first"], held["count"]
