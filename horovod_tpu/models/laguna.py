"""Laguna-style decoder: RMSNorm, rotary positions, grouped key/value
heads, window and full attention mixed layer by layer with a head count of
their own, a head-wise output gate, gated SiLU MLPs and a routed expert
layer that is told which experts it holds (docs/laguna.md).

The second decoder beside ``transformer.TransformerLM``: the same call
(``model(tokens) -> float32 logits``, or the loss itself given
``loss_tokens``), so ``make_lm_train_step`` and ``lm_loss`` take it
unchanged, and the same kernel
(``ops.pallas_attention.flash_attention``, here with grouped heads and a
window). ``LagunaLM.from_config`` reads the keys of the published
``config.json`` (poolside/Laguna-XS.2) plus ``experts_held``.

The expert layer (``ExpertLayer``) stands for one chip of an expert-parallel
deployment: it routes every token over all ``num_experts``, keeps
``experts_per_token`` of them, and computes the part of the result that
the experts it holds give, plus the shared expert. What the absent experts
would add is left out, and nothing stands in for their chips or their
traffic. No token is dropped under any imbalance: the rows routed to held
experts are sorted by expert and multiplied as grouped matrix products
(``ops.grouped_matmul``), a small slice at a time in one loop whose trip
count is the routing's: a pass costs its rows, up to every row.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .transformer import LMHead

ATTENTION_BACKENDS = ("flash", "dense")
_INIT = nn.initializers.normal(0.02)


# -- rotary positions ---------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Rotary:
    """Rotary positions of one layer type: ``dim`` leading dims of each head
    are rotated; ``factor`` (YaRN's, ``None`` for plain rotary) blends
    interpolated and extrapolated frequencies as the ``transformers``
    library does."""

    theta: float
    dim: int
    factor: Optional[float] = None
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self):
        """``[dim // 2]`` float32 inverse frequencies."""
        i = jnp.arange(0, self.dim, 2, dtype=jnp.float32)
        extrapolated = 1.0 / self.theta ** (i / self.dim)
        if self.factor is None:
            return extrapolated

        def correction_dim(rotations):
            return self.dim * math.log(self.original_max_position / (
                rotations * 2 * math.pi)) / (2 * math.log(self.theta))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), self.dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(self.dim // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        return extrapolated / self.factor * ramp + extrapolated * (1.0 - ramp)

    def __call__(self, x, positions):
        """Rotate ``x`` [B, T, H, D] at ``positions`` [B, T]: the first
        ``dim`` dims of each head in halves (``rotate_half``), float32."""
        angles = positions[..., None].astype(jnp.float32) * self.inv_freq()
        cos, sin = (jnp.concatenate([f(angles)] * 2, axis=-1)[:, :, None, :]
                    * self.attention_factor for f in (jnp.cos, jnp.sin))
        turned, kept = x[..., :self.dim].astype(jnp.float32), x[..., self.dim:]
        first, second = jnp.split(turned, 2, axis=-1)
        turned = turned * cos + jnp.concatenate([-second, first], -1) * sin
        return jnp.concatenate([turned.astype(x.dtype), kept], axis=-1)


def dense_attention(q, k, v, window: Optional[int] = None):
    """Causal attention written out, grouped heads and a window as
    ``flash_attention`` takes them; float32 softmax."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / math.sqrt(q.shape[-1])
    distance = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])
    keep = distance >= 0
    if window is not None:
        keep = keep & (distance < window)
    weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)


# -- the block's parts --------------------------------------------------------


class GatedMLP(nn.Module):
    """``(silu(x W1) * (x W3)) W2``, no biases."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, kernel_init=_INIT, name=name)
        h = nn.silu(dense(self.width, "w1")(x)) * dense(self.width, "w3")(x)
        return dense(x.shape[-1], "w2")(h)


class GroupedAttention(nn.Module):
    """Causal self-attention with ``num_heads`` query heads on
    ``num_kv_heads`` key/value heads, rotary positions, an optional window
    and a head-wise sigmoid gate on its output."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary: Rotary
    window: Optional[int] = None
    dtype: Any = jnp.bfloat16
    attention: str = "flash"

    @nn.compact
    def __call__(self, x, positions):
        if self.attention not in ATTENTION_BACKENDS:
            raise ValueError(f"attention must be one of {ATTENTION_BACKENDS},"
                             f" got {self.attention!r}")

        def heads(n, name):
            with jax.named_scope(scopes.MIXER_PROJ):
                return nn.DenseGeneral((n, self.head_dim), use_bias=False,
                                       dtype=self.dtype, kernel_init=_INIT,
                                       name=name)(x)

        q = self.rotary(heads(self.num_heads, "query"), positions)
        k = self.rotary(heads(self.num_kv_heads, "key"), positions)
        v = heads(self.num_kv_heads, "value")
        if self.attention == "flash":
            from ..ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v, causal=True, window=self.window)
        else:
            out = dense_attention(q, k, v, self.window)
        with jax.named_scope(scopes.MIXER_PROJ):
            gate = nn.Dense(self.num_heads, use_bias=False, dtype=self.dtype,
                            kernel_init=_INIT, name="gate")(x)
        out = out.astype(self.dtype) * nn.sigmoid(gate)[..., None]
        with jax.named_scope(scopes.MIXER_PROJ):
            return nn.DenseGeneral(x.shape[-1], axis=(-2, -1),
                                   use_bias=False, dtype=self.dtype,
                                   kernel_init=_INIT, name="out")(out)


# -- the expert layer ---------------------------------------------------------


SCORINGS = {"sigmoid": nn.sigmoid,
            "softmax": functools.partial(jax.nn.softmax, axis=-1)}


def route(scores, experts_per_token: int, scaling: float):
    """``(ids, weights)`` [N, k]: the ``experts_per_token`` largest of the
    ``scores`` [N, E] (a sigmoid's, or a softmax's over all ``E``:
    ``SCORINGS``), their weights normalised to sum 1 over the selected and
    scaled."""
    top, ids = jax.lax.top_k(scores, experts_per_token)
    return ids, scaling * top / jnp.sum(top, axis=-1, keepdims=True)


def _slice(p, x, weights, w1, w3, w2, slots, tile_ends, size, tile):
    """Slice ``p`` of the slots through the experts: ``(rows, token, weight,
    at, a, h, gated, y)``. ``slots`` holds, expert after expert, each one's
    rows padded to whole tiles, the index of an assignment (``rows``, of
    ``token``) or ``N * k``, past the arrays: a read there is clipped to the
    last row (finite; no gradient takes it), an update dropped. Expert
    ``e``'s tiles end at ``tile_ends[e]``, whence ``at``: each tile's group,
    the active tiles, ``tile``. ``h = silu(a w1) * (a w3)``, ``y = h w2``."""
    from ..ops.grouped_matmul import grouped_matmul

    rows = jax.lax.dynamic_slice_in_dim(slots, p * size, size)
    tiles = p * (size // tile) + jnp.arange(size // tile)
    group = jnp.minimum(jnp.sum(tiles[:, None] >= tile_ends, axis=1),
                        w1.shape[0] - 1)
    at = (group, jnp.clip(tile_ends[-1] - tiles[0], 0, tiles.size), tile)
    token = rows // weights.shape[1]
    a = x.at[token].get(mode="clip")
    h, gated = jax.vjp(lambda h1, h3: nn.silu(h1) * h3,
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _expert_loop(x, weights, w1, w3, w2, slots, tile_ends, size, tile):
    """The held experts' weighted outputs added up by token, float32 [N, d],
    a slice at a time into the carried sum (``_by_token``); backward the
    same loop, a slice recomputed and transposed at a time into gradients
    added to in place."""
    w1, w3, w2 = (w.astype(x.dtype) for w in (w1, w3, w2))

    def one(p, carry):
        _, token, weight, at, *_, y = _slice(p, x, weights, w1, w3, w2, slots,
                                             tile_ends, size, tile)
        return (_add_by_token(carry[0], y, token, weight, at),)

    return _loop(tile_ends, size, tile, one, x,
                 (_by_token(x),))[0].reshape(x.shape)


def _loop_bwd(size, tile, res, g):
    from ..ops.grouped_matmul import grouped_matmul_transposed

    x, weights, *matrices, slots, tile_ends = res
    w1, w3, w2 = (w.astype(x.dtype) for w in matrices)

    def one(p, grads):
        dx, dweights, dw1, dw3, dw2 = grads
        rows, token, weight, at, a, h, gated, y = _slice(
            p, x, weights, w1, w3, w2, slots, tile_ends, size, tile)
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


def slice_slots(capacity: int, held: int, num_experts: int):
    """``(slots, tile)``: a slice, the tiles that hold an eighth of the rows
    an even router sends here, and a tile's rows (8 below a kernel tile)."""
    from ..ops.grouped_matmul import ROW_TILE

    eighth = capacity * held // (8 * num_experts)
    tile = ROW_TILE if eighth >= ROW_TILE else 8
    return max(eighth // tile, 1) * tile, tile


def held_expert_sum(x, ids, weights, w1, w3, w2, first: int,
                    num_experts: int):
    """``sum over the held e among a token's experts of weight_e *
    expert_e(x)`` for tokens ``x`` [N, d], routed to ``ids`` [N, k] with
    ``weights`` [N, k]; the experts held are ``first .. first + len(w1)``,
    each ``(silu(x w1) * (x w3)) w2``. Returns that sum, float32 [N, d],
    and the loop's ``slices`` run, ``slots`` in use, slots it ``ran`` and,
    of those, the slots ``summed`` by token in ``moe_rows_add``.

    The assignments to held experts are sorted by expert into slots, each
    expert's rows padded to whole tiles of the grouped-product kernel
    (``ops.grouped_matmul``). One loop from slot 0 takes a slice
    (``slice_slots``) at a time as far as the slots in use reach: the work
    follows the rows routed here and no row is ever dropped."""
    from ..ops.spmd import vary_like

    held, capacity = w1.shape[0], ids.size
    local = ids.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    order = jnp.argsort(key, stable=True)  # held rows first, by expert
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    ends = jnp.cumsum(counts)
    size, tile = slice_slots(capacity, held, num_experts)
    tiles_of = -(-counts // tile)
    tile_ends = jnp.cumsum(tiles_of)
    # the slot of the p-th sorted assignment: its expert's first slot plus
    # its rank among the expert's rows
    expert = jnp.minimum(key[order], held - 1)
    slot = (tile_ends - tiles_of)[expert] * tile \
        + jnp.arange(capacity) - (ends - counts)[expert]
    room = -(-(capacity + held * tile) // size) * size
    slots = jnp.full((room,), capacity, jnp.int32).at[
        jnp.where(jnp.arange(capacity) < ends[-1], slot, room)].set(
            order.astype(jnp.int32), mode="drop")
    # the trip count is a device's own: typed as varying like the tokens, the
    # replicated weights get their gradient summed over the axis outside it
    operands = vary_like(x, x, weights, w1, w3, w2, slots, tile_ends)
    slices = -(-tile_ends[-1] * tile // size)
    ran = slices * size
    return _expert_loop(*operands, size, tile), {
        "slices": slices, "slots": tile_ends[-1] * tile, "ran": ran,
        "summed": ran * (len(_by_token(x)) == 3)}


class ExpertLayer(nn.Module):
    """Routed experts, of which this chip holds ``experts_held = (first,
    count)``, plus one shared expert (none where ``shared_width`` is 0: no
    parameter, no product). Routes over all ``num_experts`` in float32 by
    ``scoring`` (one of ``SCORINGS``), keeps ``experts_per_token``, adds
    ``shared(x)`` and the held experts' weighted outputs; what absent
    experts would add is left out.

    Sows into the collection ``moe_stats`` (when the caller makes it
    mutable) what ``obs.moe.publish`` turns into gauges: ``assignments``
    [num_experts], how many of the ``N * k`` assignments each expert got,
    ``absent``, how many went to experts not held, and the loop's numbers."""

    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    width: int
    shared_width: int
    scaling: float = 1.0
    dtype: Any = jnp.bfloat16
    scoring: str = "sigmoid"

    @nn.compact
    def __call__(self, x):
        first, held = self.experts_held
        if not 0 <= first <= first + held <= self.num_experts or held < 1:
            raise ValueError(f"experts_held {self.experts_held} is no part "
                             f"of {self.num_experts} experts")
        if self.scoring not in SCORINGS:
            raise ValueError(f"scoring must be one of {sorted(SCORINGS)}, "
                             f"got {self.scoring!r}")
        d = x.shape[-1]
        tokens = x.reshape(-1, d)
        with jax.named_scope("hvd.moe"):
            with jax.named_scope("hvd.moe.route"):
                # float32 in earnest: without ``highest`` the TPU multiplies
                # float32 operands in one bfloat16 pass
                scores = SCORINGS[self.scoring](nn.Dense(
                    self.num_experts, use_bias=False, dtype=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST, kernel_init=_INIT,
                    name="router")(tokens.astype(jnp.float32)))
                ids, weights = route(scores, self.experts_per_token,
                                     self.scaling)
            counts = jnp.zeros((self.num_experts,), jnp.int32).at[
                ids.reshape(-1)].add(1)
            self.sow("moe_stats", "assignments", counts)
            self.sow("moe_stats", "absent",
                     ids.size - jnp.sum(counts[first:first + held]))
            with jax.named_scope("hvd.moe.experts"):
                w1, w3 = (self.param(name, _INIT, (held, d, self.width))
                          for name in ("experts_w1", "experts_w3"))
                w2 = self.param("experts_w2", _INIT, (held, self.width, d))
                routed, loop = held_expert_sum(
                    tokens, ids, weights, w1, w3, w2, first, self.num_experts)
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


class LagunaBlock(nn.Module):
    """Pre-RMSNorm residual block: grouped attention, then a dense gated
    MLP (``dense_width``) or, where that is ``None``, the expert layer.

    With ``remat`` each half is a ``jax.checkpoint`` of its own: the block's
    input and the attention half's output are stored, the halves' interiors
    recomputed in backward, one at a time — while the MLP's (the expert
    layer's) backward pass runs, nothing of the attention is held but what
    ``_keep_policy`` keeps (a single checkpoint round the block compiles
    to 0.8 GB more), and the attention's output projection, which only the
    second half needed, is not recomputed at all."""

    attn: dict          # GroupedAttention's fields
    dense_width: Optional[int]
    experts: dict       # ExpertLayer's fields
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, x, positions):
        def norm(name, x):
            with jax.named_scope(scopes.NORM):
                return nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                                  name=name)(x)

        # ``nn.remat`` hands a function the module as its first argument
        def mix(block, x, positions):
            h = norm("ln_attn", x)
            with jax.named_scope(scopes.MIXER):
                return x + GroupedAttention(dtype=self.dtype, name="attn",
                                            **self.attn)(h, positions)

        def feed(block, x):
            h = norm("ln_mlp", x)
            if self.dense_width is not None:
                with jax.named_scope(scopes.MLP):
                    return x + GatedMLP(self.dense_width, self.dtype,
                                        name="mlp")(h)
            return x + ExpertLayer(dtype=self.dtype, name="moe",
                                   **self.experts)(h)

        if self.remat:
            mix = nn.remat(mix, policy=_keep_policy(self.attn["window"]))
            feed = nn.remat(feed)
        return feed(self, mix(self, x, positions))


def _keep_policy(window):
    """The checkpoint policy of a recomputed attention half. A full layer
    keeps its flash kernel's output and log-sum-exp, named where the forward
    rule makes them (``ops.pallas_attention.KEPT_NAMES``), and recomputes
    everything else: with them kept the forward Mosaic call is dead code in
    the recomputed half. A sliding layer (``window`` given) keeps nothing:
    its forward kernel skips what the window hides, so running it again is
    cheap for what keeping would hold.

    The price list, at 2 x 8,192 tokens (docs/laguna.md): a full layer's 48
    heads hold 0.20 GB for a 17.6 ms run of ``flash_fwd``, a sliding layer's
    64 heads 0.27 GB for a 7.0 ms run of ``flash_win_fwd``. ``python3 -m
    chipbench.aot --workload laguna_xs2_8k_1chip`` totals 12.82 GB with
    the two full layers keeping, under the 13.234 GB the chip leaves the
    step (none: 12.33; one sliding layer more: 13.08; two more: 13.35;
    all five: 14.15)."""
    if window is not None:
        return None
    from ..ops import pallas_attention

    return jax.checkpoint_policies.save_only_these_names(
        *pallas_attention.KEPT_NAMES)


class LagunaLM(nn.Module):
    """Decoder-only LM, ``model(tokens) -> float32 logits [B, T, vocab]``
    (``model(tokens, loss_tokens=tokens)``: their ``lm_loss``, the logits
    never whole, ``transformer.lm_head_loss``).
    Layer ``i`` is ``layer_types[i]`` (``"full_attention"`` or
    ``"sliding_attention"``) with ``heads_per_layer[i]`` query heads, and
    its MLP ``mlp_layer_types[i]`` (``"dense"`` or ``"sparse"``)."""

    vocab_size: int
    d_model: int
    head_dim: int
    num_kv_heads: int
    layer_types: Tuple[str, ...]
    heads_per_layer: Tuple[int, ...]
    mlp_layer_types: Tuple[str, ...]
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    routed_scaling: float
    window: int
    rotary_full: Rotary
    rotary_sliding: Rotary
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"
    # jax.checkpoint each half of a block: only the halves' inputs and a
    # full layer's flash kernel outputs (``_keep_policy``) are stored, the
    # rest of a block's interior is recomputed in backward
    remat: bool = False

    @classmethod
    def from_config(cls, config: dict, **overrides) -> "LagunaLM":
        """The model of a published ``config.json``'s keys, cut to
        ``num_hidden_layers`` leading layers, with ``experts_held``
        ``{"first": .., "count": ..}`` (all of them when absent)."""
        depth = config["num_hidden_layers"]
        ropes = config["rope_parameters"]

        def rotary(p):
            dim = int(config["head_dim"] * p.get("partial_rotary_factor", 1))
            if p.get("rope_type", "default") == "default":
                return Rotary(theta=p["rope_theta"], dim=dim)
            return Rotary(
                theta=p["rope_theta"], dim=dim, factor=p["factor"],
                original_max_position=p["original_max_position_embeddings"],
                beta_fast=p["beta_fast"], beta_slow=p["beta_slow"],
                attention_factor=p["attention_factor"])

        held = config.get("experts_held",
                          {"first": 0, "count": config["num_experts"]})
        fields = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            head_dim=config["head_dim"],
            num_kv_heads=config["num_key_value_heads"],
            layer_types=tuple(config["layer_types"][:depth]),
            heads_per_layer=tuple(
                config["num_attention_heads_per_layer"][:depth]),
            mlp_layer_types=tuple(config["mlp_layer_types"][:depth]),
            dense_width=config["intermediate_size"],
            expert_width=config["moe_intermediate_size"],
            shared_width=config["shared_expert_intermediate_size"],
            num_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            experts_held=(held["first"], held["count"]),
            routed_scaling=config["moe_routed_scaling_factor"],
            window=config["sliding_window"],
            rotary_full=rotary(ropes["full_attention"]),
            rotary_sliding=rotary(ropes["sliding_attention"]),
            eps=config["rms_norm_eps"])
        fields.update(overrides)
        return cls(**fields)

    @nn.compact
    def __call__(self, tokens, positions=None, loss_tokens=None):
        if not len(self.layer_types) == len(self.heads_per_layer) \
                == len(self.mlp_layer_types):
            raise ValueError("layer_types, heads_per_layer and "
                             "mlp_layer_types must be equally long")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         embedding_init=_INIT, name="tok_embed")(tokens)
        for i, (kind, heads, mlp) in enumerate(zip(
                self.layer_types, self.heads_per_layer,
                self.mlp_layer_types)):
            sliding = kind == "sliding_attention"
            attn = dict(
                num_heads=heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, attention=self.attention,
                rotary=self.rotary_sliding if sliding else self.rotary_full,
                window=self.window if sliding else None)
            experts = dict(
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                experts_held=self.experts_held, width=self.expert_width,
                shared_width=self.shared_width, scaling=self.routed_scaling)
            x = LagunaBlock(
                attn=attn, experts=experts, eps=self.eps, dtype=self.dtype,
                dense_width=self.dense_width if mlp == "dense" else None,
                remat=self.remat, name=f"block_{i}")(x, positions)
        with jax.named_scope(scopes.NORM):
            x = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                           name="ln_final")(x)
        head = LMHead(self.vocab_size, use_bias=False, dtype=jnp.float32,
                      kernel_init=_INIT, name="lm_head")
        if loss_tokens is not None:
            return head.loss(x, loss_tokens)
        with jax.named_scope(scopes.HEAD):
            return head(x).astype(jnp.float32)
