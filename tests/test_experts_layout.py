"""The expert layer's bookkeeping (``models.experts``): the slot layout one
sort gives against the scatter form it replaced, element for element; a
recomputed layer of each of the four sparse decoders against the
unrecomputed one, bit for bit; and what a recomputed half keeps of its
routing, read off a compiled step's text (``obs.moe.record_layout_program``).
"""

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import obs
from horovod_tpu.models import (KimiLinearLM, LagunaLM, SdarMoeLM,
                                SmallThinkerLM, experts, lm_loss, parts,
                                sdar)

# -- the layout ---------------------------------------------------------------


def scattered_layout(key, held, size, tile):
    """``experts.slot_layout`` as it was before one sort laid the slots
    out: a count by scatter-add, and every sorted assignment scattered to
    its expert's first slot plus its rank among the expert's rows."""
    capacity = key.size
    order = jnp.argsort(key, stable=True)
    counts = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    ends = jnp.cumsum(counts)
    tiles_of = -(-counts // tile)
    tile_ends = jnp.cumsum(tiles_of)
    expert = jnp.minimum(key[order], held - 1)
    slot = (tile_ends - tiles_of)[expert] * tile \
        + jnp.arange(capacity) - (ends - counts)[expert]
    room = -(-(capacity + held * tile) // size) * size
    slots = jnp.full((room,), capacity, jnp.int32).at[
        jnp.where(jnp.arange(capacity) < ends[-1], slot, room)].set(
            order.astype(jnp.int32), mode="drop")
    return slots, tile_ends, ends[-1]


# (experts held, experts, assignments) of the four cells' layers, and a toy's
_SHAPES = {"laguna": (32, 256, 16384 * 8), "sdar": (16, 128, 16384 * 8),
           "kimi": (8, 256, 16384 * 8), "smallthinker": (16, 64, 16384 * 6),
           "toy": (4, 16, 64 * 4)}


def _routing(name, held, num_experts, capacity, tile, rng):
    """Keys [capacity] in ``0 .. held`` (``held``: to no held expert)."""
    even = np.minimum(rng.integers(0, num_experts, capacity), held)
    if name == "random":
        return even
    if name == "nothing_held":
        return np.full(capacity, held)
    if name == "all_to_one":
        return np.full(capacity, 1)
    if name == "an_empty_expert":
        return np.where(even == 2, held, even)
    # each expert's rows a whole number of tiles: 1, 2, 0, 1, 2, 0, ...
    tiles = np.arange(1, held + 1) % 3
    keys = np.repeat(np.arange(held), tiles * tile)
    return rng.permutation(np.concatenate(
        [keys, np.full(capacity - keys.size, held)]))


@pytest.mark.parametrize("routing", ["random", "nothing_held", "all_to_one",
                                     "an_empty_expert", "whole_tiles"])
@pytest.mark.parametrize("shape", _SHAPES)
def test_one_sort_lays_the_slots_out_as_the_scatter_did(shape, routing):
    held, num_experts, capacity = _SHAPES[shape]
    size, tile = experts.slice_slots(capacity, held, num_experts)
    key = jnp.asarray(_routing(routing, held, num_experts, capacity, tile,
                               np.random.default_rng(0)), jnp.int32)
    was, now = (jax.jit(layout, static_argnums=(1, 2, 3))(
        key, held, size, tile)
        for layout in (scattered_layout, experts.slot_layout))
    for a, b in zip(was, now):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    slots, tile_ends, rows = map(np.asarray, now)
    assert slots.size % size == 0 and rows == np.sum(slots < capacity)
    if routing == "whole_tiles":
        assert rows == tile_ends[-1] * tile     # no slot is padding


# -- the four decoders, recomputed --------------------------------------------

_ROTARY = {"rope_type": "default", "rope_theta": 10000,
           "partial_rotary_factor": 1}
_CONFIGS = {
    "laguna": (LagunaLM, {
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_key_value_heads": 2, "head_dim": 16,
        "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
        "sliding_window": 16, "moe_routed_scaling_factor": 2.5,
        "rope_parameters": {"full_attention": _ROTARY,
                            "sliding_attention": _ROTARY},
        "layer_types": ["full_attention", "sliding_attention"],
        "mlp_layer_types": ["sparse", "sparse"],
        "num_attention_heads_per_layer": [4, 4],
        "experts_held": {"first": 2, "count": 4}}),
    "sdar": (SdarMoeLM, {
        "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "moe_intermediate_size": 16, "num_experts": 8,
        "num_experts_per_tok": 2, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
        "norm_topk_prob": True, "decoder_sparse_step": 1,
        "mlp_only_layers": [], "experts_held": {"first": 2, "count": 4}}),
    "smallthinker": (SmallThinkerLM, {
        "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "moe_ffn_hidden_size": 16, "moe_num_primary_experts": 8,
        "moe_num_active_primary_experts": 3,
        "sliding_window_layout": [0, 1], "rope_layout": [0, 1],
        "sliding_window_size": 16, "rope_theta": 1.5e6,
        "rms_norm_eps": 1e-6, "experts_held": {"first": 2, "count": 4}}),
    "kimi": (KimiLinearLM, {
        "vocab_size": 96, "hidden_size": 32, "intermediate_size": 64,
        "num_hidden_layers": 2, "num_attention_heads": 2,
        "rms_norm_eps": 1e-5, "linear_attn_config": {
            "kda_layers": [1], "full_attn_layers": [2], "head_dim": 16,
            "num_heads": 2, "short_conv_kernel_size": 4},
        "first_k_dense_replace": 0, "kv_lora_rank": 24,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "moe_intermediate_size": 16, "num_shared_experts": 1,
        "num_experts": 8, "num_experts_per_token": 2,
        "routed_scaling_factor": 2.446,
        "experts_held": {"first": 2, "count": 4}}),
}


@pytest.fixture(scope="module", params=_CONFIGS)
def steps(request):
    """Of a two-layer toy of one decoder, both layers sparse, its kernels
    interpreted: ``(name, {"plain" | "kept" | "default": (the compiled
    loss-and-gradients program, its text)}, params)`` — ``remat=False``,
    ``remat=True``, and ``remat=True`` with the expert layer's names taken
    out of the policy."""
    name = request.param
    cls, config = _CONFIGS[name]
    model = cls.from_config(config, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 95)
    if name == "sdar":
        noisy, weights = sdar.block_diffusion_noise(
            jax.random.PRNGKey(1), tokens, 4, 95)
        inputs, loss = (tokens, noisy), lambda m, p: m.apply(
            {"params": p}, tokens, noisy, weights=weights)
    else:
        inputs, loss = (tokens,), lambda m, p: lm_loss(
            m.apply({"params": p}, tokens), tokens)
    params = jax.jit(model.init)(jax.random.PRNGKey(2), *inputs)["params"]

    def compiled(m):
        program = jax.jit(jax.value_and_grad(
            functools.partial(loss, m))).lower(params).compile()
        return program, program.as_text()

    programs = {"plain": compiled(model),
                "kept": compiled(model.clone(remat=True))}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(
            sys.modules[cls.__module__], "keep_policy",
            lambda *kept: parts.keep_policy(*(
                module for module in kept if module != "models.experts")))
        programs["default"] = compiled(model.clone(remat=True))
    return name, programs, params


def test_a_recomputed_layer_gives_the_same_bits(steps):
    """Loss and every gradient of ``remat=True`` equal, bit for bit, those
    of ``remat=True`` with nothing of the routing kept — what a recomputed
    half keeps is what it would have built again — and ``remat=False``'s.
    (SmallThinker's first block and what lies below it apart: a block's
    input goes to the router and to the attention, and a recomputed block
    adds the two gradients in the other order, with or without the names:
    eleven leaves a rounding apart.)"""
    name, programs, params = steps
    plain, kept, default = (programs[which][0](params)
                            for which in ("plain", "kept", "default"))
    assert np.isfinite(float(plain[0]))
    for (path, a), b, c in zip(jax.tree_util.tree_leaves_with_path(kept),
                               *map(jax.tree_util.tree_leaves,
                                    (default, plain))):
        path = jax.tree_util.keystr(path)
        np.testing.assert_array_equal(a, b, err_msg=path)
        if name == "smallthinker" and (
                "block_0" in path or "tok_embed" in path):
            np.testing.assert_allclose(a, c, rtol=1e-4, atol=1e-8,
                                       err_msg=path)
        else:
            np.testing.assert_array_equal(a, c, err_msg=path)


def _recomputed_sorts(text):
    return [line for line in text.splitlines()
            if "rematted_computation" in line and "hvd.moe" in line
            and obs.moe._SORT.search(line)]


def test_a_recomputed_half_sorts_nothing_again(steps):
    """On the compiled step's text: two layers, each one backward loop; the
    recomputed halves hold no sort under ``hvd.moe`` and the gauge reads 0;
    with the layer's names out of the policy a recomputed half selects and
    sorts again (the last layer's not always: XLA may find its forward
    pass's two still at hand), and the gauge counts them."""
    name, programs, _ = steps
    read = {which: obs.moe.record_layout_program(f"{name}_{which}", text)
            for which, (_, text) in programs.items()}
    assert read["plain"] == read["kept"] == (2, 2, 2, 0)
    assert not _recomputed_sorts(programs["kept"][1])
    again = len(_recomputed_sorts(programs["default"][1]))
    assert again in (2, 4)
    assert read["default"] == (2 + again // 2,) * 2 + (2, again // 2)
    gauge = {s["labels"]["program"]: s["value"] for s in obs.registry()
             .snapshot()["horovod_moe_layout_reruns"]["samples"]}
    assert (gauge[f"{name}_kept"], gauge[f"{name}_default"]) \
        == (0, again // 2)


def test_a_forward_program_has_no_layer_to_count():
    text = ('  %sort.1 = (s32[8]{0}, s32[8]{0}) sort(%a, %b), dimensions={0}, '
            'metadata={op_name="jit(f)/hvd.moe/hvd.moe.experts/sort"}\n')
    assert obs.moe.record_layout_program("forward", text) == (0, 1, 0, 0)
