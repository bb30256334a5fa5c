"""``models.laguna``: rotary frequencies against hand-worked values, the
expert layer against a loop over experts — the eight shares adding up to
the whole layer, no token dropped under any imbalance — the routing gauges,
and the model through the data-parallel step on two devices."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import ExpertLayer, LagunaLM, lm_loss
from horovod_tpu.models import experts, parts

TOY = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 8, "num_key_value_heads": 2, "head_dim": 32,
    "rms_norm_eps": 1e-6, "num_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "sliding_window": 16, "moe_routed_scaling_factor": 2.5,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
    + ["full_attention"] + ["sliding_attention"] * 3,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "num_attention_heads_per_layer": [6, 8, 8, 8] * 2,
    "experts_held": {"first": 4, "count": 4},
}


# -- rotary positions ---------------------------------------------------------


def test_plain_rotary_frequencies():
    freq = np.asarray(parts.Rotary(theta=10000.0, dim=128).inv_freq())
    assert freq.shape == (64,)
    assert freq[0] == 1.0
    assert freq[1] == pytest.approx(10000 ** (-2 / 128), rel=1e-6)
    assert freq[63] == pytest.approx(10000 ** (-126 / 128), rel=1e-5)


def test_yarn_frequencies_against_hand_worked_values():
    """Laguna's full layers: 64 rotated dims, theta 5e5, 4096 original
    positions, factor 64, beta 64 and 1. The correction dims are
    ``64 ln(4096 / (2 pi r)) / (2 ln 5e5)``: 5.66 for 64 rotations, 15.80
    for one, so ``low`` 5 and ``high`` 16: frequencies 0..5 are
    extrapolated (unchanged), 16..31 interpolated (divided by 64), and
    between them the ramp ``(i - 5) / 11`` blends the two."""
    rotary = parts.Rotary(theta=500000.0, dim=64, factor=64.0,
                           original_max_position=4096, beta_fast=64.0,
                           beta_slow=1.0,
                           attention_factor=1.4158883083359672)
    assert 64 * math.log(4096 / (2 * math.pi * 64)) \
        / (2 * math.log(5e5)) == pytest.approx(5.660, abs=1e-3)
    assert 64 * math.log(4096 / (2 * math.pi)) \
        / (2 * math.log(5e5)) == pytest.approx(15.802, abs=1e-3)
    freq = np.asarray(rotary.inv_freq(), np.float64)
    plain = 500000.0 ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(freq[:6], plain[:6], rtol=1e-5)
    np.testing.assert_allclose(freq[16:], plain[16:] / 64, rtol=1e-5)
    # i = 10: ramp 5/11; by hand ln 5e5 = 13.1224, x 10/32 = 4.10074,
    # exp(-4.10074) = 0.016560; 6/11 of it + 5/11 of a 64th of it
    assert plain[10] == pytest.approx(0.016560, rel=1e-4)
    assert freq[10] == pytest.approx(0.0090329 + 0.0001176, rel=1e-4)
    assert np.all(np.diff(freq) < 0)


def test_rotation_turns_the_leading_dims_and_keeps_the_rest():
    rotary = parts.Rotary(theta=10000.0, dim=8, attention_factor=2.0)
    x = jnp.ones((1, 3, 2, 16), jnp.float32)
    out = np.asarray(rotary(x, jnp.arange(3)[None]))
    np.testing.assert_array_equal(out[..., 8:], 1.0)
    np.testing.assert_allclose(out[0, 0, :, :8], 2.0)  # angle 0: cos * 2
    angle = 2.0 * 10000 ** (-2 / 8)   # position 2, frequency 1
    np.testing.assert_allclose(
        out[0, 2, 0, 1], 2.0 * (math.cos(angle) - math.sin(angle)), rtol=1e-5)
    np.testing.assert_allclose(
        out[0, 2, 0, 5], 2.0 * (math.cos(angle) + math.sin(angle)), rtol=1e-5)


# -- the expert layer ---------------------------------------------------------


def expert_loop(x, ids, weights, w1, w3, w2, first):
    """The sum the layer owes, an expert at a time on every token."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w1.shape[0]):
        weight = jnp.sum(jnp.where(ids == first + e, weights, 0.0), -1)
        y = (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        out = out + weight[:, None] * y
    return out


def expert_operands(seed, tokens, d, width, held, k, num_experts,
                    ids=None):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    # products over ``d`` of one size whatever ``d`` (0.3 at the toy's 16)
    w1, w3 = (jnp.asarray(1.2 / math.sqrt(d) * rng.standard_normal(
        (held, d, width)), jnp.float32) for _ in range(2))
    w2 = jnp.asarray(0.3 * rng.standard_normal((held, width, d)),
                     jnp.float32)
    if ids is None:
        ids = jnp.asarray(np.stack([
            rng.choice(num_experts, size=k, replace=False)
            for _ in range(tokens)]), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 1.0, (tokens, k)), jnp.float32)
    return x, ids, weights, w1, w3, w2


def ids_with(counts, tokens, k, first, num_experts):
    """``ids`` [tokens, k] that send ``counts[e]`` rows to held expert
    ``first + e`` — the tokens after those of the experts before it, round
    the batch — and every other assignment to experts not held."""
    held = [[] for _ in range(tokens)]
    start = 0
    for e, count in enumerate(counts):
        for t in range(start, start + count):
            held[t % tokens].append(first + e)
        start += count
    absent = [e for e in range(num_experts)
              if not first <= e < first + len(counts)]
    return np.asarray([(h + absent)[:k] for h in held], np.int32)


# (tokens, d, width, k, num_experts, first, rows to each held expert or
# None for a seeded router, slots of a slice, slices the loop must run)
_ROUTINGS = {
    # 4 of 32 held, 4 a token: a slice is one tile of 8 slots
    "even": (64, 16, 8, 4, 32, 4, None, 8, None),
    "all_to_one_held": (64, 16, 8, 4, 32, 4, (0, 64, 0, 0), 8, 8),
    "all_to_held_only": (64, 16, 8, 4, 32, 4, (64, 64, 64, 64), 8, 32),
    "none_held": (64, 16, 8, 4, 32, 4, (0, 0, 0, 0), 8, 0),
    "one_token_here": (64, 16, 8, 4, 32, 4, (0, 0, 0, 1), 8, 1),
    # 2 of 256 held, 8 a token: every token chooses both
    "many_slices_of_a_small_slice": (32, 16, 8, 8, 256, 10, (32, 32), 8, 8),
    # 4 of 16 held, 128 tokens: a slice is two tiles; the slots in use end
    # on a slice's boundary, a tile short of it and a tile past it
    "ends_on_a_boundary": (128, 16, 8, 4, 16, 4, (8, 8, 8, 8), 16, 2),
    "ends_a_tile_short": (128, 16, 8, 4, 16, 4, (8, 8, 0, 5), 16, 2),
    "ends_a_tile_past": (128, 16, 8, 4, 16, 4, (9, 8, 8, 8), 16, 3),
    # one group's tiles across four slices: the weights' gradient adds up
    # in place, the other three matrices' never touched
    "one_expert_holds_every_row": (128, 16, 8, 4, 16, 4, (0, 0, 64, 0), 16, 4),
    # 8 of 16 held, a slice of four tiles: each holds another expert's few
    # rows, and an expert without a row lies between them
    "less_than_a_tile_each": (128, 16, 8, 4, 16, 2,
                              (3, 2, 5, 1, 0, 4, 2, 7), 32, 2),
    # rows of two pieces of 128: the sums by token go through
    # ``moe_rows_add`` — a seeded router, and a token in consecutive tiles
    # (every token chooses both held experts) over slices of two tiles
    "pieces_of_128": (64, 256, 8, 4, 32, 4, None, 8, None),
    "pieces_of_128_a_token_in_the_next_tile": (
        128, 256, 8, 8, 16, 3, (128, 120), 16, 16),
}


@pytest.mark.parametrize("routing", _ROUTINGS)
def test_the_loop_follows_the_rows_and_drops_none(routing):
    """Forward and every gradient against the loop over experts, and the
    trip count against the slots in use, under any imbalance."""
    tokens, d, width, k, num_experts, first, counts, size, slices = \
        _ROUTINGS[routing]
    held = 4 if counts is None else len(counts)
    ids = None if counts is None else jnp.asarray(
        ids_with(counts, tokens, k, first, num_experts))
    x, ids, weights, w1, w3, w2 = expert_operands(
        1, tokens, d, width, held, k, num_experts, ids)
    cot = jnp.asarray(np.random.default_rng(2).standard_normal(x.shape),
                      jnp.float32)

    def run(fn):
        out, vjp, loop = jax.vjp(lambda *a: fn(a[0], ids, *a[1:]), x,
                                 weights, w1, w3, w2, has_aux=True)
        return loop, (out, *vjp(cot))

    with jax.default_matmul_precision("highest"):
        loop, got = run(lambda x, ids, *a: experts.held_expert_sum(
            x, ids, *a, first=first, num_experts=num_experts))
        _, want = run(lambda x, ids, *a: (
            expert_loop(x, ids, *a, first=first), None))
    for g, w, name in zip(got, want, ("out", "dx", "dweights", "dw1", "dw3",
                                      "dw2")):
        np.testing.assert_allclose(g, w, rtol=2e-5, atol=2e-5, err_msg=name)
    here = np.bincount(np.asarray(ids).reshape(-1) - first + num_experts,
                       minlength=2 * num_experts)[num_experts:][:held]
    if counts is not None:
        assert here.tolist() == list(counts)
    in_use = int((-(-here // 8) * 8).sum())
    assert {name: int(v) for name, v in loop.items()} == {
        "slices": -(-in_use // size), "slots": in_use,
        "ran": -(-in_use // size) * size,
        "summed": -(-in_use // size) * size if d % 128 == 0 else 0}
    if slices is not None:
        assert int(loop["slices"]) == slices
    if not in_use:
        assert not np.asarray(got[0]).any()


# (rows of the sum, d, a tile's slots, tiles, active tiles, rows' dtype,
# scaled)
_SUMS = {
    "two_pieces": (64, 256, 8, 4, 4, "float32", True),
    "unscaled": (64, 256, 8, 4, 4, "float32", False),
    "no_active_tile": (64, 256, 8, 4, 0, "float32", True),
    "fewer_active_than_the_grid": (64, 256, 8, 4, 3, "bfloat16", True),
    "a_kernel_tile": (300, 256, 256, 2, 2, "bfloat16", True),
    "a_kernel_tile_one_active": (300, 256, 256, 2, 1, "float32", False),
    "eighteen_pieces": (40, 2304, 8, 3, 3, "bfloat16", True),
    "eighteen_pieces_unscaled": (40, 2304, 8, 3, 2, "float32", False),
}


@pytest.mark.parametrize("case", _SUMS)
def test_rows_add_to_their_tokens_bit_for_bit(case):
    """``moe_rows_add`` (interpreted) against ``sum.at[token].add(rows *
    scale, mode="drop")``: every tile's tokens distinct and drawn anew, so
    that tokens recur in consecutive tiles and a row's additions keep
    their order; each tile's last slots empty (``token == N``) with NaN
    rows; tiles past ``active`` all NaN. Bit for bit: scaled rows and
    their scales are bfloat16 values, whose product float32 holds exactly
    — the CPU compiler contracts the interpreted kernel's ``rows * scale +
    sum`` into one rounding, which the chip does not
    (benchmarks/expert_layer_bench.py ``--sum-only`` reads the difference
    there on float32 scales)."""
    from horovod_tpu.ops.grouped_matmul import moe_rows_add

    n, d, tile, tiles, active, dtype, scaled = _SUMS[case]
    rng = np.random.default_rng(len(case))
    token = np.stack([np.concatenate([   # tokens 0 and 1 in every tile
        [0, 1], 2 + rng.permutation(n - 2)[:tile - 2]]) for _ in range(tiles)])
    token[:, tile - tile // 4:] = n
    token = token.reshape(-1).astype(np.int32)
    live = (np.arange(token.size) < active * tile) & (token < n)
    rows = jnp.asarray(np.where(
        live[:, None], rng.standard_normal((token.size, d)), np.nan),
        jnp.bfloat16 if scaled else dtype).astype(dtype)
    scale = jnp.asarray(rng.uniform(0.1, 1.0, (token.size, 1)),
                        jnp.bfloat16).astype(jnp.float32) if scaled else None
    total = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)
    got = moe_rows_add(total.reshape(n, d // 128, 128), rows,
                       jnp.asarray(token), scale, jnp.int32(active),
                       row_tile=tile)
    update = rows.astype(jnp.float32) * (scale if scaled else 1.0)
    want = total.at[jnp.where(live, token, n)].add(update, mode="drop")
    assert got.shape == (n, d // 128, 128) and got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got).reshape(n, d), want)
    assert np.isfinite(np.asarray(got)).all()
    touched = np.zeros(n, bool)
    touched[token[live]] = True
    assert touched.any() == bool(active)
    np.testing.assert_array_equal(np.asarray(got).reshape(n, d)[~touched],
                                  np.asarray(total)[~touched])


def test_rows_add_refuses_what_does_not_fit():
    from horovod_tpu.ops.grouped_matmul import moe_rows_add

    total, token = jnp.zeros((16, 2, 128)), jnp.zeros((16,), jnp.int32)
    for rows, tokens in ((jnp.zeros((16, 128)), token),    # another width
                         (jnp.zeros((12, 256)), token[:12]),  # no whole tile
                         (jnp.zeros((16, 256)), token[:8])):
        with pytest.raises(ValueError, match="do not add to"):
            moe_rows_add(total, rows, tokens, None, jnp.int32(1), row_tile=8)


@pytest.mark.parametrize("active", [0, 3, 5, 8])
def test_grouped_matmul_tile_by_tile(active):
    """The three kernels (interpreted) against an einsum over the tiles:
    8 tiles of 8 rows, 3 groups (one without a tile), the first ``active``
    computed; rows and gradients of idle tiles are never read."""
    from horovod_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.default_rng(active)
    rows = jnp.asarray(rng.standard_normal((64, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((3, 16, 24)), jnp.float32)
    cot = jnp.asarray(rng.standard_normal((64, 24)), jnp.float32)
    group = jnp.asarray([0, 0, 0, 2, 2, 2, 2, 2], jnp.int32)
    live = (jnp.arange(64) < 8 * active)[:, None]

    def kernel(rows, w):
        out = grouped_matmul(jnp.where(live, rows, jnp.nan), w, group,
                             jnp.int32(active), row_tile=8)
        return jnp.where(live, out, 0.0)

    def plain(rows, w):
        out = jnp.einsum("tmk,tkn->tmn", rows.reshape(8, 8, 16), w[group])
        return jnp.where(live, out.reshape(64, 24), 0.0)

    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(kernel, rows, w)
        want, want_vjp = jax.vjp(plain, rows, w)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        for g, v in zip(got_vjp(cot), want_vjp(cot)):
            np.testing.assert_allclose(g, v, rtol=1e-5, atol=1e-5)
    assert not np.asarray(got_vjp(cot)[1][1]).any()   # group 1: no tile


@pytest.mark.parametrize("active", [0, 3, 8])
def test_the_weights_gradient_adds_to_a_running_sum(active):
    """``grouped_matmul_transposed`` (interpreted): the rows' gradient of
    the active tiles, and ``sums`` plus each group's ``rows^T x grads`` —
    a group without an active tile keeps its sum as it was."""
    from horovod_tpu.ops.grouped_matmul import grouped_matmul_transposed

    rng = np.random.default_rng(active)
    rows, grads, w, sums = (
        jnp.asarray(rng.standard_normal(shape), jnp.float32) for shape in (
            (64, 16), (64, 24), (3, 16, 24), (3, 16, 24)))
    group = jnp.asarray([0, 0, 0, 2, 2, 2, 2, 2], jnp.int32)
    live = (np.arange(8) < active)[:, None, None]
    with jax.default_matmul_precision("highest"):
        dx, total = grouped_matmul_transposed(
            rows, grads, w, sums, group, jnp.int32(active), row_tile=8)
        per_tile = np.where(live, np.einsum(
            "tmk,tmn->tkn", rows.reshape(8, 8, 16), grads.reshape(8, 8, 24)),
            0.0)
        want_dx = np.einsum("tmn,tkn->tmk", grads.reshape(8, 8, 24), w[group])
    want = np.asarray(sums).copy()
    np.add.at(want, np.asarray(group), per_tile)
    np.testing.assert_allclose(total, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(total[1], sums[1])   # group 1: no tile
    np.testing.assert_allclose(
        np.asarray(dx)[:8 * active], want_dx.reshape(64, 16)[:8 * active],
        rtol=1e-5, atol=1e-5)


def test_a_slice_is_an_eighth_of_an_even_routers_rows():
    """The three cells' shapes: 16,384 rows, 8 a token; and the tile of 8
    rows where a slice is less than one kernel tile."""
    assert experts.slice_slots(16384 * 8, 32, 256) == (2048, 256)   # Laguna
    assert experts.slice_slots(16384 * 8, 16, 128) == (2048, 256)   # SDAR
    assert experts.slice_slots(16384 * 8, 8, 256) == (512, 256)     # Kimi
    assert experts.slice_slots(128 * 4, 4, 16) == (16, 8)
    assert experts.slice_slots(32 * 8, 2, 256) == (8, 8)


def test_grouped_matmul_refuses_what_does_not_fit():
    from horovod_tpu.ops.grouped_matmul import grouped_matmul

    group, one = jnp.zeros((2,), jnp.int32), jnp.int32(1)
    with pytest.raises(ValueError, match="do not fit"):
        grouped_matmul(jnp.zeros((20, 16)), jnp.zeros((1, 16, 8)), group,
                       one, row_tile=8)
    with pytest.raises(ValueError, match="stay resident"):
        grouped_matmul(jnp.zeros((16, 4096)), jnp.zeros((1, 4096, 2048)),
                       group, one, row_tile=8)


def _expert_layer(held):
    return ExpertLayer(num_experts=16, experts_per_token=4,
                       experts_held=held, width=8, shared_width=8,
                       scaling=2.5, dtype=jnp.float32)


def test_the_shares_add_up_to_the_whole_layer():
    """Over all 8 shares of one expert layer (2 of 16 experts each), with
    what every chip computes alike — the shared expert — counted once, the
    parts sum to what the uncut layer gives."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((2, 24, 16)), jnp.float32)
    whole = _expert_layer((0, 16))
    params = whole.init(jax.random.PRNGKey(0), x)["params"]
    # weights large enough that the routed part is no rounding error
    params = jax.tree_util.tree_map(lambda p: 10.0 * p, params)
    with jax.default_matmul_precision("highest"):
        want = whole.apply({"params": params}, x)
        shared_alone = parts.GatedMLP(8, jnp.float32).apply(
            {"params": params["shared"]}, x)
        total = shared_alone
        for share in range(8):
            cut = dict(params, **{
                name: params[name][2 * share:2 * share + 2]
                for name in ("experts_w1", "experts_w3", "experts_w2")})
            part = _expert_layer((2 * share, 2)).apply({"params": cut}, x)
            total = total + (part - shared_alone)
    assert float(jnp.abs(want - shared_alone).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


def test_router_keeps_the_largest_and_normalises_them():
    scores = jnp.asarray([[0.1, 0.9, 0.5, 0.3, 0.7, 0.2]], jnp.float32)
    ids, weights = experts.route(scores, 3, 2.5)
    assert ids.tolist() == [[1, 4, 2]]
    np.testing.assert_allclose(
        weights, [[2.5 * 0.9 / 2.1, 2.5 * 0.7 / 2.1, 2.5 * 0.5 / 2.1]],
        rtol=1e-6)


def test_experts_held_outside_the_experts_is_refused():
    with pytest.raises(ValueError, match="is no part"):
        _expert_layer((12, 8)).init(jax.random.PRNGKey(0),
                                    jnp.zeros((1, 4, 16)))


# -- the model ----------------------------------------------------------------


@pytest.fixture(scope="module")
def toy():
    model = LagunaLM.from_config(TOY, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 64), 0, 512)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    return model, params, tokens


def test_from_config_lays_out_the_published_pattern(toy):
    model, params, _ = toy
    assert model.layer_types[:5] == (
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention")
    assert params["block_0"]["attn"]["query"]["kernel"].shape == (64, 6, 32)
    assert params["block_1"]["attn"]["query"]["kernel"].shape == (64, 8, 32)
    assert params["block_1"]["attn"]["key"]["kernel"].shape == (64, 2, 32)
    assert params["block_1"]["attn"]["gate"]["kernel"].shape == (64, 8)
    assert "mlp" in params["block_0"] and "moe" not in params["block_0"]
    moe = params["block_3"]["moe"]
    assert moe["router"]["kernel"].shape == (64, 16)   # all the experts
    assert moe["experts_w1"].shape == (4, 64, 32)      # the held ones
    assert model.rotary_full.factor == 64 and model.rotary_full.dim == 16
    assert model.rotary_sliding.factor is None \
        and model.rotary_sliding.dim == 32
    assert not any("bias" in jax.tree_util.keystr(path) for path, _ in
                   jax.tree_util.tree_leaves_with_path(params))


def test_flash_and_dense_attention_agree_and_remat_changes_nothing(toy):
    model, params, tokens = toy

    def loss_and_grad(m):
        return jax.value_and_grad(lambda p: lm_loss(
            m.apply({"params": p}, tokens), tokens))(params)

    flash = loss_and_grad(model)
    assert model.attention == "flash"
    for other in (model.clone(attention="dense"), model.clone(remat=True)):
        loss, grad = loss_and_grad(other)
        assert float(loss) == pytest.approx(float(flash[0]), rel=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(grad),
                        jax.tree_util.tree_leaves(flash[1])):
            np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)


def test_a_window_layer_does_not_see_past_its_window(toy):
    """Change the first token: through sliding layers alone (no full
    layer, no dense MLP in the way) the logits move only at positions
    whose windows, stacked layer on layer, reach it."""
    config = dict(TOY, num_hidden_layers=2,
                  layer_types=["sliding_attention"] * 2,
                  mlp_layer_types=["sparse"] * 2,
                  num_attention_heads_per_layer=[8, 8])
    model = LagunaLM.from_config(config, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 64), 0, 512)
    params = model.init(jax.random.PRNGKey(4), tokens)["params"]
    moved = tokens.at[0, 0].set((tokens[0, 0] + 1) % 512)
    delta = np.abs(np.asarray(model.apply({"params": params}, tokens)
                              - model.apply({"params": params}, moved)))
    reach = 2 * (16 - 1)   # two layers of window 16
    assert delta[0, :reach + 1].max() > 0
    assert delta[0, reach + 1:].max() == 0.0


def test_moe_stats_become_gauges(toy):
    from horovod_tpu import obs

    model, params, tokens = toy
    _, state = model.apply({"params": params}, tokens, mutable=["moe_stats"])
    published = obs.moe.publish(state["moe_stats"])
    assert sorted(published) == [f"block_{i}/moe" for i in range(1, 8)]
    counts = np.asarray(
        state["moe_stats"]["block_1"]["moe"]["assignments"][0])
    assert counts.shape == (16,) and counts.sum() == 2 * 64 * 4
    # a slice here is 16 slots: an eighth of 2 * 64 * 4 * 4 / 16 rows, in
    # two tiles of 8
    in_use = (-(-counts[4:8] // 8) * 8).sum()
    want = {"load_max_over_mean": counts.max() / counts.mean(),
            "held_share": counts[4:8].sum() / counts.sum(),
            "slices_run": -(-in_use // 16),
            "slot_fill": in_use / (-(-in_use // 16) * 16),
            "sum_kernel_share": 0.0,   # 64 wide: XLA's scatter-add
            "gate_zero_share": 0.0}    # a silu gate gives no exact zero
    assert published["block_1/moe"] == pytest.approx(want)
    assert 0.5 < want["slot_fill"] <= 1.0
    snapshot = obs.registry().snapshot()
    for family, key in (
            ("horovod_moe_expert_load_max_over_mean", "load_max_over_mean"),
            ("horovod_moe_held_assignment_share", "held_share"),
            ("horovod_moe_slices_run", "slices_run"),
            ("horovod_moe_slot_fill", "slot_fill"),
            ("horovod_moe_sum_kernel_share", "sum_kernel_share")):
        read = {s["labels"]["layer"]: s["value"]
                for s in snapshot[family]["samples"]}
        assert read["block_1/moe"] == pytest.approx(want[key])
    # a training step does not carry the collection
    assert "moe_stats" not in model.apply({"params": params}, tokens,
                                          mutable=["intermediates"])[1]


def test_publish_reads_the_loop_off_a_collection():
    """Hand-made: 40 slots in use of 3 slices of 16, all summed by the
    kernel; a layer without a row here ran no slice, and wasted none."""
    from horovod_tpu import obs

    def layer(assignments, absent, slices, slots):
        return {name: (np.asarray(value),) for name, value in dict(
            assignments=assignments, absent=absent, slices=slices,
            slots=slots, ran=16 * slices, summed=16 * slices).items()}

    published = obs.moe.publish({
        "block_1": {"moe": layer([10, 30, 0, 0], 10, 3, 40)},
        "block_2": {"moe": layer([0, 0, 20, 20], 40, 0, 0)},
        "block_3": {"other": {"assignments": (np.ones(4),)}}})
    assert published == {
        "block_1/moe": {"load_max_over_mean": 3.0, "held_share": 0.75,
                        "slices_run": 3, "slot_fill": 40 / 48,
                        "sum_kernel_share": 1.0},
        "block_2/moe": {"load_max_over_mean": 2.0, "held_share": 0.0,
                        "slices_run": 0, "slot_fill": 1.0,
                        "sum_kernel_share": 1.0}}
    fill = {s["labels"]["layer"]: s["value"] for s in
            obs.registry().snapshot()["horovod_moe_slot_fill"]["samples"]}
    assert fill["block_2/moe"] == 1.0


def test_the_scopes_reach_the_compiled_step(toy):
    model, params, tokens = toy
    text = jax.jit(jax.grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), tokens))).lower(
            params).compile().as_text()
    for scope in ("hvd.moe/", "hvd.moe.route", "hvd.moe.experts",
                  "hvd.moe.combine"):
        assert scope in text, scope


def test_two_devices_train_as_one(toy):
    """Through ``make_lm_train_step`` and ``hvd.DistributedOptimizer`` on a
    data mesh of two: the loss and the updated parameters are those of one
    device on the whole batch (routing, the sort and the grouped products
    under a vma-checking ``shard_map``). The expert layer's loop runs as
    many slices as a device's own rows take, another number on each: the
    replicated weights' gradient must be summed over the axis outside it,
    or the devices wait on different all-reduces."""
    import optax

    import horovod_tpu as hvd
    from benchmarks._dp_step import make_lm_train_step

    model, params, tokens = toy
    slices = [[int(layer["moe"]["slices"][0]) for layer in model.apply(
        {"params": params}, tokens[i:i + 1],
        mutable=["moe_stats"])[1]["moe_stats"].values()] for i in range(2)]
    assert slices[0] != slices[1] and min(min(slices)) > 0
    results = []
    for n in (1, 2):
        mesh = hvd.parallel.data_parallel_mesh(jax.devices()[:n])
        opt = hvd.DistributedOptimizer(optax.adamw(1e-2), axis_name="data")
        copy = jax.tree_util.tree_map(jnp.copy, params)
        step = make_lm_train_step(model, opt, mesh)
        new, _, loss = step(copy, jax.jit(opt.init)(copy), tokens)
        results.append((float(loss), new))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(results[0][1]),
                    jax.tree_util.tree_leaves(results[1][1])):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=2e-4)
