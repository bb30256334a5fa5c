"""Pallas flash-attention kernel vs dense reference (interpret mode on the
CPU suite; the same kernel compiles for real on TPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.pallas_attention import flash_attention
from horovod_tpu.parallel.ring_attention import dense_attention

B, T, H, D = 2, 64, 2, 16


def _qkv(seed):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, T, H, D)).astype(np.float32))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_dense(causal):
    q, k, v = _qkv(0)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_q_offset_matches_shifted_causal():
    """q_offset reproduces ring attention's per-shard causal masking: a
    q block at global offset sees all earlier K."""
    q, k, v = _qkv(1)
    offset = 16
    out = flash_attention(q[:, :16], k[:, :32], v[:, :32], causal=True,
                          block_q=16, block_k=16, q_offset=offset)
    # dense equivalent: q rows at positions 16..31 attending over k 0..31
    s_ref = dense_attention(
        jnp.pad(q[:, :16], ((0, 0), (16, 0), (0, 0), (0, 0))),
        k[:, :32], v[:, :32], causal=True)[:, 16:]
    np.testing.assert_allclose(np.asarray(out), np.asarray(s_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_rejects_ragged_seq():
    q = jnp.ones((1, 48, 1, 8))
    with pytest.raises(ValueError, match="multiples"):
        flash_attention(q, q, q, block_q=32, block_k=32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_matches_dense(causal):
    """custom-VJP backward kernels (FlashAttention-2 recomputation) must
    reproduce the dense-attention gradients for q, k, and v."""
    import jax

    q, k, v = _qkv(3)
    rng = np.random.default_rng(7)
    cot = jnp.asarray(rng.standard_normal((B, T, H, D)).astype(np.float32))

    def flash_loss(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal=causal,
                                        block_q=16, block_k=16), cot)

    def dense_loss(q, k, v):
        return jnp.vdot(dense_attention(q, k, v, causal=causal), cot)

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    ref = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    for g, r, name in zip(got, ref, "qkv"):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-4, atol=5e-4,
                                   err_msg=f"d{name} mismatch")


def test_flash_grad_q_offset():
    """Backward with a q_offset (the ring-attention entry point): compare
    against dense attention over the equivalent shifted causal mask."""
    import jax

    from horovod_tpu.parallel.ring_attention import dense_attention as _da

    q, k, v = _qkv(5)
    half = T // 2
    q_half = q[:, half:]  # queries living at global positions [half, T)
    cot = jnp.ones_like(q_half)

    def flash_loss(q_half, k, v):
        return jnp.vdot(flash_attention(q_half, k, v, causal=True,
                                        block_q=16, block_k=16,
                                        q_offset=half), cot)

    def dense_loss(q_full, k, v):
        return jnp.vdot(_da(q_full, k, v, causal=True)[:, half:],
                        jnp.ones_like(q_full[:, half:]))

    got = jax.grad(flash_loss, argnums=(0, 1, 2))(q_half, k, v)
    ref_full = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got[0]),
                               np.asarray(ref_full[0][:, half:]),
                               rtol=5e-4, atol=5e-4, err_msg="dq")
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(ref_full[1]),
                               rtol=5e-4, atol=5e-4, err_msg="dk")
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(ref_full[2]),
                               rtol=5e-4, atol=5e-4, err_msg="dv")


def test_flash_trains_in_transformer():
    """End-to-end: a TransformerLM with attention='flash' must train (the
    forward-only kernel regression this guards against)."""
    import jax
    import optax

    from horovod_tpu.models import TransformerLM, lm_loss

    model = TransformerLM(vocab_size=32, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=64,
                          dtype=jnp.float32, attention="flash")
    tokens = jnp.asarray(
        np.tile(np.arange(8), (2, 8)).astype(np.int32))
    variables = model.clone(attention="dense").init(
        jax.random.PRNGKey(0), tokens[:, :8])
    opt = optax.adam(1e-2)
    opt_state = opt.init(variables)

    @jax.jit
    def step(variables, opt_state):
        loss, grads = jax.value_and_grad(
            lambda v: lm_loss(model.apply(v, tokens), tokens))(variables)
        updates, opt_state = opt.update(grads, opt_state, variables)
        return optax.apply_updates(variables, updates), opt_state, loss

    losses = []
    for _ in range(10):
        variables, opt_state, loss = step(variables, opt_state)
        losses.append(float(loss))
    assert losses[-1] < losses[0] * 0.7, losses


def test_flash_under_vma_shard_map_matches_dense():
    """The flash kernel must be legal inside a vma-tracking shard_map (the
    DP product path wraps whole models in one): pallas_call outputs carry
    the union of their operands' vma type (_sds). Data-parallel over the
    batch, gradients and outputs must match the dense reference."""
    import jax
    import numpy as np
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.parallel import data_parallel_mesh

    mesh = data_parallel_mesh()
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.normal(size=(8, 128, 2, 64)).astype(
        np.float32)) for _ in range(3))

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    def sharded(fn):
        def inner(q, k, v):
            val, grads = jax.value_and_grad(fn, argnums=(0, 1, 2))(q, k, v)
            return jax.lax.psum(val, "data"), grads

        return jax.jit(shard_map(
            inner, mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
            out_specs=(P(), (P("data"), P("data"), P("data")))))

    val_f, grads_f = sharded(loss_flash)(q, k, v)
    val_d, grads_d = sharded(loss_dense)(q, k, v)
    np.testing.assert_allclose(float(val_f), float(val_d), rtol=2e-4)
    for gf, gd in zip(grads_f, grads_d):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-3, atol=2e-3)


def _shifted_dense(q, k, v, causal, q_offset):
    """Dense attention with q's rows at positions q_offset, q_offset + 1,
    ...: q padded in front so that the plain causal mask lands there."""
    padded = jnp.pad(q, ((0, 0), (q_offset, 0), (0, 0), (0, 0)))
    return dense_attention(padded, k, v, causal=causal)[:, q_offset:]


def _assert_flash_equals_dense(batch, seq_q, seq_k, q_offset, block_q,
                               block_k, causal, head_dim, dtype):
    """Forward and the three gradients of a two-head call against dense
    attention."""
    import jax

    rng = np.random.default_rng(seq_q + 7 * seq_k + 13 * q_offset)
    q, cot = (jnp.asarray(rng.standard_normal((batch, seq_q, 2, head_dim)),
                          dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal((batch, seq_k, 2, head_dim)),
                        dtype) for _ in range(2))

    def run(attention):
        out, vjp = jax.vjp(attention, q, k, v)
        return (out, *vjp(cot))

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=block_q, block_k=block_k,
        q_offset=q_offset))
    want = run(lambda q, k, v: _shifted_dense(q, k, v, causal, q_offset))
    tol = 5e-4 if dtype == "float32" else 3e-2
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol, err_msg=name)


# (id, seq_q, seq_k, q_offset, block_q, block_k, causal, head_dim, dtype,
#  resident bytes or None): the shapes that move the diagonal through the
# kernels' loops. Tiles of 16 keep the interpreter quick.
_DIAGONAL_CASES = [
    ("one_tile", 16, 16, 0, 16, 16, True, 16, "float32", None),
    ("three_tiles", 48, 48, 0, 16, 16, True, 16, "float32", None),
    ("three_tiles_noncausal", 48, 48, 0, 16, 16, False, 16, "float32", None),
    ("short_q_long_k", 32, 64, 0, 16, 16, True, 16, "float32", None),
    ("long_q_short_k", 64, 32, 0, 16, 16, True, 16, "float32", None),
    ("short_q_long_k_noncausal", 32, 64, 0, 16, 16, False, 16, "float32",
     None),
    ("offset_one_tile", 32, 48, 16, 16, 16, True, 16, "float32", None),
    ("offset_three_tiles", 16, 64, 48, 16, 16, True, 16, "float32", None),
    ("offset_inside_a_tile", 32, 64, 24, 16, 16, True, 16, "float32", None),
    ("offset_past_every_k", 32, 32, 64, 16, 16, True, 16, "float32", None),
    ("wide_q_tile", 64, 64, 0, 32, 16, True, 16, "float32", None),
    ("wide_k_tile", 64, 64, 0, 16, 32, True, 16, "float32", None),
    # 4 * 128 lanes * 4 bytes * 16 rows = one tile's worth of residents
    ("two_tiles_a_major", 96, 96, 0, 16, 16, True, 16, "float32", 65536),
    ("one_tile_a_major", 80, 80, 0, 16, 16, True, 16, "float32", 1),
    ("one_tile_a_major_offset", 48, 80, 32, 16, 16, True, 16, "float32", 1),
    ("two_tiles_a_major_noncausal", 96, 96, 0, 16, 16, False, 16, "float32",
     65536),
    ("head_dim_64", 48, 48, 0, 16, 16, True, 64, "float32", None),
    ("head_dim_128", 48, 48, 0, 16, 16, True, 128, "float32", None),
    ("bf16", 48, 48, 0, 16, 16, True, 64, "bfloat16", None),
    ("bf16_head_dim_128_noncausal", 48, 48, 0, 16, 16, False, 128,
     "bfloat16", None),
    ("default_blocks", 256, 256, 0, 512, 512, True, 64, "bfloat16", None),
]


@pytest.mark.parametrize(
    "seq_q,seq_k,q_offset,block_q,block_k,causal,head_dim,dtype,resident",
    [case[1:] for case in _DIAGONAL_CASES],
    ids=[case[0] for case in _DIAGONAL_CASES])
def test_flash_follows_the_diagonal(monkeypatch, seq_q, seq_k, q_offset,
                                    block_q, block_k, causal, head_dim,
                                    dtype, resident):
    """Forward and all three gradients against dense attention wherever
    the loop bounds, the masked / unmasked split and the major-block grid
    take another branch."""
    from horovod_tpu.ops import pallas_attention

    if resident is not None:
        # read at trace time: these cases' shapes are no other test's, so
        # no cached trace stands in for them
        monkeypatch.setattr(pallas_attention, "_RESIDENT_BYTES", resident)
        majors = seq_k // pallas_attention._major(
            seq_k, block_k,
            pallas_attention._operand_row_bytes(head_dim, dtype))
        assert majors > 1, majors
    _assert_flash_equals_dense(2, seq_q, seq_k, q_offset, block_q, block_k,
                               causal, head_dim, dtype)


# (id, seq_q, seq_k, q_offset, block_q, block_k, head_dim, dtype, resident
#  bytes or None, whether the masked tiles run as strips): tiles of whole
# lanes, so that the diagonal tile's products are cut where the shapes put
# the diagonal on a tile's own (``_strips``), and run whole where they do
# not
_STRIP_CASES = [
    ("one_tile_256", 256, 256, 0, None, None, 64, "float32", None, True),
    ("one_tile_512_d128", 512, 512, 0, None, None, 128, "float32", None,
     True),
    ("the_cells_tiles_at_1024", 1024, 1024, 0, None, None, 64, "bfloat16",
     None, True),
    ("default_tiles_at_2048_d128", 2048, 2048, 0, None, None, 128,
     "bfloat16", None, True),
    ("four_tiles_of_256", 1024, 1024, 0, 256, 256, 64, "float32", None,
     True),
    ("two_tiles_of_384", 768, 768, 0, 384, 384, 128, "float32", None, True),
    # 3072 bytes a row of the forward's residents at 256 rows a tile, 2048
    # of the backward's
    ("one_tile_a_major", 1024, 1024, 0, 256, 256, 64, "float32", 1, True),
    ("two_tiles_a_major", 1024, 1024, 0, 256, 256, 64, "float32",
     2 * 256 * 3072, True),
    ("offset_of_two_tiles", 512, 1024, 512, 256, 256, 64, "float32", None,
     True),
    ("offset_majors", 512, 1024, 256, 256, 256, 128, "float32", 1, True),
    ("offset_of_a_strip", 512, 1024, 384, 256, 256, 64, "float32", None,
     False),
    ("offset_inside_a_strip", 512, 1024, 100, 256, 256, 64, "float32", None,
     False),
    ("wide_q_tile", 512, 512, 0, 256, 128, 64, "float32", None, False),
    ("wide_k_tile", 512, 512, 0, 256, 512, 128, "float32", None, False),
    ("one_lane_block_a_tile", 256, 256, 0, 128, 128, 64, "float32", None,
     False),
]


@pytest.mark.parametrize(
    "seq_q,seq_k,q_offset,block_q,block_k,head_dim,dtype,resident,strips",
    [case[1:] for case in _STRIP_CASES],
    ids=[case[0] for case in _STRIP_CASES])
def test_flash_strips_against_dense(monkeypatch, seq_q, seq_k, q_offset,
                                    block_q, block_k, head_dim, dtype,
                                    resident, strips):
    """Forward and the three gradients against dense attention where the
    diagonal tile runs as static strips, and at the neighbouring shapes
    that must fall back to the whole masked tile; ``causal_schedule``'s
    ``trimmed`` says which of the two a shape took."""
    from horovod_tpu.ops import pallas_attention as pa

    fwd, bwd = pa._tiles(seq_q, seq_k, head_dim, dtype, block_q, block_k)
    if resident is not None:
        monkeypatch.setattr(pa, "_RESIDENT_BYTES", resident)
        assert seq_k // pa._major(
            seq_k, bwd[1], pa._operand_row_bytes(head_dim, dtype)) > 1
    for kernel, tiles in (("flash_fwd", fwd), ("flash_bwd_dq", bwd),
                          ("flash_bwd_dkv", bwd)):
        entry = pa.causal_schedule(seq_q, seq_k, q_offset, *tiles,
                                   True)[kernel]
        assert entry["trimmed"] == (entry["diagonal"] if strips else 0)
        assert entry["diagonal"] > 0
    _assert_flash_equals_dense(1, seq_q, seq_k, q_offset, block_q, block_k,
                               True, head_dim, dtype)


# -- causal_schedule alone ---------------------------------------------------

_SCHEDULE_CASES = [
    # seq_q, seq_k, q_offset, tile_q, tile_k, causal
    (8, 8, 0, 8, 8, True), (24, 24, 0, 8, 8, True), (24, 24, 0, 4, 8, True),
    (24, 24, 0, 8, 4, True), (16, 32, 0, 8, 8, True), (32, 16, 0, 8, 8, True),
    (16, 32, 8, 8, 8, True), (8, 32, 24, 8, 8, True), (16, 32, 5, 8, 8, True),
    (16, 16, 40, 8, 8, True), (24, 24, 0, 8, 8, False),
    (16, 32, 8, 8, 4, False),
]


@pytest.mark.parametrize("seq_q,seq_k,q_offset,tile_q,tile_k,causal",
                         _SCHEDULE_CASES)
def test_causal_schedule_against_brute_force(seq_q, seq_k, q_offset, tile_q,
                                             tile_k, causal):
    """Pair by pair: the executed tiles cover every kept pair, no tile that
    lies wholly in the future is executed, a tile flagged interior holds no
    masked pair — for the k walk of forward and dQ and for the q walk of
    dK/dV, which must come to the same tiles."""
    from horovod_tpu.ops.pallas_attention import (
        causal_schedule, k_tile_bounds, q_tile_bounds)

    kept = np.ones((seq_q, seq_k), bool) if not causal else (
        q_offset + np.arange(seq_q)[:, None] >= np.arange(seq_k)[None, :])
    nq, nk = seq_q // tile_q, seq_k // tile_k
    shared = dict(q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
                  causal=causal)
    by_k_walk, by_q_walk = {}, {}  # (q tile, k tile) -> masked body?
    for i in range(nq):
        interior, end = k_tile_bounds(i, num_k_tiles=nk, **shared)
        assert 0 <= interior <= end <= nk
        by_k_walk.update({(i, j): j >= interior for j in range(end)})
    for j in range(nk):
        start, interior = q_tile_bounds(j, num_q_tiles=nq, **shared)
        assert 0 <= start <= interior <= nq
        by_q_walk.update({(i, j): i < interior for i in range(start, nq)})
    assert by_k_walk == by_q_walk
    for i in range(nq):
        for j in range(nk):
            tile = kept[i * tile_q:(i + 1) * tile_q,
                        j * tile_k:(j + 1) * tile_k]
            if (i, j) not in by_k_walk:
                assert not tile.any(), (i, j)   # every kept pair is covered
            else:
                assert tile.any(), (i, j)       # nothing wholly in the future
                if not by_k_walk[i, j]:
                    assert tile.all(), (i, j)   # interior: nothing masked
                else:
                    assert not tile.all(), (i, j)
    schedule = causal_schedule(seq_q, seq_k, q_offset, tile_q, tile_k, causal)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert schedule[name]["tiles"] == len(by_k_walk)
        assert schedule[name]["diagonal"] == sum(by_k_walk.values())
        assert schedule[name]["pair_ratio"] == pytest.approx(
            len(by_k_walk) * tile_q * tile_k / kept.sum())


def executed_pairs_by_enumeration(kept, tile_q, tile_k, strips):
    """What a kernel executes, block by block: a tile with no visible pair
    not at all; a tile that holds masked pairs and runs as strips
    (``strips`` = ``_strips``' rows on a diagonal and on a window-edge tile)
    every ``strip x strip`` block of it that holds a visible pair; any other
    tile whole. Returns ``(pairs, tiles that ran as strips)``. The tile's
    kind is read off ``kept`` too: one that loses a pair above its rows'
    positions is a diagonal tile, one that loses a pair below them an edge
    tile."""
    pairs = trimmed = 0
    for i in range(0, kept.shape[0], tile_q):
        for j in range(0, kept.shape[1], tile_k):
            tile = kept[i:i + tile_q, j:j + tile_k]
            if not tile.any():
                continue
            diagonal = bool((~tile[:, -1]).any() and tile[-1, 0])
            edge = bool((~tile[:, 0]).any() and tile[0, -1])
            strip = strips[0] if diagonal and not edge else \
                strips[1] if edge and not diagonal else None
            if not strip:
                pairs += tile_q * tile_k
                continue
            trimmed += 1
            pairs += strip * strip * sum(
                tile[r:r + strip, c:c + strip].any()
                for r in range(0, tile_q, strip)
                for c in range(0, tile_k, strip))
    return pairs, trimmed


_STRIP_SCHEDULE_CASES = [
    # seq_q, seq_k, q_offset, tile_q, tile_k
    (1024, 1024, 0, 1024, 1024), (1024, 1024, 0, 512, 512),
    (1024, 1024, 0, 256, 256), (768, 768, 0, 384, 384),
    (512, 2048, 1024, 512, 512), (512, 1024, 512, 256, 256),
    (1024, 512, 0, 256, 256), (2048, 2048, 0, 1024, 1024),
    # shapes that fall back: trimmed 0, every executed tile whole
    (512, 1024, 384, 256, 256), (512, 512, 0, 256, 128),
    (512, 512, 0, 128, 256), (256, 256, 0, 128, 128), (96, 96, 0, 32, 32),
]


@pytest.mark.parametrize("seq_q,seq_k,q_offset,tile_q,tile_k",
                         _STRIP_SCHEDULE_CASES)
def test_strip_schedule_against_brute_force(seq_q, seq_k, q_offset, tile_q,
                                            tile_k):
    """``causal_schedule`` counts what the kernels execute strip by strip:
    its pairs and its ``trimmed`` equal an enumeration of the blocks that
    hold a visible pair."""
    from horovod_tpu.ops import pallas_attention as pa

    kept = q_offset + np.arange(seq_q)[:, None] >= np.arange(seq_k)[None, :]
    schedule = pa.causal_schedule(seq_q, seq_k, q_offset, tile_q, tile_k,
                                  True)
    for kernel, entry in schedule.items():
        strips = pa._strips(q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
                            causal=True, window=None)
        pairs, trimmed = executed_pairs_by_enumeration(
            kept, tile_q, tile_k, strips)
        assert entry["trimmed"] == trimmed, kernel
        assert entry["pair_ratio"] == pytest.approx(pairs / kept.sum())
        if tile_q != tile_k or q_offset % tile_q or tile_q < 256:
            assert trimmed == 0 and strips == (None, None)
        else:
            assert trimmed == entry["diagonal"] > 0


def test_schedule_at_the_cells_shape_and_its_gauge():
    """GPT-2-medium's call — T = 1024, head_dim 64, no bounds passed: the
    forward takes the sequence as one tile and the backward kernels tiles
    of 512 (PERF.md, PR 25), which run whole would execute 2.0 / 1.5 / 1.5 x
    the needed pairs; every masked tile of theirs runs as strips of 128
    rows (PR 29), 1.12 x in all three, and the gauge holds what was
    traced."""
    import jax

    from horovod_tpu.obs import registry
    from horovod_tpu.ops import pallas_attention as pa

    fwd, bwd = pa._tiles(1024, 1024, 64, "bfloat16", None, None)
    assert (fwd, bwd) == ((1024, 1024), (512, 512))
    forward = pa.causal_schedule(1024, 1024, 0, *fwd, True)["flash_fwd"]
    backward = pa.causal_schedule(1024, 1024, 0, *bwd, True)
    assert forward == {"tiles": 1, "diagonal": 1, "trimmed": 1,
                       "pair_ratio": pytest.approx(1.998 * 36 / 64,
                                                   rel=1e-3)}
    assert backward["flash_bwd_dq"] == backward["flash_bwd_dkv"] == {
        "tiles": 3, "diagonal": 2, "trimmed": 2,
        "pair_ratio": pytest.approx(1.4985 * (1 + 2 * 10 / 16) / 3,
                                    rel=1e-4)}
    want = {"flash_fwd": forward, **{name: backward[name] for name in
                                     ("flash_bwd_dq", "flash_bwd_dkv")}}
    assert all(entry["pair_ratio"] <= 1.25 for entry in want.values())

    x = jax.ShapeDtypeStruct((4, 1024, 16, 64), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(), argnums=(0, 1, 2)),
        x, x, x)
    samples = registry().snapshot()[
        "horovod_flash_executed_pair_ratio"]["samples"]
    read = {s["labels"]["kernel"]: s["value"] for s in samples}
    assert {name: read[name] for name in want} == {
        name: pytest.approx(entry["pair_ratio"])
        for name, entry in want.items()}
    # a q_offset inside a tile leaves the diagonal's place in a tile
    # unknown: the masked tiles run whole, and the gauge says so
    offset = pa.causal_schedule(1024, 2048, 1000, *bwd, True)["flash_bwd_dq"]
    assert offset["trimmed"] == 0 < offset["diagonal"]
    assert offset["pair_ratio"] == pytest.approx(
        offset["tiles"] * 512 * 512 / (1024 * 1000 + 1024 * 1025 / 2))


@pytest.mark.parametrize("args,want", [
    # seq_q, seq_k, head_dim, dtype, block_q, block_k
    ((1024, 1024, 64, "bfloat16", None, None), ((1024, 1024), (512, 512))),
    ((8192, 8192, 128, "bfloat16", None, None), ((1024, 1024), (512, 512))),
    ((128, 128, 64, "float32", None, None), ((128, 128), (128, 128))),
    ((64, 2048, 64, "float32", None, None), ((64, 1024), (64, 512))),
    # a sequence the preferred tile does not divide: halved until it does
    ((1536, 1536, 64, "bfloat16", None, None), ((512, 512), (512, 512))),
    ((1280, 768, 64, "bfloat16", None, None), ((256, 768), (256, 256))),
    # the caller's bounds hold, right or wrong for the shape
    ((1024, 1024, 64, "bfloat16", 128, 256), ((128, 256), (128, 256))),
    ((64, 64, 16, "float32", 16, 512), ((16, 64), (16, 64))),
    # rows so wide that a preferred tile of them overruns the budget
    ((2048, 2048, 1024, "float32", None, None), ((512, 256), (512, 512))),
])
def test_tiles_follow_the_shape(args, want):
    from horovod_tpu.ops import pallas_attention as pa

    assert pa._tiles(*args) == want


def test_major_blocks_fit_the_budget():
    from horovod_tpu.ops import pallas_attention as pa

    bf16_rows = pa._operand_row_bytes(64, "bfloat16")
    assert bf16_rows == pa._operand_row_bytes(128, "bfloat16") == 1024
    assert pa._operand_row_bytes(64, "float32") == 2048
    budget = pa._RESIDENT_BYTES
    assert pa._major(1024, 512, bf16_rows) == 1024  # a head's whole K, V
    assert pa._major(4 * budget // bf16_rows, 512, bf16_rows) \
        == budget // bf16_rows
    assert pa._major(3 * 512, 512, budget // 1024) == 512  # 3 tiles: 1 or 3
    assert pa._major(2048, 512, budget) == 512  # never less than a tile
