"""``flash_attention`` with grouped key/value heads and a window: forward
and the three gradients against dense attention wherever the window's
loop bounds, the group's sum and the major-block grid take another
branch; the schedule pair by pair; and the call the ``gpt2m_*`` cells make,
which must trace to the program it traced to before either existed."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import flash_attention


def dense(q, k, v, q_offset=0, window=None):
    """Causal attention written out: q head ``h`` reads K/V head
    ``h // group``; position ``t`` sees ``t - window < s <= t``."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) / np.sqrt(q.shape[-1])
    q_pos = q_offset + jnp.arange(q.shape[1])[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights,
                      v.astype(jnp.float32)).astype(q.dtype)


def forward_and_gradients(attention, q, k, v, cot):
    out, vjp = jax.vjp(attention, q, k, v)
    return (out, *vjp(cot))


def operands(seed, seq_q, seq_k, heads, kv_heads, head_dim, dtype, batch=2):
    rng = np.random.default_rng(seed)
    q, cot = (jnp.asarray(rng.standard_normal(
        (batch, seq_q, heads, head_dim)), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.standard_normal(
        (batch, seq_k, kv_heads, head_dim)), dtype) for _ in range(2))
    return q, k, v, cot


def assert_close(got, want, tol):
    for g, w, name in zip(got, want, ("out", "dq", "dk", "dv")):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(w, np.float32),
            rtol=tol, atol=tol, err_msg=name)


def assert_flash_equals_dense(batch, seq_q, seq_k, q_offset, block, window,
                              heads, kv_heads, head_dim, dtype):
    """Forward and the three gradients against dense attention, dK and dV
    in K's and V's own shapes (summed over each group)."""
    q, k, v, cot = operands(seq_q + 7 * seq_k + 13 * q_offset + heads,
                            seq_q, seq_k, heads, kv_heads, head_dim, dtype,
                            batch)
    got = forward_and_gradients(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=block, block_k=block,
            q_offset=q_offset, window=window), q, k, v, cot)
    want = forward_and_gradients(
        lambda q, k, v: dense(q, k, v, q_offset, window), q, k, v, cot)
    assert got[2].shape == k.shape and got[3].shape == v.shape
    assert_close(got, want, 5e-4 if dtype == "float32" else 3e-2)


_CASES = [
    # id, seq_q, seq_k, q_offset, block, window, heads, kv_heads, head_dim,
    # dtype, resident
    ("window_one_tile", 64, 64, 0, 16, 16, 2, 2, 64, "float32", None),
    ("window_inside_a_tile", 64, 64, 0, 16, 5, 2, 2, 64, "float32", None),
    ("window_over_tiles", 64, 64, 0, 16, 40, 2, 2, 64, "float32", None),
    ("window_of_one", 48, 48, 0, 16, 1, 2, 2, 64, "float32", None),
    ("window_past_the_sequence", 48, 48, 0, 16, 200, 2, 2, 64, "float32",
     None),
    ("window_tile_wider_than_it", 64, 64, 0, 32, 8, 2, 2, 64, "float32",
     None),
    ("groups_of_two", 48, 48, 0, 16, None, 4, 2, 64, "float32", None),
    ("groups_of_three_d128", 48, 48, 0, 16, None, 6, 2, 128, "float32",
     None),
    ("one_kv_head", 32, 32, 0, 16, None, 4, 1, 64, "float32", None),
    ("window_groups_d64", 64, 64, 0, 16, 24, 4, 2, 64, "float32", None),
    ("window_groups_d128", 64, 64, 0, 16, 24, 8, 2, 128, "float32", None),
    ("window_groups_offset", 32, 64, 32, 16, 24, 4, 2, 64, "float32", None),
    ("window_groups_offset_inside_a_tile", 32, 64, 24, 16, 20, 4, 2, 128,
     "float32", None),
    ("groups_offset", 32, 64, 24, 16, None, 4, 2, 64, "float32", None),
    ("window_groups_bf16", 64, 64, 0, 16, 24, 4, 2, 128, "bfloat16", None),
    # 4 * 128 lanes * 4 bytes * 16 rows = one tile's worth of residents
    ("window_one_tile_a_major", 96, 96, 0, 16, 24, 2, 2, 64, "float32", 1),
    ("window_two_tiles_a_major", 128, 128, 0, 16, 40, 4, 2, 64, "float32",
     65536),
    ("window_majors_offset", 48, 96, 48, 16, 24, 4, 2, 64, "float32", 1),
    ("groups_one_tile_a_major", 80, 80, 0, 16, None, 4, 2, 64, "float32", 1),
    ("window_default_tiles", 256, 256, 0, None, 128, 4, 2, 64, "bfloat16",
     None),
]


@pytest.mark.parametrize(
    "seq_q,seq_k,q_offset,block,window,heads,kv_heads,head_dim,dtype,"
    "resident", [case[1:] for case in _CASES],
    ids=[case[0] for case in _CASES])
def test_window_and_groups_against_dense(monkeypatch, seq_q, seq_k, q_offset,
                                         block, window, heads, kv_heads,
                                         head_dim, dtype, resident):
    if resident is not None:
        # read at trace time: these cases' shapes are no other test's
        monkeypatch.setattr(pa, "_RESIDENT_BYTES", resident)
        assert seq_k // pa._major(
            seq_k, block, pa._operand_row_bytes(head_dim, dtype)) > 1
    assert_flash_equals_dense(2, seq_q, seq_k, q_offset, block, window,
                              heads, kv_heads, head_dim, dtype)


# id, seq_q, seq_k, q_offset, block, window, heads, kv_heads, head_dim,
# dtype, resident, which masked tiles run as strips: tiles of whole lanes.
# The window's edge tile is cut when the window is a multiple of the tile
# (then one tile holds the edge), the diagonal tile when the window is no
# narrower than the tile (else it holds the edge too)
_STRIP_CASES = [
    ("window_equals_tile", 1024, 1024, 0, 256, 256, 4, 2, 64, "float32",
     None, "both"),
    ("window_of_two_tiles_d128", 1024, 1024, 0, 256, 512, 4, 2, 128,
     "float32", None, "both"),
    ("the_cells_tiles_and_window", 2048, 2048, 0, None, 512, 4, 1, 128,
     "bfloat16", None, "both"),
    ("window_groups_of_four_d64", 768, 768, 0, 256, 256, 8, 2, 64,
     "bfloat16", None, "both"),
    # one q tile: a diagonal tile and no edge tile
    ("window_first_q_tile_alone", 256, 256, 0, 256, 256, 4, 2, 64,
     "float32", None, "both"),
    ("window_offset_of_two_tiles", 512, 1024, 512, 256, 256, 4, 2, 64,
     "float32", None, "both"),
    ("window_one_tile_a_major", 1024, 1024, 0, 256, 256, 4, 2, 64,
     "float32", 1, "both"),
    ("window_two_tiles_a_major", 1024, 1024, 0, 256, 512, 2, 1, 128,
     "float32", 2 * 256 * 3072, "both"),
    ("window_of_three_strips", 1024, 1024, 0, 256, 384, 4, 2, 64, "float32",
     None, "diagonal"),
    ("window_no_multiple_of_the_strip", 1024, 1024, 0, 256, 300, 4, 2, 64,
     "float32", None, "diagonal"),
    ("window_narrower_than_the_tile", 512, 512, 0, 256, 128, 4, 2, 64,
     "float32", None, "none"),
    ("window_offset_inside_a_tile", 512, 1024, 384, 256, 256, 4, 2, 64,
     "float32", None, "none"),
]


@pytest.mark.parametrize(
    "seq_q,seq_k,q_offset,block,window,heads,kv_heads,head_dim,dtype,"
    "resident,strips", [case[1:] for case in _STRIP_CASES],
    ids=[case[0] for case in _STRIP_CASES])
def test_window_strips_against_dense(monkeypatch, seq_q, seq_k, q_offset,
                                     block, window, heads, kv_heads,
                                     head_dim, dtype, resident, strips):
    """Forward and the three gradients against dense attention, dK and dV
    summed over the group, where the window's edge tile and the diagonal
    tile run as static strips, and at the neighbouring shapes that keep one
    or both whole; ``causal_schedule``'s ``trimmed`` says which."""
    fwd, bwd = pa._tiles(seq_q, seq_k, head_dim, dtype, block, block, window)
    assert fwd == bwd and fwd[0] == fwd[1]
    if resident is not None:
        monkeypatch.setattr(pa, "_RESIDENT_BYTES", resident)
        assert seq_k // pa._major(
            seq_k, bwd[1], pa._operand_row_bytes(head_dim, dtype)) > 1
    schedule = pa.causal_schedule(seq_q, seq_k, q_offset, *fwd, True, window)
    shared = dict(q_offset=q_offset, tile_q=fwd[0], tile_k=fwd[1],
                  causal=True, window=window)
    diagonal = sum(end - b for _, _, b, end in (
        pa._k_walk(i, num_k_tiles=seq_k // fwd[1], **shared)
        for i in range(seq_q // fwd[0])))
    for kernel, entry in schedule.items():
        assert 0 < entry["diagonal"] >= diagonal
        assert entry["trimmed"] == {"both": entry["diagonal"],
                                    "diagonal": diagonal, "none": 0}[strips]
    assert_flash_equals_dense(1, seq_q, seq_k, q_offset, block, window,
                              heads, kv_heads, head_dim, dtype)


def test_grouped_heads_equal_repeated_heads():
    """A grouped call computes, head by head, what the same call on K and
    V repeated to every query head computes: out and dQ to the bit, dK and
    dV as the sum over the group."""
    q, k, v, cot = operands(3, 64, 64, 6, 2, 64, "float32")
    grouped = forward_and_gradients(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=24,
                                        block_q=16, block_k=16),
        q, k, v, cot)
    k_all, v_all = (jnp.repeat(x, 3, axis=2) for x in (k, v))
    repeated = forward_and_gradients(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=24,
                                        block_q=16, block_k=16),
        q, k_all, v_all, cot)
    np.testing.assert_array_equal(grouped[0], repeated[0])
    np.testing.assert_array_equal(grouped[1], repeated[1])
    for got, every in zip(grouped[2:], repeated[2:]):
        summed = every.reshape(2, 64, 2, 3, 64).sum(axis=3)
        np.testing.assert_allclose(got, summed, rtol=1e-5, atol=1e-5)


_SCHEDULE_CASES = [
    # seq_q, seq_k, q_offset, tile_q, tile_k, window
    (32, 32, 0, 8, 8, 8), (32, 32, 0, 8, 8, 3), (32, 32, 0, 8, 8, 20),
    (32, 32, 0, 4, 8, 8), (32, 32, 0, 8, 4, 8), (32, 32, 0, 16, 16, 4),
    (16, 32, 16, 8, 8, 8), (16, 32, 5, 8, 8, 12), (16, 32, 16, 8, 8, 100),
    (32, 32, 0, 8, 8, 1),
]


@pytest.mark.parametrize("seq_q,seq_k,q_offset,tile_q,tile_k,window",
                         _SCHEDULE_CASES)
def test_window_schedule_against_brute_force(seq_q, seq_k, q_offset, tile_q,
                                             tile_k, window):
    """Pair by pair: the executed tiles cover every visible pair, no tile
    that lies wholly behind the window or in the future is executed, a
    tile run without the mask holds no masked pair — for the k walk of
    forward and dQ and for the q walk of dK/dV, which must come to the same
    tiles."""
    q_pos = q_offset + np.arange(seq_q)[:, None]
    k_pos = np.arange(seq_k)[None, :]
    kept = (q_pos >= k_pos) & (q_pos - k_pos < window)
    nq, nk = seq_q // tile_q, seq_k // tile_k
    shared = dict(q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
                  causal=True, window=window)
    by_k_walk, by_q_walk = {}, {}  # (q tile, k tile) -> masked body?
    for i in range(nq):
        lo, a, b, end = pa._k_walk(i, num_k_tiles=nk, **shared)
        assert 0 <= lo <= a <= b <= end <= nk
        by_k_walk.update({(i, j): not a <= j < b for j in range(lo, end)})
    for j in range(nk):
        start, a, b, stop = pa._q_walk(j, num_q_tiles=nq, **shared)
        by_q_walk.update({(i, j): not a <= i < b
                          for i in range(start, stop)})
    assert by_k_walk == by_q_walk
    for i in range(nq):
        for j in range(nk):
            tile = kept[i * tile_q:(i + 1) * tile_q,
                        j * tile_k:(j + 1) * tile_k]
            if (i, j) not in by_k_walk:
                assert not tile.any(), (i, j)
            else:
                assert tile.any(), (i, j)
                assert by_k_walk[i, j] == (not tile.all()), (i, j)
    schedule = pa.causal_schedule(seq_q, seq_k, q_offset, tile_q, tile_k,
                                  True, window)
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert schedule[name]["tiles"] == len(by_k_walk)
        assert schedule[name]["diagonal"] == sum(by_k_walk.values())
        assert schedule[name]["pair_ratio"] == pytest.approx(
            len(by_k_walk) * tile_q * tile_k / kept.sum())


_STRIP_SCHEDULE_CASES = [
    # seq_q, seq_k, q_offset, tile, window
    (2048, 2048, 0, 512, 512), (2048, 2048, 0, 256, 512),
    (1536, 1536, 0, 256, 768), (1024, 2048, 1024, 512, 512),
    (1024, 1024, 0, 1024, 1024), (1536, 1536, 0, 384, 384),
    # the edge on two tiles, or on the diagonal tile, or an offset diagonal
    (1024, 1024, 0, 256, 384), (1024, 1024, 0, 256, 300),
    (1024, 1024, 0, 256, 128), (1024, 1024, 0, 256, 1),
    (512, 1024, 384, 256, 256), (512, 512, 0, 128, 128),
]


@pytest.mark.parametrize("seq_q,seq_k,q_offset,tile,window",
                         _STRIP_SCHEDULE_CASES)
def test_window_strip_schedule_against_brute_force(seq_q, seq_k, q_offset,
                                                   tile, window):
    """Under a window too ``causal_schedule``'s pairs and ``trimmed`` equal
    an enumeration of the ``strip x strip`` blocks that hold a visible
    pair in the tiles that run as strips, and whole tiles elsewhere."""
    from test_pallas_attention import executed_pairs_by_enumeration

    q_pos = q_offset + np.arange(seq_q)[:, None]
    k_pos = np.arange(seq_k)[None, :]
    kept = (q_pos >= k_pos) & (q_pos - k_pos < window)
    schedule = pa.causal_schedule(seq_q, seq_k, q_offset, tile, tile, True,
                                  window)
    for kernel, entry in schedule.items():
        strips = pa._strips(q_offset=q_offset, tile_q=tile, tile_k=tile,
                            causal=True, window=window)
        pairs, trimmed = executed_pairs_by_enumeration(
            kept, tile, tile, strips)
        assert entry["trimmed"] == trimmed, kernel
        assert entry["pair_ratio"] == pytest.approx(pairs / kept.sum())
        if q_offset % tile or tile < 256 or window < tile:
            assert strips == (None, None) and trimmed == 0
        elif window % tile:
            assert strips[1] is None and 0 < trimmed < entry["diagonal"]
        else:
            assert trimmed == entry["diagonal"] > 0


def test_the_window_kernels_at_8k_their_names_and_their_gauge():
    """Laguna's sliding call — T = 8192, head_dim 128, 64 query heads on 8
    K/V heads, window 512: tiles no larger than the window, a q tile's
    grid steps counted from its window's first block, fewer executed pairs
    than the plain causal call makes, and the three calls under their own
    names with their own gauge samples."""
    from horovod_tpu.obs import registry

    fwd, bwd = pa._tiles(8192, 8192, 128, "bfloat16", None, None, 512)
    assert (fwd, bwd) == ((512, 512), (512, 512))
    assert pa._tiles(8192, 8192, 128, "bfloat16", None, None) \
        == ((1024, 1024), (512, 512))
    assert pa._tiles(1024, 1024, 64, "bfloat16", None, None, 64) \
        == ((128, 128), (128, 128))
    window = pa.causal_schedule(8192, 8192, 0, 512, 512, True, 512)
    plain = pa.causal_schedule(8192, 8192, 0, 1024, 1024, True)
    assert window["flash_fwd"]["tiles"] == 31
    assert window["flash_fwd"]["tiles"] * 512 * 512 \
        < plain["flash_fwd"]["tiles"] * 1024 * 1024 / 4
    # the forward's major block is 1024 rows here: two grid steps a q tile
    rows = pa._operand_row_bytes(128, "bfloat16")
    major = pa._major(8192, 512, rows + 4 * 512)
    assert major < 8192
    assert pa._grid_majors(8192 // major, major, 512, 512) \
        == (512 + 511 + major - 2) // major + 1 < 8192 // major
    assert pa._grid_majors(8, 1024, 512, None) == 8

    q = jax.ShapeDtypeStruct((1, 8192, 64, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 8, 128), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=512,
            interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))(q, kv, kv))
    for name in ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv"):
        assert f"name={name}" in text, name
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"name={name}" not in text, name
    samples = registry().snapshot()[
        "horovod_flash_executed_pair_ratio"]["samples"]
    read = {s["labels"]["kernel"]: s["value"] for s in samples}
    assert read["flash_win_fwd"] == pytest.approx(
        window["flash_fwd"]["pair_ratio"])
    assert read["flash_win_bwd_dkv"] == pytest.approx(
        window["flash_bwd_dkv"]["pair_ratio"])
    # every executed tile is an edge or a diagonal tile, and runs as strips
    # of 128 rows: 10 of a tile's 16 blocks (2.0 x the needed pairs whole)
    for entry in window.values():
        assert entry["trimmed"] == entry["diagonal"] == entry["tiles"] == 31
    assert 1.24 < read["flash_win_bwd_dq"] < 1.25
    # the dK/dV kernel fetches a query head's rows anew every step: no more
    # of them than a k tile's window reaches
    assert pa._major(8192, 512, rows, reach=512 + 512 - 1) == 1024
    assert pa._major(8192, 512, rows) == 8192


# sha256 of ``str(jax.make_jaxpr(...))`` of the gpt2m cells' call with the
# addresses taken out, as PR 29 traces it (its masked tiles as strips; PR
# 25's program until then): the program text, kernels' bodies, grids and
# index maps included. Since PR 38 the forward rule names its ``o`` and
# ``lse`` (``KEPT_NAMES``): two ``name`` identities and the variables renamed
# after them are all that differs from PR 29's text
# ("26514842...7d2b"); they lower to nothing, and the cells' compiled step
# text (``tools/step_hlo.py``) is byte-equal to what it was
_GPT2M_CALL = "3040938cc4800a8cc2942c83f1fd8257d98e591e187dd7e45d48c69c701e8dfd"


def test_the_gpt2m_call_traces_to_the_program_it_was():
    """``(4, 1024, 16, 64)`` causal bfloat16, no window, equal heads:
    forward and backward trace to the very program the benchmark's cells
    were last measured with, so a change that is not meant to move them
    leaves their results that program's bit for bit."""
    x = jax.ShapeDtypeStruct((4, 1024, 16, 64), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, interpret=False).astype(
                jnp.float32).sum(), argnums=(0, 1, 2)))(x, x, x))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest() == _GPT2M_CALL


@pytest.mark.parametrize("kwargs,shapes,message", [
    (dict(causal=False, window=8), ((1, 16, 2, 16), (1, 16, 2, 16)),
     "window needs causal"),
    (dict(causal=True, window=0), ((1, 16, 2, 16), (1, 16, 2, 16)),
     "window needs causal"),
    (dict(causal=True, window=8, q_offset=16),
     ((1, 16, 2, 16), (1, 16, 2, 16)), "own position"),
    (dict(causal=True), ((1, 16, 3, 16), (1, 16, 2, 16)),
     "must divide"),
])
def test_calls_the_kernels_cannot_serve_are_refused(kwargs, shapes, message):
    q = jnp.zeros(shapes[0], jnp.float32)
    k = jnp.zeros(shapes[1], jnp.float32)
    with pytest.raises(ValueError, match=message):
        flash_attention(q, k, k, **kwargs)
