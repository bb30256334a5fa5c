"""The third call family of ``ops.pallas_attention``: ``flash_bd_fwd``,
``flash_bd_bwd_dq`` and ``flash_bd_bwd_dkv`` under block diffusion's
three-part mask (``flash_attention(..., block_diffusion=B)``), interpreted
on the CPU, against dense attention under the mask built from its
definition — values and dq, dk, dv, grouped heads, block 0's noisy rows (no
clean key) among them — over the branches of the schedule: a tile in
128-row strips, several tiles and several major blocks a half (a tile that
runs whole is the toy model's, ``tests/chipbench/test_chipbench_sdar.py``);
and ``causal_schedule``'s count for the mask against a brute-force
count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models.parts import block_diffusion_mask, dense_attention
from horovod_tpu.ops import pallas_attention as pa

# id: (L, block, heads, kv heads, head_dim, batch, tile bound, resident bytes)
_CASES = {
    "one_tile_in_strips": (256, 8, 4, 2, 32, 2, None, None),
    "tiles_and_major_blocks": (512, 16, 2, 1, 32, 1, 128, 128 * 2 * 2 * 128 * 4),
}


def test_the_mask_from_its_definition():
    """A noisy row sees its own block among the noisy rows and the clean
    rows of the blocks before; a clean row the clean rows up to its block's
    end; ``L (L + B)`` pairs in all, and no row empty."""
    seq, block = 12, 4
    mask = np.asarray(block_diffusion_mask(seq, block))
    for q in range(2 * seq):
        for k in range(2 * seq):
            qb, kb = q % seq // block, k % seq // block
            if q >= seq:
                want = kb == qb if k >= seq else kb < qb
            else:
                want = k < seq and kb <= qb
            assert mask[q, k] == want, (q, k)
    assert mask.sum() == seq * (seq + block)
    assert mask.any(axis=1).all()
    assert not mask[seq:seq + block, :seq].any()    # block 0: no clean key


@pytest.mark.parametrize("case", _CASES)
def test_kernels_against_dense_attention(monkeypatch, case):
    seq, block, heads, kv_heads, dim, batch, bound, resident = _CASES[case]
    if resident:
        monkeypatch.setattr(pa, "_RESIDENT_BYTES", resident)
    keys = jax.random.split(jax.random.PRNGKey(seq), 4)
    q, do = (jax.random.normal(k, (batch, 2 * seq, heads, dim))
             for k in keys[:2])
    k, v = (jax.random.normal(k, (batch, 2 * seq, kv_heads, dim))
            for k in keys[2:])

    def flash(q, k, v):
        return pa.flash_attention(q, k, v, causal=True, block_q=bound,
                                  block_k=bound, block_diffusion=block)

    with jax.default_matmul_precision("highest"):
        want, pull = jax.vjp(
            lambda q, k, v: dense_attention(
                q, k, v, block_diffusion=block), q, k, v)
        want = (want, *pull(do))
    got, pull = jax.vjp(flash, q, k, v)
    got = (got, *pull(do))
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5 * float(
            jnp.abs(b).max()), err_msg=name)
    # block 0's noisy rows see their own block alone: exact, and not zero
    first = slice(seq, seq + block)
    assert float(jnp.abs(want[0][:, first]).max()) > 0.1
    np.testing.assert_allclose(got[0][:, first], want[0][:, first],
                               rtol=2e-5, atol=2e-6)
    tiles = pa._tiles(seq, seq, dim, q.dtype, bound, bound)
    if case == "one_tile_in_strips":
        assert pa._strips(q_offset=0, tile_q=tiles[0][0], tile_k=tiles[0][1],
                          causal=True, window=None) == (128, None)
    if case == "tiles_and_major_blocks":
        rows = pa._operand_row_bytes(dim, q.dtype)
        assert tiles[1] == (128, 128)
        assert pa._major(seq, 128, rows) < seq
    snapshot = pa._metrics().snapshot()[
        "horovod_flash_executed_pair_ratio"]["samples"]
    ratios = {s["labels"]["kernel"]: s["value"] for s in snapshot}
    for name in ("flash_bd_fwd", "flash_bd_bwd_dq", "flash_bd_bwd_dkv"):
        assert ratios[name] >= 1.0


@pytest.mark.parametrize("seq, block, tile", [
    (8192, 4, 1024), (8192, 4, 512), (512, 16, 256), (256, 8, 64)])
def test_schedule_count_against_a_brute_force_count(seq, block, tile):
    """Needed pairs against the mask's own sum; executed pairs against a
    count of every square the walks and the rows' own blocks touch, strip
    by strip."""
    got = pa.causal_schedule(2 * seq, 2 * seq, 0, tile, tile, True,
                             block_diffusion=block)
    needed = seq * (seq + block)
    if seq <= 512:
        assert int(block_diffusion_mask(seq, block).sum()) == needed
    strip = 128 if tile > 128 else tile
    tiles = seq // tile
    executed = 0
    for half in range(2):
        for i in range(tiles):
            executed += i * tile * tile                 # the clear tiles
            executed += sum((r + strip) * strip         # the diagonal tile
                            for r in range(0, tile, strip))
            executed += tile * strip                    # the rows' own
    assert executed == seq * (seq + 3 * strip)
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert got[kernel]["pair_ratio"] == pytest.approx(executed / needed)
        assert got[kernel]["tiles"] == tiles * (tiles + 1) + 2 * tiles
        assert got[kernel]["trimmed"] == (2 * tiles if tile > 128 else 0)
    if seq == 8192:     # the sdar_moe_8k_1chip cell's calls
        assert got["flash_fwd"]["pair_ratio"] < 1.05


def test_what_block_diffusion_refuses():
    q = jnp.zeros((1, 128, 2, 32))
    for bad in (dict(causal=False), dict(window=16), dict(q_offset=64),
                dict(block_diffusion=3), dict(block_diffusion=256)):
        args = dict(dict(causal=True, block_diffusion=4), **bad)
        with pytest.raises(ValueError, match="block_diffusion needs"):
            pa.flash_attention(q, q, q, **args)
    with pytest.raises(ValueError, match="block_diffusion needs"):
        pa.flash_attention(q, jnp.zeros((1, 256, 2, 32)),
                           jnp.zeros((1, 256, 2, 32)), causal=True,
                           block_diffusion=4)
    with pytest.raises(ValueError, match="square tiles"):
        pa.flash_attention(q, q, q, causal=True, block_diffusion=4,
                           block_q=32)
