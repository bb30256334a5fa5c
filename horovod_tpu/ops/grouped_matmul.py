"""Pallas grouped matrix products for the experts a chip holds, and the
sum by token of what they give.

``grouped_matmul(rows, weights, tile_group, active_tiles)`` multiplies each
tile of ``row_tile`` consecutive ``rows`` [m, k] by the matrix of
``weights`` [groups, k, n] that ``tile_group`` names for it. The caller
lays the rows out so that a tile belongs to one group (a group's rows
padded to whole tiles, ``models.experts.held_expert_sum``); only the first
``active_tiles`` tiles are computed, so the work follows the rows that were
routed here, not the buffer that could hold all of them. Rows of later
tiles come back unspecified (the caller masks them).

Design: the grid walks the row tiles; a group's whole matrix is one block,
so that consecutive tiles of a group fetch it once (the index map repeats
the block index, and Pallas skips the copy); ``tile_group`` and
``active_tiles`` are scalar-prefetched, and past the last active tile every
index map stays on that tile, so an idle grid step moves no data. Four
calls, named as a trace shows them: ``expert_matmul_fwd`` (rows x W),
``expert_matmul_bwd_dx`` (the rows' gradient, dY x W^T, the same kernel
with the matrix's other axis contracted) and ``expert_matmul_bwd_dw`` (the
weights' gradient, rows^T x dY summed over a group's tiles onto a float32
running sum that the call takes and returns in place: a caller who walks
the rows a slice at a time, ``grouped_matmul_transposed``, touches only the
matrices of the groups a slice holds). Float32 accumulation throughout.

The fourth, ``moe_rows_add``, is no product: it adds a slice's rows, each
to the row of a float32 sum that its token names — what
``sum.at[token].add(rows * scale, mode="drop")`` does, which XLA compiles
for the v5e to sixteen rows at a time, each batch read, added to and
written back before the next starts, because two updates may name one row
(0.24 us a row at 2,048 rows of 2048, eight times what their bytes take:
PERF.md section 6, PR 44). The layout knows better: a tile is one group's,
and a token is routed to a group at most once, so within a tile no token
occurs twice. The call walks the tiles in order and, for one tile, starts
every row's read from the sum, waits for all, adds the tile's rows in one
pass, starts every row's write, waits for all: two round trips a tile of
256 rows. The sum stays in HBM (``pl.ANY``) and is the call's output
(``input_output_aliases``); it is carried as ``[N, d / 128, 128]``, a row's
``d`` cut into pieces of 128, because Mosaic moves whole ``(8, 128)`` tiles
of HBM and one row of a tiled ``[N, d]`` is an eighth of each of its.
An empty slot (``token == N``) moves nothing, tiles past the active ones do
nothing, and a token's additions keep the scatter-add's order (tile after
tile, slot order), so the float32 result is the same to the bit.

``jax.lax.ragged_dot`` computes the same products, and the TPU compiler
turns it into kernels of its own — but under the name ``ragged-dot-none``
and with the program's scopes dropped from their metadata (a step of the
``laguna_xs2_8k_1chip`` cell read ``unscoped_pct`` 6.4 with it; my chip
run, PR 26), so a trace could not say whose time they are; JAX's own Pallas
grouped matmul (``pallas.ops.tpu.megablox``) declares no ``vma`` on its
outputs and cannot be traced inside the step's ``shard_map``.

``interpret=True`` (automatic on the CPU backend only) runs the kernels
through the Pallas interpreter, as ``pallas_attention`` does. Inside a
vma-tracking ``shard_map`` the interpreter cannot run them; there a
``jax.numpy`` stand-in is lowered for the CPU and the kernels for any other
platform, whichever backend is attached.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _sds
from .spmd import operand_vma, vary_like

ROW_TILE = 256
_MIB = 1024 * 1024
# the largest matrix of one group that is kept whole in VMEM; the kernels
# ask for room for its two pipeline buffers, a float32 accumulator of its
# size and the row tiles (``_vmem_limit``), within a v5e core's 128 MiB
_MATRIX_BYTES = 16 * _MIB
# the rows whose copies ``moe_rows_add`` issues, or waits for, in one trip
# of its loops: side by side their address arithmetic shares bundles (25
# bundles a row over a tile's four loops against 42 one at a time)
_ROWS_AT_ONCE = 8


def _last(i, active_ref):
    """Tile ``i``, or the last active one past it (0 when none is)."""
    return jnp.maximum(jnp.minimum(i, active_ref[0] - 1), 0)


def _rows_kernel(group_ref, active_ref, a_ref, w_ref, o_ref, *,
                 transpose: bool):
    """One row tile times its group's matrix (``transpose``: times the
    matrix transposed, for the rows' gradient)."""
    del group_ref

    @pl.when(pl.program_id(0) < active_ref[0])
    def _run():
        o_ref[...] = jax.lax.dot_general(
            a_ref[...], w_ref[0],
            (((1,), (1 if transpose else 0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _weights_kernel(group_ref, active_ref, a_ref, g_ref, sum_ref, o_ref):
    """``rows^T x dY`` of one tile, added to its group's block of the
    running sum: the grid is sequential, a group's tiles are consecutive,
    its block starts from ``sum_ref``'s (the same buffer) and is written
    back when the walk leaves it. A block no tile reaches is never
    touched; with no active tile at all the first one passes through."""
    i = pl.program_id(0)
    live = i < active_ref[0]

    @pl.when((i == 0) | (
        live & (group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])))
    def _start():
        o_ref[...] = sum_ref[...]

    @pl.when(live)
    def _add():
        o_ref[0] += jax.lax.dot_general(
            a_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _rows_add_kernel(token_ref, active_ref, rows_ref, *refs, scaled: bool):
    """One tile's rows added to the rows of the sum their tokens name: every
    row's read in flight at once, one add, every row's write in flight at
    once. No token occurs twice in a tile (the caller's layout); the grid
    is sequential, so a token of the next tile finds this one's sum."""
    scale_ref = refs[0] if scaled else None
    out_ref, held, sem = refs[-3:]  # ``refs[-4]``, the sum, is ``out_ref``
    tile = rows_ref.shape[0]
    tokens, pieces, lanes = out_ref.shape
    first = pl.program_id(0) * tile

    def every_row(move):
        """``move`` on the copy between a slot's row of the sum and its
        rows of ``held``; a slot past an expert's rows has no token (nor
        has one below 0: the copies' bounds are checked here alone)."""
        def some(r, _):
            for slot in range(_ROWS_AT_ONCE):
                slot += r * _ROWS_AT_ONCE
                token = token_ref[first + slot]

                @pl.when(token.astype(jnp.uint32) < tokens)
                def _held():
                    move(out_ref.at[token],
                         held.at[pl.ds(slot * pieces, pieces)])
        jax.lax.fori_loop(0, tile // _ROWS_AT_ONCE, some, None)

    def copy_every_row(back: bool):
        """Every slot's copy started, then every one waited for."""
        def copy(row, held_rows):
            return pltpu.make_async_copy(
                *((held_rows, row) if back else (row, held_rows)), sem)
        every_row(lambda *ends: copy(*ends).start())
        every_row(lambda *ends: copy(*ends).wait())

    @pl.when(pl.program_id(0) < active_ref[0])
    def _run():
        copy_every_row(back=False)
        for q in range(pieces):
            # piece ``q`` of every row: ``held`` holds a row's pieces on
            # consecutive sublanes, as the sum does
            piece = rows_ref[:, q * lanes:(q + 1) * lanes].astype(jnp.float32)
            if scaled:
                piece = piece * scale_ref[...]
            at = pl.ds(q, tile, stride=pieces)
            held[at, :] = held[at, :] + piece
        copy_every_row(back=True)


def _rows_add_call(sums, rows, token, scale, active, tile, interpret):
    """``sums`` [N, d / 128, 128] float32 plus the ``rows`` [size, d] (times
    ``scale`` [size, 1] where given) of the active tiles, each added to the
    row ``token`` names, in ``sums``' own buffer."""
    size, d = rows.shape
    tokens, pieces, lanes = sums.shape
    if size % tile or pieces * lanes != d or token.shape != (size,):
        raise ValueError(f"rows {rows.shape} of tokens {token.shape} do not "
                         f"add to {sums.shape} in tiles of {tile} rows")
    block = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), lambda i, token, active: (_last(i, active), 0))
    scaled = scale is not None
    return pl.pallas_call(
        functools.partial(_rows_add_kernel, scaled=scaled),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(size // tile,),
            in_specs=[block(d), *([block(1)] if scaled else []),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.VMEM((tile * pieces, lanes), jnp.float32),
                            pltpu.SemaphoreType.DMA(())]),
        out_shape=_sds(sums.shape, sums.dtype, sums, rows),
        input_output_aliases={3 + scaled: 0},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            disable_bounds_checks=True,
            vmem_limit_bytes=32 * _MIB),
        interpret=interpret,
        name="moe_rows_add",
    )(token, active, rows, *([scale] if scaled else []), sums)


def _check(rows, weights, contracted: int, tile: int):
    """The number of ``tile``-row tiles of ``rows`` [m, width], checked
    against axis ``contracted`` of ``weights`` [groups, k, n]."""
    m, width = rows.shape
    if weights.shape[contracted] != width or m % tile:
        raise ValueError(f"rows {rows.shape} do not fit weights "
                         f"{weights.shape} in tiles of {tile} rows")
    if weights[0].size * weights.dtype.itemsize > _MATRIX_BYTES:
        raise ValueError(
            f"a group's matrix {weights.shape[1:]} {weights.dtype} does not "
            f"stay resident: more than {_MATRIX_BYTES} bytes")
    return m // tile


def _compiler_params(interpret: bool, weights):
    # sequential: the idle steps past the last active tile revisit its
    # blocks, and the weights' gradient accumulates over a group's tiles.
    # VMEM: the matrix twice (pipeline buffers), in float32 twice coming
    # and twice going for the weights' gradient's running sum, and 16 MiB
    # for the row tiles and temporaries
    matrix = weights[0].size * max(weights.dtype.itemsize, 4)
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=16 * _MIB + 4 * matrix)


def _rows_call(rows, weights, tile_group, active, transpose, tile, interpret):
    tiles = _check(rows, weights, 2 if transpose else 1, tile)
    out_width = weights.shape[1 if transpose else 2]
    row_block = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), lambda i, group, active: (_last(i, active), 0))
    return pl.pallas_call(
        functools.partial(_rows_kernel, transpose=transpose),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[row_block(rows.shape[1]),
                      pl.BlockSpec((1, *weights.shape[1:]),
                                   lambda i, group, active: (
                                       group[_last(i, active)], 0, 0))],
            out_specs=row_block(out_width)),
        out_shape=_sds((rows.shape[0], out_width), rows.dtype, rows, weights),
        compiler_params=_compiler_params(interpret, weights),
        interpret=interpret,
        name="expert_matmul_bwd_dx" if transpose else "expert_matmul_fwd",
    )(tile_group, active, rows, weights)


def _weights_call(rows, grads, sums, tile_group, active, tile, interpret):
    """``sums`` [groups, k, n] float32 plus each group's ``rows^T x
    grads`` over its active tiles, in ``sums``' own buffer."""
    tiles = _check(rows, sums, 1, tile)
    row_block = lambda width: pl.BlockSpec(  # noqa: E731
        (tile, width), lambda i, group, active: (_last(i, active), 0))
    matrix = pl.BlockSpec(
        (1, *sums.shape[1:]),
        lambda i, group, active: (group[_last(i, active)], 0, 0))
    return pl.pallas_call(
        _weights_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tiles,),
            in_specs=[row_block(rows.shape[1]), row_block(grads.shape[1]),
                      matrix],
            out_specs=matrix),
        out_shape=_sds(sums.shape, sums.dtype, rows, grads, sums),
        input_output_aliases={4: 0},
        compiler_params=_compiler_params(interpret, sums),
        interpret=interpret,
        name="expert_matmul_bwd_dw",
    )(tile_group, active, rows, grads, sums)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _grouped(rows, weights, tile_group, active, tile, interpret):
    return _rows_call(rows, weights, tile_group, active, False, tile,
                      interpret)


def _grouped_fwd(rows, weights, tile_group, active, tile, interpret):
    return (_rows_call(rows, weights, tile_group, active, False, tile,
                       interpret), (rows, weights, tile_group, active))


def _grouped_bwd(tile, interpret, res, g):
    # the models write their backward pass themselves
    # (``grouped_matmul_transposed``); this rule stays for callers that
    # differentiate ``grouped_matmul``: tests/chipbench's grouped-product
    # test and the tests here, so a benchmark PR can retire it
    rows, weights, tile_group, active = res
    zeros = vary_like(rows, jnp.zeros(weights.shape, jnp.float32))[0]
    return (_rows_call(g, weights, tile_group, active, True, tile, interpret),
            _weights_call(rows, g, zeros, tile_group, active, tile,
                          interpret).astype(weights.dtype),
            None, None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def _operands(like, tile_group, active_tiles, *arrays):
    """``arrays`` and the two scalar-prefetched operands, typed as ``like``
    is: inside a vma-tracking shard_map the rows vary over the data axis
    and replicated weights do not."""
    return vary_like(like, *arrays, tile_group.astype(jnp.int32),
                     jnp.reshape(active_tiles, (1,)).astype(jnp.int32))


def _on_platform(interpret, stand_in, kernels, *operands):
    """``kernels(interpret, *operands)``, or where those cannot be
    interpreted — the Pallas interpreter refuses to index a
    scalar-prefetched operand that varies over a mesh axis (a vma-tracking
    ``shard_map`` lowered for the CPU) — ``stand_in``, the same in
    ``jax.numpy``, for a program lowered for the CPU alone: one lowered for
    a TPU from a CPU box (``chipbench.aot``) gets the kernels the chip
    will run."""
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if interpret and operand_vma(operands[0]):
        return jax.lax.platform_dependent(
            *operands, cpu=stand_in,
            default=functools.partial(kernels, False))
    return kernels(interpret, *operands)


# jitted, as ``flash_attention`` is: behind the boundary the calls keep
# their names whatever transformation traces them (without it a trace shows
# ``transpose_jvp_expert_matmul_bwd_dx__``)
@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def grouped_matmul(rows: jax.Array, weights: jax.Array,
                   tile_group: jax.Array, active_tiles: jax.Array,
                   row_tile: int = ROW_TILE,
                   interpret: Optional[bool] = None) -> jax.Array:
    """``[m, n]`` in ``rows``' dtype: tile ``t`` of ``rows`` [m, k]
    (``row_tile`` rows) times ``weights[tile_group[t]]`` [k, n] for
    ``t < active_tiles``; later rows unspecified. Differentiable in
    ``rows`` (whose gradient is unspecified in the same rows) and
    ``weights``; what the caller puts into inactive tiles is never read."""
    return _on_platform(
        interpret, functools.partial(_tile_by_tile, tile=row_tile),
        lambda interpret, *a: _grouped(*a, row_tile, interpret),
        rows, *_operands(rows, tile_group, active_tiles, weights))


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def grouped_matmul_transposed(rows, grads, weights, sums, tile_group,
                              active_tiles, row_tile: int = ROW_TILE,
                              interpret: Optional[bool] = None):
    """``grouped_matmul``'s transpose at ``grads`` [m, n], for a caller who
    writes its own backward pass: the rows' gradient [m, k] (unspecified
    past the active tiles) and ``sums`` [groups, k, n] float32 plus the
    weights' gradient — in ``sums``' buffer, of which only the matrices of
    groups with an active tile are read or written."""
    def kernels(interpret, grads, rows, sums, weights, tile_group, active):
        return (_rows_call(grads, weights, tile_group, active, True,
                           row_tile, interpret),
                _weights_call(rows, grads, sums, tile_group, active,
                              row_tile, interpret))

    def stand_in(grads, rows, sums, weights, tile_group, active):
        tiled = lambda a: a.reshape(tile_group.size, row_tile, -1)  # noqa
        live = (jnp.arange(tile_group.size) < active[0])[:, None, None]
        per_tile = jnp.einsum("tmk,tmn->tkn", tiled(rows), tiled(grads),
                              preferred_element_type=jnp.float32)
        return (_tile_by_tile(grads, jnp.swapaxes(weights, 1, 2), tile_group,
                              active, row_tile),
                sums.at[tile_group].add(jnp.where(live, per_tile, 0)))

    return _on_platform(
        interpret, stand_in, kernels, grads,
        *_operands(grads, tile_group, active_tiles, rows, sums, weights))


@functools.partial(jax.jit, static_argnames=("row_tile", "interpret"))
def moe_rows_add(sums, rows, token, scale, active_tiles,
                 row_tile: int = ROW_TILE, interpret: Optional[bool] = None):
    """``sums`` [N, d / 128, 128] float32 — ``[N, d]`` with a row's ``d``
    cut into pieces of 128, so that a row is whole tiles of the chip's
    memory and moves in one transfer — plus, for every slot ``s`` of the
    first ``active_tiles`` tiles (``row_tile`` slots each) with ``0 <=
    token[s] < N``, ``rows[s]`` [d] (times ``scale[s]`` [size, 1] unless
    that is ``None``) in float32, added to row ``token[s]`` in slot order: what
    ``sums.at[token].add(rows * scale, mode="drop")`` gives, bit for bit,
    in ``sums``' buffer. The caller guarantees that no token occurs twice
    within a tile; rows of other slots are never read."""
    scaled = scale is not None

    def kernels(interpret, rows, sums, token, active, *scale):
        return _rows_add_call(sums, rows, token, *(scale or [None]), active,
                              row_tile, interpret)

    def stand_in(rows, sums, token, active, *scale):
        live = jnp.arange(token.size) < active[0] * row_tile
        rows = rows.astype(jnp.float32)
        if scaled:
            rows = rows * scale[0]
        return sums.reshape(sums.shape[0], -1).at[
            jnp.where(live, token, sums.shape[0])].add(
                rows, mode="drop").reshape(sums.shape)

    return _on_platform(
        interpret, stand_in, kernels, rows,
        *_operands(rows, token, active_tiles, sums),
        *(vary_like(rows, scale) if scaled else ()))


def _tile_by_tile(rows, weights, tile_group, active, tile: int):
    """``grouped_matmul`` in ``jax.numpy`` (``_on_platform``)."""
    tiles = rows.shape[0] // tile
    out = jnp.einsum("tmk,tkn->tmn", rows.reshape(tiles, tile, -1),
                     weights[tile_group],
                     preferred_element_type=jnp.float32)
    live = (jnp.arange(tiles) < active[0])[:, None, None]
    return jnp.where(live, out, 0).astype(rows.dtype).reshape(
        rows.shape[0], -1)
