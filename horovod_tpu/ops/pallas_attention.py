"""Pallas flash-attention kernels (forward + backward) for TPU.

The reference contains no kernels at all — device math is delegated to
NCCL/MPI (SURVEY §2: "no CUDA kernels"). On TPU the hot op worth a custom
kernel in this framework's domain is attention (the long-context extension,
``parallel.ring_attention``): a fused blockwise softmax(QK^T)V that never
materializes the [T, T] score matrix in HBM and streams K/V through VMEM
one block at a time.

Design (per pallas_guide.md): the grid is (batch*heads, tiles of the
kernel's own operand, *major blocks* of the streamed operand), the last
axis sequential ("arbitrary" semantics) with the accumulators in VMEM
scratch across it. A major block is as much of the streamed operand (K and
V for the forward and dQ; Q, dO and the row statistics for dK/dV) as fits a
VMEM budget computed from ``(seq, head_dim, dtype)`` — the whole sequence
up to 8192 bf16 rows — so the footprint stays independent of sequence
length and 16k+ contexts fit. Inside, a ``lax.fori_loop`` walks compute
tiles of the resident block (``pl.ds`` slices). Matmuls hit the MXU with
f32 accumulation; masking and rescaling ride the VPU.

The causal schedule: the loop's trip count comes from the program's own
tile index and ``q_offset`` (``k_tile_bounds`` / ``q_tile_bounds``), so it
ends at the diagonal tile (forward, dQ) or starts at it (dK/dV): no grid
step, DMA or branch is spent on a tile wholly in the future, and the index
map of the streamed operand stays on the last block a tile needs, so a
skipped major block is not fetched. The loop is split: interior tiles hold
no masked pair and run with no mask; only the tiles that straddle the
diagonal run ``_causal_mask``. Tiles are chosen from the shapes by what the
v5e measured (``_tiles``; PERF.md, PR 25): the per-step and per-row costs
outweigh wasted pairs up to 512 rows (backward) and 1024 (forward).

Static strips: a masked tile that was multiplied whole threw half of its
products away. Where the boundary's place inside the tile is known at
trace time — square tiles, ``q_offset`` a multiple of them, and for the
window's edge a window that is a multiple of them too (``_strips``) — the
tile's products run as strips of 128 rows of the kernel's own operand, each
against only the columns it can see (``_pieces``), with ``_causal_mask`` on
the one 128 x 128 block a strip has on the boundary: no tile, grid step,
loop step or statistics pass more, the same products on every visible pair.
Anywhere else the masked tile runs whole. ``causal_schedule`` says what
either comes to — tiles, masked tiles, how many of those ran as strips,
executed over needed pairs — and the gauge
``horovod_flash_executed_pair_ratio{kernel}`` holds it for the newest
trace (docs/metrics.md).

Grouped heads and a window. Query head ``h`` of ``Hq`` reads K/V head
``h // (Hq // Hkv)``: the K/V index map divides the grid's head index by
the group's size, so K and V are never repeated in HBM, and the dK/dV grid
runs over K/V heads with the group's query heads on its sequential axis, so
that dK and dV are summed over the group in the accumulators. Under a
``window`` the loops also *start* at the window's edge (``k_window_bounds``
/ ``q_window_bounds``), the sequential grid axis counts only the major
blocks a tile's window can reach, starting from its first
(``_grid_majors``, ``_major_index``), and the window's mask runs on the
tiles that straddle its edge; no tile is preferred larger than the window.
The dK/dV grid moves to another query head every step, so its q-side block
is fetched every step: under a window it is no longer than a k tile's
window reaches (``_major``'s ``reach``).

Block diffusion (``block_diffusion=B``; docs/sdar.md). A block-diffusion
model trains on a sequence's clean copy and a noisy copy side by side,
``2 L`` rows ``[clean ; noisy]``, under a mask of three parts: a clean row
sees the clean rows of its block and of those before it; a noisy row sees
the *clean* rows of the blocks before its own and the *noisy* rows of its
own; no row sees a noisy row of another block. Read row by row that is one
thing twice over: every row, clean or noisy, sees the clean rows of the
blocks strictly before its own — the causal walk over the clean half's k
tiles from the tile's place in its own half, the diagonal tile masked by
block (``k < B * (q // B)``) in the same 128-row strips — and the rows of
its own block *in its own half*, which lie on the diagonal of the K/V tile
at the q tile's own index. So the three kernels take that tile as two more
operands and the accumulators *start* from it, one ``strip x strip`` square
a strip (``_own_mask``), before the walk: a row of a sequence's first block,
which sees no clean key at all, has a finite maximum from the first step,
and everything is one softmax a row. The dK/dV grid runs over both halves'
k tiles and takes each query head twice, its clean half and its noisy one:
a clean k tile walks both, a noisy one neither, and either adds its own
squares once a query head. Executed over needed pairs is ``(L + 3 * 128) /
(L + B)``, 1.046 at ``L`` 8192 (a schedule that stopped at the causal bound
of the ``2 L`` rows would execute 2.0).

The forward's softmax is two loops a major block: scores into a VMEM
buffer with their lane-wise maximum, one cross-lane reduction a row, then
``exp``, lane-wise sums and P V — the statistics and the accumulators'
rescaling happen once a major block, not once a tile (``_fwd_kernel``).

Training is first-class: ``flash_attention`` carries a ``jax.custom_vjp``
whose backward is the FlashAttention-2 recomputation scheme — the forward
saves only O(T) per-row logsumexp statistics, and two further kernels
recompute P = exp(S - lse) blockwise to produce dQ and dK/dV without ever
materializing the [T, T] matrix.

Per-row statistics (logsumexp, delta) cross the kernel boundary in
layouts whose last two block dims tile on the TPU: a trailing 128-lane
axis (``[B*H, T, 128]``, value repeated along lanes) where a kernel needs
them as a column against ``[tile_q, tile_k]`` scores, and a lane-dense
row (``[B*H, 1, T]``) where the dK/dV kernel works on transposed scores.
That kernel computes ``S^T = K Q^T`` directly, so every matmul in this
file is a plain or transposed-RHS product — none contracts dim 0 of its
left operand.

The three ``pallas_call``s are named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv`` (``flash_win_fwd``, ``flash_win_bwd_dq``,
``flash_win_bwd_dkv`` for a call with a window; ``flash_mla_fwd``,
``flash_mla_bwd_dq``, ``flash_mla_bwd_dkv`` for one whose v is of another
width than its q and k; ``flash_bd_fwd``, ``flash_bd_bwd_dq``,
``flash_bd_bwd_dkv`` for one under block diffusion's mask): the names a compiled program's custom calls and a
profiler trace show them under (docs/tracing.md).

``interpret=True`` (automatic on the CPU backend only) runs the same
kernels through the Pallas interpreter, which is how the CPU test suite
validates them. On any other platform the kernels compile through Mosaic.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.registry import registry as _metrics

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANES = 128  # TPU vreg lane count: the trailing axis of column statistics
# what the forward kernel leaves that a recomputed block would run it again
# for: its output and the rows' log-sum-exp, under these checkpoint names. A
# name is an identity that lowers to nothing; a ``jax.checkpoint`` whose
# policy saves them (``models.parts.keep_policy``) keeps both for its backward
# pass
KEPT_NAMES = ("flash.o", "flash.lse")
# VMEM a kernel's resident major blocks may take (both pipeline buffers of
# both operands, and the forward's scores): half of the 16 MiB a v5e kernel
# gets by default, the rest is for the tiles' f32 temporaries
_RESIDENT_BYTES = 8 * 1024 * 1024

_PAIR_RATIO = _metrics().gauge(
    "horovod_flash_executed_pair_ratio",
    "Score pairs the newest traced flash kernel executes over the pairs "
    "its mask keeps (1.0: no work thrown away by the causal mask)",
    labels=("kernel",))


# -- the causal schedule ----------------------------------------------------
#
# Positions: q row r sits at q_offset + r, k column c at c; causal keeps
# the pairs with q position >= k position. A tile is *interior* when it
# holds no masked pair, *diagonal* when it holds both kinds, and is never
# executed when every pair of it is masked. The two bound functions below
# take a tile index that is a Python int (causal_schedule, on the host) or
# a traced int32 (the kernels' program ids and the index maps), so the
# counter and the loops cannot disagree.


def _clip(x, lo, hi=None):
    if isinstance(x, int):
        x = max(x, lo)
        return x if hi is None else min(x, hi)
    return jnp.clip(x, lo, hi)


def k_tile_bounds(q_tile, *, q_offset: int, tile_q: int, tile_k: int,
                  num_k_tiles: int, causal: bool):
    """``(interior, end)`` for one q tile: k tiles ``[0, interior)`` hold no
    masked pair, ``[interior, end)`` straddle the diagonal, and those from
    ``end`` on lie wholly in the future."""
    if not causal:
        return num_k_tiles, num_k_tiles
    first_q = q_offset + q_tile * tile_q
    interior = _clip((first_q + 1) // tile_k, 0, num_k_tiles)
    end = _clip((first_q + tile_q - 1) // tile_k + 1, 0, num_k_tiles)
    return interior, end


def q_tile_bounds(k_tile, *, q_offset: int, tile_q: int, tile_k: int,
                  num_q_tiles: int, causal: bool):
    """``(start, interior)`` for one k tile: q tiles before ``start`` lie
    wholly in the past (every pair masked), ``[start, interior)`` straddle
    the diagonal, and those from ``interior`` on hold no masked pair."""
    if not causal:
        return 0, 0
    first_k = k_tile * tile_k - q_offset  # in q-row coordinates
    start = _clip(_clip(first_k, 0) // tile_q, 0, num_q_tiles)
    interior = _clip((_clip(first_k + tile_k - 1, 0) + tile_q - 1) // tile_q,
                     0, num_q_tiles)
    return start, interior


def k_window_bounds(q_tile, *, q_offset: int, tile_q: int, tile_k: int,
                    num_k_tiles: int, window: Optional[int]):
    """``(lo, clear)`` for one q tile under a window (q position ``t`` sees
    the k positions ``s`` with ``t - window < s``): k tiles before ``lo``
    lie wholly behind the window, ``[lo, clear)`` straddle its edge, and
    those from ``clear`` on lose no pair to it."""
    if window is None:
        return 0, 0
    first_q = q_offset + q_tile * tile_q
    lo = _clip(_clip(first_q - window + 1, 0) // tile_k, 0, num_k_tiles)
    clear = _clip(_clip(first_q + tile_q - 1 - window + tile_k, 0) // tile_k,
                  0, num_k_tiles)
    return lo, clear


def q_window_bounds(k_tile, *, q_offset: int, tile_q: int, tile_k: int,
                    num_q_tiles: int, window: Optional[int]):
    """``(edge, stop)`` for one k tile under a window: q tiles before
    ``edge`` lose no pair of this k tile to the window, ``[edge, stop)``
    straddle its edge, and those from ``stop`` on lie wholly beyond it."""
    if window is None:
        return num_q_tiles, num_q_tiles
    first_k = k_tile * tile_k - q_offset  # in q-row coordinates
    edge = _clip(_clip(first_k + window, 0) // tile_q, 0, num_q_tiles)
    stop = _clip((_clip(first_k + tile_k - 1 + window, 0) + tile_q - 1)
                 // tile_q, 0, num_q_tiles)
    return edge, stop


def _k_walk(q_tile, *, window: Optional[int], causal: bool, **tiles):
    """``(lo, a, b, end)``: one q tile executes the k tiles ``[lo, end)``;
    ``[lo, a)`` and ``[b, end)`` run the masked body (the window's edge and
    the diagonal), ``[a, b)`` runs with no mask."""
    interior, end = k_tile_bounds(q_tile, causal=causal, **tiles)
    if window is None:
        return 0, 0, interior, end
    lo, clear = k_window_bounds(q_tile, window=window, **tiles)
    a = _clip(clear, lo, end)
    return lo, a, _clip(interior, a, end), end


def _q_walk(k_tile, *, window: Optional[int], causal: bool, **tiles):
    """``(start, a, b, stop)``: one k tile executes the q tiles
    ``[start, stop)``; ``[start, a)`` and ``[b, stop)`` run the masked body
    (the diagonal and the window's edge), ``[a, b)`` runs with no mask."""
    start, interior = q_tile_bounds(k_tile, causal=causal, **tiles)
    num_q_tiles = tiles["num_q_tiles"]
    if window is None:
        return start, interior, num_q_tiles, num_q_tiles
    edge, stop = q_window_bounds(k_tile, window=window, **tiles)
    a = _clip(interior, start, stop)
    return start, a, _clip(edge, a, stop), stop


# -- static strips ----------------------------------------------------------
#
# A masked tile multiplied whole throws half of its products away. Where the
# boundary's place inside the tile is known at trace time, the tile runs as
# strips of its own operand's rows instead, each against only the columns it
# can see: no tile, grid step, loop step or statistics pass more, only
# smaller products. That is so when the tiles are square and ``q_offset`` is
# a multiple of them — the one diagonal tile of a walk then has the diagonal
# for its own — and, for the window's edge, when the window is a multiple of
# the tile too: the one edge tile of a walk then keeps what lies strictly
# above its diagonal. Anywhere else the masked body runs whole.

# Rows of a strip: the narrowest the lanes allow. What the v5e measured
# (PERF.md, PR 29): at 128 rows each of the three kernels is faster than
# at 256 or at half the tile, and the forward's 1024-row tile is slower in
# strips of 256 or 512 than whole.
_STRIP = _LANES


def _strips(*, q_offset: int, tile_q: int, tile_k: int, causal: bool,
            window: Optional[int]):
    """``(diagonal, edge)``: the rows of a strip on a kernel's diagonal
    tiles and on its window-edge tiles, ``None`` where those run whole. A
    rule on the call's own shapes, as ``_tiles`` is."""
    if (not causal or tile_q != tile_k or q_offset % tile_q
            or not 0 < _STRIP < tile_q or tile_q % _STRIP):
        return None, None
    if window is None:
        return _STRIP, None
    if window < tile_q:  # the diagonal tile holds the window's edge too
        return None, None
    # a window that is no multiple of the tile has its edge on two tiles
    return _STRIP, None if window % tile_q else _STRIP


def _parts(rows: int, cols: int, masked: bool, strip: Optional[int] = None,
           lower: bool = True):
    """The pieces a ``rows x cols`` tile's products run in, each ``(row
    slice, first column, columns, mask)`` with ``mask`` the ``(first,
    count)`` of the piece's columns that ``_causal_mask`` runs on, or
    ``None``. Without a ``strip`` the tile is one piece, masked whole or
    not at all. With one, the strip of rows from ``r`` takes the columns
    ``[0, r + strip)`` of a tile that keeps its ``lower`` triangle and
    ``[r, cols)`` of one that keeps its upper: every block of ``strip x
    strip`` that holds a visible pair, of which the one on the tile's
    diagonal holds masked pairs too."""
    if strip is None:
        return [(slice(0, rows), 0, cols, (0, cols) if masked else None)]
    return [(slice(r, r + strip), 0 if lower else r,
             r + strip if lower else cols - r,
             (r if lower else 0, strip))
            for r in range(0, rows, strip)]


def _pieces(transposed: bool = False, *, tile_q: int, tile_k: int,
            **schedule):
    """``(clear, diagonal, edge)``: the pieces (``_parts``) in which a
    kernel runs a tile with no masked pair, its diagonal tile and its
    window-edge tile. Rows are q rows and the diagonal tile keeps its
    lower triangle, the edge tile its upper; ``transposed`` (the dK/dV
    kernel's blocks) rows are k rows and it is the other way round."""
    diagonal, edge = _strips(tile_q=tile_q, tile_k=tile_k, **schedule)
    rows, cols = (tile_k, tile_q) if transposed else (tile_q, tile_k)
    return (_parts(rows, cols, False),
            _parts(rows, cols, True, diagonal, lower=not transposed),
            _parts(rows, cols, True, edge, lower=transposed))


def causal_schedule(seq_q: int, seq_k: int, q_offset: int, tile_q: int,
                    tile_k: int, causal: bool,
                    window: Optional[int] = None,
                    block_diffusion: Optional[int] = None) -> dict:
    """What each kernel executes at these shapes: ``tiles`` (compute tiles
    run), ``diagonal`` (how many of them hold masked pairs), ``trimmed``
    (how many of those run as strips, ``_strips``; 0 where the masked body
    runs whole) and ``pair_ratio`` (executed score pairs, counted strip by
    strip, over the pairs the mask keeps). ``flash_fwd`` and
    ``flash_bwd_dq`` walk k tiles for each q tile, ``flash_bwd_dkv`` walks
    q tiles for each k tile; a call with a ``window`` runs the same three
    under their ``flash_win_*`` names.

    Under ``block_diffusion`` (``seq_q = seq_k = 2 L`` rows, the
    ``flash_bd_*`` calls) each half's q tiles make the causal walk over the
    clean half's k tiles, and every tile adds the squares of its rows' own
    blocks — a strip's square on its own diagonal, counted among ``tiles``
    and ``diagonal`` as one tile more a q tile; the mask keeps ``L (L +
    block)`` pairs."""
    halves = 1 if block_diffusion is None else 2
    seq_q, seq_k = seq_q // halves, seq_k // halves
    num_q_tiles, num_k_tiles = seq_q // tile_q, seq_k // tile_k
    schedule = dict(q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
                    causal=causal, window=window)
    # (edge tiles, clear tiles, diagonal tiles) of each walk
    by_q = [(a - lo, b - a, end - b) for lo, a, b, end in (
        _k_walk(i, num_k_tiles=num_k_tiles, **schedule)
        for i in range(num_q_tiles))]
    by_k = [(stop - b, b - a, a - start) for start, a, b, stop in (
        _q_walk(j, num_q_tiles=num_q_tiles, **schedule)
        for j in range(num_k_tiles))]

    def visible(row):
        last = min(seq_k, q_offset + row + 1)
        first = 0 if window is None else max(0, q_offset + row + 1 - window)
        return max(0, last - first)

    needed = seq_q * seq_k if not causal else sum(map(visible, range(seq_q)))
    if block_diffusion is not None:
        needed = seq_q * (seq_q + block_diffusion)

    def walk(walks):
        edge, clear, diagonal = (halves * sum(max(0, n) for n in kind)
                                 for kind in zip(*walks))
        pairs, trimmed = clear * tile_q * tile_k, 0
        for count, parts in zip((diagonal, edge), _pieces(**schedule)[1:]):
            pairs += count * sum((rows.stop - rows.start) * cols
                                 for rows, _, cols, _ in parts)
            trimmed += count if len(parts) > 1 else 0
        own = 0 if block_diffusion is None else 2 * num_q_tiles
        pairs += own * sum((rows.stop - rows.start) ** 2
                           for rows, *_ in _pieces(**schedule)[1])
        return {"tiles": edge + clear + diagonal + own,
                "diagonal": edge + diagonal + own, "trimmed": trimmed,
                "pair_ratio": pairs / needed}

    over_k, over_q = walk(by_q), walk(by_k)
    return {"flash_fwd": over_k, "flash_bwd_dq": over_k,
            "flash_bwd_dkv": over_q}


def _for_tiles(lo, hi, first, tiles_per_major: int, body):
    """``body(j)`` for each tile of the global range ``[lo, hi)`` that lies
    in the resident block of tiles ``[first, first + tiles_per_major)``,
    ``j`` counting from the block's first tile. A block that is one tile
    gets a branch in place of the loop and ``j`` as a Python int, so that
    it slices its refs statically."""
    lo = _clip(lo - first, 0, tiles_per_major)
    hi = _clip(hi - first, 0, tiles_per_major)
    static = isinstance(lo, int) and isinstance(hi, int)
    if static and hi <= lo:
        return
    if tiles_per_major == 1:
        if static:
            body(0)
        else:
            pl.when(lo < hi)(lambda: body(0))
        return

    def step(j, carry):
        body(j)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)


def _tile_slice(j, tile: int, first: int = 0, size: Optional[int] = None):
    """The rows (or lanes) of local tile ``j`` in a resident block: all of
    them, or the ``size`` from the tile's ``first``."""
    size = tile if size is None else size
    if isinstance(j, int):
        return pl.ds(j * tile + first, size)
    return pl.ds(pl.multiple_of(j * tile + first, math.gcd(tile, first)),
                 size)


def _first_tile(major_idx, tiles_per_major: int, num_tiles: int):
    """The global index of a major block's first tile; the Python 0 where
    one block holds the whole sequence, which keeps a non-causal call's
    loop bounds static."""
    return 0 if tiles_per_major == num_tiles else major_idx * tiles_per_major


def _grid_majors(num_majors: int, major: int, tile: int,
                 window: Optional[int]) -> int:
    """Steps of the sequential grid axis. Without a window it counts the
    streamed operand's major blocks from the first; with one it counts
    from the first block a tile's window reaches (``_major_index``), and a
    tile of ``tile`` rows sees ``tile + window - 1`` positions, which span
    no more blocks than this."""
    if window is None:
        return num_majors
    return min(num_majors, (tile + window - 1 + major - 2) // major + 1)


def _major_index(step, first_tile, tiles_per_major: int, grid_majors: int,
                 num_majors: int):
    """The major block that grid step ``step`` works on: ``step`` itself
    where the grid walks every block, else counted from the block that
    holds ``first_tile``, the first tile the window reaches."""
    if grid_majors == num_majors:
        return step
    return first_tile // tiles_per_major + step


def _causal_mask(s, q_pos0, k_pos0, q_axis=0, window=None, block=None):
    """Mask future positions of a score block to the _NEG_INF sentinel, and
    with a ``window`` the positions it has left behind (``q - k >=
    window``). q positions run along ``q_axis`` of ``s`` and k positions
    along the other axis (``q_axis=1`` is the dK/dV kernel's transposed
    block). Shared by forward and backward so the two can never disagree
    on what was masked. Under block diffusion (``block``, a power of two) a
    row sees the clean keys of the blocks *before* its own: those under its
    block's first position."""
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    if block is not None:
        return jnp.where(k_pos < (q_pos & -block), s, _NEG_INF)
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return jnp.where(keep, s, _NEG_INF)


def _mask_columns(s, mask, q_pos0, k_pos0, q_axis=0, window=None,
                  block=None):
    """``_causal_mask`` on the ``mask = (first, count)`` columns of the
    score block ``s`` alone (``_parts``); the positions are those of the
    block's first row and column."""
    if mask is None:
        return s
    at, count = mask
    if count == s.shape[1]:
        return _causal_mask(s, q_pos0, k_pos0, q_axis, window, block)
    if q_axis:
        q_pos0 += at
    else:
        k_pos0 += at
    masked = _causal_mask(s[:, at:at + count], q_pos0, k_pos0, q_axis, window,
                          block)
    return jnp.concatenate(
        [x for x in (s[:, :at], masked, s[:, at + count:]) if x.shape[1]],
        axis=1)


def _own_mask(s, block: int):
    """A row's own block under block diffusion: ``s`` is a square of scores
    whose q rows and k columns start at the same multiple of ``block``, and
    a row keeps the columns of its own block (two positions share a block
    when they differ in its low bits alone). Symmetric, so the dK/dV
    kernel's transposed squares take it as it is."""
    rows, cols = (jax.lax.broadcasted_iota(jnp.int32, s.shape, axis)
                  for axis in (0, 1))
    return jnp.where((rows ^ cols) < block, s, _NEG_INF)


def _col(stat):
    """[rows, 128] lane-repeated statistic -> its [rows, 1] column."""
    return stat[:, :1]


def _lane_fold(x, lanes: int, op):
    """Fold the column blocks of ``x`` [rows, n * lanes] into one
    [rows, lanes] block with ``op``: element-wise work on whole vregs, no
    reduction across lanes."""
    return functools.reduce(op, [x[:, c * lanes:(c + 1) * lanes]
                                 for c in range(x.shape[1] // lanes)])


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, scale: float, causal: bool,
                q_offset: int, tile_q: int, tile_k: int, major_k: int,
                num_k_tiles: int, window: Optional[int], grid_majors: int,
                pieces, block: Optional[int] = None):
    """One q tile against the resident K/V major block ``kk``. Grid (bh,
    q-tile, k-major), the last sequential; ``o_acc``, ``m_acc`` and
    ``l_acc`` persist across it.

    The softmax statistics are per row, and a reduction across lanes plus
    the rescaling of the accumulators cost as much for one k tile as for a
    whole major block. So they are done once a major block, in two loops
    over its k tiles that both end at the diagonal: the first leaves the
    scores in ``s_buf`` and their maximum lane by lane in ``m_lane``; then
    one reduction gives the rows' new maximum; the second takes
    ``exp(S - m)``, sums it lane by lane into ``l_lane`` and accumulates
    P V. With the whole of K resident that is the plain softmax.

    A tile runs in its ``pieces`` (``_pieces``: the strips of a diagonal
    or a window-edge tile where the shapes allow them): both loops take a
    piece's rows against its columns only, so a block the mask would empty
    is neither multiplied, written, read nor exponentiated.

    No row is ever empty: with ``q_offset >= 0`` every row keeps its pair
    with k position 0, and under a window its pair with its own position
    (``flash_attention`` requires that K holds it). A window's row may
    still find every pair of an *early* major block masked; its statistics
    stay at the sentinel there, what it accumulates is finite, and the
    first block with a visible pair rescales it by ``exp(sentinel - m)``,
    which is 0. So ``m`` is finite wherever the result depends on it.

    Block diffusion (``block``; ``refs`` then lead with the K and V tile at
    the q tile's own index, module docstring): the accumulators *start* from
    the rows' own blocks, a square a strip on that tile's diagonal, so a row
    that sees no clean key (its sequence's first block) has a finite ``m``
    from step 0 on; the walk is the causal one over the ``num_k_tiles``
    clean tiles at the tile's place in its half, masked by block."""
    if block is not None:
        k_own, v_own, *refs = refs
    o_ref, lse_ref, o_acc, m_acc, l_acc, s_buf, m_lane, l_lane = refs
    # program_id must be read at kernel top level: inside a pl.when body it
    # escapes the interpreter's scope (breaks interpret=True on CPU)
    step = pl.program_id(2)
    q_idx = pl.program_id(1)
    tiles_per_major = major_k // tile_k
    lanes = m_lane.shape[1]

    @pl.when(step == 0)
    def _init():
        if block is None:
            o_acc[...] = jnp.zeros_like(o_acc)
            m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
            l_acc[...] = jnp.zeros_like(l_acc)
            return
        q_block = q_ref[0].astype(jnp.float32) * scale
        for rows, *_ in pieces[1]:
            s = _own_mask(jax.lax.dot_general(
                q_block[rows], k_own[0, rows, :].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32), block)
            m = s.max(axis=1, keepdims=True)
            p = jnp.exp(s - m)
            o_acc[rows, :] = jax.lax.dot_general(
                p, v_own[0, rows, :].astype(jnp.float32),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            m_acc[rows, :] = jnp.broadcast_to(m, (s.shape[0], _LANES))
            l_acc[rows, :] = jnp.broadcast_to(
                p.sum(axis=1, keepdims=True), (s.shape[0], _LANES))

    if block is not None:
        q_idx = q_idx % num_k_tiles     # the tile's place in its half
    lo, a, b, end = _k_walk(
        q_idx, q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
        num_k_tiles=num_k_tiles, causal=causal, window=window)
    kk = _major_index(step, lo, tiles_per_major, grid_majors,
                      num_k_tiles // tiles_per_major)
    first = _first_tile(kk, tiles_per_major, num_k_tiles)

    # nothing to do in a major block wholly in this q tile's future. (The
    # branch also keeps the refs' slicing off the kernel's top level, where
    # the interpreter refuses it under a vma-tracking shard_map.)
    @pl.when(first < end)
    def _run():
        q_block = q_ref[0].astype(jnp.float32) * scale  # [tile_q, d]
        q_pos0 = q_offset + q_idx * tile_q
        m_lane[...] = jnp.full_like(m_lane, _NEG_INF)
        l_lane[...] = jnp.zeros_like(l_lane)
        clear, diagonal, edge = pieces

        def scores(j, parts):
            for rows, first_col, count, mask in parts:
                cols = _tile_slice(j, tile_k, first_col, count)
                k_blk = k_ref[0, cols, :].astype(jnp.float32)  # [count, d]
                s = jax.lax.dot_general(  # [rows, count] on the MXU
                    q_block[rows], k_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                s = _mask_columns(
                    s, mask, q_pos0 + rows.start,
                    kk * major_k + j * tile_k + first_col, window=window,
                    block=block)
                s_buf[rows, cols] = s
                m_lane[rows, :] = jnp.maximum(
                    m_lane[rows, :], _lane_fold(s, lanes, jnp.maximum))

        if window is not None:
            _for_tiles(lo, a, first, tiles_per_major,
                       functools.partial(scores, parts=edge))
        _for_tiles(a, b, first, tiles_per_major,
                   functools.partial(scores, parts=clear))
        if causal:
            _for_tiles(b, end, first, tiles_per_major,
                       functools.partial(scores, parts=diagonal))

        m_old = _col(m_acc[...])
        m_new = jnp.maximum(m_old, m_lane[...].max(axis=1, keepdims=True))
        corr = jnp.exp(m_old - m_new)  # 0 on the first block: m_old sentinel
        o_acc[...] = o_acc[...] * corr
        m_lanes = jnp.broadcast_to(m_new, m_lane.shape)

        def weigh(j, parts):
            for rows, first_col, count, _ in parts:
                cols = _tile_slice(j, tile_k, first_col, count)
                v_blk = v_ref[0, cols, :].astype(jnp.float32)
                p = jnp.exp(s_buf[rows, cols]
                            - jnp.tile(m_lanes[rows], (1, count // lanes)))
                l_lane[rows, :] += _lane_fold(p, lanes, jnp.add)
                o_acc[rows, :] += jax.lax.dot_general(
                    p, v_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        # a masked tile that runs whole is weighed like a clear one
        if len(edge) > 1:
            _for_tiles(lo, a, first, tiles_per_major,
                       functools.partial(weigh, parts=edge))
        _for_tiles(a if len(edge) > 1 else lo,
                   b if len(diagonal) > 1 else end, first, tiles_per_major,
                   functools.partial(weigh, parts=clear))
        if len(diagonal) > 1:
            _for_tiles(b, end, first, tiles_per_major,
                       functools.partial(weigh, parts=diagonal))

        l_new = _col(l_acc[...]) * corr \
            + l_lane[...].sum(axis=1, keepdims=True)
        l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)
        m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        l = l_acc[...]
        o_ref[0, ...] = (o_acc[...] / _col(l)).astype(o_ref.dtype)
        # per-row logsumexp residual for the backward pass
        lse_ref[0, ...] = m_acc[...] + jnp.log(l)


def _recompute_p(q_blk, k_blk, lse, *, mask, q_pos0, k_pos0,
                 transposed=False, window=None, block=None):
    """Recompute the normalized probability block P = exp(S - lse), with
    S's mask on the piece's ``mask`` columns (``_parts``; ``"own"``: the
    square of a row's own block under block diffusion, ``_own_mask``);
    shared by both backward kernels. ``q_blk`` comes scaled. All f32, MXU matmul.

    ``transposed=False``: P is [tile_q, tile_k] and ``lse`` its
    [tile_q, 1] column. ``transposed=True``: P^T is [tile_k, tile_q],
    computed directly as K Q^T, and ``lse`` its [1, tile_q] row."""
    lhs, rhs = (k_blk, q_blk) if transposed else (q_blk, k_blk)
    s = jax.lax.dot_general(
        lhs, rhs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if mask == "own":
        s = _own_mask(s, block)
    else:
        s = _mask_columns(s, mask, q_pos0, k_pos0,
                          q_axis=1 if transposed else 0, window=window,
                          block=block)
    # no row is empty (``_fwd_kernel``), so lse is finite and a masked
    # pair's exp(sentinel - lse) is the 0 it should be
    return jnp.exp(s - lse)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                   scale: float, causal: bool, q_offset: int,
                   tile_q: int, tile_k: int, major_k: int, num_k_tiles: int,
                   window: Optional[int], grid_majors: int, pieces,
                   block: Optional[int] = None):
    """dQ = (P * (dO V^T - delta)) K * scale, accumulated over the k tiles
    from the window's edge up to the diagonal. Grid (bh, q-tile, k-major)
    as the forward's, and a tile in the forward's ``pieces``: a strip of q
    rows forms P, dP and dS on its columns and adds to its rows of
    ``dq_acc``. Block diffusion as the forward's: ``dq_acc`` starts from the
    rows' own blocks, on the K and V tile that leads ``refs``."""
    if block is not None:
        k_own, v_own, *refs = refs
    dq_ref, dq_acc = refs
    step = pl.program_id(2)
    q_idx = pl.program_id(1)
    tiles_per_major = major_k // tile_k

    @pl.when(step == 0)
    def _init():
        if block is None:
            dq_acc[...] = jnp.zeros_like(dq_acc)
            return
        q_scaled = q_ref[0].astype(jnp.float32) * scale
        do_blk = do_ref[0].astype(jnp.float32)
        lse, delta = _col(lse_ref[0]), _col(delta_ref[0])
        for rows, *_ in pieces[1]:
            k_blk = k_own[0, rows, :].astype(jnp.float32)
            p = _recompute_p(q_scaled[rows], k_blk, lse[rows], mask="own",
                             q_pos0=0, k_pos0=0, block=block)
            dp = jax.lax.dot_general(  # dO V^T on the rows' own square
                do_blk[rows], v_own[0, rows, :].astype(jnp.float32),
                (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            dq_acc[rows, :] = jax.lax.dot_general(
                p * (dp - delta[rows]) * scale, k_blk,
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if block is not None:
        q_idx = q_idx % num_k_tiles     # the tile's place in its half
    lo, a, b, end = _k_walk(
        q_idx, q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
        num_k_tiles=num_k_tiles, causal=causal, window=window)
    kk = _major_index(step, lo, tiles_per_major, grid_majors,
                      num_k_tiles // tiles_per_major)
    first = _first_tile(kk, tiles_per_major, num_k_tiles)

    @pl.when(first < end)  # as the forward's
    def _run():
        q_scaled = q_ref[0].astype(jnp.float32) * scale
        do_blk = do_ref[0].astype(jnp.float32)
        lse, delta = _col(lse_ref[0]), _col(delta_ref[0])
        q_pos0 = q_offset + q_idx * tile_q

        def update(j, parts):
            for rows, first_col, count, mask in parts:
                cols = _tile_slice(j, tile_k, first_col, count)
                k_blk = k_ref[0, cols, :].astype(jnp.float32)
                v_blk = v_ref[0, cols, :].astype(jnp.float32)
                p = _recompute_p(
                    q_scaled[rows], k_blk, lse[rows], mask=mask,
                    q_pos0=q_pos0 + rows.start,
                    k_pos0=kk * major_k + j * tile_k + first_col,
                    window=window, block=block)
                dp = jax.lax.dot_general(  # dO V^T  [rows, count]
                    do_blk[rows], v_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = p * (dp - delta[rows]) * scale
                dq_acc[rows, :] += jax.lax.dot_general(
                    ds, k_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        clear, diagonal, edge = pieces
        if window is not None:
            _for_tiles(lo, a, first, tiles_per_major,
                       functools.partial(update, parts=edge))
        _for_tiles(a, b, first, tiles_per_major,
                   functools.partial(update, parts=clear))
        if causal:
            _for_tiles(b, end, first, tiles_per_major,
                       functools.partial(update, parts=diagonal))

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[0, ...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                    scale: float,
                    causal: bool, q_offset: int, tile_q: int, tile_k: int,
                    major_q: int, num_q_tiles: int, window: Optional[int],
                    group: int, grid_majors: int, pieces,
                    block: Optional[int] = None):
    """dV = P^T dO and dK = (P * (dP - delta))^T Q for one k tile of one
    K/V head, accumulated over the q tiles of the resident major block from
    the diagonal to the window's far edge, and over the query heads of the
    K/V head's group. Grid (kv head, k-tile, group x q-major), the last
    sequential: a step is one query head's major block
    (``_dkv_step``). Works on the transposed blocks P^T, dP^T = V dO^T,
    dS^T throughout, with lse and delta as [1, tile_q] rows, so no operand
    is ever transposed. A tile runs in its ``pieces`` the other way round
    (``_pieces(transposed=True)``): a strip of *k* rows against the q
    columns it can see — from its own on in the diagonal tile, up to its
    own in the window's edge tile — adding to its rows of ``dk_acc`` and
    ``dv_acc``.

    Block diffusion (``block``): the k tiles run over both halves,
    ``num_q_tiles`` counts one half's q tiles, and the sequential axis takes
    each query head twice, its clean half and then its noisy one
    (``_dkv_step`` over ``2 * group`` members). A *clean* k tile walks each
    half's q tiles from its own place on, masked by block; a noisy one walks
    none. Either adds, once a query head, what the rows of the q tile at its
    own index give their own blocks (``refs`` lead with that tile of Q and
    dO and its statistics)."""
    if block is not None:
        q_own, do_own, lse_own, delta_own, *refs = refs
    dk_ref, dv_ref, dk_acc, dv_acc = refs
    step = pl.program_id(2)
    k_idx = pl.program_id(1)
    tiles_per_major = major_q // tile_q

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    start, a, b, stop = _q_walk(
        k_idx, q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
        num_q_tiles=num_q_tiles, causal=causal, window=window)
    member, major_step = _dkv_step(step, group if block is None
                                   else 2 * group, grid_majors)
    iq = _major_index(major_step, start, tiles_per_major, grid_majors,
                      num_q_tiles // tiles_per_major)
    first = _first_tile(iq, tiles_per_major, num_q_tiles)
    # nothing to do in a major block wholly in this k tile's past, nor in
    # one wholly beyond its window
    needed = start < first + tiles_per_major
    if window is not None:
        needed = needed & (first < stop)
    if block is not None:
        needed = needed & (k_idx < num_q_tiles)     # a clean k tile

    if block is not None:
        @pl.when((member % 2 == 0) & (major_step == 0))
        def _own():
            for rows, *_ in pieces[1]:
                q_blk = q_own[0, rows, :].astype(jnp.float32)
                do_blk = do_own[0, rows, :].astype(jnp.float32)
                p_t = _recompute_p(
                    q_blk * scale, k_ref[0, rows, :].astype(jnp.float32),
                    lse_own[0, :, rows], mask="own", q_pos0=0, k_pos0=0,
                    transposed=True, block=block)
                dv_acc[rows, :] += jax.lax.dot_general(  # P^T dO [rows, d]
                    p_t, do_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp_t = jax.lax.dot_general(  # V dO^T  [rows, rows]
                    v_ref[0, rows, :].astype(jnp.float32), do_blk,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds_t = p_t * (dp_t - delta_own[0, :, rows]) * scale
                dk_acc[rows, :] += jax.lax.dot_general(  # dS^T Q [rows, d]
                    ds_t, q_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    @pl.when(needed)
    def _run():
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        k_pos0 = k_idx * tile_k

        def update(i, parts):
            for rows, first_col, count, mask in parts:
                cols = _tile_slice(i, tile_q, first_col, count)
                q_blk = q_ref[0, cols, :].astype(jnp.float32)
                do_blk = do_ref[0, cols, :].astype(jnp.float32)
                p_t = _recompute_p(
                    q_blk * scale, k_blk[rows], lse_ref[0, :, cols],
                    mask=mask,
                    q_pos0=q_offset + iq * major_q + i * tile_q + first_col,
                    k_pos0=k_pos0 + rows.start, transposed=True,
                    window=window, block=block)
                dv_acc[rows, :] += jax.lax.dot_general(  # P^T dO [rows, d]
                    p_t, do_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp_t = jax.lax.dot_general(  # V dO^T  [rows, count]
                    v_blk[rows], do_blk, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds_t = p_t * (dp_t - delta_ref[0, :, cols]) * scale
                dk_acc[rows, :] += jax.lax.dot_general(  # dS^T Q [rows, d]
                    ds_t, q_blk, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

        clear, diagonal, edge = pieces
        if causal:
            _for_tiles(start, a, first, tiles_per_major,
                       functools.partial(update, parts=diagonal))
        _for_tiles(a, b, first, tiles_per_major,
                   functools.partial(update, parts=clear))
        if window is not None:
            _for_tiles(b, stop, first, tiles_per_major,
                       functools.partial(update, parts=edge))

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


def _dkv_step(step, group: int, grid_majors: int):
    """``(member, major step)`` of the dK/dV grid's sequential axis: the
    ``group`` query heads of a K/V head one after another, each through
    its major blocks."""
    if group == 1:
        return 0, step
    if grid_majors == 1:
        return step, 0
    return step // grid_majors, step % grid_majors


def _to_bh(x):
    """[B, T, H, D] -> [B*H, T, D]: grid programs own one (batch, head)."""
    batch, seq, heads, head_dim = x.shape
    return x.transpose(0, 2, 1, 3).reshape(batch * heads, seq, head_dim)


def _from_bh(x, batch, heads):
    bh, seq, head_dim = x.shape
    return x.reshape(batch, heads, seq, head_dim).transpose(0, 2, 1, 3)


def _sds(shape, dtype, *like):
    """ShapeDtypeStruct carrying the union of ``like`` operands' vma type.

    Inside a vma-tracking ``shard_map`` (check_vma=True, the default),
    ``pallas_call`` outputs must declare how they vary over mesh axes —
    a kernel output varies exactly as much as its operands do. Outside
    shard_map the plain struct is returned."""
    from .spmd import operand_vma

    vma = operand_vma(*like)
    if vma is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _tile(seq: int, bound: Optional[int], prefer: int, row_bytes: int) -> int:
    """The compute tile along a sequence of ``seq``: the caller's ``bound``
    where there is one (or the sequence, if shorter); else ``prefer``,
    halved while it does not divide the sequence or its rows alone
    overrun ``_RESIDENT_BYTES``, as long as it stays a multiple of the
    lanes."""
    if bound is not None:
        return min(bound, seq)
    tile = min(prefer, seq)
    while tile % (2 * _LANES) == 0 and (
            seq % tile or tile * row_bytes > _RESIDENT_BYTES):
        tile //= 2
    return tile


def _tiles(seq_q: int, seq_k: int, head_dim: int, dtype,
           block_q: Optional[int], block_k: Optional[int],
           window: Optional[int] = None, v_dim: Optional[int] = None):
    """``((tile_q, tile_k) of the forward, (tile_q, tile_k) of the two
    backward kernels)``. What the v5e measured (PERF.md, PR 25): a loop
    step, a grid step and a row's statistics cost the same whatever the
    tile, so bigger tiles win until the pairs a causal tile throws away
    outweigh them. The forward's two matmuls a pair leave it cheapest at
    1024 even where that is the whole causal sequence; the backward's
    three and four are cheapest at 512. Under a window no tile is
    preferred larger than the window: every tile a row's window touches
    would be an edge tile, most of its pairs masked."""
    row_bytes = _operand_row_bytes(head_dim, dtype, v_dim)
    fwd, bwd = 1024, 512
    if window is not None:
        fwd, bwd = (min(t, max(_LANES, window)) for t in (fwd, bwd))
    fwd_q = _tile(seq_q, block_q, fwd, row_bytes)
    return ((fwd_q, _tile(seq_k, block_k, fwd, row_bytes + 4 * fwd_q)),
            (_tile(seq_q, block_q, bwd, row_bytes),
             _tile(seq_k, block_k, bwd, row_bytes)))


def _major(seq: int, tile: int, row_bytes: int,
           reach: Optional[int] = None) -> int:
    """Rows of the streamed operands that stay resident in VMEM: the most
    tiles that divide ``seq`` and fit ``_RESIDENT_BYTES`` at ``row_bytes``
    a row, at least one tile. The whole sequence where it fits — or, given
    the ``reach`` of a window in rows, no more tiles than that spans."""
    num_tiles = seq // tile
    fit = max(1, min(num_tiles, _RESIDENT_BYTES // (row_bytes * tile)))
    if reach is not None:
        fit = min(fit, -(-reach // tile))
    while num_tiles % fit:
        fit -= 1
    return fit * tile


def _operand_row_bytes(head_dim: int, dtype,
                       v_dim: Optional[int] = None) -> int:
    """VMEM bytes a row of two resident operands takes — one ``head_dim``
    wide (K, or Q), the other ``v_dim`` (V, or dO; ``head_dim`` where they
    agree): two pipeline buffers each, the widths padded to the lanes."""
    lanes = sum(-(-d // _LANES) * _LANES
                for d in (head_dim, head_dim if v_dim is None else v_dim))
    return 2 * lanes * jnp.dtype(dtype).itemsize


def _kv_major_spec(major_k: int, head_dim: int, num_k_tiles: int, group: int,
                   grid_majors: int, wrap: bool = False, **schedule):
    """BlockSpec of K or V on a (q head, q-tile, k-major) grid: the block
    of the K/V head that the q head's group shares. A major block wholly in
    a q tile's future is not fetched: the index stays on the last one the
    tile needs. ``wrap`` (block diffusion): the q tiles run over two halves
    and walk the ``num_k_tiles`` of the first from their place in their
    own."""
    tiles_per_major = major_k // schedule["tile_k"]

    def index(bh, i, step):
        if wrap:
            i = i % num_k_tiles
        lo, _, _, end = _k_walk(i, num_k_tiles=num_k_tiles, **schedule)
        kk = _major_index(step, lo, tiles_per_major, grid_majors,
                          num_k_tiles // tiles_per_major)
        return (bh if group == 1 else bh // group,
                jnp.minimum(kk, (end - 1) // tiles_per_major), 0)

    return pl.BlockSpec((1, major_k, head_dim), index)


def _own_kv_specs(tile: int, group: int, head_dim: int, v_dim: int) -> list:
    """BlockSpecs of the K and the V tile at a q tile's own index, on a (q
    head, q-tile, k-major) grid: where block diffusion's rows find their own
    blocks."""
    return [pl.BlockSpec((1, tile, d), lambda bh, i, kk: (bh // group, i, 0))
            for d in (head_dim, v_dim)]


def _compiler_params(interpret: bool):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def _kernel_names(window: Optional[int], split: bool = False,
                  block: Optional[int] = None) -> dict:
    """The ``pallas_call`` names by ``causal_schedule``'s keys: a call with
    a window, one whose v is of another width than its q (``split``:
    latent attention) and one under block diffusion's mask are named apart,
    so that a trace tells them apart."""
    prefix = "flash_bd" if block is not None else \
        "flash_mla" if split else \
        "flash" if window is None else "flash_win"
    return {name: prefix + name[len("flash"):] for name in
            ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")}


def _fwd_impl(q, k, v, causal, scale, tiles, interpret, q_offset, window,
              block=None):
    tile_q, tile_k = tiles
    batch, seq_q, heads, head_dim = q.shape
    seq_k, kv_heads, v_dim = k.shape[1], k.shape[2], v.shape[-1]
    if block is not None:
        seq_k //= 2     # the clean half: what the walk streams
    num_k_tiles = seq_k // tile_k
    # beside K and V, a row of the major block holds its f32 scores
    major_k = _major(seq_k, tile_k, _operand_row_bytes(
        head_dim, k.dtype, v_dim) + 4 * tile_q)
    grid_majors = _grid_majors(seq_k // major_k, major_k, tile_q, window)
    lanes = _LANES if tile_k % _LANES == 0 else tile_k
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    name = _kernel_names(window, v_dim != head_dim, block)["flash_fwd"]
    _PAIR_RATIO.labels(kernel=name).set(causal_schedule(
        seq_q, k.shape[1], q_offset, tile_q, tile_k, causal, window, block)
        ["flash_fwd"]["pair_ratio"])
    schedule = dict(q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
                    causal=causal, window=window)
    group = heads // kv_heads

    q_spec, o_spec = (pl.BlockSpec((1, tile_q, d), lambda bh, i, kk: (bh, i, 0))
                      for d in (head_dim, v_dim))
    k_spec, v_spec = (_kv_major_spec(major_k, d, num_k_tiles, group,
                                     grid_majors, block is not None,
                                     **schedule) for d in (head_dim, v_dim))
    own_specs, own = [], ()
    if block is not None:
        own_specs, own = _own_kv_specs(tile_q, group, head_dim, v_dim), (kb, vb)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, major_k=major_k,
                          num_k_tiles=num_k_tiles, grid_majors=grid_majors,
                          pieces=_pieces(**schedule), block=block,
                          **schedule),
        grid=(batch * heads, seq_q // tile_q, grid_majors),
        in_specs=[q_spec, k_spec, v_spec, *own_specs],
        out_specs=[
            o_spec,
            pl.BlockSpec((1, tile_q, _LANES), lambda bh, i, kk: (bh, i, 0)),
        ],
        out_shape=[
            _sds((batch * heads, seq_q, v_dim), q.dtype, q, k, v),
            _sds((batch * heads, seq_q, _LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((tile_q, v_dim), jnp.float32),
            pltpu.VMEM((tile_q, _LANES), jnp.float32),
            pltpu.VMEM((tile_q, _LANES), jnp.float32),
            pltpu.VMEM((tile_q, major_k), jnp.float32),
            pltpu.VMEM((tile_q, lanes), jnp.float32),
            pltpu.VMEM((tile_q, lanes), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=name,
    )(qb, kb, vb, *own)
    # the kernel repeats each row's value along the lane axis; the residual
    # kept for the backward pass is the O(T) vector
    return _from_bh(o, batch, heads), lse[..., 0]


def _bwd_impl(q, k, v, o, lse, do, causal, scale, tiles, interpret,
              q_offset, window, block=None):
    tile_q, tile_k = tiles
    batch, seq_q, heads, head_dim = q.shape
    seq_k, kv_heads, v_dim = k.shape[1], k.shape[2], v.shape[-1]
    group = heads // kv_heads
    halves = 1 if block is None else 2
    # under block diffusion the walks are over one half's tiles
    seq_q, seq_k = seq_q // halves, seq_k // halves
    num_q_tiles = seq_q // tile_q
    num_k_tiles = seq_k // tile_k
    row_bytes = _operand_row_bytes(head_dim, k.dtype, v_dim)
    major_k = _major(seq_k, tile_k, row_bytes)
    # the dK/dV grid takes another query head every step, so its q-side
    # block is fetched anew every step: under a window no more of it than a
    # k tile's window reaches (PERF.md, PR 29: a head's whole Q and dO, 4 MB
    # a step for two tiles of work, bound flash_win_bwd_dkv by HBM)
    major_q = _major(seq_q, tile_q, row_bytes,
                     None if window is None else tile_k + window - 1)
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    ob, dob = _to_bh(o), _to_bh(do)
    names = _kernel_names(window, v_dim != head_dim, block)
    executed = causal_schedule(halves * seq_q, halves * seq_k, q_offset,
                               tile_q, tile_k, causal, window, block)
    for key in ("flash_bwd_dq", "flash_bwd_dkv"):
        _PAIR_RATIO.labels(kernel=names[key]).set(
            executed[key]["pair_ratio"])
    schedule = dict(q_offset=q_offset, tile_q=tile_q, tile_k=tile_k,
                    causal=causal, window=window)
    # delta_i = sum_d dO_id O_id = sum_j dP_ij P_ij  (softmax Jacobian term)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)  # [B*H, Tq]

    # per-row statistics in the two layouts the kernels read (module
    # docstring): lane-repeated columns for dQ, lane-dense rows for dK/dV
    lse_cols, delta_cols = (
        jnp.broadcast_to(x[..., None], (*x.shape, _LANES))
        for x in (lse, delta))
    lse_rows, delta_rows = lse[:, None, :], delta[:, None, :]

    k_majors = _grid_majors(seq_k // major_k, major_k, tile_q, window)
    q_spec, do_spec = (pl.BlockSpec((1, tile_q, d),
                                    lambda bh, i, kk: (bh, i, 0))
                       for d in (head_dim, v_dim))
    k_spec, v_spec = (_kv_major_spec(major_k, d, num_k_tiles, group, k_majors,
                                     block is not None, **schedule)
                      for d in (head_dim, v_dim))
    col_spec = pl.BlockSpec((1, tile_q, _LANES), lambda bh, i, kk: (bh, i, 0))
    own_specs, own = [], ()
    if block is not None:
        own_specs, own = _own_kv_specs(tile_q, group, head_dim, v_dim), (kb, vb)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, major_k=major_k,
                          num_k_tiles=num_k_tiles, grid_majors=k_majors,
                          pieces=_pieces(**schedule), block=block,
                          **schedule),
        grid=(batch * heads, halves * num_q_tiles, k_majors),
        in_specs=[q_spec, k_spec, v_spec, do_spec, col_spec, col_spec,
                  *own_specs],
        out_specs=q_spec,
        out_shape=_sds((batch * heads, halves * seq_q, head_dim), q.dtype,
                       q, k, v, do),
        scratch_shapes=[pltpu.VMEM((tile_q, head_dim), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names["flash_bwd_dq"],
    )(qb, kb, vb, dob, lse_cols, delta_cols, *own)

    # dK/dV grid: (kv head, k-tile, group x q-major) — the streamed q-side
    # operands re-index by the LAST grid axis here: the query heads of the
    # K/V head's group, each through its major blocks
    num_q_majors = seq_q // major_q
    q_majors = _grid_majors(num_q_majors, major_q, tile_k, window)

    def q_head(bh, step):
        member, _ = _dkv_step(step, halves * group, q_majors)
        if block is not None:
            member = member // 2
        return bh if group == 1 else bh * group + member

    def q_major(kk, step):
        member, major_step = _dkv_step(step, halves * group, q_majors)
        start, _, _, stop = _q_walk(kk, num_q_tiles=num_q_tiles, **schedule)
        tiles_per_major = major_q // tile_q
        if block is not None:
            # each query head's clean half, then its noisy one; a noisy k
            # tile walks neither and waits where the last clean one ended
            waited = jnp.maximum(major_step, jnp.minimum(
                start // tiles_per_major, num_q_majors - 1))
            return jnp.where(kk < num_k_tiles,
                             member % 2 * num_q_majors + waited,
                             2 * num_q_majors - 1)
        if window is None:
            # a major block wholly in this k tile's past is not fetched:
            # the index waits on the first one the tile needs
            return jnp.maximum(major_step, jnp.minimum(
                start // tiles_per_major, num_q_majors - 1))
        # nor is one wholly beyond its window: the index stays on the last
        iq = _major_index(major_step, start, tiles_per_major, q_majors,
                          num_q_majors)
        first = jnp.minimum(start // tiles_per_major, num_q_majors - 1)
        last = jnp.maximum((stop - 1) // tiles_per_major, first)
        return jnp.clip(iq, first, last)

    def streamed_head(bh, kk, step):
        if block is None:
            return q_head(bh, step)
        return jnp.where(kk < num_k_tiles, q_head(bh, step),
                         bh * group + group - 1)

    kv_q_spec, kv_do_spec = (pl.BlockSpec(
        (1, major_q, d),
        lambda bh, kk, step: (streamed_head(bh, kk, step),
                              q_major(kk, step), 0))
        for d in (head_dim, v_dim))
    kv_k_spec, kv_v_spec = (pl.BlockSpec((1, tile_k, d),
                                         lambda bh, kk, step: (bh, kk, 0))
                            for d in (head_dim, v_dim))
    kv_row_spec = pl.BlockSpec(
        (1, 1, major_q),
        lambda bh, kk, step: (streamed_head(bh, kk, step), 0,
                              q_major(kk, step)))
    own_specs, own = [], ()
    if block is not None:
        # the q tile at the k tile's own index, of the step's query head
        own_specs = [pl.BlockSpec(
            (1, tile_k, d), lambda bh, kk, step: (q_head(bh, step), kk, 0))
            for d in (head_dim, v_dim)] + 2 * [pl.BlockSpec(
                (1, 1, tile_k),
                lambda bh, kk, step: (q_head(bh, step), 0, kk))]
        own = (qb, dob, lse_rows, delta_rows)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, major_q=major_q,
                          num_q_tiles=num_q_tiles, group=group,
                          grid_majors=q_majors,
                          pieces=_pieces(transposed=True, **schedule),
                          block=block, **schedule),
        grid=(batch * kv_heads, halves * num_k_tiles,
              halves * group * q_majors),
        in_specs=[kv_q_spec, kv_k_spec, kv_v_spec, kv_do_spec,
                  kv_row_spec, kv_row_spec, *own_specs],
        out_specs=[kv_k_spec, kv_v_spec],
        out_shape=[
            _sds((batch * kv_heads, halves * seq_k, head_dim), k.dtype,
                 q, k, v, do),
            _sds((batch * kv_heads, halves * seq_k, v_dim), v.dtype,
                 q, k, v, do),
        ],
        scratch_shapes=[pltpu.VMEM((tile_k, head_dim), jnp.float32),
                        pltpu.VMEM((tile_k, v_dim), jnp.float32)],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
        name=names["flash_bwd_dkv"],
    )(qb, kb, vb, dob, lse_rows, delta_rows, *own)

    return (_from_bh(dq, batch, heads), _from_bh(dk, batch, kv_heads),
            _from_bh(dv, batch, kv_heads))


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, causal, scale, fwd_tiles, bwd_tiles, interpret,
           q_offset, window, block):
    o, _ = _fwd_impl(q, k, v, causal, scale, fwd_tiles, interpret, q_offset,
                     window, block)
    return o


def _flash_fwd(q, k, v, causal, scale, fwd_tiles, bwd_tiles, interpret,
               q_offset, window, block):
    o, lse = _fwd_impl(q, k, v, causal, scale, fwd_tiles, interpret,
                       q_offset, window, block)
    o, lse = map(checkpoint_name, (o, lse), KEPT_NAMES)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, fwd_tiles, bwd_tiles, interpret, q_offset,
               window, block, res, do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, causal, scale, bwd_tiles,
                     interpret, q_offset, window, block)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "q_offset",
    "window", "block_diffusion"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    q_offset: int = 0,
                    window: Optional[int] = None,
                    block_diffusion: Optional[int] = None) -> jax.Array:
    """Fused attention, q ``[batch, seq, heads, head_dim]``, k ``[batch,
    seq_k, kv_heads, head_dim]`` and v ``[batch, seq_k, kv_heads, v_dim]``;
    the result is ``[batch, seq, heads, v_dim]``. Differentiable (custom VJP
    with FlashAttention-2 recomputation kernels).

    ``v_dim`` need not be ``head_dim`` (latent attention: q and k carry
    positional dims that v does not). The scale is ``head_dim``'s, each
    operand's blocks are of its own width, the tiles and the VMEM budget
    count both, and the three calls are then named ``flash_mla_*``.

    Grouped heads: ``heads`` is a multiple of ``kv_heads``, and query head
    ``h`` reads K/V head ``h // (heads // kv_heads)``; K and V are not
    repeated in HBM, and dK, dV come back summed over each group.

    ``q_offset`` shifts the global position of q (in elements, any
    non-negative count) for causal masking — how ring attention uses a
    kernel per KV shard. ``window`` (causal only) keeps, for the query at
    position ``t``, the keys at ``t - window < s <= t``; K must hold every
    query's own position (``q_offset + seq <= seq_k``), so that no row is
    empty. ``block_q`` / ``block_k`` are upper bounds on the compute
    tiles, which the shapes, ``causal`` and ``window`` choose (``_tiles``);
    sequence lengths must be multiples of the tiles (pad upstream; a tile
    is the whole sequence when that is shorter).

    ``block_diffusion`` (causal only, no window or offset; a length that
    divides 128) is the mask a block-diffusion model trains under: the rows
    are the clean copy of each sequence and then its noisy copy, ``seq =
    seq_k = 2 L``; a clean row sees the clean rows of its block and of those
    before it, a noisy row the *clean* rows of the blocks before its own and
    the *noisy* rows of its own, under one softmax. The tiles are chosen for
    ``L`` and must be square; the three calls are named ``flash_bd_*``.
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    if q_offset < 0:
        raise ValueError("q_offset must be non-negative")
    seq_q, seq_k = q.shape[1], k.shape[1]
    if k.shape[:-1] != v.shape[:-1] or q.shape[-1] != k.shape[-1] \
            or q.shape[2] % k.shape[2]:
        raise ValueError(
            f"k {k.shape} and v {v.shape} must agree but for v's width, k's "
            f"width must be q's {q.shape[-1]}, and their heads must divide "
            f"q's {q.shape[2]}")
    if window is not None and (not causal or window < 1
                               or q_offset + seq_q > seq_k):
        raise ValueError(
            "window needs causal=True, window >= 1 and every query's own "
            f"position among the keys (q_offset {q_offset} + {seq_q} "
            f"queries > {seq_k} keys)")
    if block_diffusion is not None:
        if not causal or window is not None or q_offset or seq_q != seq_k \
                or seq_q % 2 or block_diffusion < 1 \
                or _LANES % block_diffusion:
            raise ValueError(
                "block_diffusion needs causal=True, no window or q_offset, "
                "a block length that divides 128 and as many q as k rows, "
                f"each sequence's clean and then its noisy copy (got "
                f"{block_diffusion}, {seq_q} and {seq_k} rows)")
        seq_q = seq_k = seq_q // 2      # the tiles and the walks are a half's
    fwd_tiles, bwd_tiles = _tiles(seq_q, seq_k, q.shape[-1], k.dtype,
                                  block_q, block_k, window, v.shape[-1])
    for tile_q, tile_k in (fwd_tiles, bwd_tiles):
        if seq_q % tile_q or seq_k % tile_k:
            raise ValueError(
                f"sequence lengths ({seq_q}, {seq_k}) must be multiples of "
                f"the block sizes ({tile_q}, {tile_k}); pad inputs first.")
        if block_diffusion is not None and (
                tile_q != tile_k or tile_q % block_diffusion):
            raise ValueError(
                f"block_diffusion needs square tiles of whole blocks, got "
                f"({tile_q}, {tile_k}) for blocks of {block_diffusion}")
    return _flash(q, k, v, causal, scale, fwd_tiles, bwd_tiles, interpret,
                  q_offset, window, block_diffusion)
