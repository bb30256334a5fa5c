"""Pallas flash-attention kernels (forward + backward) for TPU.

The reference contains no kernels at all — device math is delegated to
NCCL/MPI (SURVEY §2: "no CUDA kernels"). On TPU the hot op worth a custom
kernel in this framework's domain is attention (the long-context extension,
``parallel.ring_attention``): a fused blockwise softmax(QK^T)V that never
materializes the [T, T] score matrix in HBM and streams K/V through VMEM
one block at a time.

Design (per pallas_guide.md): 3-D grids (batch*heads, outer-blocks,
inner-blocks) with the inner dimension sequential ("arbitrary" semantics);
accumulators live in VMEM scratch and persist across the inner iterations.
Per-program VMEM footprint is O(block_q * d + block_k * d) — independent of
sequence length, so 16k+ contexts fit. Matmuls hit the MXU with f32
accumulation; masking and rescaling ride the VPU. Causal blocks skip
fully-masked work (`pl.when`), halving causal cost.

Training is first-class: ``flash_attention`` carries a ``jax.custom_vjp``
whose backward is the FlashAttention-2 recomputation scheme — the forward
saves only O(T) per-row logsumexp statistics, and two further kernels
recompute P = exp(S - lse) blockwise to produce dQ and dK/dV without ever
materializing the [T, T] matrix.

Per-row statistics (logsumexp, delta) cross the kernel boundary in
layouts whose last two block dims tile on the TPU: a trailing 128-lane
axis (``[B*H, T, 128]``, value repeated along lanes) where a kernel needs
them as a column against ``[block_q, block_k]`` scores, and a lane-dense
row (``[B*H, 1, T]``) where the dK/dV kernel works on transposed scores.
That kernel computes ``S^T = K Q^T`` directly, so every matmul in this
file is a plain or transposed-RHS product — none contracts dim 0 of its
left operand.

The three ``pallas_call``s are named ``flash_fwd``, ``flash_bwd_dq`` and
``flash_bwd_dkv``: the names a compiled program's custom calls and a
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float(jnp.finfo(jnp.float32).min)
_LANES = 128  # TPU vreg lane count: the trailing axis of column statistics


def _causal_mask(s, q_pos0, k_pos0, q_axis=0):
    """Mask future positions of a score block to the _NEG_INF sentinel.
    q positions run along ``q_axis`` of ``s`` and k positions along the
    other axis (``q_axis=1`` is the dK/dV kernel's transposed block).
    Shared by forward and backward so the two can never disagree on what
    was masked."""
    q_pos = q_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_pos0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _col(stat):
    """[rows, 128] lane-repeated statistic -> its [rows, 1] column."""
    return stat[:, :1]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, o_acc, m_acc, l_acc, *,
                scale: float, causal: bool, q_offset_blocks: int,
                num_k_blocks: int, block_q: int, block_k: int):
    # program_id must be read at kernel top level: inside a pl.when body it
    # escapes the interpreter's scope (breaks interpret=True on CPU)
    kk = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    def _update():
        q_block = q_ref[0].astype(jnp.float32) * scale  # [block_q, d]
        k_blk = k_ref[0].astype(jnp.float32)            # [block_k, d]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(  # [block_q, block_k] on the MXU
            q_block, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, (q_idx + q_offset_blocks) * block_q,
                             kk * block_k)
        m = _col(m_acc[...])
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        corr = jnp.where(m == _NEG_INF, 0.0, jnp.exp(m - m_new))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(m_new == _NEG_INF, 0.0, p)
        l_new = _col(l_acc[...]) * corr + p.sum(axis=1, keepdims=True)
        l_acc[...] = jnp.broadcast_to(l_new, l_acc.shape)
        m_acc[...] = jnp.broadcast_to(m_new, m_acc.shape)
        o_acc[...] = o_acc[...] * corr + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip k-blocks that lie entirely in this q-block's future
        last_q_pos = (q_idx + q_offset_blocks + 1) * block_q - 1

        @pl.when(last_q_pos >= kk * block_k)
        def _run():
            _update()
    else:
        _update()

    @pl.when(kk == num_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_acc[...], 1e-30)
        o_ref[0, ...] = (o_acc[...] / _col(l)).astype(o_ref.dtype)
        # per-row logsumexp residual for the backward pass; fully-masked
        # rows stay at the _NEG_INF sentinel (m saturates f32 addition)
        lse_ref[0, ...] = m_acc[...] + jnp.log(l)


def _recompute_p(q_blk, k_blk, lse, *, scale, causal, q_pos0, k_pos0,
                 transposed=False):
    """Recompute the normalized probability block P = exp(S - lse) and S's
    mask; shared by both backward kernels. All f32, MXU matmul.

    ``transposed=False``: P is [block_q, block_k] and ``lse`` its
    [block_q, 1] column. ``transposed=True``: P^T is [block_k, block_q],
    computed directly as K Q^T, and ``lse`` its [1, block_q] row."""
    lhs, rhs = (k_blk, q_blk * scale) if transposed else (q_blk * scale,
                                                          k_blk)
    s = jax.lax.dot_general(
        lhs, rhs, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    if causal:
        s = _causal_mask(s, q_pos0, k_pos0, q_axis=1 if transposed else 0)
    # fully-masked rows have lse at the sentinel; exp(s - sentinel) would
    # be exp(0) = 1 for masked s, so zero those rows explicitly
    p = jnp.exp(s - lse)
    return jnp.where(lse <= _NEG_INF / 2, 0.0, p)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, scale: float, causal: bool,
                   q_offset_blocks: int, num_k_blocks: int, block_q: int,
                   block_k: int):
    """dQ = (P * (dO V^T - delta)) K * scale, accumulated over k blocks.
    Grid (bh, q-block, k-block), k innermost sequential."""
    kk = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kk == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _update():
        q_blk = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do_blk = do_ref[0].astype(jnp.float32)
        p = _recompute_p(
            q_blk, k_blk, _col(lse_ref[0]), scale=scale, causal=causal,
            q_pos0=(q_idx + q_offset_blocks) * block_q, k_pos0=kk * block_k)
        dp = jax.lax.dot_general(  # dO V^T  [block_q, block_k]
            do_blk, v_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _col(delta_ref[0])) * scale
        dq_acc[...] += jax.lax.dot_general(
            ds, k_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last_q_pos = (q_idx + q_offset_blocks + 1) * block_q - 1

        @pl.when(last_q_pos >= kk * block_k)
        def _run():
            _update()
    else:
        _update()

    @pl.when(kk == num_k_blocks - 1)
    def _finalize():
        dq_ref[0, ...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale: float,
                    causal: bool, q_offset_blocks: int, num_q_blocks: int,
                    block_q: int, block_k: int):
    """dV = P^T dO and dK = (P * (dP - delta))^T Q, accumulated over q
    blocks. Grid (bh, k-block, q-block), q innermost sequential. Works on
    the transposed blocks P^T, dP^T = V dO^T, dS^T throughout, with lse and
    delta as [1, block_q] rows, so no operand is ever transposed."""
    iq = pl.program_id(2)
    k_idx = pl.program_id(1)

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _update():
        q_blk = q_ref[0].astype(jnp.float32)
        k_blk = k_ref[0].astype(jnp.float32)
        v_blk = v_ref[0].astype(jnp.float32)
        do_blk = do_ref[0].astype(jnp.float32)
        p_t = _recompute_p(
            q_blk, k_blk, lse_ref[0], scale=scale, causal=causal,
            q_pos0=(iq + q_offset_blocks) * block_q, k_pos0=k_idx * block_k,
            transposed=True)
        dv_acc[...] += jax.lax.dot_general(  # P^T dO  [block_k, d]
            p_t, do_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp_t = jax.lax.dot_general(  # V dO^T  [block_k, block_q]
            v_blk, do_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds_t = p_t * (dp_t - delta_ref[0]) * scale
        dk_acc[...] += jax.lax.dot_general(  # dS^T Q  [block_k, d]
            ds_t, q_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # skip q-blocks that lie entirely before this k-block (P == 0 there)
        last_q_pos = (iq + q_offset_blocks + 1) * block_q - 1

        @pl.when(last_q_pos >= k_idx * block_k)
        def _run():
            _update()
    else:
        _update()

    @pl.when(iq == num_q_blocks - 1)
    def _finalize():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


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


def _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret, q_offset):
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    num_k_blocks = seq_k // block_k
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        q_offset_blocks=q_offset // block_q, num_k_blocks=num_k_blocks,
        block_q=block_q, block_k=block_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=(batch * heads, seq_q // block_q, num_k_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, head_dim), lambda bh, i, kk: (bh, i, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda bh, i, kk: (bh, kk, 0)),
            pl.BlockSpec((1, block_k, head_dim), lambda bh, i, kk: (bh, kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, head_dim),
                         lambda bh, i, kk: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda bh, i, kk: (bh, i, 0)),
        ],
        out_shape=[
            _sds((batch * heads, seq_q, head_dim), q.dtype, q, k, v),
            _sds((batch * heads, seq_q, _LANES), jnp.float32, q, k, v),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(qb, kb, vb)
    # the kernel repeats each row's value along the lane axis; the residual
    # kept for the backward pass is the O(T) vector
    return _from_bh(o, batch, heads), lse[..., 0]


def _bwd_impl(q, k, v, o, lse, do, causal, scale, block_q, block_k,
              interpret, q_offset):
    batch, seq_q, heads, head_dim = q.shape
    seq_k = k.shape[1]
    num_q_blocks = seq_q // block_q
    num_k_blocks = seq_k // block_k
    qb, kb, vb = _to_bh(q), _to_bh(k), _to_bh(v)
    ob, dob = _to_bh(o), _to_bh(do)
    # delta_i = sum_d dO_id O_id = sum_j dP_ij P_ij  (softmax Jacobian term)
    delta = jnp.sum(dob.astype(jnp.float32) * ob.astype(jnp.float32),
                    axis=-1)  # [B*H, Tq]

    qkv_spec_q = pl.BlockSpec((1, block_q, head_dim),
                              lambda bh, i, kk: (bh, i, 0))
    qkv_spec_k = pl.BlockSpec((1, block_k, head_dim),
                              lambda bh, i, kk: (bh, kk, 0))
    # per-row statistics in the two layouts the kernels read (module
    # docstring): lane-repeated columns for dQ, lane-dense rows for dK/dV
    lse_cols, delta_cols = (
        jnp.broadcast_to(x[..., None], (*x.shape, _LANES))
        for x in (lse, delta))
    lse_rows, delta_rows = lse[:, None, :], delta[:, None, :]
    col_spec = pl.BlockSpec((1, block_q, _LANES),
                            lambda bh, i, kk: (bh, i, 0))

    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, causal=causal,
            q_offset_blocks=q_offset // block_q, num_k_blocks=num_k_blocks,
            block_q=block_q, block_k=block_k),
        grid=(batch * heads, num_q_blocks, num_k_blocks),
        in_specs=[qkv_spec_q, qkv_spec_k, qkv_spec_k, qkv_spec_q,
                  col_spec, col_spec],
        out_specs=qkv_spec_q,
        out_shape=_sds((batch * heads, seq_q, head_dim), q.dtype,
                       q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qb, kb, vb, dob, lse_cols, delta_cols)

    # dK/dV grid: (bh, k-block, q-block) — the q dimension is innermost so
    # the (1, block_q, d) operands re-index by the LAST grid axis here
    kv_q_spec = pl.BlockSpec((1, block_q, head_dim),
                             lambda bh, kk, i: (bh, i, 0))
    kv_k_spec = pl.BlockSpec((1, block_k, head_dim),
                             lambda bh, kk, i: (bh, kk, 0))
    kv_row_spec = pl.BlockSpec((1, 1, block_q),
                               lambda bh, kk, i: (bh, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, causal=causal,
            q_offset_blocks=q_offset // block_q, num_q_blocks=num_q_blocks,
            block_q=block_q, block_k=block_k),
        grid=(batch * heads, num_k_blocks, num_q_blocks),
        in_specs=[kv_q_spec, kv_k_spec, kv_k_spec, kv_q_spec,
                  kv_row_spec, kv_row_spec],
        out_specs=[kv_k_spec, kv_k_spec],
        out_shape=[
            _sds((batch * heads, seq_k, head_dim), k.dtype, q, k, v, do),
            _sds((batch * heads, seq_k, head_dim), v.dtype, q, k, v, do),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, head_dim), jnp.float32),
                        pltpu.VMEM((block_k, head_dim), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qb, kb, vb, dob, lse_rows, delta_rows)

    return (_from_bh(dq, batch, heads), _from_bh(dk, batch, heads),
            _from_bh(dv, batch, heads))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, q_offset):
    o, _ = _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                     q_offset)
    return o


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               q_offset):
    o, lse = _fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret,
                       q_offset)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, q_offset, res,
               do):
    q, k, v, o, lse = res
    return _bwd_impl(q, k, v, o, lse, do, causal, scale, block_q, block_k,
                     interpret, q_offset)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "q_offset"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None,
                    q_offset: int = 0) -> jax.Array:
    """Fused attention, shapes [batch, seq, heads, head_dim]. Differentiable
    (custom VJP with FlashAttention-2 recomputation kernels).

    ``q_offset`` shifts the global position of q (in elements) for causal
    masking — how ring attention uses a kernel per KV shard. Sequence
    lengths must be multiples of the block sizes (pad upstream; blocks
    auto-shrink to the sequence length when shorter).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    if seq_q % block_q or seq_k % block_k:
        raise ValueError(
            f"sequence lengths ({seq_q}, {seq_k}) must be multiples of the "
            f"block sizes ({block_q}, {block_k}); pad inputs first.")
    if q_offset < 0 or q_offset % block_q:
        raise ValueError(
            "q_offset must be a non-negative multiple of block_q")
    return _flash(q, k, v, causal, scale, block_q, block_k, interpret,
                  q_offset)
