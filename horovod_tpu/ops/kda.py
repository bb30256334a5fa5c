"""Chunked gated delta rule (Kimi Delta Attention's recurrence) for TPU.

Per head, with a state ``S`` [d_k, d_v], a per-channel decay ``alpha_t =
exp(g_t)`` in (0, 1] and a write strength ``beta_t`` in [0, 1]::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

``kda_recurrent`` is that, token by token (``lax.scan``): the definition,
for tests and for reading. ``kda`` is the chunked form, the one that trains:
a scan over 16,384 tokens is bound by latency and its backward would keep a
state per token.

The chunked form (chunks of ``CHUNK`` tokens; ``G_r`` the cumulative sum of
``g`` inside a chunk, row ``r`` inclusive; ``S`` the state the chunk starts
from). Writing each token's net write as ``u_r``, ``S_r = Diag(exp G_r) S +
sum_{i<=r} Diag(exp(G_r - G_i)) k_i u_i^T`` and

    (I + Diag(beta) A) U = Diag(beta) (V - K+ S),
    A_ri = sum_c k_rc k_ic exp(G_rc - G_ic)   (i < r),   K+_r = k_r exp(G_r)

a unit lower-triangular system (the UT/WY form), solved once a chunk in
float32: with ``M = (I + Diag(beta) A)^-1 Diag(beta)``, ``W = M K+`` and
``U~ = M V``, a chunk is three small products and an update::

    U  = U~ - W S
    O  = Q+ S + B U        Q+_r = scale q_r exp(G_r),  B_ri = scale sum_c
                           q_rc k_ic exp(G_rc - G_ic)  (i <= r)
    S' = Diag(gamma) S + K-^T U      K-_i = k_i exp(G_last - G_i),
                                     gamma = exp(G_last)

No exponent above is positive where it is used, so nothing overflows
however fast a channel forgets: ``A`` and ``B`` are formed in sub-blocks of
``_SUB`` rows — an off-diagonal sub-block as a product of rows decayed down
to the sub-block's first row and keys decayed up to it, a diagonal one pair
by pair (``_decayed_products``).

Who does what. ``_prepare`` (XLA, differentiated by JAX, a batch of chunks
at a time under ``jax.checkpoint`` so that its pair-by-pair tensors never
exist for a whole sequence) turns q, k, v, g, beta into the chain's
operands ``W, U~, Q+, K-, B, gamma``. The chain itself, the only
sequential part, is two Pallas kernels: ``kda_fwd`` walks a head's chunks
with ``S`` (kept transposed, [d_v, d_k], so the decay scales lanes) in VMEM
and, when a gradient is wanted, leaves each chunk's starting state in HBM;
``kda_bwd`` walks them in reverse with the state's gradient in VMEM,
recomputes ``U`` from the saved state and returns the operands' gradients,
which JAX pulls back through ``_prepare``. ``kda`` ties them with a
``custom_vjp`` that keeps q, k, v, g, beta and the chunk-boundary states —
0.54 GB a layer at 16,384 tokens x 32 heads x 128^2 — and nothing of a
chunk's interior; the saved states are in the operands' type, which is all
the backward's products take of them. ``kda_fed(feed, *args)`` keeps still
less: the ``args`` of whatever makes q, k, v, g, beta (a layer's
projections), which its backward runs again.

``interpret`` as in ``pallas_attention``; left ``None`` the choice follows
the platform the program is *lowered* for (``lax.platform_dependent``), so
a step compiled for a described TPU from a CPU box gets the kernels.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _sds
from .spmd import vary_like

CHUNK = 64
_SUB = 16            # rows of a sub-block of A and B
_HEADS_A_STEP = 4    # independent chains a grid step interleaves
# (chunk, head) pairs whose operands are prepared together: bounds the
# pair-by-pair tensors of the diagonal sub-blocks ([pairs, C/16, 16, 16, d_k]
# float32: 134 MB at d_k 128) and the float32 intermediates of the transpose
_PREPARE_TOGETHER = 256
_HI = jax.lax.Precision.HIGHEST


# -- the definition ---------------------------------------------------------


def kda_recurrent(q, k, v, g, beta, scale: Optional[float] = None):
    """The recurrence token by token, float32. q, k, g ``[B, T, H, d_k]``,
    v ``[B, T, H, d_v]``, beta ``[B, T, H]``. Returns ``(o [B, T, H, d_v]
    in v's dtype, the final state [B, H, d_k, d_v] float32)``."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731

    def token(state, x):
        q_t, k_t, v_t, g_t, beta_t = x
        state = state * jnp.exp(g_t)[..., None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=_HI)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - seen),
            precision=_HI)
        return state, scale * jnp.einsum("bhkv,bhk->bhv", state, q_t,
                                         precision=_HI)

    batch, _, heads, d_k = q.shape
    state, o = jax.lax.scan(
        token, jnp.zeros((batch, heads, d_k, v.shape[-1]), jnp.float32),
        tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1).astype(v.dtype), state


# -- a chunk's operands (XLA) -----------------------------------------------


@jax.custom_vjp
def _unit_lower_inverse(lower):
    """``(I + L)^-1`` for strictly lower-triangular ``L`` [..., C, C],
    float32, by substitution: row by row inside diagonal blocks of ``_SUB``,
    block by block below them. (The product form ``(I - L)(I + L^2)...``
    is exact too, but cancels catastrophically where keys repeat.)"""
    size = lower.shape[-1]
    blocks = range(0, size, _SUB)
    eye = jnp.eye(_SUB, dtype=lower.dtype)
    diagonal = jnp.stack([lower[..., i:i + _SUB, i:i + _SUB]
                          for i in blocks], axis=-3)

    def row(r, inverse):
        # rows from r on are still the identity's, and L[r, r:] is zero
        new = eye[r] - jnp.einsum("...j,...jk->...k", diagonal[..., r, :],
                                  inverse, precision=_HI)
        return jax.lax.dynamic_update_index_in_dim(inverse, new, r, -2)

    # the carry varies over mesh axes as the operand does (shard_map)
    diagonal = jax.lax.fori_loop(
        1, _SUB, row, *vary_like(lower, jnp.broadcast_to(eye, diagonal.shape)))
    rows = []
    for n, i in enumerate(blocks):
        own = diagonal[..., n, :, :]
        parts = [own, jnp.zeros((*own.shape[:-1], size - i - _SUB),
                                own.dtype)]
        if i:
            above = jnp.concatenate([r[..., :i] for r in rows], axis=-2)
            parts.insert(0, -jnp.matmul(
                own, jnp.matmul(lower[..., i:i + _SUB, :i], above,
                                precision=_HI), precision=_HI))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _inverse_fwd(lower):
    inverse = _unit_lower_inverse(lower)
    return inverse, inverse


def _inverse_bwd(inverse, g):
    transposed = jnp.swapaxes(inverse, -1, -2)
    grad = -jnp.matmul(transposed, jnp.matmul(g, transposed, precision=_HI),
                       precision=_HI)
    return (jnp.tril(grad, -1),)


_unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def _decayed_products(x, k, cum):
    """``P_ri = sum_c x_rc k_ic exp(cum_rc - cum_ic)`` for ``i <= r`` inside
    each chunk, zero above the diagonal. x ``[..., X, C, d]`` (X kinds of
    rows against the same keys), k and cum ``[..., C, d]``, ``cum``
    non-increasing along C. Every exponent taken is <= 0."""
    *lead, kinds, size, d = x.shape
    n = size // _SUB
    blocked = lambda a: a.reshape(*a.shape[:-2], n, _SUB, d)  # noqa: E731
    cum_b, k_b = blocked(cum), blocked(k)
    x_b = x.reshape(*lead, kinds, n, _SUB, d)
    first = cum_b[..., :1, :]                       # [..., n, 1, d]
    # below the diagonal sub-blocks: rows decayed down to their sub-block's
    # first row, keys of earlier sub-blocks decayed up to it
    x_hat = x_b * jnp.exp(cum_b - first)[..., None, :, :, :]
    k_up = k[..., None, :, :] * jnp.exp(jnp.minimum(
        first - cum[..., None, :, :], 0.0))         # [..., n, C, d]
    below = jnp.einsum("...xnrd,...nid->...xnri", x_hat, k_up,
                       precision=_HI).reshape(*lead, kinds, size, size)
    # the diagonal sub-blocks pair by pair
    pair = jnp.exp(jnp.minimum(
        cum_b[..., :, None, :] - cum_b[..., None, :, :], 0.0))
    keyed = (k_b[..., None, :, :] * pair)[..., None, :, :, :, :]
    own = jnp.sum(x_b[..., None, :] * keyed, axis=-1)  # [..., X, n, SUB, SUB]
    placed = jnp.einsum("...nri,nm->...nrmi", own, jnp.eye(n, dtype=x.dtype)
                        ).reshape(*lead, kinds, size, size)
    r = jnp.arange(size)
    earlier = (r[None, :] // _SUB) < (r[:, None] // _SUB)
    return jnp.where(earlier, below,
                     jnp.where(r[None, :] <= r[:, None], placed, 0.0))


def _chunk_operands(q, k, v, g, beta, *, scale: float):
    """The chain's operands for chunks ``[..., C, d]``: ``(W, U~, Q+, K-,
    B, gamma)``, float32 inside, the first five cast to q's dtype."""
    dtype = q.dtype
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    cum = jnp.cumsum(g, axis=-2)
    decay = jnp.exp(cum)
    last = cum[..., -1:, :]
    products = _decayed_products(jnp.stack([k, q], axis=-3), k, cum)
    a = jnp.tril(products[..., 0, :, :], -1)
    b = scale * products[..., 1, :, :]
    solve = _unit_lower_inverse(beta[..., :, None] * a)
    wu = jnp.matmul(solve, beta[..., :, None] * jnp.concatenate(
        [k * decay, v], axis=-1), precision=_HI)
    operands = (wu[..., :k.shape[-1]], wu[..., k.shape[-1]:],
                scale * q * decay, k * jnp.exp(last - cum), b)
    return (*(x.astype(dtype) for x in operands), jnp.exp(last))


def _to_chunks(x, chunk: int):
    """``[B, T, H, d]`` -> ``[N, B*H, C, d]`` for the ``N`` chunks of ``C =
    chunk`` tokens, the sequence padded with zeros to a whole number of
    them."""
    batch, seq, heads, d = x.shape
    x = jnp.pad(x, ((0, 0), (0, -seq % chunk), (0, 0), (0, 0)))
    x = x.reshape(batch, -1, chunk, heads, d)
    return x.transpose(1, 0, 3, 2, 4).reshape(-1, batch * heads, chunk, d)


def _to_tokens(o, batch: int, seq: int):
    """``_to_chunks`` undone, the padding dropped."""
    n, lanes, chunk, d = o.shape
    o = o.reshape(n, batch, lanes // batch, chunk, d).transpose(1, 0, 3, 2, 4)
    return o.reshape(batch, n * chunk, lanes // batch, d)[:, :seq]


def _prepare(q, k, v, g, beta, *, scale: float, chunk: int):
    """q, k, g ``[B, T, H, d_k]``, v ``[B, T, H, d_v]``, beta ``[B, T, H]``
    -> the chain's operands, each ``[N, B*H, C, .]`` (gamma ``[N, B*H, 1,
    d_k]``). A sequence that is no whole number of chunks is padded with
    tokens that neither decay nor write (g = 0, beta = 0)."""
    batch, _, heads, _ = q.shape
    chunks = functools.partial(_to_chunks, chunk=chunk)
    xs = (chunks(q), chunks(k), chunks(v), chunks(g.astype(jnp.float32)),
          chunks(beta.astype(jnp.float32)[..., None])[..., 0])
    n = xs[0].shape[0]
    # a few chunks at a time: XLA fuses the pair-by-pair tensors into their
    # sums going forward, but keeps them for the transpose (4 GB for a layer
    # of 16,384 tokens x 32 heads) unless they are this small
    together = math.gcd(n, max(1, _PREPARE_TOGETHER // (batch * heads)))
    body = jax.checkpoint(functools.partial(_chunk_operands, scale=scale))
    out = jax.lax.map(
        lambda a: body(*a),
        tuple(x.reshape(n // together, together, *x.shape[1:]) for x in xs))
    return tuple(x.reshape(n, *x.shape[2:]) for x in out)


# -- the chain (Pallas) -----------------------------------------------------


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(w_ref, u_ref, q_ref, k_ref, b_ref, gamma_ref, o_ref,
                final_ref, *rest, heads: int):
    """One chunk of ``heads`` heads' chains. Grid (head groups, chunks), the
    chunks sequential; ``state`` [heads, d_v, d_k] persists across them. With
    a ``starts_ref`` each chunk's starting state is left in HBM."""
    *starts_ref, state = rest
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    # inside a branch: the interpreter refuses refs sliced at the kernel's
    # top level under a vma-tracking shard_map (``pallas_attention``)
    @pl.when(n >= 0)
    def _run():
        dtype = w_ref.dtype
        for h in range(heads):
            s = state[h]                                   # [d_v, d_k]
            s_c = s.astype(dtype)
            if starts_ref:
                starts_ref[0][0, h] = s_c
            u = u_ref[0, h].astype(jnp.float32) - _dot(w_ref[0, h], s_c,
                                                       (1, 1))
            u_c = u.astype(dtype)
            o = _dot(q_ref[0, h], s_c, (1, 1)) + _dot(b_ref[0, h], u_c,
                                                      (1, 0))
            o_ref[0, h] = o.astype(o_ref.dtype)
            state[h] = s * gamma_ref[0, h] + _dot(u_c, k_ref[0, h], (0, 0))

    @pl.when(n == pl.num_programs(1) - 1)
    def _finalize():
        final_ref[...] = state[...]


def _bwd_kernel(w_ref, u_ref, q_ref, k_ref, bt_ref, gamma_ref, starts_ref,
                do_ref, dw_ref, du_ref, dq_ref, dk_ref, db_ref, dgamma_ref,
                dstate, *, heads: int):
    """The chain's transpose for one chunk of ``heads`` heads, the chunks
    walked last to first (the index maps reverse them); ``dstate`` is the
    gradient of the state the chunk leaves, transposed like the state."""
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    @pl.when(step >= 0)
    def _run():
        dtype = w_ref.dtype
        for h in range(heads):
            s_c, ds = starts_ref[0, h], dstate[h]          # [d_v, d_k]
            ds_c = ds.astype(dtype)
            w, q, k, do = w_ref[0, h], q_ref[0, h], k_ref[0, h], do_ref[0, h]
            u = u_ref[0, h].astype(jnp.float32) - _dot(w, s_c, (1, 1))
            u_c = u.astype(dtype)
            du = _dot(bt_ref[0, h], do, (1, 0)) + _dot(k, ds_c, (1, 1))
            du_c = du.astype(dtype)
            du_ref[0, h] = du_c
            db_ref[0, h] = _dot(do, u_c, (1, 1)).astype(db_ref.dtype)
            dq_ref[0, h] = _dot(do, s_c, (1, 0)).astype(dq_ref.dtype)
            dk_ref[0, h] = _dot(u_c, ds_c, (1, 0)).astype(dk_ref.dtype)
            dw_ref[0, h] = (-_dot(du_c, s_c, (1, 0))).astype(dw_ref.dtype)
            dgamma_ref[0, h] = jnp.sum(ds * s_c.astype(jnp.float32), axis=0,
                                       keepdims=True)
            dstate[h] = ds * gamma_ref[0, h] + _dot(do, q, (0, 0)) \
                - _dot(du_c, w, (0, 0))


def _heads_a_step(lanes: int) -> int:
    return math.gcd(lanes, _HEADS_A_STEP)


def _compiler_params(interpret: bool):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _spec(array, heads: int, reverse: Optional[int] = None):
    """One chunk of ``heads`` heads of ``array`` [N, B*H, ., .]; with
    ``reverse`` (the number of chunks) the grid walks them last to first."""
    index = (lambda i, n: (n, i, 0, 0)) if reverse is None else \
        (lambda i, n: (reverse - 1 - n, i, 0, 0))
    return pl.BlockSpec((1, heads, *array.shape[2:]), index)


def _chain_fwd(operands, save: bool, interpret: bool):
    """``(O [N, B*H, C, d_v], final state [B*H, d_v, d_k], the chunks'
    starting states [N, B*H, d_v, d_k] or None)``."""
    w, u, q, k, b, gamma = operands
    n, lanes, _, d_k = w.shape
    d_v = u.shape[-1]
    heads = _heads_a_step(lanes)
    ins = (w, u, q, k, b, gamma)
    state = jax.ShapeDtypeStruct((n, lanes, d_v, d_k), w.dtype)
    out_shape = [_sds(u.shape, u.dtype, *ins),
                 _sds((lanes, d_v, d_k), jnp.float32, *ins)]
    out_specs = [_spec(u, heads),
                 pl.BlockSpec((heads, d_v, d_k), lambda i, n: (i, 0, 0))]
    if save:
        out_shape.append(_sds(state.shape, state.dtype, *ins))
        out_specs.append(_spec(state, heads))
    o, final, *starts = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=heads),
        grid=(lanes // heads, n),
        in_specs=[_spec(x, heads) for x in ins],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, d_v, d_k), jnp.float32)],
        compiler_params=_compiler_params(interpret), interpret=interpret,
        name="kda_fwd")(*ins)
    return o, final, (starts[0] if save else None)


def _chain_bwd(operands, starts, do, interpret: bool):
    """The gradients of ``_prepare``'s outputs, in their order."""
    w, u, q, k, b, gamma = operands
    n, lanes = w.shape[:2]
    heads = _heads_a_step(lanes)
    ins = (w, u, q, k, jnp.swapaxes(b, -1, -2), gamma, starts, do)
    outs = (w, u, q, k, b, gamma)
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, heads=heads),
        grid=(lanes // heads, n),
        in_specs=[_spec(x, heads, reverse=n) for x in ins],
        out_specs=[_spec(x, heads, reverse=n) for x in outs],
        out_shape=[_sds(x.shape, x.dtype, *ins) for x in outs],
        scratch_shapes=[pltpu.VMEM((heads, *starts.shape[2:]), jnp.float32)],
        compiler_params=_compiler_params(interpret), interpret=interpret,
        name="kda_bwd")(*ins))


def _by_platform(fn, interpret: Optional[bool], *args):
    """``fn(*args, interpret)``; with ``interpret`` ``None``, interpreted
    where the program is lowered for the CPU and compiled anywhere else."""
    if interpret is not None:
        return fn(*args, interpret)
    return jax.lax.platform_dependent(
        *args, cpu=lambda *a: fn(*a, True), default=lambda *a: fn(*a, False))


def _forward(q, k, v, g, beta, scale, chunk, interpret, save: bool):
    batch, seq, heads, d_k = q.shape
    scale = 1.0 / math.sqrt(d_k) if scale is None else scale
    operands = _prepare(q, k, v, g, beta, scale=scale, chunk=chunk)
    o, final, starts = _by_platform(
        lambda *a: _chain_fwd(a[:-1], save, a[-1]), interpret, *operands)
    final = final.reshape(batch, heads, v.shape[-1], d_k).swapaxes(-1, -2)
    return _to_tokens(o, batch, seq), final, starts


def _as_given(*operands):
    return operands


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _kda(feed, scale, chunk, interpret, *args):
    o, final, _ = _forward(*feed(*args), scale, chunk, interpret, False)
    return o, final


def _kda_fwd(feed, scale, chunk, interpret, *args):
    o, final, starts = _forward(*feed(*args), scale, chunk, interpret, True)
    return (o, final), (args, starts)


def _kda_bwd(feed, scale, chunk, interpret, res, cotangents):
    args, starts = res
    do, _ = cotangents      # the final state is a reading, not a result
    # the feed's results again, nothing of its interior kept: its transpose
    # recomputes it once the chain's own is done
    inputs, feed_back = (args, lambda grads: grads) if feed is _as_given \
        else jax.vjp(jax.checkpoint(feed), *args)
    scale = 1.0 / math.sqrt(inputs[0].shape[-1]) if scale is None else scale
    operands, pull_back = jax.vjp(
        functools.partial(_prepare, scale=scale, chunk=chunk), *inputs)
    return feed_back(pull_back(_by_platform(
        lambda *a: _chain_bwd(a[:6], a[6], a[7], a[-1]), interpret,
        *operands, starts, _to_chunks(do, chunk).astype(operands[1].dtype))))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_fed(feed, *args, scale: Optional[float] = None, chunk: int = CHUNK,
            interpret: Optional[bool] = None):
    """``kda(*feed(*args))`` that keeps ``args`` for its backward pass, not
    ``feed``'s results: ``feed`` — whatever turns a layer's projections into
    q, k, v, g and beta: a convolution, a normalisation, the decay's
    non-linearity — runs again there, and its transpose after the chain's.
    At 16,384 tokens x 32 heads x 128 that is 1.3 GB a layer less held
    between the forward and the backward pass. ``feed`` is a function of
    arrays alone, differentiable in all of them."""
    if chunk % _SUB:
        raise ValueError(f"chunk must be a multiple of {_SUB}, got {chunk}")
    return _kda(feed, scale, chunk, interpret, *args)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, scale: Optional[float] = None, chunk: int = CHUNK,
        interpret: Optional[bool] = None):
    """The gated delta rule in chunks. q, k ``[batch, seq, heads, d_k]`` and
    v ``[batch, seq, heads, d_v]`` in the compute type, ``g`` ``[batch, seq,
    heads, d_k]`` the log of the per-channel decay (float32, <= 0), ``beta``
    ``[batch, seq, heads]`` in [0, 1]. Returns ``(o [batch, seq, heads,
    d_v] in v's dtype, the final state [batch, heads, d_k, d_v] float32)``.
    Differentiable in q, k, v, g and beta through ``o``; the final state is
    a reading and carries no gradient. ``chunk`` is a multiple of 16; any
    ``seq`` (padded inside). The state starts at zero. ``scale`` defaults
    to ``d_k ** -0.5``."""
    return kda_fed(_as_given, q, k, v, g, beta, scale=scale, chunk=chunk,
                   interpret=interpret)
