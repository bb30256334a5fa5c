"""Chunked gated delta rule (Kimi Delta Attention's recurrence, and the
gated delta rule of arXiv:2412.06464) for TPU.

Per head, with a state ``S`` [d_k, d_v] — keys and values of any widths of
their own —, a decay ``alpha_t = exp(g_t)`` in (0, 1], one a channel (g
``[.., H * d_k]``: KDA) or one a head (g ``[.., H]``: ``Diag(alpha_t)`` is
then a multiple of the identity), and a write strength ``beta_t`` >= 0 (in
[0, 1] for KDA; up to 2 where ``I - beta k k^T`` may have a negative
eigenvalue: the triangular solve below is the same, its conditioning is
not)::

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
by pair, a key at a time (``_Chunk``). Under a decay a head the exponent
leaves the sum, ``A_ri = (k_r . k_i) exp(G_r - G_i)``: one product and one
``[C, C]`` matrix of exponents, none positive below the diagonal
(``_HeadChunk``; the kernels are then named ``gdn_fwd`` / ``gdn_bwd``).

Who does what. Two Pallas kernels, and nothing beside them. ``kda_fwd``
walks the chunks of a group of ``_HEADS_A_STEP`` heads (where the heads are
no whole number of groups of whole vregs — 15 heads 96 and 192 wide — the
last group reaches past the heads there are: ``_heads_a_step``) with their
states
(kept transposed, [d_v, d_k], so the decay scales lanes) in VMEM. A grid
step reads a chunk of q, k, v, g and beta out of the arguments as they lie
(``_blocks``), forms the chunk's operands in VMEM — the cumulative sums of
g, ``A`` and ``B``, ``(I + Diag(beta) A)^-1`` by substitution, ``W``,
``U~``, ``Q+``, ``K-``, ``gamma``: ``_Chunk`` — and advances the chain with
them (``_advance``, a head at a time); when a gradient is wanted it leaves
each chunk's starting state in HBM. The five operands never reach HBM.
``kda_bwd`` walks the chunks in reverse with the state's gradient in VMEM:
it forms the same operands again, recomputes ``U`` from the saved state,
transposes the chain (``_retreat``), and pulls the operands' gradients back
through the operands in the same grid step (``_Chunk.pull_back``: the
inverse's ``-M^T G M^T`` below the diagonal, the decayed products' two-sided
gradient, a sum from the chunk's end for ``dg``) to ``dq, dk, dv, dg,
dbeta``, which are all it writes. g, its cumulative sums, every exponent,
``A``, ``B``, the substitution, ``W``/``U~`` and the carried state are
float32, their products at full precision (multi-pass on the MXU); the
chain's own products take the five operands in the compute type. ``kda``
ties the two with a ``custom_vjp`` that keeps q, k, v, g, beta and the
chunk-boundary states — 0.54 GB a layer at 16,384 tokens x 32 heads x 128^2
(Kimi-Linear's shape) — and nothing of a chunk's interior; the saved states
are in the operands' type, which is all the backward's products take of
them. ``kda_fed(feed,
*args)`` keeps still less: the ``args`` of whatever makes q, k, v, g, beta
(a layer's projections), which its backward runs again. Under a
``jax.checkpoint`` that recomputes the layer even those are recomputed, and
``kda_fwd`` with them — unless its policy saves ``KEPT_NAMES``, the names
the forward rule gives the kernel's ``o`` (134 MB) and starting states (268
MB, bfloat16): then the recomputation does not make the call
(``models.parts.keep_policy``, as ``models.kimi_linear`` asks it).

The layout. The kernels read and write q, k, v, g and their gradients as
``[B, T, H * d]``, a head's channels side by side (``_specs``: blocks of
``(1, chunk, group * d)``; a decay a head as ``[B, T, H]``, laid out for the
kernels as beta is), and that is ``kda_fed``'s contract, arguments and
result: nothing between a layer's projections and the kernels, or
between the kernels and the layer's output projection, holds heads on an
axis of their own. ``kda`` is the four-axis form of the definition
(``[B, T, H, d]``, as ``kda_recurrent`` takes them), a reshape round
``kda_fed`` for tests and benchmarks; on the TPU that reshape is a copy, so
a model calls ``kda_fed``.

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
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _sds

CHUNK = 64
# what ``kda_fwd`` leaves that a recomputed block would run it again for:
# its output and the chunks' starting states, under these checkpoint names
KEPT_NAMES = ("kda.o", "kda.starts")
_SUB = 16            # rows of a sub-block of A and B
_HEADS_A_STEP = 4    # independent chains a grid step interleaves
_HI = jax.lax.Precision.HIGHEST


# -- the definition ---------------------------------------------------------


def kda_recurrent(q, k, v, g, beta, scale: Optional[float] = None):
    """The recurrence token by token, float32. q, k ``[B, T, H, d_k]``, v
    ``[B, T, H, d_v]``, g as q (a decay a channel) or ``[B, T, H]`` (a decay
    a head), beta ``[B, T, H]``. Returns ``(o [B, T, H, d_v] in v's dtype,
    the final state [B, H, d_k, d_v] float32)``."""
    scale = 1.0 / math.sqrt(q.shape[-1]) if scale is None else scale
    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 1, 0)  # noqa: E731
    if g.ndim == 3:     # one decay a head: every channel's
        g = g[..., None]

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


# -- a chunk's operands, formed inside the kernels ---------------------------


def _dot(a, b, contract, precision=None):
    return jax.lax.dot_general(a, b, ((contract[:1], contract[1:]), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _dot32(a, b, contract):
    """A float32 product at full precision (multi-pass on the MXU)."""
    return _dot(a, b, contract, _HI)


def _iota(shape, axis: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


class _Chunk:
    """One head's chunk: what q, k, v, the cumulative sums of g [C, .]
    (float32) and beta ``[C, 1]`` imply for the chain, and the pieces its
    transpose needs again. Every exponent taken is <= 0."""

    def __init__(self, q, k, v, cum, beta, scale: float):
        size, d = k.shape
        n = size // _SUB
        self.q, self.k, self.v, self.scale = q, k, v, scale
        # beta across the lanes once: a [C, 1] column read out of lane h
        # costs a lane permutation a vreg wherever it meets a matrix
        across = jnp.broadcast_to(beta, (size, max(size, d, v.shape[1])))
        self.by_beta = lambda x: across[:, :x.shape[1]] * x  # noqa: E731
        row, col = _iota((size, size), 0), _iota((size, size), 1)
        self.lower = col <= row
        self.strictly = col < row
        self.earlier = (col // _SUB) < (row // _SUB)
        self.own = (col // _SUB) == (row // _SUB)
        self.tri = self.lower.astype(jnp.float32)
        blocked = lambda a: a.reshape(n, _SUB, a.shape[-1])  # noqa: E731
        self.blocked = blocked
        last = cum[size - 1:]
        self.decay = jnp.exp(cum)
        self.to_last = jnp.exp(last - cum)
        self.gamma = jnp.exp(last)
        self.k_plus = k * self.decay
        self.q_plus = scale * q * self.decay
        self.k_minus = k * self.to_last
        # below the diagonal sub-blocks: rows decayed down to their
        # sub-block's first row, keys of earlier sub-blocks decayed up to it
        self.k3, self.q3, self.cum3 = blocked(k), blocked(q), blocked(cum)
        first = self.cum3[:, :1]
        self.down = jnp.exp(self.cum3 - first)                 # [n, SUB, d]
        self.k_hat, self.q_hat = self.k3 * self.down, self.q3 * self.down
        self.up = [None] + [jnp.exp(jnp.minimum(first[a] - cum, 0.0))
                            for a in range(1, n)]              # [C, d] each
        below = [jnp.zeros((2 * _SUB, size), jnp.float32)] + [
            _dot32(jnp.concatenate([self.k_hat[a], self.q_hat[a]], axis=0),
                   k * self.up[a], (1, 1)) for a in range(1, n)]
        below_a = jnp.concatenate([x[:_SUB] for x in below], axis=0)
        below_b = jnp.concatenate([x[_SUB:] for x in below], axis=0)
        # the diagonal sub-blocks pair by pair, a key at a time: column j of
        # every sub-block at once
        lane = _iota((n, _SUB, size), 2) % _SUB
        rank = _iota((n, _SUB, 1), 1)
        own_a = own_b = jnp.zeros((n, _SUB, size), jnp.float32)
        columns = []
        for j in range(_SUB):
            t = self.pair(j) * self.k3[:, j:j + 1]
            a_col = jnp.sum(self.k3 * t, axis=-1, keepdims=True)
            b_col = jnp.sum(self.q3 * t, axis=-1, keepdims=True)
            own_a = jnp.where(lane == j, a_col, own_a)
            own_b = jnp.where(lane == j, b_col, own_b)
            columns.append(jnp.where(rank > j, a_col, 0.0))
        self.a = jnp.where(self.earlier, below_a, jnp.where(
            self.own & self.strictly, own_a.reshape(size, size), 0.0))
        self.b = scale * jnp.where(self.earlier, below_b, jnp.where(
            self.own & self.lower, own_b.reshape(size, size), 0.0))
        self._solve(across, columns, row, col)

    def _solve(self, across, columns, row, col):
        """``(I + Diag(beta) A)^-1`` by substitution — inside the diagonal
        sub-blocks a column at a time (row r loses L[r, j] times row j,
        which is final by then; ``columns[j]`` is column j of every
        diagonal sub-block of ``A`` below its diagonal, [n, SUB, 1]), then
        sub-block against sub-block — and with it ``W`` and ``U~``."""
        size = self.k.shape[0]
        blocked, v = self.blocked, self.v
        beta3 = blocked(across[:, :size])
        inverse = blocked((row == col).astype(jnp.float32))
        for j in range(_SUB - 1):
            inverse = inverse - (beta3 * columns[j]) * inverse[:, j:j + 1]
        inverse = inverse.reshape(size, size)
        strip = self.by_beta(self.a)
        width = _SUB
        while width < size:
            coupling = jnp.where(
                (col // (2 * width) == row // (2 * width))
                & (col // width < row // width), strip, 0.0)
            # only the second sub-block of a pair gains entries: its rows
            # alone go through the two products
            blocks = [inverse[r:r + width] for r in range(0, size, width)]
            gain = _dot32(_dot32(jnp.concatenate(blocks[1::2], axis=0),
                                 coupling, (1, 0)), inverse, (1, 0))
            done = 0
            for i in range(1, len(blocks), 2):
                rows = blocks[i].shape[0]
                blocks[i] = blocks[i] - gain[done:done + rows]
                done += rows
            inverse = jnp.concatenate(blocks, axis=0)
            width *= 2
        self.inverse = inverse
        self.w = _dot32(inverse, self.by_beta(self.k_plus), (1, 0))
        self.u = _dot32(inverse, self.by_beta(v), (1, 0))

    def _solve_back(self, dw, du):
        """``_solve``'s transpose: ``(dr_v, dbeta, dK+, dA)`` with ``dv =
        beta * dr_v``. W, U~ = inverse @ (beta * [K+, V]); the inverse's
        transpose is -M^T G M^T below the diagonal, G = [dW, dU~] [beta K+,
        beta V]^T."""
        by_beta = self.by_beta
        dr_k = _dot32(self.inverse, dw, (0, 0))
        dr_v = _dot32(self.inverse, du, (0, 0))
        dstrip = -jnp.where(self.strictly, _dot32(dr_k, self.w, (1, 1))
                            + _dot32(dr_v, self.u, (1, 1)), 0.0)
        dbeta = jnp.sum(dr_k * self.k_plus, axis=-1, keepdims=True) \
            + jnp.sum(dr_v * self.v, axis=-1, keepdims=True) \
            + jnp.sum(dstrip * self.a, axis=-1, keepdims=True)
        return dr_v, dbeta, by_beta(dr_k), by_beta(dstrip)

    def pair(self, j: int):
        """``exp(G_r - G_j)`` for key ``j`` of every sub-block against its
        rows, [n, SUB, d]; 1 where r < j, which the masks drop."""
        return jnp.exp(jnp.minimum(self.cum3 - self.cum3[:, j:j + 1], 0.0))

    def chain(self, dtype):
        """``(W, U~, Q+, K-, B)`` in the chain's type."""
        return tuple(x.astype(dtype) for x in (
            self.w, self.u, self.q_plus, self.k_minus, self.b))

    def pull_back(self, dw, du, dq_plus, dk_minus, db, dgamma):
        """The operands' transpose: their gradients (float32) to ``(dq, dk,
        dv, dg, dbeta)``."""
        size, d = self.k.shape
        n = size // _SUB
        k, q, by_beta, blocked = self.k, self.q, self.by_beta, self.blocked
        dr_v, dbeta, dk_plus, da = self._solve_back(dw, du)
        db = self.scale * jnp.where(self.lower, db, 0.0)
        dk = dk_plus * self.decay + dk_minus * self.to_last
        dq = self.scale * self.decay * dq_plus
        carried = dk_minus * self.k_minus
        dcum = dk_plus * self.k_plus + dq_plus * self.q_plus - carried
        dlast = jnp.sum(carried, axis=0, keepdims=True) + dgamma * self.gamma
        dcum = dcum + jnp.where(_iota((size, 1), 0) == size - 1, dlast, 0.0)
        # the decayed products, two-sided: a pair's gradient reaches its row
        # (rows) and its key (keys), and G through both
        da_off = jnp.where(self.earlier, da, 0.0)
        db_off = jnp.where(self.earlier, db, 0.0)
        rows, keys = [jnp.zeros((2, _SUB, d), jnp.float32)], 0.0
        for a in range(1, n):
            grads = jnp.concatenate([da_off[a * _SUB:(a + 1) * _SUB],
                                     db_off[a * _SUB:(a + 1) * _SUB]], axis=0)
            rows.append(_dot32(grads, k * self.up[a], (1, 0)).reshape(
                2, _SUB, d) * self.down[a])
            keys = keys + self.up[a] * _dot32(grads, jnp.concatenate(
                [self.k_hat[a], self.q_hat[a]], axis=0), (0, 0))
        k_rows = jnp.stack([x[0] for x in rows])               # [n, SUB, d]
        q_rows = jnp.stack([x[1] for x in rows])
        # the diagonal sub-blocks: their gradients' columns side by side
        gather = (_iota((size, _SUB), 0) % _SUB
                  == _iota((size, _SUB), 1)).astype(jnp.float32)
        da_own = blocked(_dot32(jnp.where(self.own, da, 0.0), gather, (1, 0)))
        db_own = blocked(_dot32(jnp.where(self.own, db, 0.0), gather, (1, 0)))
        rank = _iota((n, _SUB, 1), 1)
        key_rows = jnp.zeros((n, _SUB, d), jnp.float32)
        for j in range(_SUB):
            # from the tile of 8 rows that holds key j on: the rows before
            # it take nothing from it, and half the keys lie in the second
            first = j - j % 8
            e, k_j, q_j, da_j, db_j = (x[:, first:] for x in (
                self.pair(j), self.k3, self.q3, da_own[:, :, j:j + 1],
                db_own[:, :, j:j + 1]))
            t = e * self.k3[:, j:j + 1]
            skipped = ((0, 0), (first, 0), (0, 0))
            k_rows = k_rows + jnp.pad(da_j * t, skipped)
            q_rows = q_rows + jnp.pad(db_j * t, skipped)
            to_key = jnp.sum((da_j * k_j + db_j * q_j) * e, axis=1,
                             keepdims=True)
            key_rows = key_rows + jnp.where(rank == j, to_key, 0.0)
        k_rows, q_rows = k_rows.reshape(size, d), q_rows.reshape(size, d)
        keys = keys + key_rows.reshape(size, d)
        dk = dk + k_rows + keys
        dq = dq + q_rows
        dcum = dcum + k * (k_rows - keys) + q * q_rows
        # g's cumulative sum, transposed: a sum from the chunk's end
        return dq, dk, by_beta(dr_v), _dot32(self.tri, dcum, (0, 0)), dbeta


class _HeadChunk(_Chunk):
    """``_Chunk`` where a head has one decay for all its channels (the gated
    delta rule of arXiv:2412.06464): ``cum`` is ``[C, 1]``, and ``A_ri = (k_r
    . k_i) exp(G_r - G_i)`` is one product under one matrix of exponents —
    every one of them <= 0 below the diagonal, so no sub-blocks are needed
    to keep them there. ``_Chunk`` fed the same decay broadcast over a
    head's channels is exact too and 2.5 times slower at keys 96 wide (on
    the chip 6.79 + 15.70 ms a layer of 8,192 tokens x 15 heads against
    3.41 + 5.49: PERF.md section 6), which is what this body is for."""

    def __init__(self, q, k, v, cum, beta, scale: float):
        size, d = k.shape
        n = size // _SUB
        self.q, self.k, self.v, self.scale = q, k, v, scale
        width = max(size, d, v.shape[1])
        across = jnp.broadcast_to(beta, (size, width))
        self.by_beta = lambda x: across[:, :x.shape[1]] * x  # noqa: E731
        row, col = _iota((size, size), 0), _iota((size, size), 1)
        self.lower = col <= row
        self.strictly = col < row
        self.own = (col // _SUB) == (row // _SUB)
        self.tri = self.lower.astype(jnp.float32)
        self.blocked = lambda a: a.reshape(n, _SUB, a.shape[-1])  # noqa: E731
        # G across the lanes once, as beta; G_i along the lanes is the
        # diagonal of that, summed out of its rows
        cum = jnp.broadcast_to(cum, (size, width))
        last = jnp.sum(jnp.where(_iota((size, 1), 0) == size - 1, cum, 0.0),
                       axis=0, keepdims=True)
        self.decay = jnp.exp(cum)[:, :d]
        self.to_last = jnp.exp(last - cum)[:, :d]
        self.gamma = jnp.exp(last)[:, :d]
        self.k_plus = k * self.decay
        self.q_plus = scale * q * self.decay
        self.k_minus = k * self.to_last
        along = jnp.sum(jnp.where(row == col, cum[:, :size], 0.0), axis=0,
                        keepdims=True)
        self.between = jnp.exp(jnp.minimum(cum[:, :size] - along, 0.0))
        pairs = _dot32(jnp.concatenate([k, q], axis=0), k, (1, 1))
        self.a = jnp.where(self.strictly, pairs[:size] * self.between, 0.0)
        self.b = scale * jnp.where(self.lower, pairs[size:] * self.between,
                                   0.0)
        # the diagonal sub-blocks' columns side by side: [C, SUB], column j
        # of its sub-block in lane j
        gather = (_iota((size, _SUB), 0) % _SUB
                  == _iota((size, _SUB), 1)).astype(jnp.float32)
        own = self.blocked(_dot32(jnp.where(self.own, self.a, 0.0), gather,
                                  (1, 0)))
        self._solve(across, [own[:, :, j:j + 1] for j in range(_SUB - 1)],
                    row, col)

    def pull_back(self, dw, du, dq_plus, dk_minus, db, dgamma):
        """As ``_Chunk.pull_back``, ``dg`` one number a row, ``[C, 1]``."""
        size = self.k.shape[0]
        k, q = self.k, self.q
        dr_v, dbeta, dk_plus, da = self._solve_back(dw, du)
        db = jnp.where(self.lower, db, 0.0)
        dk = dk_plus * self.decay + dk_minus * self.to_last
        dq = self.scale * self.decay * dq_plus
        carried = dk_minus * self.k_minus
        dcum = jnp.sum(dk_plus * self.k_plus + dq_plus * self.q_plus
                       - carried, axis=-1, keepdims=True)
        total = lambda x: jnp.sum(jnp.sum(  # noqa: E731
            x, axis=0, keepdims=True), axis=-1, keepdims=True)
        dlast = total(carried) + total(dgamma) * self.gamma[:, :1]
        dcum = dcum + jnp.where(_iota((size, 1), 0) == size - 1, dlast, 0.0)
        # the two products' gradients under the exponents, and the
        # exponents' own: G_r - G_i holds g_c for i < c <= r
        to_kk = da * self.between
        to_qk = self.scale * db * self.between
        both = jnp.concatenate([to_kk, to_qk], axis=0)
        dk = dk + _dot32(to_kk, k, (1, 0)) + _dot32(
            both, jnp.concatenate([k, q], axis=0), (0, 0))
        dq = dq + _dot32(to_qk, k, (1, 0))
        from_end = _dot32(self.tri, jnp.concatenate(
            [jnp.broadcast_to(dcum, (size, 128)),
             da * self.a + db * self.b], axis=1), (0, 0))
        dg = jnp.sum(jnp.where(self.strictly, from_end[:, 128:], 0.0),
                     axis=-1, keepdims=True) + from_end[:, :1]
        return dq, dk, self.by_beta(dr_v), dg, dbeta


def _chunk(q, k, v, cum, beta, scale):
    """A chunk's operands under a decay a channel (``cum`` as wide as k) or
    a head (``cum`` one column)."""
    form = _Chunk if cum.shape[1] == k.shape[1] else _HeadChunk
    return form(q, k, v, cum, beta, scale)


# One head's step of each kernel, jitted so that a step's heads (and both
# branches of ``_by_platform``) share one trace; inside a kernel the call is
# inlined.


@functools.partial(jax.jit, static_argnames=("scale", "dtype"))
def _advance(q, k, v, cum, beta, s, *, scale: float, dtype):
    """A chunk of the chain from the state ``s`` [d_v, d_k] it starts from:
    ``(s in the chain's type, O, the state it leaves)``."""
    chunk = _chunk(q, k, v, cum, beta, scale)
    w, u_c, q, k, b = chunk.chain(dtype)
    s_c = s.astype(dtype)
    u = u_c.astype(jnp.float32) - _dot(w, s_c, (1, 1))
    u_c = u.astype(dtype)
    o = _dot(q, s_c, (1, 1)) + _dot(b, u_c, (1, 0))
    return s_c, o, s * chunk.gamma + _dot(u_c, k, (0, 0))


@functools.partial(jax.jit, static_argnames=("scale",))
def _retreat(q, k, v, cum, beta, s_c, ds, do, *, scale: float):
    """The transpose of ``_advance`` for one chunk: ``s_c`` the state it
    started from, ``ds`` the gradient of the state it left, ``do`` of its
    output. Returns ``(dq, dk, dv, dg, dbeta, the gradient of the state it
    started from)``."""
    dtype = s_c.dtype
    chunk = _chunk(q, k, v, cum, beta, scale)
    w, u_c, q, k, b = chunk.chain(dtype)
    ds_c = ds.astype(dtype)
    u = u_c.astype(jnp.float32) - _dot(w, s_c, (1, 1))
    u_c = u.astype(dtype)
    du = _dot(b, do, (0, 0)) + _dot(k, ds_c, (1, 1))
    du_c = du.astype(dtype)
    grads = chunk.pull_back(
        -_dot(du_c, s_c, (1, 0)), du, _dot(do, s_c, (1, 0)),
        _dot(u_c, ds_c, (1, 0)), _dot(do, u_c, (1, 1)),
        jnp.sum(ds * s_c.astype(jnp.float32), axis=0, keepdims=True))
    return (*grads, ds * chunk.gamma + _dot(do, q, (0, 0))
            - _dot(du_c, w, (0, 0)))


# -- the two kernels ---------------------------------------------------------


def _there(heads: int, group: int):
    """``there(h, x)``: a step's block ``x`` of its head ``h``, zeros where
    the last group of ``group`` reaches past the ``heads`` there are. What
    a block reads past an array's end is unspecified (the interpreter puts
    NaN there); zeros neither write nor are read out (beta is zero there by
    ``_blocks``), so such a head's state, saved with the others', stays
    zero. Where the groups are whole nothing is asked or changed."""
    whole = heads % group
    if not whole:
        return lambda h, x: x
    first = pl.program_id(0) % (-(-heads // group)) * group
    return lambda h, x: x if h < whole else jnp.where(
        first + h < heads, x, jnp.zeros_like(x))


def _heads(q_ref, k_ref, v_ref, g_ref, beta_ref, there):
    """A grid step's operands ``(q, k, v, cum, beta)``, head by head, from
    its blocks of q, k, v, g ``[1, C, heads * d]`` and beta ``[1, 1, C,
    heads]`` (g laid out as beta where a head has one decay: ``cum`` is
    then one column a head). The cumulative sums of g are one product for
    the whole group,
    ahead of every head's own work: a head's pair-by-pair part needs
    nothing else, and with its sums queued on the MXU behind the products
    of the head before, the schedule ran the heads one after another."""
    size, heads = beta_ref.shape[2:]
    tri = (_iota((size, size), 1) <= _iota((size, size), 0))
    a_head = len(g_ref.shape) == 4
    cum = _dot32(tri.astype(jnp.float32), g_ref[0, 0] if a_head else g_ref[0],
                 (1, 0))
    d_k, d_v = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    for h in range(heads):
        keys = slice(h * d_k, (h + 1) * d_k)
        values = slice(h * d_v, (h + 1) * d_v)
        yield (there(h, q_ref[0, :, keys]).astype(jnp.float32),
               there(h, k_ref[0, :, keys]).astype(jnp.float32),
               there(h, v_ref[0, :, values]).astype(jnp.float32),
               there(h, cum[:, h:h + 1] if a_head else cum[:, keys]),
               beta_ref[0, 0][:, h:h + 1])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, final_ref,
                *rest, scale: float, heads: int):
    """One chunk of a group of heads: their operands formed, their chains
    advanced. Grid (head groups, chunks), the chunks sequential; ``state``
    [heads, d_v, d_k] persists across them. With a ``starts_ref`` each
    chunk's starting state is left in HBM."""
    *starts_ref, state = rest
    n = pl.program_id(1)
    there = _there(heads, state.shape[0])

    @pl.when(n == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    # inside a branch: the interpreter refuses refs sliced at the kernel's
    # top level under a vma-tracking shard_map (``pallas_attention``)
    @pl.when(n >= 0)
    def _run():
        d_v = state.shape[1]
        for h, operands in enumerate(
                _heads(q_ref, k_ref, v_ref, g_ref, beta_ref, there)):
            start, o, state[h] = _advance(*operands, state[h], scale=scale,
                                          dtype=q_ref.dtype)
            if starts_ref:
                starts_ref[0][0, h] = start
            o_ref[0, :, h * d_v:(h + 1) * d_v] = o.astype(o_ref.dtype)

    @pl.when(n == pl.num_programs(1) - 1)
    def _finalize():
        final_ref[...] = state[...]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *,
                scale: float, heads: int):
    """The transpose for one chunk of a group of heads, the chunks walked
    last to first (the index maps reverse them): the operands formed again,
    the chain's transpose, and the operands' own, in one step. ``dstate``
    is the gradient of the state the chunk leaves, transposed like it."""
    step = pl.program_id(1)
    there = _there(heads, dstate.shape[0])

    @pl.when(step == 0)
    def _init():
        dstate[...] = jnp.zeros_like(dstate)

    @pl.when(step >= 0)
    def _run():
        _, d_v, d_k = dstate.shape
        dbeta, dg_a_head = [], []
        for h, operands in enumerate(
                _heads(q_ref, k_ref, v_ref, g_ref, beta_ref, there)):
            dq, dk, dv, dg, dbeta_h, dstate[h] = _retreat(
                *operands, starts_ref[0, h], dstate[h],
                there(h, do_ref[0, :, h * d_v:(h + 1) * d_v]), scale=scale)
            dq_ref[0, :, h * d_k:(h + 1) * d_k] = dq.astype(dq_ref.dtype)
            dk_ref[0, :, h * d_k:(h + 1) * d_k] = dk.astype(dk_ref.dtype)
            dv_ref[0, :, h * d_v:(h + 1) * d_v] = dv.astype(dv_ref.dtype)
            if len(dg_ref.shape) == 4:
                dg_a_head.append(dg)
            else:
                dg_ref[0, :, h * d_k:(h + 1) * d_k] = dg
            dbeta.append(dbeta_h)
        dbeta_ref[0, 0] = jnp.concatenate(dbeta, axis=-1)
        if dg_a_head:
            dg_ref[0, 0] = jnp.concatenate(dg_a_head, axis=-1)


def _heads_a_step(heads: int, *widths: int) -> int:
    """How many heads a grid step takes: as many of ``_HEADS_A_STEP`` as
    divide ``heads``, where a group of heads ``widths`` wide is whole vregs
    of lanes (or every lane there is). Where it is not — 15 heads 96 and 192
    wide — ``_HEADS_A_STEP``, and the last group reaches past the heads
    there are: what a block reads past the array's end the kernels replace
    by zeros (``_there``), what it writes there is dropped."""
    group = math.gcd(heads, _HEADS_A_STEP)
    if group == heads or all(group * d % 128 == 0 for d in widths):
        return group
    return _HEADS_A_STEP


def _compiler_params(interpret: bool):
    return None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))


def _whole_chunks(x, chunk: int, more: int = 0):
    """``[B, T, .]`` with T padded with zeros to whole chunks (and ``more``
    zeros a token)."""
    return jnp.pad(x, ((0, 0), (0, -x.shape[1] % chunk), (0, more)))


def _blocks(q, k, v, g, beta, chunk: int):
    """The kernels' view of the arguments: q, k, v ``[B, T', H * d]`` as
    they were given (the index maps pick a chunk of a group of heads out of
    them as they lie), so g where a channel has a decay of its own; beta,
    and g where a head has one decay, ``[B, H' / group, T', group]``.
    ``T'`` is T padded to whole chunks, ``H'`` the heads to whole groups,
    with tokens and heads that neither decay nor write (g = 0, beta = 0)."""
    batch, _, heads = beta.shape
    group = _heads_a_step(heads, q.shape[-1] // heads, v.shape[-1] // heads)

    def by_group(x):
        x = _whole_chunks(x.astype(jnp.float32), chunk, -heads % group)
        return x.reshape(batch, x.shape[1], -1, group).swapaxes(1, 2)

    a_head = g.shape == beta.shape
    beta, g = by_group(beta), g.astype(jnp.float32)
    return (*(_whole_chunks(x, chunk) for x in (q, k, v)),
            by_group(g) if a_head else _whole_chunks(g, chunk), beta)


def _ungrouped(x, like):
    """``_blocks``' ``by_group`` undone: ``[B, T, H]`` as ``like``."""
    batch, seq, heads = like.shape
    return x.swapaxes(1, 2).reshape(batch, x.shape[2], -1)[:, :seq, :heads]


def _specs(ins, chunk: int, reverse: Optional[int] = None):
    """For ``ins`` laid out as ``_blocks``', over a grid (batch x head
    groups, chunks): the heads a step takes, the grid's first extent, and
    the block specs ``wide(d)``, ``a_head`` (beta's) and ``states(d_v,
    d_k)`` ([N, B*H', d_v, d_k]); with ``reverse`` (the number of chunks)
    the grid walks them last to first."""
    batch, groups, _, group = ins[4].shape
    at = (lambda c: c) if reverse is None else (lambda c: reverse - 1 - c)
    wide = lambda d: pl.BlockSpec(  # noqa: E731
        (1, chunk, group * d), lambda i, c: (i // groups, at(c), i % groups))
    a_head = pl.BlockSpec((1, 1, chunk, group),
                          lambda i, c: (i // groups, i % groups, at(c), 0))
    states = lambda d_v, d_k: pl.BlockSpec(  # noqa: E731
        (1, group, d_v, d_k), lambda i, c: (at(c), i, 0, 0))
    return group, batch * groups, wide, a_head, states


def _kernel_name(g, beta, which: str) -> str:
    """``kda_*`` under a decay a channel, ``gdn_*`` under a decay a head."""
    return ("gdn_" if g.shape == beta.shape else "kda_") + which


def _scan_fwd(q, k, v, g, beta, scale, chunk, save: bool, interpret: bool):
    """``(O [B, T', H * d_v], final state [B*H', d_v, d_k], the chunks'
    starting states [N, B*H', d_v, d_k] in q's type or None)``."""
    heads = beta.shape[-1]
    d_k, d_v = q.shape[-1] // heads, v.shape[-1] // heads
    ins = _blocks(q, k, v, g, beta, chunk)
    n = ins[0].shape[1] // chunk
    group, steps, wide, a_head, states = _specs(ins, chunk)
    lanes = steps * group
    out_shape = [_sds(ins[2].shape, v.dtype, *ins),
                 _sds((lanes, d_v, d_k), jnp.float32, *ins)]
    out_specs = [wide(d_v),
                 pl.BlockSpec((group, d_v, d_k), lambda i, c: (i, 0, 0))]
    if save:
        out_shape.append(_sds((n, lanes, d_v, d_k), q.dtype, *ins))
        out_specs.append(states(d_v, d_k))
    o, final, *starts = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, heads=heads),
        grid=(steps, n),
        in_specs=[wide(d_k), wide(d_k), wide(d_v),
                  a_head if ins[3].ndim == 4 else wide(d_k), a_head],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((group, d_v, d_k), jnp.float32)],
        compiler_params=_compiler_params(interpret), interpret=interpret,
        name=_kernel_name(g, beta, "fwd"))(*ins)
    return o, final, (starts[0] if save else None)


def _scan_bwd(q, k, v, g, beta, starts, do, scale, chunk, interpret: bool):
    """``(dq, dk, dv, dg, dbeta)`` in the arguments' shapes, as the kernel
    wrote them; dq, dk and dv in their types, dg and dbeta float32."""
    seq, heads = beta.shape[1:]
    d_k, d_v = q.shape[-1] // heads, v.shape[-1] // heads
    blocks = _blocks(q, k, v, g, beta, chunk)
    ins = (*blocks, starts, _whole_chunks(do.astype(q.dtype), chunk))
    n = starts.shape[0]
    group, steps, wide, a_head, states = _specs(ins, chunk, reverse=n)
    specs = [wide(d_k), wide(d_k), wide(d_v),
             a_head if blocks[3].ndim == 4 else wide(d_k), a_head]
    *grads, dbeta = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, heads=heads),
        grid=(steps, n),
        in_specs=[*specs, states(d_v, d_k), wide(d_v)],
        out_specs=specs,
        out_shape=[_sds(x.shape, x.dtype, *ins) for x in blocks],
        scratch_shapes=[pltpu.VMEM((group, d_v, d_k), jnp.float32)],
        compiler_params=_compiler_params(interpret), interpret=interpret,
        name=_kernel_name(g, beta, "bwd"))(*ins)
    if grads[3].ndim == 4:
        grads[3] = _ungrouped(grads[3], beta)
    return (*(x[:, :seq] for x in grads), _ungrouped(dbeta, beta))


def _by_platform(fn, interpret: Optional[bool], *args):
    """``fn(*args, interpret)``; with ``interpret`` ``None``, interpreted
    where the program is lowered for the CPU and compiled anywhere else."""
    if interpret is not None:
        return fn(*args, interpret)
    return jax.lax.platform_dependent(
        *args, cpu=lambda *a: fn(*a, True), default=lambda *a: fn(*a, False))


def _scale(scale: Optional[float], q, beta) -> float:
    """``d_k ** -0.5`` unless given; beta's last axis is the head count."""
    d_k = q.shape[-1] // beta.shape[-1]
    return 1.0 / math.sqrt(d_k) if scale is None else scale


def _forward(q, k, v, g, beta, scale, chunk, interpret, save: bool):
    batch, seq, heads = beta.shape
    o, final, starts = _by_platform(
        lambda *a: _scan_fwd(*a[:-1], _scale(scale, q, beta), chunk, save,
                             a[-1]),
        interpret, q, k, v, g, beta)
    final = final.reshape(batch, -1, v.shape[-1] // heads,
                          q.shape[-1] // heads)[:, :heads]
    return o[:, :seq], final.swapaxes(-1, -2), starts


def _as_given(*operands):
    return operands


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _kda(feed, scale, chunk, interpret, *args):
    o, final, _ = _forward(*feed(*args), scale, chunk, interpret, False)
    return o, final


def _kda_fwd(feed, scale, chunk, interpret, *args):
    o, final, starts = _forward(*feed(*args), scale, chunk, interpret, True)
    o, starts = map(checkpoint_name, (o, starts), KEPT_NAMES)
    return (o, final), (args, starts)


def _kda_bwd(feed, scale, chunk, interpret, res, cotangents):
    args, starts = res
    do, _ = cotangents      # the final state is a reading, not a result
    # the feed's results again, nothing of its interior kept: its transpose
    # recomputes it once the kernel's own is done
    inputs, feed_back = (args, lambda grads: grads) if feed is _as_given \
        else jax.vjp(jax.checkpoint(feed), *args)
    grads = _by_platform(
        lambda *a: _scan_bwd(*a[:-1], _scale(scale, inputs[0], inputs[4]),
                             chunk, a[-1]),
        interpret, *inputs, starts, do)
    return feed_back(tuple(x.astype(like.dtype)
                           for x, like in zip(grads, inputs)))


_kda.defvjp(_kda_fwd, _kda_bwd)


def kda_fed(feed, *args, scale: Optional[float] = None, chunk: int = CHUNK,
            interpret: Optional[bool] = None):
    """The delta rule on what ``feed(*args)`` returns, **heads side by side
    in the last axis**: q, k ``[B, T, H * d_k]``, v ``[B, T, H * d_v]``, g
    ``[B, T, H * d_k]`` (a decay a channel) or ``[B, T, H]`` (a decay a
    head) and beta ``[B, T, H]`` (whose last axis is the head count).
    Returns
    ``(o [B, T, H * d_v] in v's dtype, the final state [B, H, d_k, d_v]
    float32)``. That is how a layer's projections leave their matmuls and
    how the kernels' index maps read them; ``[B, T, H, d]`` tiles (heads,
    lanes) on the TPU where ``[B, T, H * d]`` tiles (tokens, lanes), so a
    reshape between the two is a copy of the whole tensor (``kda`` makes
    it, for callers that hold heads on an axis of their own).

    It keeps ``args`` for its backward pass, not ``feed``'s results:
    ``feed`` — whatever turns a layer's projections into q, k, v, g and
    beta: a convolution, a normalisation, the decay's non-linearity — runs
    again there, and its transpose after the chain's. At Kimi-Linear's
    16,384 tokens x 32 heads x 128 that is 1.3 GB a layer less held between
    the forward and the backward pass. ``feed`` is a function of arrays alone,
    differentiable in all of them.

    The forward rule names ``o`` and the chunks' starting states
    (``KEPT_NAMES``) — an identity without a ``jax.checkpoint`` policy that
    saves them; with one (``models.parts.keep_policy``) a recomputed layer
    holds those 0.40 GB from its forward to its backward pass and does not
    run ``kda_fwd`` a second time for them (``feed`` still runs in this
    function's backward)."""
    if chunk % _SUB:
        raise ValueError(f"chunk must be a multiple of {_SUB}, got {chunk}")
    return _kda(feed, scale, chunk, interpret, *args)


@functools.partial(jax.jit, static_argnames=("scale", "chunk", "interpret"))
def kda(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
        beta: jax.Array, scale: Optional[float] = None, chunk: int = CHUNK,
        interpret: Optional[bool] = None):
    """The gated delta rule in chunks. q, k ``[batch, seq, heads, d_k]`` and
    v ``[batch, seq, heads, d_v]`` in the compute type, ``g`` the log of the
    decay (float32, <= 0), ``[batch, seq, heads, d_k]`` a channel or
    ``[batch, seq, heads]`` a head, ``beta`` ``[batch, seq, heads]`` >= 0
    (in [0, 2] in the models). Returns ``(o [batch, seq, heads,
    d_v] in v's dtype, the final state [batch, heads, d_k, d_v] float32)``.
    Differentiable in q, k, v, g and beta through ``o``; the final state is
    a reading and carries no gradient. ``chunk`` is a multiple of 16; any
    ``seq`` (padded inside). The state starts at zero. ``scale`` defaults
    to ``d_k ** -0.5``. A reshape round ``kda_fed``, which takes and
    returns the heads side by side."""
    flat = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
    o, final = kda_fed(_as_given, flat(q), flat(k), flat(v),
                       g if g.ndim == 3 else flat(g), beta,
                       scale=scale, chunk=chunk, interpret=interpret)
    return o.reshape(v.shape), final
