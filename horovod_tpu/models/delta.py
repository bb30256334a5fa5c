"""What Kimi-Linear's KDA and Olmo-Hybrid's gated delta rule share between
their projections and ``ops.kda``: the short causal convolution, the taps'
and the decay's initializers, sums over a head's channels where they lie,
what feeds the rule (``conditioned``), the rule behind its ``chunked |
recurrent`` switch (``delta_rule``) and the per-head output norm.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

KDA_BACKENDS = ("chunked", "recurrent")


def taps_init(key, shape, dtype=jnp.float32):
    """Uniform in +-1/sqrt(taps): a depthwise convolution's usual start."""
    bound = 1.0 / math.sqrt(shape[0])
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def decay_rate_init(key, shape, dtype=jnp.float32):
    """``A``: the log of a rate drawn uniformly from [1, 16], a head."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def decay_bias_init(key, shape, dtype=jnp.float32):
    """``b``: softplus(b) is a step drawn log-uniformly from [0.001, 0.1]."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                      math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def causal_conv(x, taps):
    """Depthwise causal convolution along the sequence: ``y_t = sum_j
    taps[j] * x_{t - (n - 1) + j}`` for x ``[B, T, C]`` and taps ``[n, C]``,
    the last tap on the token itself, zeros before the sequence."""
    n = taps.shape[0]
    padded = jnp.pad(x, ((0, 0), (n - 1, 0), (0, 0)))
    return sum(padded[:, j:j + x.shape[1]] * taps[j].astype(x.dtype)
               for j in range(n))


# A head's channels lie side by side in the last axis, ``[B, T, heads * d]``,
# from the projections to the delta rule's kernels and back: on the TPU that
# form tiles (tokens, lanes) — with Kimi-Linear's d = 128 a head of a token
# is the lanes of one vreg; any d goes (Olmo-Hybrid's: 96 and 192) — where
# ``[B, T, heads, d]`` tiles (heads, lanes), so a reshape between the two
# copies the whole tensor, and a reduction written over a
# split last axis makes XLA move the heads into sublanes first. What a head
# needs summed is therefore summed where it lies: a product with the 0/1
# matrix of which channel is whose (the MXU adds a head's lanes), and the
# same matrix transposed hands a head's number back to its channels. The
# matrix is exact in bfloat16, so the six passes of ``Precision.HIGHEST``
# are a float32 sum (8.9e-8 from the sum by head on the chip; ``HIGH``'s
# three keep sixteen bits, 5.6e-6) and, the products reading their tensor
# from HBM once either way, cost 0.2 ms a layer over ``HIGH`` (PERF.md §6).
_BY_HEAD = jax.lax.Precision.HIGHEST


def _whose(width: int, heads: int):
    """``[width, heads]`` float32: 1 where a channel is of that head."""
    return (jnp.arange(width)[:, None] // (width // heads)
            == jnp.arange(heads)).astype(jnp.float32)


def _head_sums(x, whose):
    """Each head's sum of ``x [..., heads * d]``, float32: ``[..., heads]``."""
    return jnp.dot(x, whose, precision=_BY_HEAD)


def _to_channels(x, whose):
    """``x [..., heads]`` repeated over each head's channels."""
    return jnp.dot(x, whose.T, precision=_BY_HEAD)


def _l2norm(x, whose):
    """``x [..., heads * d]`` with each head's channels scaled to unit
    length, float32."""
    x = x.astype(jnp.float32)
    return x * _to_channels(jax.lax.rsqrt(
        _head_sums(jnp.square(x), whose) + 1e-6), whose)


def conditioned(q, k, v, raw, write, taps, rate, bias, *, heads: int, dtype,
                conv_scope: str, strongest: float = 1.0):
    """What lies between a delta-rule layer's projections and its rule: the
    short convolutions and SiLU on q, k and v (under ``conv_scope``), q and
    k normalised a head, the log-decay ``g = -exp(rate) * softplus(raw +
    bias)`` in float32 — as wide as ``raw``: one a channel (``[B, T, heads
    * d]``, a head's rate over its channels) or one a head (``[B, T,
    heads]``) — and ``beta = strongest * sigmoid(write)``, in [0, 1] unless
    a model takes more (the rule itself takes up to 2). q, k ``[B, T,
    heads * d_k]``, v ``[B, T, heads * d_v]``, write ``[B, T, heads]`` in;
    ``(q, k, v, g, beta [B, T, heads])`` out: what ``ops.kda.kda_fed``
    takes, no tensor reshaped on the way."""
    whose = _whose(q.shape[-1], heads)
    with jax.named_scope(conv_scope):
        q, k, v = (nn.silu(causal_conv(a, t)) for a, t in zip((q, k, v), taps))
    decay, channels = jnp.exp(rate), raw.shape[-1] // heads
    if channels > 1:
        decay = jnp.repeat(decay, channels)
    g = -decay * jax.nn.softplus(raw.astype(jnp.float32) + bias)
    q, k = (_l2norm(a, whose).astype(dtype) for a in (q, k))
    beta = nn.sigmoid(write.astype(jnp.float32))
    if strongest != 1.0:
        beta = strongest * beta
    return q, k, v, g, beta


def delta_rule(feed, projected, heads: int, backend: str):
    """``(o [B, T, heads * d_v], the final state)`` of the delta rule on
    what ``feed(*projected)`` returns (``conditioned``'s), by ``backend``,
    one of ``KDA_BACKENDS``: ``ops.kda.kda_fed``, the chunked kernels, or
    for the tests ``ops.kda.kda_recurrent``, the definition a token."""
    if backend not in KDA_BACKENDS:
        raise ValueError(f"the delta rule must be one of {KDA_BACKENDS}, got "
                         f"{backend!r}")
    from ..ops.kda import kda_fed, kda_recurrent

    # the kernel's backward keeps the projections and forms what ``feed``
    # makes of them again (``ops.kda.kda_fed``)
    if backend == "chunked":
        return kda_fed(feed, *projected)
    # the definition takes heads on an axis of their own
    *fed, g, beta = feed(*projected)
    q, k, v = (a.reshape(*a.shape[:2], heads, -1) for a in fed)
    if g.shape[-1] != heads:    # a decay a channel
        g = g.reshape(k.shape)
    o, state = kda_recurrent(q, k, v, g, beta)
    return o.reshape(*o.shape[:2], -1), state


class HeadRMSNorm(nn.Module):
    """``nn.RMSNorm`` over each head of ``x [B, T, heads * d]`` with one
    learned ``scale [d]`` shared by the heads: the mean square in float32,
    ``x * (rsqrt(. + epsilon) * scale)`` cast to ``dtype``."""

    heads: int
    epsilon: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        d = x.shape[-1] // self.heads
        scale = self.param("scale", nn.initializers.ones, (d,), jnp.float32)
        whose = _whose(x.shape[-1], self.heads)
        mean_square = _head_sums(
            jnp.square(x.astype(jnp.float32)), whose) / d
        by = _to_channels(jax.lax.rsqrt(mean_square + self.epsilon), whose)
        return (x * (by * jnp.tile(scale, self.heads))).astype(self.dtype)
