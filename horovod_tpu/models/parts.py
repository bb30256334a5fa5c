"""What more than one decoder builds a block from: the initializer, the
bias-free projection, the block-level norm, the gated MLP, rotary positions,
attention behind its ``flash | dense`` switch (the kernel, or the same mask
written out for the tests), and a recomputed layer's checkpoint policy.
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes

ATTENTION_BACKENDS = ("flash", "dense")
INIT = nn.initializers.normal(0.02)


def dense(features, name, dtype, axis=-1):
    """A bias-free projection ``name`` onto ``features`` (a width, or
    ``(heads, head_dim)``) of the trailing ``axis``."""
    return nn.DenseGeneral(features, axis=axis, use_bias=False, dtype=dtype,
                           kernel_init=INIT, name=name)


def rms_norm(x, name, eps, dtype):
    """A block-level RMSNorm ``name`` (``ln_attn``, ``ln_mlp``,
    ``ln_final``) of ``x``, under ``hvd.norm``."""
    with jax.named_scope(scopes.NORM):
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)(x)


class GatedMLP(nn.Module):
    """``(silu(x W1) * (x W3)) W2``, no biases."""

    width: int
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        matrix = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=self.dtype, kernel_init=INIT, name=name)
        h = nn.silu(matrix(self.width, "w1")(x)) * matrix(self.width, "w3")(x)
        return matrix(x.shape[-1], "w2")(h)


@dataclasses.dataclass(frozen=True)
class Rotary:
    """Rotary positions of one layer type: ``dim`` leading dims of each head
    are rotated; ``factor`` (YaRN's, ``None`` for plain rotary) blends
    interpolated and extrapolated frequencies as the ``transformers``
    library does."""

    theta: float
    dim: int
    factor: Optional[float] = None
    original_max_position: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def inv_freq(self):
        """``[dim // 2]`` float32 inverse frequencies."""
        i = jnp.arange(0, self.dim, 2, dtype=jnp.float32)
        extrapolated = 1.0 / self.theta ** (i / self.dim)
        if self.factor is None:
            return extrapolated

        def correction_dim(rotations):
            return self.dim * math.log(self.original_max_position / (
                rotations * 2 * math.pi)) / (2 * math.log(self.theta))

        low = max(math.floor(correction_dim(self.beta_fast)), 0)
        high = min(math.ceil(correction_dim(self.beta_slow)), self.dim - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(self.dim // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        return extrapolated / self.factor * ramp + extrapolated * (1.0 - ramp)

    def __call__(self, x, positions):
        """Rotate ``x`` [B, T, H, D] at ``positions`` [B, T]: the first
        ``dim`` dims of each head in halves (``rotate_half``), float32."""
        angles = positions[..., None].astype(jnp.float32) * self.inv_freq()
        cos, sin = (jnp.concatenate([f(angles)] * 2, axis=-1)[:, :, None, :]
                    * self.attention_factor for f in (jnp.cos, jnp.sin))
        turned, kept = x[..., :self.dim].astype(jnp.float32), x[..., self.dim:]
        first, second = jnp.split(turned, 2, axis=-1)
        turned = turned * cos + jnp.concatenate([-second, first], -1) * sin
        return jnp.concatenate([turned.astype(x.dtype), kept], axis=-1)


def block_diffusion_mask(seq: int, block_length: int):
    """``[2 seq, 2 seq]`` bool, the mask from its definition: rows and
    columns are a sequence's clean copy and then its noisy one."""
    at = jnp.arange(2 * seq)
    noisy, block = at >= seq, at % seq // block_length
    q_noisy, k_noisy = noisy[:, None], noisy[None, :]
    q_block, k_block = block[:, None], block[None, :]
    return jnp.where(q_noisy,
                     jnp.where(k_noisy, k_block == q_block,
                               k_block < q_block),
                     ~k_noisy & (k_block <= q_block))


def dense_attention(q, k, v, window: Optional[int] = None,
                    block_diffusion: Optional[int] = None):
    """Causal attention written out, grouped heads, a window and the
    block-diffusion mask (``block_diffusion_mask``, in place of the causal
    one) as ``flash_attention`` takes them; float32 softmax."""
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / math.sqrt(q.shape[-1])
    if block_diffusion is not None:
        keep = block_diffusion_mask(q.shape[1] // 2, block_diffusion)
    else:
        distance = jnp.arange(q.shape[1])[:, None] - jnp.arange(k.shape[1])
        keep = distance >= 0
        if window is not None:
            keep = keep & (distance < window)
    weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights.astype(v.dtype), v)


def attend(q, k, v, backend: str, **mask):
    """Causal attention by ``backend``, one of ``ATTENTION_BACKENDS``:
    ``ops.pallas_attention.flash_attention`` or, for the tests,
    ``dense_attention``, under the ``mask`` both take (``window=``,
    ``block_diffusion=``)."""
    if backend not in ATTENTION_BACKENDS:
        raise ValueError(f"attention must be one of {ATTENTION_BACKENDS}, "
                         f"got {backend!r}")
    if backend == "flash":
        from ..ops.pallas_attention import flash_attention

        return flash_attention(q, k, v, causal=True, **mask)
    return dense_attention(q, k, v, **mask)


def keep_policy(*modules: str):
    """The ``jax.checkpoint`` policy of a recomputed layer that keeps what
    ``modules`` of this package name and recomputes the rest:
    ``save_only_these_names`` over the ``KEPT_NAMES`` of each — the outputs
    of their kernels that the forward rules of ``"ops.pallas_attention"``
    and ``"ops.kda"`` name, the routing and slot layout of
    ``"models.experts"``. ``None``, the default policy, for a layer that
    keeps nothing."""
    if not modules:
        return None
    return jax.checkpoint_policies.save_only_these_names(*(
        name for module in modules for name in importlib.import_module(
            f"..{module}", __package__).KEPT_NAMES))
