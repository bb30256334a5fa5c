"""SDAR-style sparse-expert decoder trained as a block-diffusion model
(docs/sdar.md): pre-RMSNorm blocks, grouped heads with an RMSNorm a head on
q and k before a full-width rotation, every layer a routed expert layer
with a softmax router and no shared expert (JetLM/SDAR-30B-A3B-Chat's
``config.json``, ``model_type`` ``sdar_moe``: a Qwen3-MoE decoder).

The fifth decoder, and the first whose step is no next-token one. A
sequence of ``L`` clean tokens goes through the stack twice at once, its
clean copy and a *noisy* copy in which whole positions are replaced by a
mask token at a rate drawn a block of ``block_length`` positions
(:func:`block_diffusion_noise`, what an input pipeline calls): ``2 L`` rows
``[clean ; noisy]``, both halves at positions ``0 .. L - 1``, under one
mask of three parts — a clean row sees the clean rows of its own block and
of those before it, a noisy row the *clean* rows of the blocks before its
own and the *noisy* rows of its own — which
``ops.pallas_attention.flash_attention(..., block_diffusion=)`` runs as the
``flash_bd_*`` kernels. The loss is the cross entropy of the noisy rows
against their own clean ids, no shift, each row times its weight (``1 /
rate`` where it was masked, else 0), over the ``G * L`` rows
(``head.lm_head_loss`` with ``targets`` and ``weights``): ``model(
clean, noisy, weights=weights)``; without ``weights`` the call returns the
noisy rows' float32 logits ``[G, L, vocab]``.

In the last layer the clean half is needed for its keys and values alone:
after that layer's attention only the noisy rows go on, through its expert
layer, the final norm and the head.

The expert layer is ``experts.ExpertLayer`` told ``experts_held`` (one chip
of an expert-parallel deployment), ``scoring="softmax"`` and no shared
expert; its routing gauges are Laguna's. The loss sows, into the collection
``bd_stats``, the share of rows that carry a weight and the mean weight
over them (``obs.bd.publish``).
"""

from __future__ import annotations

from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .experts import ExpertLayer, held_of
from .head import norm_and_head
from .parts import INIT, Rotary, attend, dense, keep_policy, rms_norm


def block_diffusion_noise(key, tokens, block_length: int, mask_id: int,
                          eps: float = 1e-3):
    """``(noisy, weights)`` for clean ``tokens [G, L]``: a mask rate a block
    of ``block_length`` positions, ``t = eps + (1 - eps) * u`` with ``u``
    uniform (the linear schedule, clipped away from 0), each position of the
    block replaced by ``mask_id`` with probability ``t``; a masked position
    weighs ``1 / t`` in the loss, any other 0 (float32)."""
    batch, seq = tokens.shape
    if seq % block_length:
        raise ValueError(f"{seq} tokens are no whole blocks of "
                         f"{block_length}")
    rate_key, mask_key = jax.random.split(key)
    rate = eps + (1.0 - eps) * jax.random.uniform(
        rate_key, (batch, seq // block_length), jnp.float32)
    rate = jnp.repeat(rate, block_length, axis=1)
    masked = jax.random.uniform(mask_key, tokens.shape, jnp.float32) < rate
    return (jnp.where(masked, jnp.asarray(mask_id, tokens.dtype), tokens),
            jnp.where(masked, 1.0 / rate, 0.0))


class BlockDiffusionAttention(nn.Module):
    """Self-attention of ``[clean ; noisy]`` rows under the block-diffusion
    mask: ``num_heads`` query heads on ``num_kv_heads`` key/value heads, q
    and k RMS-normalised a head (one learned scale of ``head_dim`` each)
    and then rotated over the whole head. ``noisy_only``: the output
    projection takes the noisy half alone and returns ``[G, L, d]``."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary: Rotary
    block_length: int
    noisy_only: bool = False
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"

    @nn.compact
    def __call__(self, x, positions):
        def heads(n, name, norm=None):
            with jax.named_scope(scopes.MIXER_PROJ):
                y = dense((n, self.head_dim), name, self.dtype)(x)
            if norm is None:
                return y
            return self.rotary(nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                                          name=norm)(y), positions)

        with jax.named_scope("hvd.bd"):
            q = heads(self.num_heads, "query", "q_norm")
            k = heads(self.num_kv_heads, "key", "k_norm")
            v = heads(self.num_kv_heads, "value")
            with jax.named_scope("hvd.bd.attn"):
                out = attend(q, k, v, self.attention,
                             block_diffusion=self.block_length)
            if self.noisy_only:
                out = out[:, out.shape[1] // 2:]
            with jax.named_scope(scopes.MIXER_PROJ):
                return dense(x.shape[-1], "out", self.dtype,
                             axis=(-2, -1))(out.astype(self.dtype))


class SdarBlock(nn.Module):
    """Pre-RMSNorm residual block: block-diffusion attention, then the
    expert layer. ``last``: only the noisy half leaves the attention.

    With ``remat`` each half is a ``jax.checkpoint`` of its own, as
    ``laguna.LagunaBlock``'s; the attention half keeps its flash kernel's
    output and log-sum-exp as a full layer of Laguna's does
    (``parts.keep_policy``), so that ``flash_bd_fwd`` runs once a layer,
    and the expert half its routing and slot layout
    (``experts.KEPT_NAMES``), so that a layer selects and sorts once
    (docs/sdar.md has the price list)."""

    attn: dict          # BlockDiffusionAttention's fields
    experts: dict       # ExpertLayer's fields
    last: bool = False
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, x, positions):
        # ``nn.remat`` hands a function the module as its first argument
        def mix(block, x, positions):
            h = rms_norm(x, "ln_attn", self.eps, self.dtype)
            with jax.named_scope(scopes.MIXER):
                if self.last:
                    x = x[:, x.shape[1] // 2:]
                return x + BlockDiffusionAttention(
                    eps=self.eps, dtype=self.dtype, noisy_only=self.last,
                    name="attn", **self.attn)(h, positions)

        def feed(block, x):
            return x + ExpertLayer(dtype=self.dtype, name="moe",
                                   **self.experts)(
                rms_norm(x, "ln_mlp", self.eps, self.dtype))

        if self.remat:
            mix = nn.remat(mix, policy=keep_policy("ops.pallas_attention"))
            feed = nn.remat(feed, policy=keep_policy("models.experts"))
        return feed(self, mix(self, x, positions))


class SdarMoeLM(nn.Module):
    """Block-diffusion LM over ``[clean ; noisy]`` rows (module
    docstring): ``model(clean, noisy) -> float32 logits [G, L, vocab]`` of
    the noisy rows, ``model(clean, noisy, weights=w)`` the weighted loss at
    the masked positions, the logits never whole."""

    vocab_size: int
    d_model: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    expert_width: int
    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    rope_theta: float
    block_length: int = 4
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"    # "dense": the tests' written-out attention
    # jax.checkpoint each half of a block: the halves' inputs and the flash
    # kernel's outputs are stored, the rest is recomputed in backward
    remat: bool = False

    @classmethod
    def from_config(cls, config: dict, **overrides) -> "SdarMoeLM":
        """The model of a published ``config.json``'s keys, cut to
        ``num_hidden_layers`` leading layers, with ``experts_held``
        ``{"first": .., "count": ..}`` (all of them when absent) and
        ``block_length`` (4, the released checkpoint's, when absent)."""
        if config.get("mlp_only_layers") or config.get(
                "decoder_sparse_step", 1) != 1:
            raise ValueError("dense layers among the sparse ones are not "
                             "supported")
        if not config.get("norm_topk_prob", True):
            raise ValueError("norm_topk_prob false is not supported")
        fields = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            num_layers=config["num_hidden_layers"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            expert_width=config["moe_intermediate_size"],
            num_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            experts_held=held_of(config),
            rope_theta=config["rope_theta"],
            block_length=config.get("block_length", 4),
            eps=config["rms_norm_eps"])
        fields.update(overrides)
        return cls(**fields)

    @nn.compact
    def __call__(self, clean, noisy, weights=None):
        seq = clean.shape[1]
        tokens = jnp.concatenate([clean, noisy], axis=1)
        positions = jnp.broadcast_to(jnp.tile(jnp.arange(seq), 2),
                                     tokens.shape)
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         embedding_init=INIT, name="tok_embed")(tokens)
        attn = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, attention=self.attention,
                    block_length=self.block_length,
                    rotary=Rotary(theta=self.rope_theta, dim=self.head_dim))
        experts = dict(num_experts=self.num_experts,
                       experts_per_token=self.experts_per_token,
                       experts_held=self.experts_held,
                       width=self.expert_width, shared_width=0,
                       scoring="softmax")
        for i in range(self.num_layers):
            x = SdarBlock(attn=attn, experts=experts, eps=self.eps,
                          dtype=self.dtype, remat=self.remat,
                          last=i == self.num_layers - 1,
                          name=f"block_{i}")(x, positions)
        if weights is None:
            return norm_and_head(x, self.vocab_size, self.eps, self.dtype)
        counted = jnp.sum(weights > 0)
        self.sow("bd_stats", "masked_share", counted / weights.size)
        self.sow("bd_stats", "mean_weight",
                 jnp.sum(weights) / jnp.maximum(counted, 1))
        return norm_and_head(x, self.vocab_size, self.eps, self.dtype,
                             targets=clean, weights=weights)
