"""Laguna-style decoder: RMSNorm, rotary positions, grouped key/value
heads, window and full attention mixed layer by layer with a head count of
their own, a head-wise output gate, gated SiLU MLPs and a routed expert
layer that is told which experts it holds (docs/laguna.md).

The second decoder beside ``transformer.TransformerLM``: the same call
(``model(tokens) -> float32 logits``, or the loss itself given
``loss_tokens``), so ``make_lm_train_step`` and ``lm_loss`` take it
unchanged, and the same kernel
(``ops.pallas_attention.flash_attention``, here with grouped heads and a
window). ``LagunaLM.from_config`` reads the keys of the published
``config.json`` (poolside/Laguna-XS.2) plus ``experts_held``: the expert
layer (``experts.ExpertLayer``) stands for one chip of an expert-parallel
deployment.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .experts import ExpertLayer, held_of
from .head import norm_and_head
from .parts import (INIT, GatedMLP, Rotary, attend, dense, keep_policy,
                    rms_norm)


class GroupedAttention(nn.Module):
    """Causal self-attention with ``num_heads`` query heads on
    ``num_kv_heads`` key/value heads, rotary positions, an optional window
    and a head-wise sigmoid gate on its output."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary: Rotary
    window: Optional[int] = None
    dtype: Any = jnp.bfloat16
    attention: str = "flash"

    @nn.compact
    def __call__(self, x, positions):
        def heads(n, name):
            with jax.named_scope(scopes.MIXER_PROJ):
                return dense((n, self.head_dim), name, self.dtype)(x)

        q = self.rotary(heads(self.num_heads, "query"), positions)
        k = self.rotary(heads(self.num_kv_heads, "key"), positions)
        v = heads(self.num_kv_heads, "value")
        out = attend(q, k, v, self.attention, window=self.window)
        with jax.named_scope(scopes.MIXER_PROJ):
            gate = nn.Dense(self.num_heads, use_bias=False, dtype=self.dtype,
                            kernel_init=INIT, name="gate")(x)
        out = out.astype(self.dtype) * nn.sigmoid(gate)[..., None]
        with jax.named_scope(scopes.MIXER_PROJ):
            return dense(x.shape[-1], "out", self.dtype, axis=(-2, -1))(out)


class LagunaBlock(nn.Module):
    """Pre-RMSNorm residual block: grouped attention, then a dense gated
    MLP (``dense_width``) or, where that is ``None``, the expert layer.

    With ``remat`` each half is a ``jax.checkpoint`` of its own: the block's
    input and the attention half's output are stored, the halves' interiors
    recomputed in backward, one at a time — while the MLP's (the expert
    layer's) backward pass runs, nothing of the attention is held but what
    its policy keeps (a single checkpoint round the block compiles to 0.8
    GB more), and the attention's output projection, which only the second
    half needed, is not recomputed at all.

    What a recomputed attention half keeps (``parts.keep_policy``): a full
    layer its flash kernel's output and log-sum-exp, a sliding layer
    (``window`` given) nothing: its forward kernel skips what the window
    hides, so running it again is cheap for what keeping would hold. The
    price list, at 2 x 8,192 tokens (docs/laguna.md): a full layer's 48
    heads hold 0.20 GB for a 17.6 ms run of ``flash_fwd``, a sliding layer's
    64 heads 0.27 GB for a 7.0 ms run of ``flash_win_fwd``. ``python3 -m
    chipbench.aot --workload laguna_xs2_8k_1chip`` totals 12.82 GB with
    the two full layers keeping, under the 13.234 GB the chip leaves the
    step (none: 12.33; one sliding layer more: 13.08; two more: 13.35;
    all five: 14.15).

    What a recomputed MLP half keeps: the expert layer's routing and slot
    layout (``experts.KEPT_NAMES``: 1.6 MB a layer), so that it selects and
    sorts nothing again; a dense MLP names nothing and keeps nothing."""

    attn: dict          # GroupedAttention's fields
    dense_width: Optional[int]
    experts: dict       # ExpertLayer's fields
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    remat: bool = False

    @nn.compact
    def __call__(self, x, positions):
        # ``nn.remat`` hands a function the module as its first argument
        def mix(block, x, positions):
            h = rms_norm(x, "ln_attn", self.eps, self.dtype)
            with jax.named_scope(scopes.MIXER):
                return x + GroupedAttention(dtype=self.dtype, name="attn",
                                            **self.attn)(h, positions)

        def feed(block, x):
            h = rms_norm(x, "ln_mlp", self.eps, self.dtype)
            if self.dense_width is not None:
                with jax.named_scope(scopes.MLP):
                    return x + GatedMLP(self.dense_width, self.dtype,
                                        name="mlp")(h)
            return x + ExpertLayer(dtype=self.dtype, name="moe",
                                   **self.experts)(h)

        if self.remat:
            kept = () if self.attn["window"] is not None \
                else ("ops.pallas_attention",)
            mix = nn.remat(mix, policy=keep_policy(*kept))
            feed = nn.remat(feed, policy=keep_policy("models.experts"))
        return feed(self, mix(self, x, positions))


class LagunaLM(nn.Module):
    """Decoder-only LM, ``model(tokens) -> float32 logits [B, T, vocab]``
    (``model(tokens, loss_tokens=tokens)``: their ``lm_loss``, the logits
    never whole, ``head.lm_head_loss``).
    Layer ``i`` is ``layer_types[i]`` (``"full_attention"`` or
    ``"sliding_attention"``) with ``heads_per_layer[i]`` query heads, and
    its MLP ``mlp_layer_types[i]`` (``"dense"`` or ``"sparse"``)."""

    vocab_size: int
    d_model: int
    head_dim: int
    num_kv_heads: int
    layer_types: Tuple[str, ...]
    heads_per_layer: Tuple[int, ...]
    mlp_layer_types: Tuple[str, ...]
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    routed_scaling: float
    window: int
    rotary_full: Rotary
    rotary_sliding: Rotary
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"
    # jax.checkpoint each half of a block: only the halves' inputs and a
    # full layer's flash kernel outputs (``LagunaBlock``) are stored, the
    # rest of a block's interior is recomputed in backward
    remat: bool = False

    @classmethod
    def from_config(cls, config: dict, **overrides) -> "LagunaLM":
        """The model of a published ``config.json``'s keys, cut to
        ``num_hidden_layers`` leading layers, with ``experts_held``
        ``{"first": .., "count": ..}`` (all of them when absent)."""
        depth = config["num_hidden_layers"]
        ropes = config["rope_parameters"]

        def rotary(p):
            dim = int(config["head_dim"] * p.get("partial_rotary_factor", 1))
            if p.get("rope_type", "default") == "default":
                return Rotary(theta=p["rope_theta"], dim=dim)
            return Rotary(
                theta=p["rope_theta"], dim=dim, factor=p["factor"],
                original_max_position=p["original_max_position_embeddings"],
                beta_fast=p["beta_fast"], beta_slow=p["beta_slow"],
                attention_factor=p["attention_factor"])

        fields = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            head_dim=config["head_dim"],
            num_kv_heads=config["num_key_value_heads"],
            layer_types=tuple(config["layer_types"][:depth]),
            heads_per_layer=tuple(
                config["num_attention_heads_per_layer"][:depth]),
            mlp_layer_types=tuple(config["mlp_layer_types"][:depth]),
            dense_width=config["intermediate_size"],
            expert_width=config["moe_intermediate_size"],
            shared_width=config["shared_expert_intermediate_size"],
            num_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_tok"],
            experts_held=held_of(config),
            routed_scaling=config["moe_routed_scaling_factor"],
            window=config["sliding_window"],
            rotary_full=rotary(ropes["full_attention"]),
            rotary_sliding=rotary(ropes["sliding_attention"]),
            eps=config["rms_norm_eps"])
        fields.update(overrides)
        return cls(**fields)

    @nn.compact
    def __call__(self, tokens, positions=None, loss_tokens=None):
        if not len(self.layer_types) == len(self.heads_per_layer) \
                == len(self.mlp_layer_types):
            raise ValueError("layer_types, heads_per_layer and "
                             "mlp_layer_types must be equally long")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         embedding_init=INIT, name="tok_embed")(tokens)
        for i, (kind, heads, mlp) in enumerate(zip(
                self.layer_types, self.heads_per_layer,
                self.mlp_layer_types)):
            sliding = kind == "sliding_attention"
            attn = dict(
                num_heads=heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, attention=self.attention,
                rotary=self.rotary_sliding if sliding else self.rotary_full,
                window=self.window if sliding else None)
            experts = dict(
                num_experts=self.num_experts,
                experts_per_token=self.experts_per_token,
                experts_held=self.experts_held, width=self.expert_width,
                shared_width=self.shared_width, scaling=self.routed_scaling)
            x = LagunaBlock(
                attn=attn, experts=experts, eps=self.eps, dtype=self.dtype,
                dense_width=self.dense_width if mlp == "dense" else None,
                remat=self.remat, name=f"block_{i}")(x, positions)
        return norm_and_head(x, self.vocab_size, self.eps, self.dtype,
                             loss_tokens)
