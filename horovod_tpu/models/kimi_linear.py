"""Kimi-Linear-style decoder: layers of Kimi Delta Attention (KDA, a gated
delta-rule linear attention over a short causal convolution) mixed 3 : 1
with latent attention (MLA, no positional encoding), gated SiLU MLPs and,
after a leading dense layer, the routed expert layer ``experts.ExpertLayer``
(docs/kimi-linear.md).

The third decoder beside ``transformer.TransformerLM`` and
``laguna.LagunaLM``: the same call (``model(tokens) -> float32 logits``),
so ``make_lm_train_step`` and ``lm_loss`` take it unchanged.
``KimiLinearLM.from_config`` reads the keys of the published
``config.json`` (moonshotai/Kimi-Linear-48B-A3B-Instruct) plus
``experts_held``. Two kernels carry it: ``ops.kda.kda_fed`` (the chunked
delta rule, its tensors ``[B, T, heads * d]`` as the projections make them)
and ``ops.pallas_attention.flash_attention`` with a v narrower than its q
and k (192 against 128 as published).

Per KDA layer, two numbers say whether the recurrence forgets or blows up:
the mean decay ``mean(alpha)`` and the largest ``|S|`` at the sequence's
end, sown into the collection ``kda_stats`` (``obs.kda.publish``).
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .delta import (HeadRMSNorm, conditioned, decay_bias_init,
                    decay_rate_init, delta_rule, taps_init)
from .experts import ExpertLayer, held_of
from .head import norm_and_head
from .parts import INIT, GatedMLP, attend, dense, keep_policy, rms_norm


class KDAMixer(nn.Module):
    """Kimi Delta Attention: q, k, v through a short convolution and SiLU,
    q and k normalised, a per-channel decay and a per-head write strength
    from the input, the delta rule, a normalised and gated output. Every
    tensor keeps its heads side by side, ``[B, T, heads * head_dim]``."""

    num_heads: int
    head_dim: int
    conv_size: int = 4
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    kda: str = "chunked"

    @nn.compact
    def __call__(self, x):
        heads, dh = self.num_heads, self.head_dim
        width = heads * dh
        with jax.named_scope("hvd.kda"):
            with jax.named_scope(scopes.MIXER_PROJ):
                q, k, v = (dense(width, name, self.dtype)(x)
                           for name in ("query", "key", "value"))
            taps = tuple(self.param(name, taps_init, (self.conv_size, width))
                         for name in ("conv_q", "conv_k", "conv_v"))
            rate = self.param("decay_rate", decay_rate_init, (heads,))
            bias = self.param("decay_bias", decay_bias_init, (width,))
            with jax.named_scope(scopes.MIXER_PROJ):
                raw = dense(width, "decay_b", self.dtype)(
                    dense(dh, "decay_a", self.dtype)(x))
                write = dense(heads, "beta", self.dtype)(x)
            feed = functools.partial(conditioned, heads=heads,
                                     dtype=self.dtype,
                                     conv_scope="hvd.kda.conv")
            projected = (q, k, v, raw, write, taps, rate, bias)
            with jax.named_scope("hvd.kda.scan"):
                o, state = delta_rule(feed, projected, heads, self.kda)
            if self.is_mutable_collection("kda_stats"):
                self.sow("kda_stats", "mean_decay",
                         jnp.mean(jnp.exp(feed(*projected)[3])))
                self.sow("kda_stats", "state_max", jnp.max(jnp.abs(state)))
            with jax.named_scope(scopes.MIXER_PROJ):
                gate = dense(width, "gate_b", self.dtype)(
                    dense(dh, "gate_a", self.dtype)(x))
            o = HeadRMSNorm(heads, self.eps, self.dtype, name="out_norm")(
                o.astype(self.dtype)) * nn.sigmoid(gate)
            with jax.named_scope(scopes.MIXER_PROJ):
                return dense(x.shape[-1], "out", self.dtype)(o)


class LatentAttention(nn.Module):
    """Causal attention whose keys and values come from one compressed
    latent: ``kv_rank`` dims normalised and expanded to each head's
    ``nope_dim`` key dims and ``v_dim`` value dims, plus ``rope_dim`` key
    dims shared by all heads; queries uncompressed. No rotation is applied
    to any of them (``mla_use_nope``)."""

    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "flash"

    @nn.compact
    def __call__(self, x):
        heads = self.num_heads
        with jax.named_scope("hvd.mla"):
            with jax.named_scope(scopes.MIXER_PROJ):
                q = dense((heads, self.nope_dim + self.rope_dim), "query",
                           self.dtype)(x)
                down = dense(self.kv_rank + self.rope_dim, "kv_a",
                              self.dtype)(x)
            latent, k_pe = jnp.split(down, [self.kv_rank], axis=-1)
            latent = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                                name="kv_norm")(latent)
            with jax.named_scope(scopes.MIXER_PROJ):
                up = dense((heads, self.nope_dim + self.v_dim), "kv_b",
                            self.dtype)(latent)
            k_nope, v = jnp.split(up, [self.nope_dim], axis=-1)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                k_pe[:, :, None, :], (*k_nope.shape[:3], self.rope_dim))],
                axis=-1)
            with jax.named_scope("hvd.mla.attn"):
                out = attend(q, k, v, self.attention)
            out = out.astype(self.dtype)
            with jax.named_scope(scopes.MIXER_PROJ):
                return dense(x.shape[-1], "out", self.dtype,
                              axis=(-2, -1))(out)


class KimiBlock(nn.Module):
    """Pre-RMSNorm residual block: a KDA or a latent-attention mixer, then
    a dense gated MLP (``dense_width``) or, where that is ``None``, the
    expert layer.

    What a recomputed block keeps (``parts.keep_policy``, as
    ``KimiLinearLM`` asks): its mixer kernel's outputs — ``kda_fwd``'s ``o``
    and chunk-starting states, ``flash_mla_fwd``'s ``o`` and log-sum-exp,
    named where the two forward rules make them — and, of a sparse block,
    the expert layer's routing and slot layout (``experts.KEPT_NAMES``: 1.6
    MB), and recomputes everything else: norms, projections, gate, MLP, the
    router's product and the experts' rows. With its outputs kept
    the forward Mosaic call is dead code in the recomputed block; the
    kernels' backward paths are unchanged (``kda_bwd`` still takes the
    feed's run there). At 16,384 tokens x 32 heads a KDA layer holds ``o``
    134 MB + starts 268 MB and the latent layer ``o`` 134 MB + lse 2 MB from
    its forward to its backward pass, for one run of an 11 ms and a 29 ms
    kernel each.

    Every block keeps: ``python3 -m chipbench.aot --workload
    kimi_linear_16k_1chip`` totals 13.436 GB with the five of ``kda, kda,
    kda, mla, kda`` keeping, under the 13.59 GB the chip leaves the step
    (the latent block and the last 3 / 2 / 0 KDA blocks: 13.034 / 12.631 /
    12.227; none, the default policy: 12.117). Where a stack did not fit,
    the blocks nearest the output should keep first: the backward pass
    frees their residuals first, so they are never dearer than one below."""

    mixer: str          # "kda" | "mla"
    kda: dict           # KDAMixer's fields
    mla: dict           # LatentAttention's fields
    dense_width: Optional[int]
    experts: dict       # ExpertLayer's fields
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        h = rms_norm(x, "ln_attn", self.eps, self.dtype)
        with jax.named_scope(scopes.MIXER):
            if self.mixer == "kda":
                x = x + KDAMixer(eps=self.eps, dtype=self.dtype, name="kda",
                                 **self.kda)(h)
            else:
                x = x + LatentAttention(eps=self.eps, dtype=self.dtype,
                                        name="mla", **self.mla)(h)
        h = rms_norm(x, "ln_mlp", self.eps, self.dtype)
        if self.dense_width is not None:
            with jax.named_scope(scopes.MLP):
                return x + GatedMLP(self.dense_width, self.dtype,
                                    name="mlp")(h)
        return x + ExpertLayer(dtype=self.dtype, name="moe", **self.experts)(h)


class KimiLinearLM(nn.Module):
    """Decoder-only LM, ``model(tokens) -> float32 logits [B, T, vocab]``
    (``model(tokens, loss_tokens=tokens)``: their ``lm_loss``, the logits
    never whole, ``head.lm_head_loss``).
    Layer ``i`` mixes with ``mixers[i]`` (``"kda"`` or ``"mla"``) and its
    MLP is ``mlp_layer_types[i]`` (``"dense"`` or ``"sparse"``). No
    positional encoding of any kind."""

    vocab_size: int
    d_model: int
    mixers: Tuple[str, ...]
    mlp_layer_types: Tuple[str, ...]
    num_heads: int
    kda_head_dim: int
    conv_size: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    dense_width: int
    expert_width: int
    shared_width: int
    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    routed_scaling: float
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attention: str = "flash"    # "dense": the tests' written-out attention
    kda: str = "chunked"        # "recurrent": the tests' token-by-token scan
    # jax.checkpoint each block: only the block-boundary activations and the
    # mixer kernel's outputs are stored (``KimiBlock``: 0.40 GB a KDA
    # layer, 0.14 GB a latent one at 16,384 tokens x 32 heads); the rest of
    # a block's interior is recomputed in backward
    remat: bool = False

    @classmethod
    def from_config(cls, config: dict, **overrides) -> "KimiLinearLM":
        """The model of a published ``config.json``'s keys, cut to
        ``num_hidden_layers`` leading layers, with ``experts_held``
        ``{"first": .., "count": ..}`` (all of them when absent). The
        config counts layers from 1: ``linear_attn_config.kda_layers`` and
        ``full_attn_layers`` name them, the first
        ``first_k_dense_replace`` have a dense MLP."""
        depth = config["num_hidden_layers"]
        linear = config["linear_attn_config"]
        kinds = {**{i: "kda" for i in linear["kda_layers"]},
                 **{i: "mla" for i in linear["full_attn_layers"]}}
        fields = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            mixers=tuple(kinds[i] for i in range(1, depth + 1)),
            mlp_layer_types=tuple(
                "dense" if i < config["first_k_dense_replace"] else "sparse"
                for i in range(depth)),
            num_heads=config["num_attention_heads"],
            kda_head_dim=linear["head_dim"],
            conv_size=linear["short_conv_kernel_size"],
            nope_dim=config["qk_nope_head_dim"],
            rope_dim=config["qk_rope_head_dim"],
            v_dim=config["v_head_dim"], kv_rank=config["kv_lora_rank"],
            dense_width=config["intermediate_size"],
            expert_width=config["moe_intermediate_size"],
            shared_width=config["moe_intermediate_size"]
            * config["num_shared_experts"],
            num_experts=config["num_experts"],
            experts_per_token=config["num_experts_per_token"],
            experts_held=held_of(config),
            routed_scaling=config["routed_scaling_factor"],
            eps=config["rms_norm_eps"])
        if linear["num_heads"] != fields["num_heads"]:
            raise ValueError("KDA and latent-attention layers with head "
                             "counts of their own are not supported")
        fields.update(overrides)
        return cls(**fields)

    @nn.compact
    def __call__(self, tokens, loss_tokens=None):
        if len(self.mixers) != len(self.mlp_layer_types):
            raise ValueError("mixers and mlp_layer_types must be equally "
                             "long")
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         embedding_init=INIT, name="tok_embed")(tokens)
        block_cls = nn.remat(KimiBlock, policy=keep_policy(
            "ops.kda", "ops.pallas_attention", "models.experts")) \
            if self.remat else KimiBlock
        kda = dict(num_heads=self.num_heads, head_dim=self.kda_head_dim,
                   conv_size=self.conv_size, kda=self.kda)
        mla = dict(num_heads=self.num_heads, nope_dim=self.nope_dim,
                   rope_dim=self.rope_dim, v_dim=self.v_dim,
                   kv_rank=self.kv_rank, attention=self.attention)
        experts = dict(
            num_experts=self.num_experts,
            experts_per_token=self.experts_per_token,
            experts_held=self.experts_held, width=self.expert_width,
            shared_width=self.shared_width, scaling=self.routed_scaling)
        for i, (mixer, mlp) in enumerate(zip(self.mixers,
                                             self.mlp_layer_types)):
            x = block_cls(
                mixer=mixer, kda=kda, mla=mla, experts=experts, eps=self.eps,
                dtype=self.dtype,
                dense_width=self.dense_width if mlp == "dense" else None,
                name=f"block_{i}")(x)
        return norm_and_head(x, self.vocab_size, self.eps, self.dtype,
                             loss_tokens)
