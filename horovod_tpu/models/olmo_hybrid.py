"""Olmo-Hybrid-style decoder: layers of a gated delta rule (arXiv:2412.06464:
one decay a head, keys and values of their own widths, write strengths up
to 2) over a short causal convolution, mixed 3 : 1 with full attention
whose q and k are normalised and carry no positions, in dense **post-norm**
blocks — ``x + norm(mixer(x))``, ``h + norm(mlp(h))`` — with gated SiLU
MLPs (allenai/Olmo-Hybrid-7B's ``config.json``).

The fourth decoder beside ``transformer.TransformerLM``, ``laguna.LagunaLM``
and ``kimi_linear.KimiLinearLM``: the same call (``model(tokens) -> float32
logits``), so ``make_lm_train_step`` and ``lm_loss`` take it unchanged.
Two kernels carry it: ``ops.kda.kda_fed`` with a decay ``[B, T, heads]``
(its ``gdn_fwd`` / ``gdn_bwd``; the tensors ``[B, T, heads * d]`` as the
projections make them, as in ``kimi_linear``; ``models.delta`` holds what
the two share) and
``ops.pallas_attention.flash_attention``.

The model stands for one chip of a deployment that divides each layer's
heads: ``OlmoHybridLM.from_config`` reads the published keys plus
``heads_held`` ``{"first": .., "count": ..}``, and every mixer computes the
part of its output that the heads it holds give — a delta-rule head or an
attention head reads the whole input and writes its own rows of the output
projection, so the parts of all the shares add up to the uncut mixer's
output. What the absent heads would add is left out, and nothing stands in
for their chips. The full layers' q and k norms take their mean square over
the channels held.

Per delta-rule layer three numbers are sown into the collection
``gdn_stats`` (``obs.kda.publish_gdn``): the mean decay, the largest
``|S|`` at the sequence's end, and the share of writes stronger than 1 —
those under which ``I - beta k k^T`` has a negative eigenvalue.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .delta import (HeadRMSNorm, conditioned, decay_bias_init,
                    decay_rate_init, delta_rule, taps_init)
from .head import norm_and_head
from .parts import INIT, GatedMLP, attend, dense, keep_policy, rms_norm

LAYER_TYPES = ("linear_attention", "full_attention")


class GatedDeltaMixer(nn.Module):
    """The gated delta rule's layer over the ``num_heads`` heads held: q,
    k (``key_dim`` a head) and v (``value_dim``) through a short
    convolution and SiLU, q and k normalised, a decay and a write strength
    a head from the input, the rule, then a per-head RMSNorm times a SiLU
    gate as wide as v, and the output projection. Every tensor keeps its
    heads side by side, ``[B, T, heads * d]``."""

    num_heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    allow_neg_eigval: bool = True
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    rule: str = "chunked"

    @nn.compact
    def __call__(self, x):
        heads = self.num_heads
        keys, values = heads * self.key_dim, heads * self.value_dim
        with jax.named_scope("hvd.gdn"):
            with jax.named_scope(scopes.MIXER_PROJ):
                q, k, v = (dense(width, name, self.dtype)(x)
                           for name, width in (("query", keys), ("key", keys),
                                               ("value", values)))
                # from zero: the step starts at softplus(dt_bias), as
                # drawn, under a residual stream of any scale (a post-norm
                # block does not normalise its mixer's input; a head whose
                # decay forgets within a token leaves o = beta (q . k) v,
                # and out_norm divides by it)
                raw = nn.DenseGeneral(
                    heads, use_bias=False, dtype=self.dtype, name="decay",
                    kernel_init=nn.initializers.zeros_init())(x)
                write = dense(heads, "beta", self.dtype)(x)
            taps = tuple(self.param(name, taps_init, (self.conv_size, width))
                         for name, width in (("conv_q", keys),
                                             ("conv_k", keys),
                                             ("conv_v", values)))
            rate = self.param("A_log", decay_rate_init, (heads,))
            bias = self.param("dt_bias", decay_bias_init, (heads,))
            feed = functools.partial(
                conditioned, heads=heads, dtype=self.dtype,
                conv_scope="hvd.gdn.conv",
                strongest=2.0 if self.allow_neg_eigval else 1.0)
            projected = (q, k, v, raw, write, taps, rate, bias)
            with jax.named_scope("hvd.gdn.scan"):
                o, state = delta_rule(feed, projected, heads, self.rule)
            if self.is_mutable_collection("gdn_stats"):
                g, beta = feed(*projected)[3:]
                self.sow("gdn_stats", "mean_decay", jnp.mean(jnp.exp(g)))
                self.sow("gdn_stats", "state_max", jnp.max(jnp.abs(state)))
                self.sow("gdn_stats", "beta_above_one",
                         jnp.mean((beta > 1.0).astype(jnp.float32)))
            with jax.named_scope(scopes.MIXER_PROJ):
                gate = dense(values, "gate", self.dtype)(x)
            o = HeadRMSNorm(heads, self.eps, self.dtype, name="out_norm")(
                o.astype(self.dtype)) * nn.silu(gate)
            with jax.named_scope(scopes.MIXER_PROJ):
                return dense(x.shape[-1], "out", self.dtype)(o)


class NormedAttention(nn.Module):
    """Causal self-attention over the ``num_heads`` query heads held on
    ``num_kv_heads`` key/value heads: q and k RMS-normalised over all the
    channels held with a learned scale each, no positional encoding."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"

    @nn.compact
    def __call__(self, x):
        def heads(n, name, norm=None):
            with jax.named_scope(scopes.MIXER_PROJ):
                y = dense(n * self.head_dim, name, self.dtype)(x)
            if norm is not None:
                y = nn.RMSNorm(epsilon=self.eps, dtype=self.dtype,
                               name=norm)(y)
            return y.reshape(*y.shape[:2], n, self.head_dim)

        q = heads(self.num_heads, "query", "q_norm")
        k = heads(self.num_kv_heads, "key", "k_norm")
        v = heads(self.num_kv_heads, "value")
        out = attend(q, k, v, self.attention)
        out = out.astype(self.dtype).reshape(*x.shape[:2], -1)
        with jax.named_scope(scopes.MIXER_PROJ):
            return dense(x.shape[-1], "out", self.dtype)(out)


class OlmoHybridBlock(nn.Module):
    """Post-RMSNorm residual block: ``h = x + norm(mixer(x))``, ``h +
    norm(mlp(h))``; the mixer a delta-rule layer or full attention."""

    mixer: str          # one of LAYER_TYPES
    gdn: dict           # GatedDeltaMixer's fields
    attn: dict          # NormedAttention's fields
    mlp_width: int
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        def add_normed(owner, name, x, y):
            # the norm is hvd.norm's, the residual add its part's, as in
            # the pre-norm decoders
            y = rms_norm(y, name, self.eps, self.dtype)
            with jax.named_scope(owner):
                return x + y

        with jax.named_scope(scopes.MIXER):
            if self.mixer == "linear_attention":
                mixed = GatedDeltaMixer(eps=self.eps, dtype=self.dtype,
                                        name="gdn", **self.gdn)(x)
            else:
                mixed = NormedAttention(eps=self.eps, dtype=self.dtype,
                                        name="attn", **self.attn)(x)
        h = add_normed(scopes.MIXER, "ln_attn", x, mixed)
        with jax.named_scope(scopes.MLP):
            out = GatedMLP(self.mlp_width, self.dtype, name="mlp")(h)
        return add_normed(scopes.MLP, "ln_mlp", h, out)


class OlmoHybridLM(nn.Module):
    """Decoder-only LM, ``model(tokens) -> float32 logits [B, T, vocab]``
    (``model(tokens, loss_tokens=tokens)``: their ``lm_loss``, the logits
    never whole, ``head.lm_head_loss``).
    Layer ``i`` mixes with ``layer_types[i]``. The head counts are those
    held here. No positional encoding of any kind: the delta-rule layers
    carry order."""

    vocab_size: int
    d_model: int
    layer_types: Tuple[str, ...]
    linear_heads: int
    linear_key_dim: int
    linear_value_dim: int
    conv_size: int
    allow_neg_eigval: bool
    num_heads: int
    num_kv_heads: int
    head_dim: int
    mlp_width: int
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"    # "dense": the tests' written-out attention
    rule: str = "chunked"       # "recurrent": the tests' token-by-token scan
    # jax.checkpoint each block: the block-boundary activations and the
    # mixer kernel's outputs are stored (``parts.keep_policy``), the
    # rest of a block's interior is recomputed in backward
    remat: bool = False

    @classmethod
    def from_config(cls, config: dict, **overrides) -> "OlmoHybridLM":
        """The model of a published ``config.json``'s keys, cut to
        ``num_hidden_layers`` leading layers, holding ``heads_held``
        ``{"first": .., "count": ..}`` of every layer's
        ``num_attention_heads`` heads (all of them when absent). Seeded
        weights know no head's number, so ``first`` only says which rows of
        a whole model's projections these would be."""
        published = config["num_attention_heads"]
        if not published == config["num_key_value_heads"] \
                == config["linear_num_key_heads"] \
                == config["linear_num_value_heads"]:
            raise ValueError("layers with head counts of their own are not "
                             "supported")
        held = config.get("heads_held", {"first": 0, "count": published})
        if not 0 <= held["first"] <= held["first"] + held["count"] \
                <= published or held["count"] < 1:
            raise ValueError(f"heads_held {held} is no part of {published} "
                             f"heads")
        depth = config["num_hidden_layers"]
        fields = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            layer_types=tuple(config["layer_types"][:depth]),
            linear_heads=held["count"],
            linear_key_dim=config["linear_key_head_dim"],
            linear_value_dim=config["linear_value_head_dim"],
            conv_size=config["linear_conv_kernel_dim"],
            allow_neg_eigval=config["linear_allow_neg_eigval"],
            num_heads=held["count"], num_kv_heads=held["count"],
            head_dim=config.get("head_dim")
            or config["hidden_size"] // published,
            mlp_width=config["intermediate_size"],
            eps=config["rms_norm_eps"])
        fields.update(overrides)
        return cls(**fields)

    @nn.compact
    def __call__(self, tokens, loss_tokens=None):
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f"layer_types must be of {LAYER_TYPES}, got "
                             f"{sorted(unknown)}")
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         embedding_init=INIT, name="tok_embed")(tokens)
        block_cls = nn.remat(OlmoHybridBlock, policy=keep_policy(
            "ops.kda", "ops.pallas_attention")) \
            if self.remat else OlmoHybridBlock
        gdn = dict(num_heads=self.linear_heads, key_dim=self.linear_key_dim,
                   value_dim=self.linear_value_dim, conv_size=self.conv_size,
                   allow_neg_eigval=self.allow_neg_eigval, rule=self.rule)
        attn = dict(num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                    head_dim=self.head_dim, attention=self.attention)
        for i, mixer in enumerate(self.layer_types):
            x = block_cls(mixer=mixer, gdn=gdn, attn=attn,
                          mlp_width=self.mlp_width, eps=self.eps,
                          dtype=self.dtype, name=f"block_{i}")(x)
        return norm_and_head(x, self.vocab_size, self.eps, self.dtype,
                             loss_tokens)
