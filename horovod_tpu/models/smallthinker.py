"""SmallThinker-style sparse decoder (docs/smallthinker.md): pre-RMSNorm
blocks, grouped key/value heads, *full* causal layers that carry no
positions at all beside *window* layers whose q and k are rotated over the
whole head, and in every layer routed ReLU-gated experts under a softmax
router that reads the block's input from before the attention
(PowerInfer/SmallThinker-21BA3B-Instruct's ``config.json``).

For block input ``x``::

    a   = x + Attn(RMSNorm_1(x))
    p   = softmax over the k largest of (x W_r)     # x itself, un-normed
    out = a + sum_e p_e W2_e(relu(W1_e h) * (W3_e h)),   h = RMSNorm_2(a)

so the routing of a layer depends on nothing its attention computes and the
router's gradient reaches the block's input directly. The same call as the
other decoders (``model(tokens) -> float32 logits``, or the loss itself
given ``loss_tokens``): ``make_lm_train_step`` takes it unchanged. The
expert layer is ``experts.ExpertLayer`` told ``experts_held`` (one chip of
an expert-parallel deployment), ``scoring="softmax"``, ``gate="relu"``, no
shared expert, and called with ``routed_by=x``.
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .experts import ExpertLayer, held_of
from .head import norm_and_head
from .parts import INIT, Rotary, attend, dense, keep_policy, rms_norm


class GroupedCausalAttention(nn.Module):
    """Causal self-attention with ``num_heads`` query heads on
    ``num_kv_heads`` key/value heads, no bias, gate or q/k norm. ``rotary``
    rotates q and k; ``None``: the layer has no positions (absent, not
    zero: the causal mask alone orders the tokens). ``window``: a query
    sees that many keys, itself included."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    rotary: Optional[Rotary] = None
    window: Optional[int] = None
    dtype: Any = jnp.bfloat16
    attention: str = "flash"

    @nn.compact
    def __call__(self, x, positions):
        def heads(n, name):
            with jax.named_scope(scopes.MIXER_PROJ):
                return dense((n, self.head_dim), name, self.dtype)(x)

        q, k = heads(self.num_heads, "query"), heads(self.num_kv_heads, "key")
        if self.rotary is not None:
            q, k = self.rotary(q, positions), self.rotary(k, positions)
        out = attend(q, k, heads(self.num_kv_heads, "value"), self.attention,
                     window=self.window)
        with jax.named_scope(scopes.MIXER_PROJ):
            return dense(x.shape[-1], "out", self.dtype,
                         axis=(-2, -1))(out.astype(self.dtype))


class SmallThinkerBlock(nn.Module):
    """Pre-RMSNorm residual block: attention, then the expert layer, whose
    router reads the block's input ``x`` and whose experts read the
    attention half's output.

    With ``remat`` each half is a ``jax.checkpoint`` of its own, as
    ``laguna.LagunaBlock``'s, and every attention half keeps its flash
    kernel's output and log-sum-exp (``parts.keep_policy``), a window layer
    too: a window of 4,096 at 16,384 positions keeps 44 % of the causal
    pairs, so running ``flash_win_fwd`` again is not the cheap thing it is
    under Laguna's window of 512.

    Where the routing is computed: inside the second half, with the expert
    layer, from the block's input — which is the first half's input and so
    stored anyway. That half keeps what its backward pass takes of the
    routing (``experts.KEPT_NAMES``: the selected experts, their scores, the
    slots and the tile ends, 1.2 MB a layer at 16,384 tokens, named where
    the layer makes them), so the recomputed half runs the router's product
    and softmax and neither ``top_k`` nor the slots' sort again. Its
    operations stay under ``hvd.moe.route`` and outside ``hvd.mixer``. The
    price list at 1 x 16,384 (docs/smallthinker.md): a layer's kept ``o``
    and ``lse`` are 0.12 GB, for an 18.8 ms run of ``flash_fwd`` or an 8.9
    ms run of ``flash_win_fwd``; ``python3 -m chipbench.aot --workload
    smallthinker_16k_1chip`` totals 12.20 GB with all four keeping."""

    attn: dict          # GroupedCausalAttention's fields
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
                return x + GroupedCausalAttention(
                    dtype=self.dtype, name="attn", **self.attn)(h, positions)

        def feed(block, x, a):
            return a + ExpertLayer(dtype=self.dtype, name="moe",
                                   **self.experts)(
                rms_norm(a, "ln_mlp", self.eps, self.dtype), routed_by=x)

        if self.remat:
            mix = nn.remat(mix, policy=keep_policy("ops.pallas_attention"))
            feed = nn.remat(feed, policy=keep_policy("models.experts"))
        return feed(self, x, mix(self, x, positions))


class SmallThinkerLM(nn.Module):
    """Decoder-only LM, ``model(tokens) -> float32 logits [B, T, vocab]``
    (``model(tokens, loss_tokens=tokens)``: their ``lm_loss``, the logits
    never whole, ``head.lm_head_loss``). Layer ``i`` sees ``window`` keys
    where ``windowed[i]`` and every earlier one otherwise, and rotates q and
    k where ``rotated[i]``."""

    vocab_size: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    windowed: Tuple[bool, ...]
    rotated: Tuple[bool, ...]
    window: int
    rope_theta: float
    expert_width: int
    num_experts: int
    experts_per_token: int
    experts_held: Tuple[int, int]
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attention: str = "flash"
    # jax.checkpoint each half of a block: the halves' inputs and the flash
    # kernels' outputs (``SmallThinkerBlock``) are stored, the rest of a
    # block's interior is recomputed in backward
    remat: bool = False

    @classmethod
    def from_config(cls, config: dict, **overrides) -> "SmallThinkerLM":
        """The model of a published ``config.json``'s keys, cut to
        ``num_hidden_layers`` leading layers, with ``experts_held``
        ``{"first": .., "count": ..}`` (all of them when absent)."""
        if not (config.get("moe_primary_router_apply_softmax", True)
                and config.get("norm_topk_prob", True)):
            raise ValueError("a router without softmax, or whose selected "
                             "weights are not renormalised, is not supported")
        if config.get("rope_scaling") is not None:
            raise ValueError("rope_scaling is not supported")
        depth = config["num_hidden_layers"]
        fields = dict(
            vocab_size=config["vocab_size"], d_model=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            windowed=tuple(map(bool, config["sliding_window_layout"][:depth])),
            rotated=tuple(map(bool, config["rope_layout"][:depth])),
            window=config["sliding_window_size"],
            rope_theta=config["rope_theta"],
            expert_width=config["moe_ffn_hidden_size"],
            num_experts=config["moe_num_primary_experts"],
            experts_per_token=config["moe_num_active_primary_experts"],
            experts_held=held_of(
                {"num_experts": config["moe_num_primary_experts"], **config}),
            eps=config["rms_norm_eps"])
        fields.update(overrides)
        return cls(**fields)

    @nn.compact
    def __call__(self, tokens, positions=None, loss_tokens=None):
        if len(self.windowed) != len(self.rotated):
            raise ValueError("windowed and rotated must be equally long")
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         embedding_init=INIT, name="tok_embed")(tokens)
        experts = dict(num_experts=self.num_experts,
                       experts_per_token=self.experts_per_token,
                       experts_held=self.experts_held,
                       width=self.expert_width, shared_width=0,
                       scoring="softmax", gate="relu")
        for i, (windowed, rotated) in enumerate(zip(self.windowed,
                                                    self.rotated)):
            attn = dict(
                num_heads=self.num_heads, num_kv_heads=self.num_kv_heads,
                head_dim=self.head_dim, attention=self.attention,
                window=self.window if windowed else None,
                rotary=Rotary(theta=self.rope_theta, dim=self.head_dim)
                if rotated else None)
            x = SmallThinkerBlock(
                attn=attn, experts=experts, eps=self.eps, dtype=self.dtype,
                remat=self.remat, name=f"block_{i}")(x, positions)
        return norm_and_head(x, self.vocab_size, self.eps, self.dtype,
                             loss_tokens)
