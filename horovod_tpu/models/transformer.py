"""Decoder-only Transformer LM with pluggable attention backends.

The reference has no model code at all (SURVEY §5.7: tensors are opaque
byte buffers); its examples pull models from torchvision/Keras apps. This
build's models live in-repo, and the transformer is the flagship for the
long-context extensions: the same module runs dense attention, the Pallas
flash kernel (``ops.pallas_attention``), or sequence-parallel ring/Ulysses
attention (``parallel.ring_attention``) — selected by a config knob, so the
examples/benchmarks can compare backends without touching model code.

TPU-first choices: bf16 compute with f32 params, pre-LayerNorm residual
blocks, static shapes throughout, causal masking only (an LM), positions
passed in explicitly so sequence-parallel shards (shard-major global order)
embed their true global positions.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .head import LMHead

ATTENTION_BACKENDS = ("dense", "flash", "ring", "ulysses")


class CausalSelfAttention(nn.Module):
    """Multi-head causal self-attention over [B, T, d_model]."""

    num_heads: int
    dtype: Any = jnp.bfloat16
    attention: str = "dense"
    seq_axis: Optional[str] = None  # mesh axis for ring/ulysses

    @nn.compact
    def __call__(self, x, positions):
        if self.attention not in ATTENTION_BACKENDS:
            raise ValueError(
                f"attention must be one of {ATTENTION_BACKENDS}, "
                f"got {self.attention!r}")
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError(f"d_model {d_model} not divisible by "
                             f"{self.num_heads} heads")
        head_dim = d_model // self.num_heads
        dense = partial(nn.DenseGeneral, dtype=self.dtype,
                        features=(self.num_heads, head_dim))
        with jax.named_scope(scopes.MIXER_PROJ):
            q = dense(name="query")(x)
            k = dense(name="key")(x)
            v = dense(name="value")(x)  # each [B, T, H, Dh]

        if self.attention == "flash":
            from ..ops.pallas_attention import flash_attention

            out = flash_attention(q, k, v, causal=True)
        elif self.attention == "ring":
            from ..parallel.ring_attention import ring_attention

            if self.seq_axis is None:
                raise ValueError("attention='ring' requires seq_axis")
            out = ring_attention(q, k, v, self.seq_axis, causal=True)
        elif self.attention == "ulysses":
            from ..parallel.ring_attention import ulysses_attention

            if self.seq_axis is None:
                raise ValueError("attention='ulysses' requires seq_axis")
            out = ulysses_attention(q, k, v, self.seq_axis, causal=True)
        else:
            from ..parallel.ring_attention import dense_attention

            out = dense_attention(q, k, v, causal=True)
        del positions  # causal order is positional by construction
        out = out.astype(self.dtype)
        with jax.named_scope(scopes.MIXER_PROJ):
            return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                                   name="out")(out)


class TransformerBlock(nn.Module):
    num_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    attention: str = "dense"
    seq_axis: Optional[str] = None

    @nn.compact
    def __call__(self, x, positions):
        with jax.named_scope(scopes.NORM):
            h = nn.LayerNorm(dtype=self.dtype, name="ln_attn")(x)
        with jax.named_scope(scopes.MIXER):
            x = x + CausalSelfAttention(
                num_heads=self.num_heads, dtype=self.dtype,
                attention=self.attention, seq_axis=self.seq_axis,
                name="attn")(h, positions)
        with jax.named_scope(scopes.NORM):
            h = nn.LayerNorm(dtype=self.dtype, name="ln_mlp")(x)
        with jax.named_scope(scopes.MLP):
            h = nn.Dense(self.d_ff, dtype=self.dtype, name="mlp_in")(h)
            h = nn.gelu(h)
            return x + nn.Dense(x.shape[-1], dtype=self.dtype,
                                name="mlp_out")(h)


class TransformerLM(nn.Module):
    """GPT-style LM: token + learned position embeddings, N pre-LN blocks,
    tied-free output head. Returns f32 logits [B, T, vocab] — or, given
    ``loss_tokens`` [B, T], the scalar ``lm_loss(logits, loss_tokens)``
    from ``head.lm_head_loss``, which never holds the logits whole.

    ``positions`` (global token positions, [B, T]) defaults to
    ``arange(T)``; sequence-parallel callers pass the shard's global
    positions (shard-major: shard i holds [i*T_local, (i+1)*T_local)).
    """

    vocab_size: int
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 256
    d_ff: int = 1024
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attention: str = "dense"
    seq_axis: Optional[str] = None
    # jax.checkpoint each block: only the L block-boundary activations are
    # stored; each block's interior (attention scores, MLP intermediates —
    # the dominant term) is recomputed in backward. ~1/3 more FLOPs for
    # roughly d_ff/d_model-fold less activation memory — the standard
    # lever for long sequences on HBM-bound chips.
    remat: bool = False

    @nn.compact
    def __call__(self, tokens, positions=None, loss_tokens=None):
        if positions is None:
            positions = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
        with jax.named_scope(scopes.EMBED):
            x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype,
                         name="tok_embed")(tokens)
            x = x + nn.Embed(self.max_seq_len, self.d_model,
                             dtype=self.dtype, name="pos_embed")(positions)
        block_cls = nn.remat(TransformerBlock) if self.remat \
            else TransformerBlock
        for i in range(self.num_layers):
            x = block_cls(
                num_heads=self.num_heads, d_ff=self.d_ff, dtype=self.dtype,
                attention=self.attention, seq_axis=self.seq_axis,
                name=f"block_{i}")(x, positions)
        with jax.named_scope(scopes.NORM):
            x = nn.LayerNorm(dtype=self.dtype, name="ln_final")(x)
        head = LMHead(self.vocab_size, dtype=jnp.float32, name="lm_head")
        if loss_tokens is not None:
            return head.loss(x, loss_tokens)
        with jax.named_scope(scopes.HEAD):
            return head(x).astype(jnp.float32)
