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

import math
from functools import partial, reduce
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes

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
    from :func:`lm_head_loss`, which never holds the logits whole.

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


def lm_loss(logits: jax.Array, tokens: jax.Array) -> jax.Array:
    """Next-token cross entropy (shift-by-one), mean over B and T-1."""
    import optax

    with jax.named_scope(scopes.HEAD):
        return optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], tokens[:, 1:]).mean()


# rows of B * T whose float32 logits ``lm_head_loss`` holds at a time. The
# head's gradient [d, V] is read and written once a block: on a v5e (197
# TFLOP/s, 819 GB/s) that traffic is 320 / rows of the block's three
# products' time whatever d and V are, a sixth here, for an eighth of a
# 16,384-token step's logits held
LOSS_ROWS = 2048


def _loss_block(kernel, bias, scale, x, targets, counted):
    """One block of :func:`lm_head_loss`: rows ``x [R, d]`` with their next
    tokens ``targets [R]`` and ``counted [R]`` (0.0 for a sequence's last
    position) give the rows' summed loss and, already times ``scale`` (one
    over the positions counted), ``dx [R, d]`` and the block's shares of
    the head's gradient: the float32 logits live and die here."""
    x32 = x.astype(jnp.float32)
    logits = jnp.dot(x32, kernel)
    if bias is not None:
        logits = logits + bias
    shifted = logits - logits.max(-1, keepdims=True)
    exp = jnp.exp(shifted)
    total = exp.sum(-1, keepdims=True)
    hit = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1) \
        == targets[:, None]
    loss = (jnp.log(total[:, 0])
            - jnp.where(hit, shifted, 0.0).sum(-1)) * counted
    dlogits = (exp / total - hit) * (counted * scale)[:, None]
    dx = jnp.dot(dlogits, kernel.T).astype(x.dtype)
    dbias = None if bias is None else dlogits.sum(0)
    return loss.sum(), dx, jnp.dot(x32.T, dlogits), dbias


def _head_loss_fwd(x, kernel, bias, tokens):
    *batch, d = x.shape
    rows = math.prod(batch)
    seq = tokens.shape[-1]
    scale = 1.0 / (rows - rows // seq)
    # row (b, t) is scored against token t + 1; the last has none
    targets = jnp.roll(tokens, -1, axis=-1).reshape(rows)
    counted = jnp.broadcast_to(jnp.arange(seq) < seq - 1,
                               tokens.shape).reshape(rows).astype(jnp.float32)
    return _visit_blocks(kernel, bias, scale, x.reshape(rows, d), targets,
                         counted, batch)


def _visit_blocks(kernel, bias, scale, x, targets, counted, batch):
    """``_loss_block`` over the rows ``x [rows, d]``, ``LOSS_ROWS`` at a
    time: the loss and what the backward rule scales, ``dx`` shaped
    ``[*batch, d]``."""
    rows, d = x.shape
    # the blocks written out one after another, not a ``lax.scan``: the
    # compiler orders them by the sums they feed, and a loop's carried
    # [d, V] sum cost Kimi-Linear's step 0.3 GB more than this does
    loss, dx, dkernel, dbias = zip(*(
        _loss_block(kernel, bias, scale, *(a[start:start + LOSS_ROWS]
                                           for a in (x, targets, counted)))
        for start in range(0, rows, LOSS_ROWS)))
    return reduce(jnp.add, loss) * scale, (
        jnp.concatenate(dx).reshape(*batch, d), reduce(jnp.add, dkernel),
        None if bias is None else reduce(jnp.add, dbias))


@jax.custom_vjp
def _head_loss(x, kernel, bias, tokens):
    return _head_loss_fwd(x, kernel, bias, tokens)[0]


def _head_loss_bwd(grads, g):
    return (*jax.tree_util.tree_map(lambda a: (g * a).astype(a.dtype),
                                    grads), None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def _weighted_loss_fwd(x, kernel, bias, targets, weights):
    *batch, d = x.shape
    rows = math.prod(batch)
    return _visit_blocks(kernel, bias, 1.0 / rows, x.reshape(rows, d),
                         targets.reshape(rows),
                         weights.reshape(rows).astype(jnp.float32), batch)


@jax.custom_vjp
def _weighted_loss(x, kernel, bias, targets, weights):
    return _weighted_loss_fwd(x, kernel, bias, targets, weights)[0]


_weighted_loss.defvjp(
    _weighted_loss_fwd, lambda grads, g: (*_head_loss_bwd(grads, g), None))


def lm_head_loss(x: jax.Array, kernel: jax.Array, bias: Optional[jax.Array],
                 tokens: Optional[jax.Array] = None, *,
                 targets: Optional[jax.Array] = None,
                 weights: Optional[jax.Array] = None) -> jax.Array:
    """``lm_loss(x @ kernel + bias, tokens)`` for final hidden states ``x
    [B, T, d]``, without the float32 logits ``[B, T, V]`` or their gradient
    ever existing whole: the head and its loss a block of ``LOSS_ROWS`` rows
    at a time.

    Given ``targets`` and ``weights`` (both ``[B, T]``) in place of
    ``tokens``, row ``(b, t)`` is scored against ``targets[b, t]`` itself,
    no shift, its cross entropy times ``weights[b, t]``, and the sum is
    divided by the ``B * T`` rows: the loss of a masked-diffusion objective,
    whose weights are zero off the masked positions
    (``models.sdar.block_diffusion_noise``). Neither gets a gradient.

    A block's visit forms its float32 logits, their log-sum-exp and the
    block's share of the loss and, while it has them, their gradient
    (softmax less one-hot, over the ``B * (T - 1)`` positions counted),
    ``dx = dlogits @ kernel.T`` and ``dkernel += x.T @ dlogits`` — the three
    products of the whole-tensor head, with float32 operands at the same
    precision, none a second time. The forward rule returns the loss and
    keeps ``dx``, ``dkernel``, ``dbias``; the backward rule scales them by
    the cotangent. Only the order of the sums over rows differs from the
    whole tensor's, and one visit does what autodiff's several passes over
    the whole logits did: the step's head costs less time as well as less
    memory (PERF.md, PR 41), so every LM's step takes it."""
    from ..ops.spmd import vary_like

    if (tokens is None) == (targets is None) \
            or (targets is None) != (weights is None):
        raise ValueError("lm_head_loss takes tokens, or targets and weights")
    with jax.named_scope(scopes.HEAD):
        # replicated parameters beside sharded rows: typed alike inside the
        # rule, their gradient summed over the mesh axis once, outside it
        if bias is None:
            (kernel,) = vary_like(x, kernel)
        else:
            kernel, bias = vary_like(x, kernel, bias)
        if targets is not None:
            return _weighted_loss(x, kernel, bias, targets, weights)
        return _head_loss(x, kernel, bias, tokens)


class LMHead(nn.Dense):
    """The output head ``lm_head``: ``head(x)`` is ``nn.Dense``'s logits,
    ``head.loss(x, tokens)`` the same parameters under
    :func:`lm_head_loss`."""

    def loss(self, x, tokens=None, **weighted):
        if self.is_initializing():
            self(x[..., :1, :])     # ``nn.Dense`` declares the parameters
        params = self.variables["params"]
        return lm_head_loss(x, params["kernel"], params.get("bias"), tokens,
                            **weighted)
