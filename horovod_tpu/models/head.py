"""The output head every LM ends in, and its loss (``lm_loss`` over whole
logits, ``lm_head_loss`` a block of rows at a time so that the float32
logits never exist whole), with the norm-and-head tail the RMSNorm decoders
share (``norm_and_head``).
"""

from __future__ import annotations

import math
from functools import reduce
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from . import scopes
from .parts import INIT, rms_norm


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


def norm_and_head(x, vocab_size: int, eps: float, dtype, tokens=None,
                  **weighted):
    """A decoder's end, called inside the LM's ``__call__``: the RMSNorm
    ``ln_final``, then the bias-free float32 ``lm_head``: its logits, or
    ``LMHead.loss`` given ``tokens`` (or ``targets`` and ``weights``)."""
    x = rms_norm(x, "ln_final", eps, dtype)
    head = LMHead(vocab_size, use_bias=False, dtype=jnp.float32,
                  kernel_init=INIT, name="lm_head")
    if tokens is not None or weighted:
        return head.loss(x, tokens, **weighted)
    with jax.named_scope(scopes.HEAD):
        return head(x).astype(jnp.float32)
