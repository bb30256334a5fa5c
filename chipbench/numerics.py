"""The arithmetic of the plain references, at the stated precision and at
the control's.

``exact`` is float32 with ``highest`` matmul precision (the caller sets
``jax.default_matmul_precision("highest")`` around the whole reference).
``fp8`` is the control of ``correct``: where a configuration computes in
bfloat16 the step that would tempt a later PR is fp8, so the control runs
those products the way an fp8 training path does — both operands rounded
to e4m3 with one scale per tensor going forward, the incoming gradient
rounded to e5m2 with one scale per tensor going back, accumulation in
float32. Everything the configuration keeps in float32 stays float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _rounded(x, dtype):
    """``x`` rounded to ``dtype`` under one scale for the whole tensor."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def round_forward(x, dtype):
    """Rounds the value, passes the gradient through unchanged."""
    return _rounded(x, dtype)


round_forward.defvjp(lambda x, dtype: (_rounded(x, dtype), None),
                     lambda dtype, _, g: (g,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def round_backward(x, dtype):
    """Passes the value through unchanged, rounds its gradient."""
    return x


round_backward.defvjp(lambda x, dtype: (x, None),
                      lambda dtype, _, g: (_rounded(g, dtype),))


class Exact:
    """Products as written: float32 operands, float32 result."""

    name = "float32"

    @staticmethod
    def product(fn, a, b):
        return fn(a, b)


class Fp8:
    """Products with e4m3 operands forward and an e5m2 gradient back."""

    name = "fp8"

    @staticmethod
    def product(fn, a, b):
        out = fn(round_forward(a, jnp.float8_e4m3fn),
                 round_forward(b, jnp.float8_e4m3fn))
        return round_backward(out, jnp.float8_e5m2)


NUMERICS = {"float32": Exact, "fp8": Fp8}


def _norms_by_path(per_leaf, tree, *others) -> dict:
    """L2 norm in float32 of ``per_leaf(x, *ys)`` for every leaf ``x`` of
    ``tree`` (and its fellows in ``others``), by the leaf's key path; one
    jitted call for the whole tree."""
    leaves = jax.tree_util.tree_leaves_with_path(tree)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    norms = jax.jit(lambda *lists: [
        jnp.sqrt(jnp.sum(jnp.square(per_leaf(*map(f32, xs)))))
        for xs in zip(*lists)])(
            [x for _, x in leaves], *map(jax.tree_util.tree_leaves, others))
    return {jax.tree_util.keystr(path): float(n)
            for (path, _), n in zip(leaves, norms)}


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf of ``tree``, by its key path."""
    return _norms_by_path(lambda x: x, tree)


def difference_norms(new, old) -> dict:
    """L2 norm of ``new - old`` leaf by leaf, by key path."""
    return _norms_by_path(lambda x, y: x - y, new, old)
