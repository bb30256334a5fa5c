"""Autograd rules of the three collectives (reference:
``test/test_torch.py:377-428`` allreduce grad, ``:570-611`` allgather grad,
``:768-800`` broadcast grad; TF mirrors at ``test_tensorflow.py:334-367``,
``:592-643``, ``:723-764``).

The reference registers explicit backward rules: allreduce's backward is an
allreduce of the cotangent, allgather's backward is the local slice
(reduce-scatter) of the cotangent, broadcast's backward psums cotangents to
the root (zero elsewhere). In JAX these arise from the transpose rules of
``psum``/``all_gather``/the masked-psum broadcast — these tests pin the
resulting semantics against analytic expectations so a regression in the op
implementations (or a JAX behavior change) is caught.

Loss phrasing — the data-parallel convention, deliberately: each shard
differentiates its LOCAL contribution ``L_i`` to the global loss
``L = sum_i L_i`` and the collective's own transpose supplies the
cross-shard fold, exactly how ``DistributedOptimizer`` produces gradients.
This phrasing is correct under BOTH shard_map tracing regimes: with vma
typing (newer JAX) the cotangent of an axis-invariant collective output is
auto-psummed; under legacy tracing (older JAX, or ``check_vma=False``)
psum's transpose-is-psum supplies the identical fold. The previous
phrasing — wrapping the loss in an extra ``lax.psum`` to spell out "the"
global loss — double-counts by the axis size under the legacy transpose
(each psum transposes to a psum, so the already-folded cotangent gets
folded again): a real test bug, fixed here, that made all five tests fail
by exactly a factor of N on pre-vma JAX.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.ops import spmd
from horovod_tpu.parallel import DATA_AXIS, data_parallel_mesh

N = 8  # conftest forces an 8-device CPU world


def _run(fn, *args, in_specs, out_specs):
    mesh = data_parallel_mesh()
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs))(*args)


def test_allreduce_grad(hvd):
    """L = sum_i w_i . allreduce_sum(x) via local contributions
    L_i = w_i . y => dL/dx_j = sum_i w_i, on every shard (allreduce
    backward == allreduce of cotangents)."""
    x = jnp.arange(N * 3, dtype=jnp.float32).reshape(N, 3)
    w = jnp.arange(1.0, N + 1)[:, None] * jnp.ones((N, 3))  # shard i -> i+1

    def per_shard(x, w):
        def loss(x):
            y = spmd.allreduce(x, DATA_AXIS, average=False)
            return jnp.vdot(w[0], y)

        return jax.grad(loss)(x)

    g = _run(per_shard, x, w,
             in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(DATA_AXIS))
    expected = np.full((N, 3), sum(range(1, N + 1)), np.float32)
    np.testing.assert_allclose(np.asarray(g), expected)


def test_allreduce_mean_grad(hvd):
    """Average variant: backward divides by the world size
    (``torch/mpi_ops.py:110-121`` divides the cotangent for average=True).
    Local contributions as in ``test_allreduce_grad``, L_i = w_i . y with
    y the mean, so dL/dx_j = sum_i w_i / N. The weight is what makes L_i a
    per-shard value: a loss of ``y`` alone is replicated, which vma
    tracking differentiates as ONE loss, not as the sum of N copies."""
    x = jnp.arange(N * 2, dtype=jnp.float32).reshape(N, 2)
    w = jnp.arange(1.0, N + 1)[:, None] * jnp.ones((N, 2))  # shard i -> i+1

    def per_shard(x, w):
        def loss(x):
            y = spmd.allreduce(x, DATA_AXIS, average=True)
            return jnp.vdot(w[0], y)

        return jax.grad(loss)(x)

    g = _run(per_shard, x, w,
             in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(DATA_AXIS))
    expected = np.full((N, 2), sum(range(1, N + 1)) / N, np.float32)
    np.testing.assert_allclose(np.asarray(g), expected, rtol=1e-6)


def test_allgather_grad(hvd):
    """L = sum_i c_i . allgather(x) via L_i = c_i . y => dL/dx_j =
    sum_i c_i sliced to shard j's segment (allgather backward ==
    reduce-scatter of cotangents, the local-slice rule of
    ``test_torch.py:570-611``)."""
    k = 2  # rows per shard
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.standard_normal((N * k, 3)).astype(np.float32))
    c = jnp.asarray(rng.standard_normal((N, N * k, 3)).astype(np.float32))

    def per_shard(x, c):
        def loss(x):
            y = spmd.allgather(x, DATA_AXIS)  # (N*k, 3) on every shard
            return jnp.vdot(c[0], y)

        return jax.grad(loss)(x)

    g = _run(per_shard, x, c,
             in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(DATA_AXIS))
    c_sum = np.asarray(c).sum(axis=0)  # sum of every shard's cotangent
    np.testing.assert_allclose(np.asarray(g), c_sum, rtol=1e-5)


def test_broadcast_grad(hvd):
    """L = sum_i c_i . broadcast(x, root) via L_i = c_i . y => dL/dx =
    sum_i c_i on the root shard, zero elsewhere (``test_torch.py:768-800``)."""
    root = 2
    x = jnp.ones((N, 4), jnp.float32)
    c = jnp.arange(1.0, N + 1)[:, None] * jnp.ones((N, 4))

    def per_shard(x, c):
        def loss(x):
            y = spmd.broadcast(x[0], root, DATA_AXIS)
            return jnp.vdot(c[0], y)

        return jax.grad(loss)(x)

    g = _run(per_shard, x, c,
             in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(DATA_AXIS))
    g = np.asarray(g)
    total = sum(range(1, N + 1))
    for i in range(N):
        expected = total if i == root else 0.0
        np.testing.assert_allclose(g[i], np.full(4, expected),
                                   err_msg=f"shard {i}")


def test_reducescatter_grad(hvd):
    """reducescatter backward == allgather of cotangents (transpose pair of
    the allgather rule)."""
    k = 2
    x = jnp.ones((N, N * k), jnp.float32)
    c = jnp.arange(1.0, N + 1)[:, None] * jnp.ones((N, k))

    def per_shard(x, c):
        def loss(x):
            y = spmd.reducescatter(x[0], DATA_AXIS)  # (k,) rows per shard
            return jnp.vdot(c[0], y)

        return jax.grad(loss)(x)

    g = _run(per_shard, x, c,
             in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(DATA_AXIS))
    # every shard's x contributes its segment-s rows to shard s's output,
    # so dL/dx is the concatenation of all shards' cotangents — identical
    # on every shard.
    expected = np.repeat(np.arange(1.0, N + 1), k)[None, :].repeat(N, axis=0)
    np.testing.assert_allclose(np.asarray(g), expected)
