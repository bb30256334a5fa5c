"""``ops.kda``: the chunked gated delta rule against the recurrence token
by token — values, the final state and every gradient — at sequences that
are no whole number of chunks, with a decay near 0 and near 1 and beta at
both ends, under a decay a channel and a decay a head (keys and values of
their own widths, head counts that are no multiple of a grid step's four,
beta up to 2); what its kernels form in VMEM (the unit lower-triangular
inverse, the decayed products) against the closed form of one chunk; and
``flash_attention`` with a v narrower than its q and k against attention
written out."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import kda as kda_ops
from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.kda import kda, kda_recurrent


def operands(seed, batch=1, seq=150, heads=2, d_k=32, d_v=16, log_decay=0.1,
             beta=None, dtype=jnp.float32, a_head=False, strongest=1.0):
    """Unit q and k, normal v; ``g`` is ``-log_decay * softplus(normal)``,
    one a channel or, with ``a_head``, one a head; ``beta`` is ``strongest``
    times a sigmoid of normals unless it is given."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = (jax.random.normal(key, (batch, seq, heads, d_k))
            for key in keys[:2])
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    v = jax.random.normal(keys[2], (batch, seq, heads, d_v))
    g = -log_decay * jax.nn.softplus(jax.random.normal(
        keys[3], q.shape[:3] if a_head else q.shape))
    if beta is None:
        write = strongest * jax.nn.sigmoid(
            jax.random.normal(keys[4], q.shape[:3]))
    else:
        write = jnp.full(q.shape[:3], beta, jnp.float32)
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, write)


def _gradients(fn, args, weight):
    return jax.grad(lambda *a: jnp.sum(fn(*a)[0].astype(jnp.float32)
                                       * weight), argnums=(0, 1, 2, 3, 4))(
                                           *args)


_CASES = {
    # id: operands' keywords
    "a_chunk_and_a_part": dict(seq=150),
    "shorter_than_a_chunk": dict(seq=40),
    "whole_chunks_two_sequences": dict(seq=128, batch=2),
    # alpha = exp(g): about 0.999 a token, and e^-14 (a millionth) a token —
    # the second overflows any form that divides by a cumulative decay
    "decay_near_one": dict(log_decay=0.001),
    "decay_near_zero": dict(log_decay=20.0),
    "never_writes": dict(beta=0.0),
    "always_overwrites": dict(beta=1.0),
    "values_wider_than_keys": dict(d_k=16, d_v=48, heads=3),
    # the gated delta rule (arXiv:2412.06464): one decay a head, beta in
    # (0, 2), keys half as wide as values; 3 and 5 heads are one group of
    # four reaching past the heads there are, and one such after a whole one
    "a_head_3_heads": dict(a_head=True, strongest=2.0, d_k=24, d_v=48,
                           heads=3),
    "a_head_5_heads_two_sequences": dict(
        a_head=True, strongest=2.0, d_k=16, d_v=32, heads=5, batch=2,
        seq=100),
    "a_head_decay_near_zero": dict(a_head=True, strongest=2.0,
                                   log_decay=20.0, seq=80),
    "a_head_negative_eigenvalue": dict(a_head=True, beta=1.9, seq=70),
}


@pytest.mark.parametrize("case", _CASES)
def test_chunked_against_token_by_token(case):
    args = operands(3, **_CASES[case])
    o, state = kda(*args)
    want_o, want_state = kda_recurrent(*args)
    scale = float(jnp.abs(want_o).max()) or 1.0
    np.testing.assert_allclose(o, want_o, atol=2e-6 * max(scale, 1.0))
    # a head's decays are differences of one cumulative sum: at e^-14 a
    # token that sum reaches 900 in a chunk, and its float32 rounding is
    # 5e-5 of an exponent near the chunk's end (a channel's sums start
    # again every 16 rows)
    np.testing.assert_allclose(
        state, want_state,
        atol=5e-6 if case == "a_head_decay_near_zero" else 2e-6)
    if case == "never_writes":
        assert float(jnp.abs(o).max()) == 0.0
    if _CASES[case].get("strongest") == 2.0:
        assert 0.2 < float((args[4] > 1.0).mean()) < 0.8
    weight = jax.random.normal(jax.random.PRNGKey(9), o.shape)
    for name, got, want in zip("q k v g beta".split(),
                               _gradients(kda, args, weight),
                               _gradients(kda_recurrent, args, weight)):
        np.testing.assert_allclose(
            got, want, atol=1e-5 * max(1.0, float(jnp.abs(want).max())),
            err_msg=name)


def test_repeated_keys_do_not_cancel():
    """Every token the same key, beta 1, no decay: ``I + A`` is all ones
    below the diagonal, whose inverse by a product of powers cancels
    catastrophically; by substitution it is the bidiagonal (1, -1). With
    q = k, a scale of 1 and v the identity a one-chunk call returns ``B``
    (all ones on and below the diagonal) times that inverse: the
    identity."""
    q, k, v, g, beta = operands(5, seq=64, heads=1, beta=1.0)
    k = jnp.broadcast_to(k[:, :1], k.shape)
    args = (q, k, v, jnp.zeros_like(g), beta)
    o, _ = kda(*args)
    np.testing.assert_allclose(o, kda_recurrent(*args)[0], atol=1e-5)
    eye = jnp.eye(64).reshape(1, 64, 1, 64)
    o, _ = kda(k, k, eye, jnp.zeros_like(g), beta, scale=1.0)
    np.testing.assert_allclose(o[0, :, 0], np.eye(64), atol=1e-6)


def _one_chunk(q, k, v, g, beta, scale, xp=jnp):
    """A chunk from a zero state in closed form, one head: ``(O, S') = (B M
    V, K-^T M V)``, ``M = (I + Diag(beta) A)^-1 Diag(beta)``, with ``A``
    and ``B`` by the pair (module docstring); q, k, g ``[C, d_k]``, v ``[C,
    d_v]``, beta ``[C]``."""
    size = q.shape[0]
    cum = xp.cumsum(g, axis=0)
    visible = np.tril(np.ones((size, size), bool))
    pairs = xp.exp(xp.where(visible[..., None],
                            cum[:, None, :] - cum[None, :, :], -xp.inf))
    a = xp.einsum("rc,ic,ric->ri", k, k, pairs) * np.tril(
        np.ones((size, size)), -1)
    b = scale * xp.einsum("rc,ic,ric->ri", q, k, pairs)
    solve = xp.linalg.inv(xp.eye(size) + beta[:, None] * a) * beta[None, :]
    u = solve @ v
    return b @ u, (k * xp.exp(cum[-1:] - cum)).T @ u


def test_one_chunk_against_the_closed_form_and_its_gradient():
    """What ``kda_fwd`` forms in VMEM — the decayed products, the unit
    lower-triangular inverse — read through a one-chunk call, and what
    ``kda_bwd`` pulls back through them against JAX's derivative of the
    closed form (``jnp.linalg.inv``'s among it)."""
    args = operands(11, seq=64, heads=1, log_decay=0.3)
    alone = lambda x: x[0, :, 0]  # noqa: E731
    weight = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    scale = 32 ** -0.5
    with jax.default_matmul_precision("highest"):
        def closed(*a):
            return _one_chunk(*map(alone, a), scale)

        want_o, want_state = closed(*args)
        o, state = kda(*args)
        np.testing.assert_allclose(alone(o), want_o, rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(state[0, 0], want_state, rtol=1e-4,
                                   atol=1e-5)
        got, want = (jax.grad(lambda *a: jnp.sum(alone(f(*a)[0]) * weight),
                              argnums=(0, 1, 2, 3, 4))(*args)
                     for f in (kda, lambda *a: (closed(*a)[0][None, :, None],)))
    for name, a, b in zip("q k v g beta".split(), got, want):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-4, err_msg=name)


def test_decayed_products_against_the_definition():
    """``sum_c x_rc k_ic exp(G_rc - G_ic)`` on and below the diagonal, by
    the pair in float64, read through a one-chunk call; no exponent the
    kernel takes is positive even where ``G`` falls by hundreds inside a
    chunk."""
    rng = np.random.default_rng(1)
    q, k = (rng.standard_normal((64, 8)) for _ in range(2))
    v = rng.standard_normal((64, 8))
    g = -rng.uniform(0, 12, (64, 8))
    beta = rng.uniform(0, 1, 64)
    want_o, want_state = _one_chunk(q, k, v, g, beta, 1.0, xp=np)
    o, state = kda(*(jnp.asarray(x, jnp.float32)[None, :, None]
                     for x in (q, k, v, g)),
                   jnp.asarray(beta, jnp.float32)[None, :, None], scale=1.0)
    assert float(-np.cumsum(g, 0).min()) > 300
    assert np.isfinite(np.asarray(o)).all()
    np.testing.assert_allclose(o[0, :, 0], want_o, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state[0, 0], want_state, rtol=1e-4, atol=1e-6)


def test_bfloat16_operands_and_a_chunk_that_is_not_the_default():
    args = operands(7, seq=96, dtype=jnp.bfloat16)
    want, _ = kda_recurrent(*args)
    for chunk in (32, 64):
        o, state = kda(*args, chunk=chunk)
        assert o.dtype == jnp.bfloat16 and state.dtype == jnp.float32
        np.testing.assert_allclose(o.astype(jnp.float32),
                                   want.astype(jnp.float32), atol=0.03)
    with pytest.raises(ValueError, match="multiple of 16"):
        kda(*args, chunk=24)


@pytest.mark.parametrize("a_head,names", [
    (False, ("kda_fwd", "kda_bwd")), (True, ("gdn_fwd", "gdn_bwd"))])
def test_the_chain_is_two_named_kernels(a_head, names):
    args = operands(1, seq=64, a_head=a_head)
    text = str(jax.make_jaxpr(jax.grad(lambda *a: kda(*a)[0].sum(),
                                       argnums=(0, 3)))(*args))
    assert all(name in text for name in names)
    assert not any(name in text for name in
                   {"kda_fwd", "kda_bwd", "gdn_fwd", "gdn_bwd"} - set(names))


@pytest.mark.parametrize("heads,widths,group", [
    (32, (128, 128), 4),    # Kimi-Linear: whole groups of whole vregs
    (2, (32, 16), 2),       # every head there is
    (15, (96, 192), 4),     # Olmo-Hybrid's share: 3 groups and 3 heads
    (30, (96, 192), 4), (5, (32, 64), 4), (3, (16, 48), 4)])
def test_heads_a_grid_step_takes(heads, widths, group):
    """``gcd(heads, 4)`` where that is every head or whole vregs of lanes;
    else four, the last group reaching past the heads there are."""
    assert kda_ops._heads_a_step(heads, *widths) == group


@pytest.mark.parametrize("a_head", [False, True])
def test_a_group_past_the_heads_reads_zeros(a_head):
    """5 heads in groups of four: the second group holds three heads that
    are not there. What a block reads past an array's end is unspecified —
    the interpreter puts NaN there —, and the kernels take zeros in its
    place: those heads' states, saved with the others' for the backward
    pass, stay zero, and everything either kernel writes is finite."""
    q, k, v, g, beta = operands(3, a_head=a_head, strongest=2.0, d_k=24,
                                d_v=48, heads=5, batch=2, seq=150)
    flat = lambda x: x.reshape(*x.shape[:2], -1)  # noqa: E731
    ins = (flat(q), flat(k), flat(v), g if a_head else flat(g), beta)
    o, final, starts = kda_ops._scan_fwd(*ins, 0.2, 64, True, True)
    assert starts.shape == (3, 2 * 8, 48, 24)       # 8 heads a sequence
    absent = starts.reshape(3, 2, 8, 48, 24)[:, :, 5:]
    assert float(jnp.abs(absent).max()) == 0.0
    assert bool(jnp.isfinite(starts).all() & jnp.isfinite(final).all())
    grads = kda_ops._scan_bwd(*ins, starts, jnp.ones_like(o[:, :150]), 0.2,
                              64, True)
    assert all(bool(jnp.isfinite(x).all()) for x in grads)


def test_no_loop_outside_the_two_kernels():
    """Nothing of the gradient program iterates but the kernels' own
    grids: what fed the chain from XLA loops (``lax.map`` over batches of
    chunks, a ``fori_loop`` in the triangular inverse) is inside
    ``kda_fwd`` and ``kda_bwd``."""
    def walk(jaxpr, seen):
        for eqn in jaxpr.eqns:
            seen.append(eqn.primitive.name)
            if eqn.primitive.name != "pallas_call":
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub, seen)
        return seen

    args = operands(1, seq=150, dtype=jnp.bfloat16)
    seen = walk(jax.make_jaxpr(jax.grad(
        lambda *a: kda(*a, interpret=True)[0].astype(jnp.float32).sum(),
        argnums=(0, 1, 2, 3, 4)))(*args).jaxpr, [])
    assert seen.count("pallas_call") == 2, seen
    assert not {"while", "scan"} & set(seen), seen


# -- flash attention with a v of its own width --------------------------------


def _dense(q, k, v):
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    keep = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
    weights = jax.nn.softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


@pytest.mark.parametrize("widths,kv_heads,blocks", [
    ((48, 32), 4, 128),     # v narrower than q: latent attention's shape
    ((24, 16), 4, None),    # one tile, the whole sequence
    ((32, 64), 2, 128),     # wider, and grouped heads
])
def test_flash_with_another_v_width_against_dense(widths, kv_heads, blocks):
    qk, dv = widths
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (2, 256, 4, qk))
    k = jax.random.normal(keys[1], (2, 256, kv_heads, qk))
    v = jax.random.normal(keys[2], (2, 256, kv_heads, dv))
    weight = jax.random.normal(keys[3], (2, 256, 4, dv))
    flash = lambda *a: pa.flash_attention(  # noqa: E731
        *a, causal=True, block_q=blocks, block_k=blocks)
    dense = lambda q, k, v: _dense(  # noqa: E731
        q, *(jnp.repeat(x, 4 // kv_heads, axis=2) for x in (k, v)))
    out = flash(q, k, v)
    assert out.shape == (2, 256, 4, dv)
    np.testing.assert_allclose(out, dense(q, k, v), atol=2e-5)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * weight),
                          argnums=(0, 1, 2))(q, k, v) for f in (flash, dense))
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_split_widths_name_their_kernels_and_count_both_in_the_budget():
    assert pa._kernel_names(None) == {
        "flash_fwd": "flash_fwd", "flash_bwd_dq": "flash_bwd_dq",
        "flash_bwd_dkv": "flash_bwd_dkv"}
    assert pa._kernel_names(512)["flash_bwd_dq"] == "flash_win_bwd_dq"
    assert pa._kernel_names(None, True) == {
        "flash_fwd": "flash_mla_fwd", "flash_bwd_dq": "flash_mla_bwd_dq",
        "flash_bwd_dkv": "flash_mla_bwd_dkv"}
    # two operands, two pipeline buffers each, widths padded to the lanes
    assert pa._operand_row_bytes(128, jnp.bfloat16) == 2 * 2 * 128 * 2
    assert pa._operand_row_bytes(128, jnp.bfloat16, 128) \
        == pa._operand_row_bytes(128, jnp.bfloat16)
    assert pa._operand_row_bytes(192, jnp.bfloat16, 128) \
        == 2 * (256 + 128) * 2
    # where the widths agree the tiles are what they were
    assert pa._tiles(8192, 8192, 128, jnp.bfloat16, None, None, None, 128) \
        == pa._tiles(8192, 8192, 128, jnp.bfloat16, None, None)
    q = jnp.zeros((1, 128, 2, 48))
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: pa.flash_attention(
        q, k, v, causal=True).sum()))(q, q, jnp.zeros((1, 128, 2, 32))))
    for name in ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv"):
        assert name in text, name
    with pytest.raises(ValueError, match="k's width must be q's"):
        pa.flash_attention(q, jnp.zeros((1, 128, 2, 32)),
                           jnp.zeros((1, 128, 2, 32)))


def _flat(x):
    return x.reshape(*x.shape[:2], -1)


def test_a_feed_is_recomputed_not_kept():
    """``kda_fed(feed, *args)`` is the delta rule on ``feed(*args)``, heads
    side by side (``[B, T, H * d]``), in value and in every gradient, and
    what its backward keeps is ``args``: nothing of the feed's results is
    among a gradient program's saved arrays."""
    q, k, v, g, beta = operands(2, seq=80)
    heads, d_k = q.shape[2:]
    gain = jnp.linspace(0.5, 1.5, heads * d_k)

    def feed(q, k, v, g, beta, gain):
        q = (q * gain).reshape(*q.shape[:2], heads, d_k)
        return (_flat(q / jnp.linalg.norm(q, axis=-1, keepdims=True)),
                jnp.tanh(k), v, g, beta)

    def by_head(q, k, v, g, beta):
        split = lambda x: x.reshape(*x.shape[:2], heads, -1)  # noqa: E731
        return _flat(kda(*map(split, (q, k, v, g)), beta)[0])

    args = (*map(_flat, (q, k, v, g)), beta, gain)
    weight = jax.random.normal(jax.random.PRNGKey(4), args[2].shape)
    fed = lambda *a: kda_ops.kda_fed(feed, *a)[0]  # noqa: E731
    plain = lambda *a: by_head(*feed(*a))          # noqa: E731
    np.testing.assert_allclose(fed(*args), plain(*args), atol=1e-6)
    got, want = (jax.grad(lambda *a: jnp.sum(f(*a) * weight),
                          argnums=tuple(range(6)))(*args)
                 for f in (fed, plain))
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5)
    _, residuals = jax.vjp(fed, *args)
    kept = [x.shape for x in jax.tree_util.tree_leaves(residuals)
            if hasattr(x, "shape") and x.ndim == 3
            and x.shape[:2] == q.shape[:2] and x.shape[2] > heads]
    assert len(kept) == 4, kept     # q, k, v and g as given, no more


def test_heads_side_by_side_is_the_four_axis_form_bit_for_bit():
    """``kda_fed`` on ``[B, T, H * d]`` as given (an identity feed) against
    ``kda`` on the same numbers by head, at a sequence that is no whole
    number of chunks and a v wider than k: the values, the final state and
    all five gradients are equal to the bit — ``kda`` is a reshape round
    the flat path and nothing else."""
    args = operands(6, seq=90, heads=3, d_k=16, d_v=48)
    flat = (*map(_flat, args[:4]), args[4])
    weight = jax.random.normal(jax.random.PRNGKey(8), (1, 90, 3, 48))

    def all_of(fn, args, weight):
        return jax.jit(lambda *a: (fn(*a), _gradients(fn, a, weight)))(*args)

    (o, state), got = all_of(
        lambda *a: kda_ops.kda_fed(kda_ops._as_given, *a), flat, _flat(weight))
    (want_o, want_state), want = all_of(kda, args, weight)
    assert o.shape == (1, 90, 3 * 48)
    np.testing.assert_array_equal(o, _flat(want_o))
    np.testing.assert_array_equal(state, want_state)
    for name, a, b in zip("q k v g beta".split(), got, want):
        assert a.ndim == 3, name
        np.testing.assert_array_equal(a, b.reshape(a.shape), err_msg=name)


@pytest.mark.parametrize("text,want", [
    ("kimi", dict(shape=(1, 16384, 32, 128), d_v=128, decay="channel",
                  beta=1.0)),
    ("olmo", dict(shape=(1, 8192, 15, 96), d_v=192, decay="head", beta=2.0)),
    ("olmo,chunk=32", dict(chunk=32, decay="head")),
    ("2,256,3,16,d_v=48,dtype=float32", dict(shape=(2, 256, 3, 16), d_v=48,
                                             dtype="float32"))])
def test_the_kernel_bench_reads_its_cases(text, want):
    """``benchmarks/kda_kernel_bench.py --case``: the two cells' calls by
    name, and the options that give values, a decay and a beta of their
    own."""
    from benchmarks.kda_kernel_bench import operands, parse_case

    case = parse_case(text)
    assert {key: case[key] for key in want} == want
    if case["shape"][1] <= 256:
        (q, _, v), g, beta, cot = operands(case, jnp.float32)
        assert v.shape == cot.shape == (*q.shape[:3], case["d_v"])
        assert g.shape == (q.shape if case["decay"] == "channel"
                           else q.shape[:3])
        assert float(beta.max()) <= case["beta"]
    with pytest.raises(ValueError, match="unknown option|channel or head"):
        parse_case(text + ",decay=token")

