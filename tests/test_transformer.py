"""Transformer LM model family: attention-backend equivalence and training.

The reference has no model code (SURVEY §5.7); these tests cover the
long-context extension's flagship — the same module must produce identical
logits under dense, flash-kernel, ring (sequence-parallel), and Ulysses
attention, and train data-parallel through DistributedOptimizer.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd_pkg
from horovod_tpu.models import TransformerLM, lm_loss
from horovod_tpu.parallel import DATA_AXIS, data_parallel_mesh

VOCAB, B, T = 64, 2, 64
CFG = dict(vocab_size=VOCAB, num_layers=2, num_heads=8, d_model=64,
           d_ff=128, max_seq_len=256, dtype=jnp.float32)


def _tokens(seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, VOCAB, (B, T)).astype(np.int32))


def _init(attention, tokens, seq_axis=None):
    """Model + params; params are backend-independent (same structure)."""
    model = TransformerLM(attention=attention, seq_axis=seq_axis, **CFG)
    variables = model.clone(attention="dense", seq_axis=None).init(
        jax.random.PRNGKey(0), tokens[:, :8])
    return model, variables


def test_forward_shape_and_dtype(hvd):
    tokens = _tokens()
    model, variables = _init("dense", tokens)
    logits = model.apply(variables, tokens)
    assert logits.shape == (B, T, VOCAB)
    assert logits.dtype == jnp.float32


def test_flash_matches_dense(hvd):
    """The Pallas kernel (interpret mode on CPU) must agree with the
    reference dense path."""
    tokens = _tokens()
    dense_m, variables = _init("dense", tokens)
    flash_m = TransformerLM(attention="flash", **CFG)
    ref = dense_m.apply(variables, tokens)
    out = flash_m.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("backend", ["ring", "ulysses"])
def test_sequence_parallel_matches_dense(hvd, backend):
    """Sharding the sequence over 8 devices must reproduce the dense logits
    (ring: shard-major rotation; ulysses: head re-sharding all_to_all)."""
    tokens = _tokens()
    dense_m, variables = _init("dense", tokens)
    ref = dense_m.apply(variables, tokens)

    sp_model = TransformerLM(attention=backend, seq_axis="data", **CFG)
    mesh = data_parallel_mesh()

    def fwd(variables, tokens_shard, positions_shard):
        return sp_model.apply(variables, tokens_shard, positions_shard)

    # sequence axis sharded: [B, T] -> per-shard [B, T/8]; shard-major
    # positions supplied explicitly
    positions = jnp.broadcast_to(jnp.arange(T), (B, T))
    out = jax.jit(shard_map(
        fwd, mesh=mesh,
        in_specs=(P(), P(None, DATA_AXIS), P(None, DATA_AXIS)),
        out_specs=P(None, DATA_AXIS)))(variables, tokens, positions)
    assert out.shape == ref.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def _train_losses(model, mesh, axis_name, tokens, data_spec, steps,
                  positions=None):
    """Shared DistributedOptimizer training loop over a mesh."""
    _, variables = _init(model.attention, tokens, seq_axis=model.seq_axis)
    opt = hvd_pkg.DistributedOptimizer(optax.adam(1e-2), axis_name=axis_name)
    opt_state = opt.init(variables)
    args = (tokens,) if positions is None else (tokens, positions)

    def step(variables, opt_state, *args):
        def loss_fn(v):
            return lm_loss(model.apply(v, *args), args[0])

        loss, grads = jax.value_and_grad(loss_fn)(variables)
        updates, opt_state = opt.update(grads, opt_state, variables)
        return (optax.apply_updates(variables, updates), opt_state,
                jax.lax.pmean(loss, axis_name))

    jitted = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(P(), P()) + (data_spec,) * len(args),
        out_specs=(P(), P(), P())))
    losses = []
    for _ in range(steps):
        variables, opt_state, loss = jitted(variables, opt_state, *args)
        losses.append(float(loss))
    return losses


def test_dp_training_loss_decreases(hvd):
    """End-to-end: DistributedOptimizer over the mesh, loss must drop."""
    rng = np.random.default_rng(1)
    # learnable structure: fixed repeating pattern
    seq = np.tile(np.arange(8), (8, T // 8 + 1))[:, :T].astype(np.int32)
    tokens = jnp.asarray(seq + rng.integers(0, 2, (8, T)))
    losses = _train_losses(TransformerLM(**CFG), data_parallel_mesh(),
                           DATA_AXIS, tokens, P(DATA_AXIS), steps=15)
    assert losses[-1] < losses[0] * 0.7, losses


def test_invalid_backend_rejected(hvd):
    tokens = _tokens()
    model = TransformerLM(attention="nope", **CFG)
    variables = TransformerLM(**CFG).init(jax.random.PRNGKey(0),
                                          tokens[:, :8])
    with pytest.raises(ValueError, match="attention must be one of"):
        model.apply(variables, tokens)


def test_ring_requires_seq_axis(hvd):
    tokens = _tokens()
    model = TransformerLM(attention="ring", **CFG)
    variables = TransformerLM(**CFG).init(jax.random.PRNGKey(0),
                                          tokens[:, :8])
    with pytest.raises(ValueError, match="requires seq_axis"):
        model.apply(variables, tokens)


def test_dp_sp_composition(hvd):
    """2-D mesh (docs/long-context.md): batch over 'data' (2), sequence
    over 'seq' (4); ring attention per seq group; DistributedOptimizer
    averages over both axes. Must train."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "seq"))
    rng = np.random.default_rng(3)
    seq = np.tile(np.arange(8), (4, T // 8)).astype(np.int32)
    tokens = jnp.asarray(seq + rng.integers(0, 2, (4, T)))
    positions = jnp.broadcast_to(jnp.arange(T), tokens.shape)
    losses = _train_losses(
        TransformerLM(attention="ring", seq_axis="seq", **CFG), mesh,
        ("data", "seq"), tokens, P("data", "seq"), steps=12,
        positions=positions)
    assert losses[-1] < losses[0] * 0.8, losses


def test_remat_matches_plain():
    """remat=True must be a pure memory/FLOP trade: identical logits and
    gradients, activations recomputed in backward instead of stored."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.models import TransformerLM, lm_loss

    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 32), 0, 64)
    kw = dict(vocab_size=64, num_layers=2, num_heads=2, d_model=32,
              d_ff=64, max_seq_len=64, dtype=jnp.float32)
    plain = TransformerLM(**kw)
    remat = TransformerLM(remat=True, **kw)
    params = plain.init(jax.random.PRNGKey(1), tokens)

    def loss_of(model):
        return lambda p: lm_loss(model.apply(p, tokens), tokens)

    # the flag must be observable, not just numerically equivalent: the
    # grad jaxpr of the remat model carries checkpoint (remat) equations,
    # the plain one does not — otherwise silently dropping nn.remat would
    # keep this test green while losing the memory trade it exists for
    jaxpr_r = str(jax.make_jaxpr(jax.grad(loss_of(remat)))(params))
    jaxpr_p = str(jax.make_jaxpr(jax.grad(loss_of(plain)))(params))
    assert "remat" in jaxpr_r, "remat=True produced no checkpoint eqns"
    assert "remat" not in jaxpr_p

    lp, gp = jax.value_and_grad(loss_of(plain))(params)
    lr, gr = jax.value_and_grad(loss_of(remat))(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-6)
    flat_p = jax.tree_util.tree_leaves(gp)
    flat_r = jax.tree_util.tree_leaves(gr)
    for a, b in zip(flat_p, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
