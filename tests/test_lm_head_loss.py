"""``models.head.lm_head_loss``: the head and its loss a block of
rows at a time against ``lm_loss`` over whole logits (the loss and the
gradients to the hidden states, the kernel and the bias), the four LMs
through the step body's loss against ``lm_loss`` of their logits (equal
numbers, and no float32 array of ``B * T * V`` elements in the step body's
program), and two devices through ``make_lm_train_step`` against one (the
replicated head's gradient summed over the mesh axis once)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks._dp_step import lm_step_loss, make_lm_train_step
from horovod_tpu.models import (KimiLinearLM, LagunaLM, OlmoHybridLM,
                                TransformerLM, head, lm_head_loss, lm_loss)


@pytest.mark.parametrize("batch, seq", [(1, 4096), (2, 2048 + 100), (3, 50)],
                         ids=["whole_blocks", "blocks_and_a_tail", "a_tail"])
@pytest.mark.parametrize("bias", [True, False], ids=["bias", "no_bias"])
def test_blocked_head_and_loss_against_whole_logits(batch, seq, bias):
    """Rows in whole blocks of ``LOSS_ROWS``, blocks and a remainder, fewer
    than a block; one and several sequences (a sequence's last position
    counts for nothing wherever in a block it falls); with and without a
    bias; hidden states in bfloat16 as the models hand them over. The
    cotangent scales the kept gradients."""
    d, vocab = 16, 40
    keys = jax.random.split(jax.random.PRNGKey(seq), 4)
    x = jax.random.normal(keys[0], (batch, seq, d)).astype(jnp.bfloat16)
    kernel = 0.3 * jax.random.normal(keys[1], (d, vocab))
    tokens = jax.random.randint(keys[3], (batch, seq), 0, vocab)
    args = (x, kernel) + ((0.1 * jax.random.normal(keys[2], (vocab,)),)
                          if bias else ())

    def whole(x, kernel, b=0.0):
        return 3.0 * lm_loss(x.astype(jnp.float32) @ kernel + b, tokens)

    def blocked(x, kernel, b=None):
        return 3.0 * lm_head_loss(x, kernel, b, tokens)

    argnums = tuple(range(len(args)))
    want = jax.jit(jax.value_and_grad(whole, argnums))(*args)
    got = jax.jit(jax.value_and_grad(blocked, argnums))(*args)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)
    assert got[1][0].dtype == jnp.bfloat16
    for a, b in zip(got[1], want[1]):
        a, b = (np.asarray(g, np.float32) for g in (a, b))
        # bfloat16 for the hidden states' gradient, f32 rounding otherwise
        np.testing.assert_allclose(
            a, b, rtol=1e-2 if a.shape == x.shape else 2e-5,
            atol=2e-6 * np.abs(b).max())


def _f32_sizes(jaxpr, last: int) -> set:
    """Sizes of the float32 arrays whose last dimension is ``last`` that
    the equations of ``jaxpr`` and of every jaxpr inside them produce."""
    sizes = set()
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = var.aval
            if getattr(aval, "dtype", None) == jnp.float32 and aval.shape \
                    and aval.shape[-1] == last:
                sizes.add(aval.size)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            sizes |= _f32_sizes(sub, last)
    return sizes


def _toy_model(family):
    """A one-layer toy LM of each decoder, its mixer written out (no
    kernel's interpreter to trace: the head knows nothing of them), and its
    vocabulary."""
    if family == "laguna":
        from test_laguna_model import TOY

        return LagunaLM.from_config(
            dict(TOY, num_hidden_layers=1), dtype=jnp.float32,
            attention="dense"), 512
    if family == "kimi_linear":
        from test_kimi_linear_model import SMALL

        return KimiLinearLM.from_config(
            dict(SMALL, num_hidden_layers=1), dtype=jnp.float32,
            attention="dense", kda="recurrent"), 512
    if family == "olmo_hybrid":
        from test_olmo_hybrid_model import SMALL

        return OlmoHybridLM.from_config(
            dict(SMALL, num_hidden_layers=1), dtype=jnp.float32,
            attention="dense", rule="recurrent"), 256
    return TransformerLM(vocab_size=384, num_layers=1, num_heads=2,
                         d_model=32, d_ff=64, max_seq_len=128,
                         dtype=jnp.float32), 384


@pytest.mark.parametrize("family, remat", [
    ("laguna", True), ("kimi_linear", True), ("olmo_hybrid", True),
    ("transformer", True), ("transformer", False)])
def test_the_step_never_holds_its_logits_whole(monkeypatch, family, remat):
    """The step body's loss (``lm_step_loss``) against ``lm_loss`` of the
    same model's logits, the models recomputing their blocks as their cells
    build them (``gpt2-medium`` does not): the same loss and
    parameter gradients; the first's gradient program holds float32 arrays
    ``[..., V]`` of a block's rows at most, the second's the whole ``B * T
    * V`` (less the last positions, where the slice fused)."""
    monkeypatch.setattr(head, "LOSS_ROWS", 32)
    model, vocab = _toy_model(family)
    model = model.clone(remat=remat)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 48), 0, vocab)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"]

    def traced(loss):
        program = jax.jit(jax.value_and_grad(loss)).trace(params)
        return program.lower().compile()(params), \
            _f32_sizes(program.jaxpr.jaxpr, vocab)

    want, whole = traced(lambda p: lm_loss(
        model.apply({"params": p}, tokens), tokens))
    got, blocked = traced(lambda p: lm_step_loss(model, p, tokens))
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (got[1], want[1]))):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-6)
    rows = tokens.size
    assert max(whole) == rows * vocab
    # a block of rows and the head's own kernel [d, V], whichever is larger
    assert 32 * vocab in blocked
    assert max(blocked) == max(32, params["lm_head"]["kernel"].shape[0]) \
        * vocab < rows * vocab


def test_two_devices_step_as_one(monkeypatch):
    """``TransformerLM`` (a head with a bias) through ``make_lm_train_step``
    on a data mesh of two against one device on the whole batch, several
    blocks of rows a device: the replicated kernel and bias are typed like
    the sharded rows inside the rule (``ops.spmd.vary_like``) and their
    gradients summed over the axis once, outside it — a sum too many or too
    few shows in the updated head (SGD: the update is the gradient)."""
    import optax

    import horovod_tpu as hvd

    monkeypatch.setattr(head, "LOSS_ROWS", 32)
    model = TransformerLM(vocab_size=384, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=128,
                          dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (4, 48), 0, 384)
    params = jax.jit(model.init)(jax.random.PRNGKey(1), tokens)["params"]
    results = []
    for n in (1, 2):
        mesh = hvd.parallel.data_parallel_mesh(jax.devices()[:n])
        opt = hvd.DistributedOptimizer(optax.sgd(1.0), axis_name="data")
        copy = jax.tree_util.tree_map(jnp.copy, params)
        new, _, loss = make_lm_train_step(model, opt, mesh)(
            copy, jax.jit(opt.init)(copy), tokens)
        results.append((float(loss), jax.tree_util.tree_map(
            lambda a, b: np.asarray(a - b), new, params)))
    assert results[0][0] == pytest.approx(results[1][0], rel=1e-6)
    for name in ("kernel", "bias"):
        one, two = (r[1]["lm_head"][name] for r in results)
        assert np.abs(one).max() > 1e-4
        np.testing.assert_allclose(two, one, rtol=1e-4, atol=1e-7)
    for a, b in zip(*(jax.tree_util.tree_leaves(r[1]) for r in results)):
        np.testing.assert_allclose(b, a, rtol=1e-3, atol=1e-6)


def test_initialising_through_the_loss_makes_the_same_parameters():
    """``model.init(key, tokens, loss_tokens=tokens)`` declares ``lm_head``
    as ``model.init(key, tokens)`` does: the same tree, the same values."""
    model, vocab = _toy_model("transformer")
    tokens = jnp.zeros((2, 16), jnp.int32)
    plain = jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    through = jax.jit(lambda key: model.init(
        key, tokens, loss_tokens=tokens))(jax.random.PRNGKey(0))["params"]
    assert jax.tree_util.tree_structure(plain) \
        == jax.tree_util.tree_structure(through)
    for a, b in zip(*map(jax.tree_util.tree_leaves, (plain, through))):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("batch, seq", [(2, 2048 + 100), (3, 50)],
                         ids=["blocks_and_a_tail", "a_tail"])
def test_targets_and_weights_against_whole_logits(batch, seq):
    """A row scored against its own target, no shift, times its weight
    (zero on half of the rows, up to hundreds on others), the sum over the
    ``B * T`` rows: the loss and the gradients to the hidden states and the
    kernel; targets and weights get none."""
    d, vocab = 16, 40
    keys = jax.random.split(jax.random.PRNGKey(seq), 5)
    x = jax.random.normal(keys[0], (batch, seq, d)).astype(jnp.bfloat16)
    kernel = 0.3 * jax.random.normal(keys[1], (d, vocab))
    targets = jax.random.randint(keys[2], (batch, seq), 0, vocab)
    rate = jax.random.uniform(keys[3], (batch, seq), minval=1e-3)
    weights = jnp.where(jax.random.uniform(keys[4], (batch, seq)) < rate,
                        1.0 / rate, 0.0)

    def whole(x, kernel):
        logp = jax.nn.log_softmax(x.astype(jnp.float32) @ kernel, -1)
        picked = jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return -3.0 * jnp.sum(weights * picked) / targets.size

    def blocked(x, kernel):
        return 3.0 * lm_head_loss(x, kernel, None, targets=targets,
                                  weights=weights)

    want = jax.jit(jax.value_and_grad(whole, (0, 1)))(x, kernel)
    got = jax.jit(jax.value_and_grad(blocked, (0, 1)))(x, kernel)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)
    for a, b in zip(got[1], want[1]):
        a, b = (np.asarray(g, np.float32) for g in (a, b))
        np.testing.assert_allclose(
            a, b, rtol=1e-2 if a.shape == x.shape else 2e-5,
            atol=2e-6 * np.abs(b).max())
    with pytest.raises(ValueError, match="tokens, or targets and weights"):
        lm_head_loss(x, kernel, None, targets, targets=targets,
                     weights=weights)
    with pytest.raises(ValueError, match="tokens, or targets and weights"):
        lm_head_loss(x, kernel, None, targets=targets)


def test_weight_one_and_shifted_targets_are_the_next_token_loss():
    """The weighted form given what the next-token form makes for itself —
    each row's next token, every position but a sequence's last counted,
    ``T / (T - 1)`` each since it divides by all ``B * T`` rows — against
    the next-token form: the same loss and gradients."""
    batch, seq, d, vocab = 2, 2048 + 100, 16, 40
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    x = jax.random.normal(keys[0], (batch, seq, d)).astype(jnp.bfloat16)
    kernel = 0.3 * jax.random.normal(keys[1], (d, vocab))
    bias = 0.1 * jax.random.normal(keys[2], (vocab,))
    tokens = jax.random.randint(keys[3], (batch, seq), 0, vocab)
    weights = jnp.broadcast_to(
        jnp.where(jnp.arange(seq) < seq - 1, seq / (seq - 1.0), 0.0),
        tokens.shape)

    def weighted(x, kernel, bias):
        return lm_head_loss(x, kernel, bias, targets=jnp.roll(tokens, -1, -1),
                            weights=weights)

    want = jax.jit(jax.value_and_grad(
        lambda *a: lm_head_loss(*a, tokens), (0, 1, 2)))(x, kernel, bias)
    got = jax.jit(jax.value_and_grad(weighted, (0, 1, 2)))(x, kernel, bias)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=2e-6)
    for a, b in zip(got[1], want[1]):
        a, b = (np.asarray(g, np.float32) for g in (a, b))
        np.testing.assert_allclose(a, b, rtol=1e-2 if a.shape == x.shape
                                   else 2e-5, atol=2e-6 * np.abs(b).max())
