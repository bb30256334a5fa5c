"""The plain references against the program's models at a toy size on the
CPU (the flash kernel interpreted), the seeded trees against the flax
layout, and the lower-precision control failing where it must."""

import functools
import os

import jax
import jax.numpy as jnp
import optax
import pytest

from chipbench import cell as cells
from chipbench import check, numerics

TOY = os.path.join(cells.ROOT, "tests", "chipbench", "toy", "BENCHMARK.json")


@pytest.fixture(scope="module")
def spec():
    return cells.Spec(TOY)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


@pytest.mark.parametrize("name", ["gpt2m_1chip", "resnet50_1chip"])
def test_seeded_tree_has_the_layout_of_the_programs_model(name):
    """At the published sizes, from shapes alone."""
    cell = cells.Spec().cell(name)
    family, config = cell.family, cell.config
    model = family.build(config)
    if config["family"] == "gpt2":
        want = jax.eval_shape(model.clone(attention="dense").init,
                              jax.random.PRNGKey(0),
                              jnp.zeros((2, 8), jnp.int32))
        want = (want["params"],)
    else:
        side = config["image_side"]
        want = jax.eval_shape(model.init, jax.random.PRNGKey(0),
                              jnp.zeros((2, side, side, 3), jnp.float32))
        want = (want["params"], want["batch_stats"])
    got = jax.eval_shape(functools.partial(family.init_model_state, config),
                         jax.random.PRNGKey(0))
    assert _shapes(got) == _shapes(want)
    leaves = len(jax.tree_util.tree_leaves(got))
    assert leaves == (390 if config["family"] == "gpt2" else 267)


def _lm_grads(cell, dtype):
    from horovod_tpu.models import lm_loss

    family, config, traffic = cell.family, cell.config, cell.traffic
    keys = cells.seed_keys(11, 2)
    (params,) = family.init_model_state(config, keys[0])
    (tokens,) = family.make_pool(config, traffic, keys[1])[0]
    model = family.build(config).clone(dtype=dtype)
    assert model.attention == "flash"  # interpreted on the CPU backend
    loss, grad = jax.jit(jax.value_and_grad(lambda p: lm_loss(
        model.apply({"params": p}, tokens), tokens)))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grad = jax.jit(jax.value_and_grad(functools.partial(
            family.reference_loss, config=config)))(params, tokens)
        low_loss, low_grad = jax.jit(jax.value_and_grad(functools.partial(
            family.reference_loss, config=config, num=numerics.Fp8)))(
                params, tokens)
    return (float(loss), grad), (float(ref_loss), ref_grad), \
        (float(low_loss), low_grad)


def test_gpt2_reference_against_the_program_in_float32(spec):
    (loss, grad), (ref_loss, ref_grad), _ = _lm_grads(
        spec.cell("toy_lm_1dev"), jnp.float32)
    assert loss == pytest.approx(ref_loss, rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    live = [k for k in ref if k not in check.dead_leaves(ref)]
    assert len(live) == len(ref) - 2  # the two key biases
    assert max(err[k] / ref[k] for k in live) < 1e-4
    assert check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0] < 1e-5


def test_gpt2_program_in_bfloat16_holds_and_the_fp8_control_fails(spec):
    cell = spec.cell("toy_lm_1dev")
    limit = cell.limits()["first_gradient"]["limit"]
    (loss, grad), (ref_loss, ref_grad), (_, low_grad) = _lm_grads(
        cell, jnp.bfloat16)
    ref = numerics.leaf_norms(ref_grad)
    sound = check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0]
    control = check.worst_leaf_gap(numerics.leaf_norms(low_grad), ref)[0]
    assert loss == pytest.approx(ref_loss, rel=1e-4)
    assert sound < limit < control
    assert control > 3 * sound


def test_resnet_reference_against_the_program_in_float32(spec):
    cell = spec.cell("toy_resnet_1dev")
    family, config, traffic = cell.family, cell.config, cell.traffic
    keys = cells.seed_keys(12, 2)
    params, stats = family.init_model_state(config, keys[0])
    images, labels = family.make_pool(config, traffic, keys[1])[0]
    model = family.build(config).clone(dtype=jnp.float32)

    def loss_fn(p):
        logits, _ = model.apply({"params": p, "batch_stats": stats}, images,
                                train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    loss, grad = jax.jit(jax.value_and_grad(loss_fn))(params)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grad = jax.jit(jax.value_and_grad(functools.partial(
            family.reference_loss, config=config)))(params, images, labels)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    live = [k for k in ref if ref[k] > 0]
    # every block starts as the identity: its inner leaves get exact zeros
    assert 0 < len(live) < len(ref)
    assert all(err[k] == 0.0 for k in ref if k not in live)
    assert max(err[k] / ref[k] for k in live) < 1e-3


@pytest.mark.parametrize("name", ["toy_lm_1dev", "toy_resnet_1dev"])
def test_the_control_fails_the_comparison_through_three_steps(spec, name):
    """The reference trainer in fp8, put in the program's place, is not
    correct."""
    cell = spec.cell(name)
    keys = cells.seed_keys(13, 2)
    run = functools.partial(cell.family.reference_run, cell.config,
                            cell.traffic, keys, check.STEPS)
    reference, control = run(), run(precision="fp8")
    lines = []
    assert not check.verdict(check.compare(control, reference),
                             cell.limits(), lines.append)
    assert any("> limit" in x for x in lines)
    same = check.compare(reference, reference)
    assert all(gap == 0.0 for gap, _ in same.values())


def test_fp8_rounding_rounds_values_forward_and_gradients_back():
    x = jnp.linspace(-1.0, 1.0, 257)
    rounded = numerics.round_forward(x, jnp.float8_e4m3fn)
    assert 0 < float(jnp.max(jnp.abs(rounded - x))) < 2 ** -4
    assert len(set(map(float, rounded))) < 257
    g = jax.grad(lambda v: jnp.sum(
        numerics.round_forward(v, jnp.float8_e4m3fn) * x))(x)
    assert bool(jnp.all(g == x))  # straight through
    g = jax.grad(lambda v: jnp.sum(
        numerics.round_backward(v, jnp.float8_e5m2) * x))(x)
    assert bool(jnp.any(g != x)) and float(jnp.max(jnp.abs(g - x))) < 2 ** -3
    assert bool(jnp.all(numerics.round_backward(x, jnp.float8_e5m2) == x))
