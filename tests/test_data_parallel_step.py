"""``hvd.parallel.data_parallel_step``: the one place a data-parallel step
is traced and compiled (``horovod_tpu/parallel/step.py``).

The tracing mode follows the ``DistributedOptimizer`` the step calls — a
wire codec or the factored (dcn, ici) route make the exchange carry the
bytes itself — and the caller gives no flag for it; the compile options
follow the mesh; the program keeps the caller's name; anything that is
not a ``DistributedOptimizer`` over a mesh axis is refused. All on the
virtual 8-device CPU mesh: what the program asks for, never a speed.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import optimizers
from horovod_tpu.ops import spmd

BACKWARD = "transpose(jvp(hvd.loss))"
LR = 0.1


def _mesh(axis):
    devices = np.asarray(jax.devices()[:8])
    if isinstance(axis, str):
        return Mesh(devices, (axis,))
    return Mesh(devices.reshape(2, 4), tuple(axis))


def _job():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    params = {"w1": 0.3 * jax.random.normal(keys[0], (12, 16)),
              "b1": jnp.zeros((16,)),
              "w2": 0.3 * jax.random.normal(keys[1], (16, 4))}
    return params, jax.random.normal(keys[2], (32, 12)), \
        jax.random.normal(keys[3], (32, 4))


def _loss(params, x, y):
    with jax.named_scope("hvd.loss"):
        hidden = jnp.tanh(x @ params["w1"] + params["b1"])
        return jnp.mean((hidden @ params["w2"] - y) ** 2)


def _build(opt, axis, donate_argnums=()):
    """The step as a user writes it: the per-shard function, the
    optimizer it calls, the mesh and the layout — nothing about how the
    exchange is traced."""

    def train_step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(_loss)(params, x, y)
        updates, opt_state = opt.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                jax.lax.pmean(loss, axis))

    return hvd.parallel.data_parallel_step(
        train_step, opt, _mesh(axis),
        in_specs=(P(), P(), P(axis), P(axis)), out_specs=(P(), P(), P()),
        donate_argnums=donate_argnums)


def _collectives(text, opcode):
    """``(result element type, op_name)`` of every ``opcode`` in HLO text."""
    out = []
    for line in text.splitlines():
        found = re.search(r"=\s*\(?(\w+)\[.*?\s" + opcode
                          + r"(-start)?\(", line)
        if found:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((found.group(1), name.group(1) if name else ""))
    return out


@pytest.fixture()
def no_prebuild_record():
    """A codec or a route resolved from the environment before
    ``hvd.init()`` is kept for the next ``init()`` to audit; a test that
    does so leaves nothing behind for another test's ``init()``."""
    yield
    optimizers._prebuild_compression_resolutions.clear()
    optimizers._prebuild_hierarchical_resolutions.clear()


@pytest.mark.parametrize(
    "axis,wrap,env,wire,gradients_under,legs,tolerance", [
        ("data", {}, {}, "f32", BACKWARD, False, 1e-6),
        ("data", {"compression": hvd.Compression.bf16}, {}, "bf16",
         "hvd.exchange", False, 2e-2),
        ("data", {"compression": hvd.Compression.int8}, {}, None,
         "hvd.exchange", False, 5e-2),
        ("data", {}, {"HOROVOD_COMPRESSION": "bf16"}, "bf16", "hvd.exchange",
         False, 2e-2),
        (("dcn", "ici"), {"hierarchical": True}, {}, "f32", "hvd.exchange",
         True, 1e-6),
        (("dcn", "ici"), {}, {"HOROVOD_HIERARCHICAL_ALLREDUCE": "1"}, "f32",
         "hvd.exchange", True, 1e-6),
        (("dcn", "ici"), {"hierarchical": False}, {}, "f32", BACKWARD, False,
         1e-6),
    ], ids=["plain", "bf16", "int8", "bf16-from-env", "hierarchical",
            "hierarchical-from-env", "two-axes-flat"])
def test_tracing_mode_follows_the_optimizer(monkeypatch, no_prebuild_record,
                                            axis, wrap, env, wire,
                                            gradients_under, legs,
                                            tolerance):
    assert not hvd.is_initialized()   # env knobs resolve at build time
    for name, value in env.items():
        monkeypatch.setenv(name, value)
    opt = hvd.DistributedOptimizer(optax.sgd(LR), axis_name=axis, **wrap)
    params, x, y = _job()
    opt_state = opt.init(params)
    step = _build(opt, axis)
    lowered = step.lower(params, opt_state, x, y)

    # as the program issued them, before XLA combines or promotes them
    issued = _collectives(lowered.as_text(dialect="hlo", debug_info=True),
                          "all-reduce")
    under = [(dtype, name) for dtype, name in issued
             if gradients_under in name]
    assert under, issued
    if gradients_under == "hvd.exchange":
        # nothing was summed behind the optimizer's back
        assert not any(BACKWARD in name for _, name in issued), issued
    else:
        assert not any("hvd.exchange" in name for _, name in issued), issued
    if wire is not None:
        assert {dtype for dtype, _ in under} == {wire}, under
    compiled = lowered.compile().as_text()
    if wire is None:   # the quantized route: an s8 scatter and gather leg
        assert {"s8"} <= {d for d, _ in _collectives(compiled, "all-to-all")}
        assert {"s8"} <= {d for d, _ in _collectives(compiled, "all-gather")}
    factored = (_collectives(compiled, "reduce-scatter"),
                _collectives(compiled, "all-gather"))
    if legs:
        assert all(factored), "the factored route did not fire"
        assert all("hvd.exchange" in name
                   for found in factored for _, name in found), factored
    elif wire is not None:
        assert not any(factored), factored

    # and it is right: the mean of the shards' gradients is the global
    # batch's gradient, so one step is one SGD step on the whole batch
    new_params, _, loss = step(params, opt_state, x, y)
    expected_loss, grads = jax.value_and_grad(_loss)(params, x, y)
    np.testing.assert_allclose(float(loss), float(expected_loss), rtol=1e-5)
    for name, leaf in params.items():
        np.testing.assert_allclose(
            np.asarray(new_params[name]), np.asarray(leaf - LR * grads[name]),
            rtol=tolerance, atol=tolerance * LR)


def test_program_keeps_the_callers_name(hvd):
    """``chipbench/scopes.py`` finds the step's program by ``train_step``
    in ``fun_name`` and its phases by ``jit(train_step)/shard_map/`` in
    ``op_name``: the function the package wraps in between leaves both."""
    opt = hvd.DistributedOptimizer(optax.sgd(LR), axis_name="data")
    params, x, y = _job()
    step = _build(opt, "data")
    before = len(hvd.obs.compile_events())
    text = step.lower(params, opt.init(params), x, y).compile().as_text()
    # (a reduction's own computation carries a path relative to its caller)
    rooted = [n for n in re.findall(r'op_name="([^"]*)"', text)
              if n.startswith("jit(")]
    assert any("hvd.loss" in n for n in rooted)
    assert all(n.startswith("jit(train_step)/shard_map/")
               for n in rooted if "hvd." in n), rooted
    assert all(n.startswith("jit(train_step)/") for n in rooted), rooted
    mine = {e.stage: e.fun_name for e in hvd.obs.compile_events()[before:]
            if "train_step" in e.fun_name}
    assert {"trace", "lower", "backend_compile"} <= set(mine), mine


def test_returns_what_jit_returns_donation_included():
    opt = hvd.DistributedOptimizer(optax.sgd(LR), axis_name="data")
    params, x, y = _job()
    opt_state = opt.init(params)
    step = _build(opt, "data", donate_argnums=(0, 1))
    compiled = step.lower(params, opt_state, x, y).compile()
    assert "input_output_alias" in compiled.as_text()
    kept = _build(opt, "data")(params, opt_state, x, y)
    donated = step(params, opt_state, x, y)
    assert all(leaf.is_deleted() for leaf in jax.tree_util.tree_leaves(params))
    assert not x.is_deleted()
    jax.tree_util.tree_map(np.testing.assert_array_equal, kept, donated)


def test_compile_options_come_from_the_mesh_and_the_optimizers_axis(
        monkeypatch):
    """One call of ``overlap_compiler_options``, with the mesh the caller
    gave and the axis the optimizer reduces over; what it answers reaches
    ``jax.jit`` (``tests/test_step_overlap.py`` compiles with the real
    answer for a described v5e)."""
    asked = []

    def options(mesh, axis_name):
        asked.append((mesh, axis_name))
        return {"xla_tpu_no_such_option_in_any_libtpu": True}

    monkeypatch.setattr(spmd, "overlap_compiler_options", options)
    opt = hvd.DistributedOptimizer(optax.sgd(LR), axis_name="data")
    params, x, y = _job()
    step = _build(opt, "data")
    assert asked == [(_mesh("data"), "data")]
    with pytest.raises(jax.errors.JaxRuntimeError, match="No such compile"):
        step.lower(params, opt.init(params), x, y).compile()


@pytest.mark.parametrize("make,complaint", [
    (lambda: optax.sgd(LR), "DistributedOptimizer"),
    (lambda: hvd.DistributedOptimizer(optax.sgd(LR)), "axis_name"),
], ids=["plain-optax", "eager-optimizer"])
def test_anything_but_a_mesh_optimizer_is_refused(make, complaint):
    with pytest.raises(ValueError, match=complaint):
        _build(make(), "data")
