"""The data-parallel step asks the compiler to run its gradient exchange
beside other work — when, and only when, the mesh's data axis holds
more than one TPU (``ops.spmd.overlap_compiler_options``, handed to
``jax.jit`` by ``hvd.parallel.data_parallel_step``, which both
``benchmarks/_dp_step.py`` builders call).

On the CPU's virtual devices the options must never reach the compiler;
for a described ``v5e:2x2`` (nothing attached, nothing run: counts, never
speeds) a four-device step compiles with its large single-tensor
all-reduces as async collective fusions (the form the chip overlaps,
PERF.md §6, PR 27) and a one-device step compiles as without the helper.
The topology is described inside a fixture, never at import
(``on-chip-measurement`` guide, section 2).
"""

import functools
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from benchmarks._dp_step import (make_dp_train_step, make_lm_train_step,
                                 synthesize_image_job, synthesize_lm_job)
from horovod_tpu.obs import compiles
from horovod_tpu.obs.registry import registry
from horovod_tpu.ops import spmd
from tools.step_hlo import without_source_locations as bare

REFUSED = "xla_tpu_no_such_option_in_any_libtpu"


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip can be written to the persistent
    cache but not read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def fresh_probe():
    """The helper remembers what a compiler accepted; a test that changes
    the options starts and leaves with nothing remembered."""
    spmd._accepted_by.cache_clear()
    yield
    spmd._accepted_by.cache_clear()


def _mesh(devices, n):
    return Mesh(np.asarray(devices[:n]), ("data",))


# -- (a) what the helper returns ---------------------------------------------


@pytest.mark.parametrize("described,n,engaged", [
    (False, 1, False), (False, 4, False), (True, 1, False), (True, 4, True)],
    ids=["cpu-1", "cpu-4", "v5e-1", "v5e-4"])
def test_options_follow_the_axis_and_the_platform(request, fresh_probe,
                                                  described, n, engaged):
    devices = (request.getfixturevalue("topo").devices if described
               else jax.devices())
    options = spmd.overlap_compiler_options(_mesh(devices, n), "data")
    assert options == (spmd._OVERLAP_OPTIONS if engaged else {})
    # the string "true" is accepted by the compiler and changes nothing
    assert not any(isinstance(v, str) for v in options.values())


def test_an_axis_of_one_on_a_wider_mesh_gets_no_options(topo, fresh_probe):
    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "model"))
    assert spmd.overlap_compiler_options(mesh, "data") == {}
    assert spmd.overlap_compiler_options(mesh, "model")
    assert spmd.overlap_compiler_options(mesh, ("data", "model"))


# -- (b) the CPU compiler never sees them -------------------------------------


def _image_job(mesh):
    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import ResNetBlock

    model = ResNet(stage_sizes=[1], num_filters=8, num_classes=10,
                   block_cls=ResNetBlock, dtype=jnp.float32)
    x, y, variables = synthesize_image_job(model, mesh, 16, 16, 10)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   axis_name="data")
    state = (variables["params"], jax.jit(opt.init)(variables["params"]),
             variables["batch_stats"])
    return functools.partial(make_dp_train_step, model, opt, mesh,
                             donate=False), state, (x, y)


def _lm_job(mesh):
    from horovod_tpu.models import TransformerLM

    model = TransformerLM(vocab_size=128, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=32,
                          attention="dense")
    tokens, variables = synthesize_lm_job(model, mesh, 8, 32)
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4), axis_name="data")
    state = (variables["params"], jax.jit(opt.init)(variables["params"]))
    # the LM builder donates its state: hand each call a copy
    return (lambda: _undonated(make_lm_train_step(model, opt, mesh))), \
        state, (tokens,)


def _undonated(step):
    return lambda *args: step(*jax.tree_util.tree_map(jnp.copy, args))


@pytest.mark.parametrize("job", [_image_job, _lm_job], ids=["image", "lm"])
def test_cpu_step_is_the_unoptioned_step_bit_for_bit(monkeypatch, job):
    build, state, batch = job(_mesh(jax.devices(), 4))
    monkeypatch.setattr(spmd, "_OVERLAP_OPTIONS", {})
    plain = build()(*state, *batch)
    # an option no compiler knows: had it reached the CPU's, the call
    # below would raise INVALID_ARGUMENT
    monkeypatch.setattr(spmd, "_OVERLAP_OPTIONS",
                        {**spmd._OVERLAP_OPTIONS, REFUSED: True})
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        optioned = build()(*state, *batch)
    for a, b in zip(jax.tree_util.tree_leaves(plain),
                    jax.tree_util.tree_leaves(optioned)):
        assert a.dtype == b.dtype and bool(jnp.array_equal(a, b))
    assert np.isfinite(float(plain[-1]))


# -- (c) compiled for a described v5e -----------------------------------------


def _small_lm_step(devices, n):
    """A small LM step over ``n`` described devices, from shapes alone. The
    vocabulary makes the embedding's gradient (42 MB in bfloat16) and the
    head's (84 MB in float32) larger than the combiner's bucket, as
    GPT-2-medium's are: each then has an all-reduce of its own."""
    from horovod_tpu.models import TransformerLM

    mesh = _mesh(devices, n)
    model = TransformerLM(vocab_size=40960, num_layers=2, num_heads=4,
                          d_model=512, d_ff=1024, max_seq_len=128,
                          attention="dense")
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4), axis_name="data")
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 8), jnp.int32))["params"])
    opt_state = jax.eval_shape(opt.init, params)

    def placed(tree, spec):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=NamedSharding(mesh, spec)), tree)

    tokens = jax.ShapeDtypeStruct((2 * n, 128), jnp.int32)
    return make_lm_train_step(model, opt, mesh).lower(
        placed(params, P()), placed(opt_state, P()),
        placed(tokens, P("data"))).compile().as_text()


def _gpt2m_4chip_step(devices, n):
    from chipbench import aot
    from chipbench import cell as cells

    assert n == 4
    return aot.compile_cell(cells.Spec().cell("gpt2m_4chip"),
                            devices).as_text()


def _gauge(name, program):
    (value,) = [s["value"] for s in registry().snapshot()[name]["samples"]
                if s["labels"] == {"program": program}]
    return value


@pytest.mark.parametrize("compile_step", [
    _small_lm_step, pytest.param(_gpt2m_4chip_step, marks=pytest.mark.slow)],
    ids=["small-lm", "gpt2m_4chip"])
def test_four_device_step_fuses_its_single_tensor_all_reduces(
        topo, no_compile_cache, fresh_probe, compile_step):
    from chipbench.run import allreduce_group_sizes

    text = compile_step(topo.devices, 4)
    program = compile_step.__name__
    total, asynchronous = compiles.record_exchange_collectives(program, text)
    # the embedding's and the head's; the rest ride in combined buckets,
    # which the compiler cannot cut into steps
    assert asynchronous == 2 and total > asynchronous, (total, asynchronous)
    assert _gauge("horovod_exchange_collectives", program) == total
    assert _gauge("horovod_exchange_async_collectives", program) == 2
    # the benchmark's placement check reads every printed all-reduce, each
    # step's copy of a fused one included, and finds the whole mesh
    sizes = allreduce_group_sizes(text)
    assert len(sizes) > total and all(s in (0, 4) for s in sizes), sizes


def test_four_device_step_without_the_options_is_synchronous(
        topo, no_compile_cache, fresh_probe, monkeypatch):
    """What the gauges tell apart: the same step, not asked to overlap."""
    monkeypatch.setattr(spmd, "_OVERLAP_OPTIONS", {})
    total, asynchronous = compiles.record_exchange_collectives(
        "unoptioned", _small_lm_step(topo.devices, 4))
    assert total > 0 and asynchronous == 0
    assert _gauge("horovod_exchange_async_collectives", "unoptioned") == 0


def test_one_device_step_compiles_as_without_the_helper(
        topo, no_compile_cache, fresh_probe, monkeypatch):
    with_helper = _small_lm_step(topo.devices, 1)
    monkeypatch.setattr(spmd, "overlap_compiler_options", lambda *a: {})
    assert bare(_small_lm_step(topo.devices, 1)) == bare(with_helper)
    assert "async_collective_name" not in with_helper


# -- (d) a compiler that refuses an option ------------------------------------


def test_a_refused_option_falls_back_with_one_warning(
        topo, no_compile_cache, fresh_probe, monkeypatch):
    monkeypatch.setattr(spmd, "_OVERLAP_OPTIONS",
                        {**spmd._OVERLAP_OPTIONS, REFUSED: True})
    mesh = _mesh(topo.devices, 4)
    with pytest.warns(RuntimeWarning, match=REFUSED) as caught:
        assert spmd.overlap_compiler_options(mesh, "data") == {}
        assert spmd.overlap_compiler_options(mesh, "data") == {}
        text = _small_lm_step(topo.devices, 4)
    assert len(caught) == 1, [str(w.message) for w in caught]
    # today's program: the step still compiles, its all-reduces synchronous
    total, asynchronous = compiles.record_exchange_collectives(
        "refused", text)
    assert total > 0 and asynchronous == 0
