"""The names the program gives its device time (docs/tracing.md, "Scopes
in a compiled step"): the phase scopes of both ``_dp_step`` builders as
they reach the compiled HLO on the virtual 8-device mesh, the flash
kernels' names in the jaxpr, and the compile ledger behind
``horovod_compiles_total`` / ``hvd.obs.compile_events()``."""

import re
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from benchmarks._dp_step import (make_dp_train_step, make_lm_train_step,
                                 synthesize_image_job, synthesize_lm_job)
from horovod_tpu.ops.compression import Compression

SCOPES = ("hvd.loss", "transpose(jvp(hvd.loss))", "hvd.exchange",
          "hvd.optimizer", "hvd.apply_updates", "hvd.sync_stats")
BACKWARD = "transpose(jvp(hvd.loss))"


def _lower_image_step(hvd, compression):
    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import ResNetBlock

    mesh = hvd.parallel.data_parallel_mesh()
    model = ResNet(stage_sizes=[1], num_filters=8, num_classes=10,
                   block_cls=ResNetBlock, dtype=jnp.float32)
    x, y, variables = synthesize_image_job(model, mesh, 16, 16, 10)
    opt = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                   axis_name="data",
                                   compression=compression)
    opt_state = jax.jit(opt.init)(variables["params"])
    step = make_dp_train_step(model, opt, mesh, donate=False)
    return step.lower(variables["params"], opt_state,
                      variables["batch_stats"], x, y)


def _lower_lm_step(hvd, compression):
    from horovod_tpu.models import TransformerLM

    mesh = hvd.parallel.data_parallel_mesh()
    model = TransformerLM(vocab_size=128, num_layers=1, num_heads=2,
                          d_model=32, d_ff=64, max_seq_len=128,
                          attention="flash")
    tokens, variables = synthesize_lm_job(model, mesh, 8, 128)
    opt = hvd.DistributedOptimizer(optax.adamw(3e-4, weight_decay=0.01),
                                   axis_name="data",
                                   compression=compression)
    opt_state = jax.jit(opt.init)(variables["params"])
    return make_lm_train_step(model, opt, mesh).lower(
        variables["params"], opt_state, tokens)


def _all_reduce_scopes(text):
    """The ``op_name`` of every all-reduce in HLO text."""
    out = []
    for line in text.splitlines():
        if re.search(r"\ball-reduce(-start)?\(", line):
            found = re.search(r'op_name="([^"]*)"', line)
            out.append(found.group(1) if found else "")
    return out


@pytest.mark.parametrize("lower,compression,gradients_under", [
    (_lower_image_step, None, BACKWARD),
    # a codec makes the exchange carry the bytes (optimizers.exchange_route)
    (_lower_image_step, Compression.bf16, "hvd.exchange"),
    (_lower_lm_step, None, BACKWARD),
], ids=["image", "image-explicit-reduce", "lm"])
def test_compiled_step_holds_every_scope(hvd, lower, compression,
                                         gradients_under):
    lowered = lower(hvd, compression)
    text = lowered.compile().as_text()
    for scope in SCOPES:
        assert re.search(r'op_name="[^"]*' + re.escape(scope), text), scope
    # forward operations are under the scope and not transposed
    assert re.search(r'op_name="[^"]*/jvp\(hvd\.loss\)/', text)

    # as the program issued them, before XLA combines them (which keeps
    # one's metadata for all); inside a called function (the embedding's
    # ``jit(_take)``) the path is relative until XLA inlines it
    issued = [s for s in _all_reduce_scopes(
        lowered.as_text(dialect="hlo", debug_info=True)) if "hvd." in s]
    gradients = [s for s in issued if "hvd.sync_stats" not in s]
    assert len(gradients) >= 4 and len(issued) > len(gradients)
    assert all(gradients_under in s for s in gradients), \
        sorted(set(gradients))
    if gradients_under == "hvd.exchange":
        assert not any(BACKWARD in s for s in issued)
    # and as compiled, inlined and combined: none outside the three scopes
    compiled = _all_reduce_scopes(text)
    assert compiled and all(
        gradients_under in s or "hvd.sync_stats" in s for s in compiled), \
        compiled


def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _pallas_names(sub, out)
    return out


def test_flash_kernels_carry_their_names():
    from horovod_tpu.ops.pallas_attention import flash_attention

    q = jnp.ones((1, 128, 2, 16), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, interpret=True).sum()

    forward = jax.make_jaxpr(loss)(q, q, q)
    assert _pallas_names(forward.jaxpr, []) == ["flash_fwd"]
    both = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)
    assert sorted(_pallas_names(both.jaxpr, [])) == [
        "flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]


def _listeners():
    from jax._src import monitoring

    return monitoring.get_event_duration_listeners()


def test_compile_ledger_counts_and_names_a_fresh_program(hvd):
    from horovod_tpu.obs import compiles

    def ledger_probe_fresh(x):
        return x * 5 - 2

    fn = jax.jit(ledger_probe_fresh)
    x = jnp.ones(11)
    before = compiles.compiles_total()
    fn(x)
    assert compiles.compiles_total() == before + 1
    mine = [e for e in hvd.obs.compile_events()
            if "ledger_probe_fresh" in e.fun_name]
    assert {e.stage for e in mine} >= {"trace", "lower", "backend_compile"}
    assert all(e.seconds >= 0 for e in mine)
    assert [e.at for e in mine] == sorted(e.at for e in mine)
    families = hvd.metrics_snapshot()
    assert families["horovod_compiles_total"]["samples"][0]["value"] \
        == compiles.compiles_total()
    stages = {s["labels"]["stage"] for s in
              families["horovod_compile_seconds_total"]["samples"]}
    assert {"trace", "lower", "backend_compile"} <= stages

    # the same program again: nothing compiles, nothing is recorded
    at = compiles.compiles_total(), hvd.obs.compile_events()[-1]
    fn(x)
    assert (compiles.compiles_total(), hvd.obs.compile_events()[-1]) == at


def test_shutdown_removes_the_listener_and_keeps_the_list():
    import horovod_tpu as hvd
    from horovod_tpu.obs import compiles

    on_event = compiles.ledger()._on_event
    hvd.init()
    assert on_event in _listeners()
    hvd.init()  # idempotent: still one
    assert _listeners().count(on_event) == 1
    x = jnp.ones(5)
    jax.jit(lambda x: x + 17)(x)
    held = hvd.obs.compile_events()
    assert held
    hvd.shutdown()
    assert on_event not in _listeners()
    total = compiles.compiles_total()
    jax.jit(lambda x: x - 23)(x)
    assert compiles.compiles_total() == total
    assert hvd.obs.compile_events() == held


def test_ledger_keeps_a_few_entries_for_each_program():
    from horovod_tpu.obs import compiles
    from horovod_tpu.obs.compiles import CompileLedger

    trace, lower, fetch, backend = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/compilation_cache/cache_retrieval_time_sec",
        "/jax/core/compile/backend_compile_duration")
    seconds = compiles._COMPILE_SECONDS.labels(stage="trace")
    traced = seconds.value
    ledger = CompileLedger(maxlen=8)
    ledger._on_event(trace, 1e-6, fun_name="zeros_like")  # an eager call
    ledger._on_event(trace, 1e-6, fun_name="multiply")    # nested in:
    ledger._on_event(trace, 60.0, fun_name="train_step")
    time.sleep(0.001)   # what comes later began after train_step's ended
    ledger._on_event(trace, 2e-6, fun_name="_where")      # a lowering rule's
    # waiting to be folded or settled, and readable meanwhile
    assert [e.fun_name for e in ledger.events()] == ["train_step", "_where"]
    ledger._on_event(lower, 1.5, fun_name="jit(train_step)")
    ledger._on_event(fetch, 2.0)        # no fun_name in this JAX
    ledger._on_event(backend, 2.25, fun_name="jit(train_step)")
    ledger._on_event("/jax/some/other/event", 9.0)
    assert [(e.fun_name, e.stage, e.seconds) for e in ledger.events()] == [
        ("train_step", "trace", 60.0),
        ("jit(train_step)", "lower", 1.5),
        ("jit(train_step)", "cache_retrieval", 2.0),
        ("jit(train_step)", "backend_compile", 2.25)]
    # nested traces are counted once, inside the outermost
    assert seconds.value - traced == pytest.approx(60.0 + 2e-6)
    for i in range(6):  # bounded: the oldest fall out
        ledger._on_event(lower, 0.1, fun_name=f"jit(f{i})")
    assert len(ledger.events()) == 8
    assert ledger.events()[-1].fun_name == "jit(f5)"
