"""``chipbench/scopes.py`` and the readers on it, on a synthetic ``run``:
compiled HLO text as a v5e build writes it (metadata in the text, none in
the trace's event names) joined with per-instruction device seconds."""

import types

import pytest

from chipbench import cell as cells
from chipbench import scopes

P = "jit(train_step)/jit(main)/shard_map/"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _meta(op_name):
    return f', metadata={{op_name="{P}{op_name}" source_file="x.py"}}'


HLO = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "",
    "%fused_computation.1 (p: f32[8]) -> f32[8] {",
    "  %p = f32[8]{0} parameter(0)",
    # a fused computation's own instruction: never in a trace, and its
    # scope is not what the fusion counts under
    "  ROOT %inner.1 = f32[8]{0} multiply(%p, %p)"
    + _meta("hvd.optimizer/mul"),
    "}",
    "",
    "ENTRY %main.1 (a: f32[8]) -> f32[8] {",
    "  %a = f32[8]{0} parameter(0)" + ', metadata={op_name="params"}',
    "  %fwd_matmul.1 = bf16[4,8]{1,0} convolution(%a, %a)"
    + _meta("jvp(hvd.loss)/TransformerLM/block_0/mlp/dot_general"),
    "  %flash_fwd.24 = (bf16[4,8]{1,0}, f32[4,128]{1,0}) custom-call(%a), "
    + MOSAIC + _meta("jvp(hvd.loss)/TransformerLM/block_0/attn/"
                     "jit(flash_attention)/flash_fwd/pallas_call"),
    "  %bwd_fusion.7 = f32[8]{0} fusion(%a), kind=kLoop, "
    "calls=%fused_computation.1"
    + _meta("transpose(jvp(hvd.loss))/TransformerLM/block_0/mlp/mul"),
    "  %flash_bwd_dq.1 = bf16[4,8]{1,0} custom-call(%a), " + MOSAIC
    + _meta("transpose(jvp(hvd.loss))/TransformerLM/block_0/attn/"
            "jit(flash_attention)/flash_bwd_dq/pallas_call"),
    "  %flash_bwd_dkv.1 = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) custom-call(%a),"
    " " + MOSAIC
    + _meta("transpose(jvp(hvd.loss))/TransformerLM/block_0/attn/"
            "jit(flash_attention)/flash_bwd_dkv/pallas_call"),
    # a collective under the backward scope: allreduce_ms's, no phase's
    "  %psum_invariant.3 = f32[8]{0} all-reduce(%bwd_fusion.7), "
    "replica_groups={{0,1,2,3}}, to_apply=%add"
    + _meta("transpose(jvp(hvd.loss))/TransformerLM/lm_head/"
            "psum_invariant"),
    # XLA's combined all-reduce: a tuple type and no metadata at all
    "  %all-reduce.5 = (bf16[8]{0}, bf16[8]{0}) all-reduce(%a, %a), "
    "replica_groups={{0,1,2,3}}, to_apply=%add",
    "  %div.9 = f32[8]{0} multiply(%psum_invariant.3, %a)"
    + _meta("hvd.exchange/div"),
    "  %pmean_scale.2 = f32[]{:T(128)} multiply(%a, %a)"
    + _meta("hvd.sync_stats/div"),
    "  %adam_fusion.3 = f32[8]{0} fusion(%div.9), kind=kLoop, "
    "calls=%fused_computation.1" + _meta("hvd.optimizer/add"),
    "  %apply_fusion.4 = f32[8]{0} fusion(%adam_fusion.3), kind=kLoop, "
    "calls=%fused_computation.1" + _meta("hvd.apply_updates/add"),
    # the compiler's own data movement has no metadata: it counts with
    # its first consumer (here through a chain), ...
    "  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)",
    "  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)",
    "  %fwd_norm.5 = f32[8]{0} fusion(%copy-done.2), kind=kLoop, "
    "calls=%fused_computation.1" + _meta("jvp(hvd.loss)/TransformerLM/ln_f"),
    # ... as exchange compute where that is a collective, ...
    "  %copy.8 = bf16[8]{0} copy(%bwd_fusion.7)",
    "  %all-reduce.6 = bf16[8]{0} all-reduce(%copy.8), "
    "replica_groups={{0,1,2,3}}, to_apply=%add",
    # ... with its producer where nothing consumes it (a step output), ...
    "  %copy.9 = f32[8]{0} copy(%apply_fusion.4)",
    # ... and nowhere when neither has a scope
    "  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)",
    # a Mosaic call that is not one of the program's kernels by name
    "  %custom-call.77 = f32[8]{0} custom-call(%a), " + MOSAIC,
    # a custom call with a kernel's name that is not a Mosaic call
    "  ROOT %flash_fwd.99 = f32[8]{0} custom-call(%a), "
    'custom_call_target="ConcatBitcast"' + _meta("jvp(hvd.loss)/x"),
    "}",
])

# seconds over two steady steps, each phase a different sum
OP_SECONDS = {
    "fwd_matmul.1": 0.010, "flash_fwd.24": 0.006, "flash_fwd.99": 0.002,
    "bwd_fusion.7": 0.030, "flash_bwd_dq.1": 0.004,
    "flash_bwd_dkv.1": 0.008,
    "psum_invariant.3": 0.012, "all-reduce.5": 0.020,
    "div.9": 0.0006, "pmean_scale.2": 0.0002,
    "adam_fusion.3": 0.014, "apply_fusion.4": 0.002,
    "copy-start.1": 0.001, "custom-call.77": 0.0004,
    "not_in_the_text.1": 0.003,
    "copy-start.2": 0.0001, "copy-done.2": 0.0003, "fwd_norm.5": 0.0016,
    "copy.8": 0.0012, "all-reduce.6": 0.005, "copy.9": 0.0008,
}
BUSY_S = sum(OP_SECONDS.values())


@pytest.fixture(scope="module")
def spec():
    return cells.Spec()


def _run(hlo=HLO, trace=True, opened_at=100.0):
    device = {"window_s": 0.2, "steps": 2, "busy_s": BUSY_S,
              "op_seconds": dict(OP_SECONDS)}
    return {"hlo": hlo, "trace": {"devices": [device]} if trace else None,
            "window": types.SimpleNamespace(opened_at=opened_at)}


def test_instructions_join_names_to_phases():
    known = scopes.instructions(HLO)
    assert known["fwd_matmul.1"][0] == "forward"
    assert known["bwd_fusion.7"][0] == "backward"
    assert known["inner.1"][0] == "optimizer"   # held, never looked up
    assert known["psum_invariant.3"][0] == "collective"
    assert known["all-reduce.5"] == ("collective", "", False)
    assert known["copy-start.1"] == ("unscoped", "", False)
    assert known["copy-start.2"][0] == known["copy-done.2"][0] == "forward"
    assert known["copy.8"][0] == "exchange_compute"
    assert known["copy.9"][0] == "optimizer"
    assert known["a"][0] == "unscoped"
    assert known["flash_fwd.24"][2] and not known["flash_fwd.99"][2]
    assert "train_step" in known["div.9"][1]


@pytest.mark.parametrize("op_name,collective,phase", [
    ("jit(f)/jvp(hvd.loss)/Model/dense/dot_general", False, "forward"),
    ("jit(f)/transpose(jvp(hvd.loss))/Model/dense/dot_general", False,
     "backward"),
    ("jit(f)/transpose(jvp(hvd.loss))/Model/psum_invariant", True,
     "collective"),
    ("jit(f)/hvd.exchange/psum", True, "collective"),
    ("jit(f)/hvd.exchange/div", False, "exchange_compute"),
    ("jit(f)/hvd.sync_stats/div", False, "exchange_compute"),
    ("jit(f)/hvd.optimizer/mul", False, "optimizer"),
    ("jit(f)/hvd.apply_updates/add", False, "optimizer"),
    ("jit(f)/cond/branch_1_fun/hvd.exchange/convert_element_type", False,
     "exchange_compute"),
    ("jit(f)/broadcast_in_dim", False, "unscoped"),
    ("", False, "unscoped"),
])
def test_phase_of(op_name, collective, phase):
    assert scopes.phase_of(op_name, collective) == phase


# per step (two steps), in milliseconds
EXPECTED_MS = {
    "forward_ms": (10 + 6 + 2 + 0.1 + 0.3 + 1.6) / 2,
    "backward_ms": (30 + 4 + 8) / 2,
    "optimizer_ms": (14 + 2 + 0.8) / 2,
    "exchange_compute_ms": (0.6 + 0.2 + 1.2) / 2,
    "flash_fwd_ms": 6 / 2,
    "flash_dq_ms": 4 / 2,
    "flash_dkv_ms": 8 / 2,
}


@pytest.mark.parametrize("metric", sorted(EXPECTED_MS))
def test_reader_on_a_synthetic_run(spec, metric):
    assert spec.reader(metric).read(_run()) \
        == pytest.approx(EXPECTED_MS[metric])


def test_unscoped_pct_counts_what_no_scope_holds(spec):
    # the copy, the unnamed Mosaic call and the instruction that the text
    # does not hold
    unscoped = 0.001 + 0.0004 + 0.003
    assert spec.reader("unscoped_pct").read(_run()) \
        == pytest.approx(100.0 * unscoped / BUSY_S)
    assert scopes.unscoped_operations(_run(), top=2) == [
        ["not_in_the_text.1", pytest.approx(0.0015)],
        ["copy-start.1", pytest.approx(0.0005)]]


def test_phases_and_collectives_make_up_the_busy_time():
    seconds = scopes.phase_seconds(_run())
    assert seconds["collective"] \
        == pytest.approx((0.012 + 0.020 + 0.005) / 2)
    assert sum(seconds[p] for p in scopes.PHASES) \
        == pytest.approx(seconds["busy"])


TRACE_READERS = sorted(EXPECTED_MS) + ["unscoped_pct"]


@pytest.mark.parametrize("metric", TRACE_READERS)
def test_reader_gives_nothing_without_a_trace(spec, metric):
    assert spec.reader(metric).read(_run(trace=False)) is None


@pytest.mark.parametrize("metric", TRACE_READERS)
def test_reader_gives_nothing_on_a_program_without_scopes(spec, metric):
    """The parent commit: no scope in any ``op_name``, kernels unnamed."""
    bare = HLO.replace("hvd.", "").replace("flash_fwd", "flash_attention") \
        .replace("flash_bwd_dq", "flash_attention") \
        .replace("flash_bwd_dkv", "flash_attention")
    assert spec.reader(metric).read(_run(hlo=bare)) is None


def _event(at, fun_name, stage, seconds):
    from horovod_tpu.obs import CompileEvent

    return CompileEvent(at, fun_name, stage, seconds)


LEDGER = [
    (10.0, "init_model_state", "trace", 0.5),
    (11.0, "flash_attention", "trace", 0.25),      # inside train_step's
    (12.0, "train_step", "trace", 3.0),
    (13.0, "jit_train_step", "lower", 1.5),
    (20.0, "jit_train_step", "cache_retrieval", 9.0),
    (20.0, "jit_train_step", "backend_compile", 9.25),
    (150.0, "train_step", "trace", 7.0),           # after the window opened
    (160.0, "jit_reference_grad", "cache_retrieval", 4.0),
]


@pytest.mark.parametrize("metric,ledger,expected", [
    ("step_trace_lower_s", LEDGER, 4.5),
    ("cache_retrieval_s", LEDGER, 9.0),
    # a cold run: compiled, nothing fetched
    ("cache_retrieval_s", [e for e in LEDGER if e[2] != "cache_retrieval"],
     0.0),
    # no step program in the ledger before the window: nothing to read
    ("step_trace_lower_s", LEDGER[:2] + LEDGER[-2:], None),
    ("cache_retrieval_s", [], None),
])
def test_compile_ledger_readers(spec, monkeypatch, metric, ledger, expected):
    import horovod_tpu.obs

    monkeypatch.setattr(horovod_tpu.obs, "compile_events",
                        lambda: [_event(*e) for e in ledger])
    value = spec.reader(metric).read(_run(opened_at=100.0))
    assert value == (None if expected is None else pytest.approx(expected))


@pytest.mark.parametrize("metric", ["step_trace_lower_s",
                                    "cache_retrieval_s"])
def test_compile_ledger_readers_on_a_program_without_a_ledger(
        spec, monkeypatch, metric):
    import horovod_tpu.obs

    monkeypatch.delattr(horovod_tpu.obs, "compile_events")
    assert spec.reader(metric).read(_run()) is None


def test_new_metrics_follow_their_cells(spec):
    """What ``test_chipbench_shapes`` pinned to PR 23's set, as it stands
    with the phase, kernel and ledger metrics."""
    names = {c: {m["name"] for m in spec.cell(c).per_layer}
             for c in spec.cell_names()}
    everywhere = {"forward_ms", "backward_ms", "optimizer_ms",
                  "exchange_compute_ms", "unscoped_pct",
                  "step_trace_lower_s", "cache_retrieval_s"}
    kernels = {"flash_fwd_ms", "flash_dq_ms", "flash_dkv_ms"}
    for cell, have in names.items():
        assert everywhere <= have, cell
        assert (kernels <= have) == cell.startswith("gpt2m"), cell
    assert names["gpt2m_1chip"] - names["resnet50_1chip"] \
        == {"flash_ms", "flash_roofline"} | kernels
    layers = {m["layer"] for m in spec.data["per_layer"]}
    assert layers == {"entry and compile cache", "step builders",
                      "gradient exchange", "kernels", "device", "models",
                      "optimizer update"}


def test_the_bs32_cell_files_make_a_cell(spec, tmp_path):
    """``resnet50_bs32_1chip`` has its traffic mix and its chip-read limits
    but no ``workloads`` entry (PERF.md §7: it holds 1.9 GB, under the
    floor for a new cell); entering it takes this one entry."""
    import json

    from chipbench import check

    assert "resnet50_bs32_1chip" not in spec.cell_names()
    data = dict(spec.data)
    data["workloads"] = spec.data["workloads"] + [{
        "name": "resnet50_bs32_1chip", "config": "resnet50",
        "traffic": "img224_global32", "chips": 1, "why": "see PERF.md"}]
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(data))
    bs32 = cells.Spec(str(path)).cell("resnet50_bs32_1chip")
    assert (bs32.chips, bs32.per_chip_batch, bs32.traffic["pool"]) \
        == (1, 32, 8)
    assert bs32.traffic["loop"] == "closed" and bs32.traffic["trainers"] == 1
    # a step-time sample is 250 ms or more at the 11.47 ms step measured
    assert 21 * 11.47 < 250 <= bs32.traffic["steps_per_timing_sample"] * 11.47
    assert bs32.config == spec.cell("resnet50_1chip").config
    assert {m["name"] for m in bs32.per_layer} \
        == {m["name"] for m in spec.cell("resnet50_1chip").per_layer}
    limits = bs32.limits()
    assert set(limits) == set(check.COMPARED + check.OPTIONAL)
    assert all(v["limit"] > 0 and "PR 24" in v["set_from"]
               for v in limits.values())
