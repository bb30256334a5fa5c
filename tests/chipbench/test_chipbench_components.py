"""``chipbench/components.py`` and the eight readers this brought, on a
hand-made ``run``: a few instructions of compiled HLO text as a v5e build
writes it (``op_name``s with the models' component scopes) joined with a
reduced trace's seconds per instruction; then the toy cell's compiled
step, by instruction count."""

import contextlib
import re
import types

import pytest

from chipbench import cell as cells
from chipbench import components, scopes

P = "jit(train_step)/jit(main)/shard_map/"
FWD, BWD = "jvp(hvd.loss)/LM/", "transpose(jvp(hvd.loss))/LM/"
MOSAIC = 'custom_call_target="tpu_custom_call"'


def _meta(op_name):
    return f', metadata={{op_name="{P}{op_name}" source_file="x.py"}}'


def _op(name, op_name, operand="%a"):
    return (f"  %{name} = f32[8]{{0}} fusion({operand}), kind=kLoop, "
            f"calls=%fused_computation.1" + _meta(op_name))


HLO = "\n".join([
    "HloModule jit_train_step, is_scheduled=true",
    "",
    "%fused_computation.1 (p: f32[8]) -> f32[8] {",
    "  %p = f32[8]{0} parameter(0)",
    # a fused computation's own instruction: never in a trace
    "  ROOT %inner.1 = f32[8]{0} multiply(%p, %p)"
    + _meta(BWD + "block_0/hvd.mlp/mlp_in/mul"),
    "}",
    "",
    "ENTRY %main.1 (a: f32[8]) -> f32[8] {",
    "  %a = f32[8]{0} parameter(0)",
    _op("embed_gather.1", FWD + "hvd.embed/tok_embed/jit(_take)/gather"),
    _op("embed_scatter.2", BWD + "hvd.embed/tok_embed/scatter-add"),
    _op("norm_fwd.3", FWD + "block_0/hvd.norm/ln_attn/mul"),
    # recomputed: the stack written twice, still the norm's
    _op("norm_again.4", BWD + FWD + "checkpoint/rematted_computation/"
        "block_0/hvd.norm/ln_attn/mul"),
    _op("qkv.5", FWD + "block_0/hvd.mixer/attn/hvd.mixer.proj/query/"
        "dot_general"),
    "  %flash_fwd.6 = bf16[4,8]{1,0} custom-call(%qkv.5), " + MOSAIC
    + _meta(FWD + "block_0/hvd.mixer/attn/jit(flash_attention)/flash_fwd/"
            "pallas_call"),
    # a mixer's own norm is the mixer's
    _op("out_norm.7", FWD + "block_0/hvd.mixer/kda/hvd.kda/out_norm/mul"),
    _op("out_proj.8", BWD + "block_0/hvd.mixer/attn/hvd.mixer.proj/out/"
        "dot_general"),
    _op("mlp_in.9", FWD + "block_0/hvd.mlp/mlp_in/dot_general"),
    _op("mla_attn.10", BWD + "block_3/hvd.mixer/mla/hvd.mla/hvd.mla.attn/"
        "mul"),
    _op("shared_w1.11", FWD + "block_1/moe/hvd.moe/hvd.moe.experts/shared/"
        "w1/dot_general"),
    _op("lm_head.12", FWD + "hvd.head/lm_head/dot_general"),
    _op("loss.13", BWD + "hvd.head/jit(take_along_axis)/mul"),
    # no owner: the expert layer's reshape and residual add, remat's copy
    _op("moe_reshape.14", FWD + "block_1/moe/reshape"),
    _op("residual.15", FWD + "block_1/add"),
    # the compiler's copies have no metadata: they count with their first
    # consumer, here through a chain, ...
    "  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%a)",
    "  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)",
    _op("lm_head_bwd.16", BWD + "hvd.head/lm_head/dot_general",
        "%copy-done.2"),
    "  %copy.3 = f32[8]{0} copy(%a)",
    _op("mixer_add.17", FWD + "block_0/hvd.mixer/add", "%copy.3"),
    # ... under no owner where the consumer has none, ...
    "  %copy.4 = f32[8]{0} copy(%a)",
    _op("remat_save.18", BWD + FWD + "remat2", "%copy.4"),
    # ... with their producer where nothing consumes them, ...
    "  %copy.5 = f32[8]{0} copy(%mlp_in.9)",
    # ... and in no component where that is another phase's
    "  %copy.6 = f32[8]{0} copy(%a)",
    "  %all-reduce.7 = f32[8]{0} all-reduce(%copy.6), "
    "replica_groups={{0,1,2,3}}, to_apply=%add",
    # a collective under a component's backward scope: allreduce_ms's
    "  %psum_invariant.8 = f32[8]{0} all-reduce(%loss.13), "
    "replica_groups={{0,1,2,3}}, to_apply=%add"
    + _meta(BWD + "hvd.head/lm_head/psum_invariant"),
    # a weight gradient fused with its leaf's AdamW counts where the root
    # is: the optimizer's, whatever the path goes on to say
    _op("adam.19", "hvd.optimizer/add"),
    _op("exchange.20", "hvd.exchange/div"),
    "  ROOT %copy.9 = f32[8]{0} copy(%adam.19)",
    "}",
])

# seconds over two steady steps
OP_SECONDS = {
    "embed_gather.1": 0.002, "embed_scatter.2": 0.004,
    "norm_fwd.3": 0.006, "norm_again.4": 0.008,
    "qkv.5": 0.010, "flash_fwd.6": 0.012, "out_norm.7": 0.014,
    "out_proj.8": 0.016, "mla_attn.10": 0.018, "mixer_add.17": 0.020,
    "copy.3": 0.0002,
    "mlp_in.9": 0.022, "copy.5": 0.0004,
    "shared_w1.11": 0.024,
    "lm_head.12": 0.026, "loss.13": 0.028, "lm_head_bwd.16": 0.030,
    "copy-start.2": 0.0006, "copy-done.2": 0.0008,
    "moe_reshape.14": 0.032, "residual.15": 0.034, "remat_save.18": 0.036,
    "copy.4": 0.001,
    "copy.6": 0.05, "all-reduce.7": 0.05, "psum_invariant.8": 0.05,
    "adam.19": 0.05, "exchange.20": 0.05, "copy.9": 0.05,
    "not_in_the_text.1": 0.05,
}
# per step (two steps), in milliseconds
EXPECTED = {
    "embed_ms": (2 + 4) / 2,
    "norm_ms": (6 + 8) / 2,
    "mixer_ms": (10 + 12 + 14 + 16 + 18 + 20 + 0.2) / 2,
    "mixer_proj_ms": (10 + 16) / 2,
    "mlp_ms": (22 + 0.4) / 2,
    "head_ms": (26 + 28 + 30 + 0.6 + 0.8) / 2,
}
MOE_MS = 24 / 2
OTHER_MS = (32 + 34 + 36 + 1) / 2
SEVEN = sorted(EXPECTED) + ["component_other_pct"]


@pytest.fixture(scope="module")
def spec():
    return cells.Spec()


def _run(hlo=HLO, trace=True, opened_at=100.0):
    device = {"window_s": 0.5, "steps": 2,
              "busy_s": sum(OP_SECONDS.values()),
              "op_seconds": dict(OP_SECONDS)}
    return {"hlo": hlo, "trace": {"devices": [device]} if trace else None,
            "window": types.SimpleNamespace(opened_at=opened_at)}


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_a_hand_made_run(spec, metric):
    assert spec.reader(metric).read(_run()) \
        == pytest.approx(EXPECTED[metric])


def test_component_other_pct_and_what_it_is_made_of(spec, capsys):
    total = sum(EXPECTED.values()) - EXPECTED["mixer_proj_ms"] + MOE_MS \
        + OTHER_MS
    assert spec.reader("component_other_pct").read(_run()) \
        == pytest.approx(100.0 * OTHER_MS / total)
    assert capsys.readouterr().out == ""    # a reader reads, and says nothing
    # the largest first, each with the end of the path it counts under
    assert components.other_operations(_run(), top=2) == [
        ["remat_save.18", "jvp(hvd.loss)/LM/remat2", pytest.approx(18.0)],
        ["residual.15", "LM/block_1/add", pytest.approx(17.0)]]


def test_the_time_under_moe_follows_from_the_line(spec):
    """Where no ``moe_*`` metric lists a cell, the line still gives the time
    under ``hvd.moe``: the phases less ``other`` and the five components."""
    line = {m: spec.reader(m).read(_run())
            for m in SEVEN + ["forward_ms", "backward_ms"]}
    phases = line["forward_ms"] + line["backward_ms"]
    assert phases * (1 - line["component_other_pct"] / 100) - sum(
        line[m] for m in EXPECTED if m != "mixer_proj_ms") \
        == pytest.approx(MOE_MS)


def test_the_owners_and_other_make_up_forward_and_backward(spec):
    found = components.seconds(_run())
    phases = sum(spec.reader(m).read(_run())
                 for m in ("forward_ms", "backward_ms"))
    parts = [found[o] for o in components.OWNERS] + [found["other"]]
    assert 1e3 * sum(parts) == pytest.approx(phases, rel=1e-12)
    assert found["total"] == pytest.approx(sum(parts), rel=1e-12)
    assert 1e3 * found["moe"] == pytest.approx(MOE_MS)
    assert sum(spec.reader(m).read(_run()) for m in EXPECTED
               if m != "mixer_proj_ms") + MOE_MS + OTHER_MS \
        == pytest.approx(phases)
    # what other phases hold is in no component
    assert phases == pytest.approx(1e3 * (sum(OP_SECONDS.values()) - 7 * 0.05)
                                   / 2)


def test_a_copy_without_metadata_inherits_its_consumers_owner():
    inherited = components.inherited_op_names(HLO)
    assert components.owner_of(inherited["copy.3"]) == "mixer"
    assert components.owner_of(inherited["copy-start.2"]) \
        == components.owner_of(inherited["copy-done.2"]) == "head"
    assert components.owner_of(inherited["copy.4"]) == "other"
    assert components.owner_of(inherited["copy.5"]) == "mlp"  # its producer
    assert "copy.6" not in inherited        # a copy into an all-reduce
    assert scopes.instructions(HLO)["copy.6"][0] == "exchange_compute"
    assert "hvd.optimizer" in inherited["copy.9"]
    assert "qkv.5" not in inherited     # has a path of its own


@pytest.mark.parametrize("op_name,owner", [
    (FWD + "hvd.embed/tok_embed/gather", "embed"),
    (FWD + "block_2/hvd.mixer/attn/hvd.mixer.proj/query/dot_general",
     "mixer"),
    (FWD + "block_2/hvd.mixer/mla/hvd.mla/kv_norm/mul", "mixer"),
    (FWD + "block_2/hvd.mlp/mlp/w1/dot_general", "mlp"),
    (FWD + "block_2/moe/hvd.moe/hvd.moe.experts/shared/w1/dot_general",
     "moe"),
    (BWD + FWD + "checkpoint/rematted_computation/block_2/hvd.norm/ln_mlp/"
     "mul", "norm"),
    (BWD + "hvd.head/mul", "head"),
    (FWD + "block_2/mla/hvd.mla/mul", "other"),     # no mixer scope round it
    (FWD + "block_2/hvd.mlpx/mul", "other"),
    ("", "other"),
])
def test_owner_of(op_name, owner):
    assert components.owner_of(P + op_name) == owner


@pytest.mark.parametrize("metric", SEVEN)
def test_reader_gives_nothing_without_a_trace(spec, metric):
    assert spec.reader(metric).read(_run(trace=False)) is None
    assert spec.reader(metric).read({"trace": None}) is None


@pytest.mark.parametrize("metric", SEVEN)
def test_reader_gives_nothing_on_a_program_without_the_scopes(spec, metric):
    """The parent commit: ``hvd.loss`` and ``hvd.moe`` and none of the six."""
    bare = re.sub(r"hvd\.(embed|norm|mixer\.proj|mixer|mlp|head)/", "", HLO)
    assert "hvd.moe" in bare and "hvd.loss" in bare
    assert spec.reader("forward_ms").read(_run(hlo=bare)) is not None
    assert spec.reader(metric).read(_run(hlo=bare)) is None


def _event(at, fun_name, stage, seconds):
    from horovod_tpu.obs import CompileEvent

    return CompileEvent(at, fun_name, stage, seconds)


LEDGER = [
    (10.0, "init_model_state", "trace", 0.5),
    (10.5, "jit_init_model_state", "lower", 0.25),
    (11.0, "jit_init_model_state", "cache_retrieval", 0.125),
    (11.0, "jit_init_model_state", "backend_compile", 0.75),
    (30.0, "flash_attention", "trace", 0.25),      # inside train_step's
    (32.0, "train_step", "trace", 3.0),
    (33.0, "_take", "trace", 0.0625),              # inside its lowering
    (33.5, "jit_train_step", "lower", 1.5),
    (40.0, "jit_train_step", "backend_compile", 6.0),
    (41.0, "jit_leaf_norms", "backend_compile", 2.0),
    (150.0, "jit_reference_grad", "backend_compile", 4.0),     # after
]


@pytest.mark.parametrize("ledger,expected", [
    (LEDGER, 0.5 + 0.25 + 0.75 + 2.0),
    # nothing but the step before the window: its own programs are there
    ([e for e in LEDGER if "train_step" in e[1]], 0.0),
    (LEDGER[-1:], None),
    ([], None),
])
def test_setup_other_compile_s(spec, monkeypatch, ledger, expected):
    import horovod_tpu.obs

    monkeypatch.setattr(horovod_tpu.obs, "compile_events",
                        lambda: [_event(*e) for e in ledger])
    value = spec.reader("setup_other_compile_s").read(
        _run(trace=False, opened_at=100.0))
    assert value == (None if expected is None else pytest.approx(expected))


def test_setup_other_compile_s_on_a_program_without_a_ledger(
        spec, monkeypatch):
    import horovod_tpu.obs

    monkeypatch.delattr(horovod_tpu.obs, "compile_events")
    assert spec.reader("setup_other_compile_s").read(_run()) is None


LMS = ["gpt2m_1chip", "gpt2m_4chip", "laguna_xs2_8k_1chip",
       "kimi_linear_16k_1chip"]


def test_the_new_metrics_follow_their_cells(spec):
    """By name, wherever later entries put them in the list. The four that
    every model can report carry no ``workloads`` key, so a cell entered by
    one ``workloads`` entry has them; the four of a language model's blocks
    list the language-model cells, as the kernels' metrics list theirs."""
    by_name = {m["name"]: m for m in spec.data["per_layer"]}
    everywhere = ["norm_ms", "head_ms", "component_other_pct",
                  "setup_other_compile_s"]
    of_lms = ["embed_ms", "mixer_ms", "mixer_proj_ms", "mlp_ms"]
    for name in everywhere:
        assert "workloads" not in by_name[name], name
    for name in of_lms:
        assert by_name[name]["workloads"] == [
            "gpt2m_1chip", "gpt2m_4chip", "laguna_xs2_8k_1chip",
            "kimi_linear_16k_1chip"], name
    for cell in spec.cell_names():
        have = {m["name"] for m in spec.cell(cell).per_layer}
        assert set(everywhere) <= have, cell
        assert (set(of_lms) <= have) == (cell in LMS), cell
        assert (set(of_lms) & have == set()) == (cell not in LMS), cell
    for name in SEVEN:
        m = by_name[name]
        assert (m["layer"], m["source"], m["better"], m["moves"]) == (
            "models", "device_trace", "lower", "samples_per_s_per_chip")
        assert m["unit"] == ("%" if name == "component_other_pct" else "ms")
    m = by_name["setup_other_compile_s"]
    assert (m["layer"], m["source"], m["better"], m["moves"], m["unit"]) == (
        "entry and compile cache", "program_counter", "lower", "setup_s",
        "s")


def test_the_readers_names_are_the_models(spec):
    """The benchmark imports nothing of the program to read it (the parent
    has no such module): the two lists are kept equal here."""
    from horovod_tpu.models import scopes as declared

    six = {declared.EMBED, declared.NORM, declared.MIXER,
           declared.MIXER_PROJ, declared.MLP, declared.HEAD}
    assert components.PROJ == declared.MIXER_PROJ
    assert {"hvd." + o for o in components.OWNERS} | {components.PROJ} \
        == six | {"hvd.moe"}


@pytest.fixture(scope="module")
def toy_hlo():
    """The toy language-model cell's step, compiled on the CPU."""
    import os

    import jax

    from chipbench import aot

    toy = cells.Spec(os.path.join(cells.ROOT, "tests", "chipbench", "toy",
                                  "BENCHMARK.json")).cell("toy_lm_1dev")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(aot, "mosaic_kernels", contextlib.nullcontext)
        return aot.compile_cell(toy, jax.devices()).as_text()


def test_the_toy_cells_step_has_an_owner_for_its_instructions(toy_hlo):
    """Of the instructions under ``hvd.loss``, forward and backward, what no
    component owns is under 5 % (the positions' ``broadcast_in_dim`` and
    little else)."""
    inherited = components.inherited_op_names(toy_hlo)
    owners = [components.owner_of(op_name or inherited.get(name, ""))
              for name, (phase, op_name, _)
              in scopes.instructions(toy_hlo).items()
              if phase in ("forward", "backward")]
    assert len(owners) > 100
    assert set(owners) >= {"embed", "norm", "mixer", "mlp", "head"}
    assert owners.count("other") < 0.05 * len(owners), (
        owners.count("other"), len(owners))


def _walks_agree(hlo):
    """Every instruction without metadata: the phase ``scopes.instructions``
    gave it is the phase of the ``op_name`` this module's walk found for
    it (none where a collective was found first, or nothing). Returns how
    many inherited."""
    inherited = components.inherited_op_names(hlo)
    bare = {name: phase for name, (phase, op_name, _)
            in scopes.instructions(hlo).items() if not op_name}
    assert set(inherited) <= set(bare)
    for name, phase in bare.items():
        if phase in ("collective", "exchange_compute"):
            assert name not in inherited, name
        else:
            assert scopes.phase_of(inherited.get(name, "")) == phase, name
    return len(inherited)


def test_the_two_walks_agree_on_the_hand_made_text():
    assert _walks_agree(HLO) >= 3


def test_the_two_walks_agree_on_the_toy_cells_step(toy_hlo):
    """``inherited_op_names`` is ``scopes.instructions``'s walk written a
    second time (that file is not this PR's to edit): held in step here
    until one of them goes."""
    _walks_agree(toy_hlo)
