"""Family ``smallthinker``: the plain reference (a router on the block's
input, ReLU-gated held experts, full layers without positions beside
rotated window layers) against the program at a toy size on the CPU — 4 of
16 experts held, a group of 7 query heads, the kernels interpreted, each
half of a block recomputed —, the program's step through
``data_parallel_step`` against the reference trainer, the shape functions
against totals worked by hand, the configuration file against the catalog's
reading of the published config, the readers on a reduced trace, and the
rehearsal of a toy cell through the run command. The toy benchmark file is
this family's own (``tests/chipbench/smallthinker_toy``).

Nothing here counts the benchmark's cells or metrics, or asks which is the
last: a later PR adds one.

The real cell's step compiles for a described v5e in the ``slow`` test at
the end (only one process at a time may hold the TPU compiler: the topology
is described inside a fixture; about a minute of compiling)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as cells
from chipbench import check, numerics

from test_chipbench_run_cpu import last_line, run_cell

TOY = os.path.join("tests", "chipbench", "smallthinker_toy",
                   "BENCHMARK.json")
CELL = "smallthinker_16k_1chip"
CONFIG = "smallthinker-21b-a3b"
NEW = ("st_flash_win_ms", "st_flash_win_roofline", "st_flash_full_ms",
       "st_flash_full_roofline", "st_expert_matmul_ms",
       "st_expert_matmul_roofline", "st_moe_ms", "st_moe_route_ms",
       "st_moe_experts_ms")
REDUCED = ["num_hidden_layers", "moe_num_primary_experts", "vocab_size"]


@pytest.fixture(scope="module")
def toy():
    return cells.Spec(os.path.join(cells.ROOT, TOY)).cell(
        "toy_smallthinker_1dev")


@pytest.fixture(scope="module")
def real():
    return cells.Spec().cell(CELL)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


# -- program against reference ----------------------------------------------


def test_reference_against_the_program_in_float32(toy):
    """Loss and every gradient leaf on one seeded batch of 2 x 64 tokens:
    the ``flash_*`` and ``flash_win_*`` kernels (interpreted, a group of 7)
    against dense attention a block of rows at a time, the expert loop's
    slices against a loop over the held experts, the router on the block's
    input, the blocked loss against the reference's own blocks."""
    family, config, traffic = toy.family, toy.config, toy.traffic
    keys = cells.seed_keys(11, 2)
    (params,) = family.init_model_state(config, keys[0])
    (tokens,) = family.make_pool(config, traffic, keys[1])[0]
    model = family.build(config).clone(dtype=jnp.float32)
    assert (model.attention, model.remat, model.experts_held,
            model.num_experts) == ("flash", True, (4, 4), 16)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grad = jax.jit(jax.value_and_grad(functools.partial(
            family.reference_loss, config=config)))(params, tokens)
        loss, grad = jax.jit(jax.value_and_grad(lambda p: model.apply(
            {"params": p}, tokens, loss_tokens=tokens)))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    assert len(ref) == 1 + 4 * (2 + 4 + 4) + 1 + 1
    assert min(ref.values()) > 0
    # tolerance: float32 summation order; bfloat16 would read 0.02
    assert max(err[k] / ref[k] for k in ref) < 1e-4
    assert check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0] < 1e-5
    source = open(family.__file__, encoding="utf-8").read()
    assert source.count("horovod_tpu") == 1       # in build alone
    assert "from benchmarks" not in source        # laguna's make_step


def test_three_steps_through_the_data_parallel_step(toy):
    """The program's step as the benchmark builds it — ``make_step``:
    ``make_lm_train_step`` over ``data_parallel_step`` with
    ``hvd.DistributedOptimizer``, unchanged for this decoder — in float32,
    three steps from the seed, against the reference trainer's (its own
    AdamW, the routers' update withheld, the moments on the host)."""
    import horovod_tpu as hvd

    family, config, traffic = toy.family, toy.config, toy.traffic
    assert family.router_frozen(config)
    keys = cells.seed_keys(13, 2)
    reference = family.reference_run(config, traffic, keys, check.STEPS)

    (params,) = family.init_model_state(config, keys[0])
    pool = family.make_pool(config, traffic, keys[1])
    model = family.build(config).clone(dtype=jnp.float32, attention="dense")
    hvd.init()
    try:
        mesh = hvd.parallel.data_parallel_mesh(jax.devices()[:1])
        opt = hvd.DistributedOptimizer(family.optimizer(config),
                                       axis_name="data")
        step = family.make_step(model, opt, mesh)
        state = family.assemble((jax.tree_util.tree_map(jnp.copy, params),),
                                jax.jit(opt.init)(params))
        losses, grad_norms = [], None
        with jax.default_matmul_precision("highest"):
            for i in range(check.STEPS):
                *state, loss = step(*state, *pool[i])
                losses.append(float(loss))
                if i == 0:
                    grad_norms = numerics.leaf_norms(
                        family.first_gradient(state[1], config))
    finally:
        hvd.shutdown()
    program = {"losses": losses, "grad_norms": grad_norms,
               "update_norms": numerics.difference_norms(state[0], params)}
    gaps = check.compare(program, reference)
    # float32 on both sides: summation order, and Adam's division by the
    # root of a small second moment for ``update``
    assert gaps["loss"][0] < 1e-5
    assert gaps["first_gradient"][0] < 1e-4
    assert gaps["update"][0] < 1e-3
    routers = [k for k in program["update_norms"] if "router" in k]
    assert len(routers) == 4
    assert all(program["update_norms"][k] == 0.0 for k in routers)
    assert all(program["grad_norms"][k] > 0 for k in routers)


def test_seeded_tree_has_the_layout_of_the_programs_model(real, toy):
    """At the published widths, from shapes alone; 656.5 M parameters,
    worked out leaf by leaf as the configuration states them."""
    tokens = jnp.zeros((1, 16), jnp.int32)
    for cell in (real, toy):
        family, config = cell.family, cell.config
        want = jax.eval_shape(
            family.build(config).clone(attention="dense").init,
            jax.random.PRNGKey(0), tokens)["params"]
        (got,) = jax.eval_shape(
            functools.partial(family.init_model_state, config),
            jax.random.PRNGKey(0))
        assert _shapes(got) == _shapes(want)
        assert len(jax.tree_util.tree_leaves(got)) == 1 + 4 * 10 + 2
    (tree,) = jax.eval_shape(functools.partial(
        real.family.init_model_state, real.config), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == 656_529_920 == 4 * 115_512_320 + 194_480_640
    assert 16 * count / 1e9 == pytest.approx(10.50, abs=0.01)
    assert "656,529,920" in real.config["deployment"]["parameters_here"]
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in tree["block_3"].items()}
    assert sizes["attn"] == 2 * 9_175_040 + 2 * 1_310_720
    assert sizes["attn"] + sizes["ln_attn"] + sizes["ln_mlp"] + 163_840 \
        == 21_140_480
    assert sizes["moe"] == 163_840 + 16 * 5_898_240
    assert tree["block_0"]["moe"]["router"]["kernel"].shape == (2560, 64)
    assert tree["block_0"]["moe"]["experts_w1"].shape == (16, 2560, 768)
    assert tree["tok_embed"]["embedding"].shape == (37984, 2560)
    assert tree["lm_head"]["kernel"].shape == (2560, 37984)
    assert set(tree["block_0"]["attn"]) == {"query", "key", "value", "out"}


# -- shape functions against totals worked by hand --------------------------


def test_flops_per_sample_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    d, seq = 2560, 16384
    attention = 2 * d * 28 * 128 + 2 * d * 4 * 128
    router, expert = d * 64, 3 * d * 768
    assert (attention, router, expert) == (20_971_520, 163_840, 5_898_240)
    # an expected 6 * 16 / 64 = 1.5 held experts a token
    layer = attention + router + 1.5 * expert
    head = d * 37984
    assert family.matmul_parameters(config) == 4 * layer + head \
        == 217_169_920
    full, window = seq * (seq + 1) // 2, 4096 * 4097 // 2 + 12288 * 4096
    assert (family.visible_pairs(seq), family.visible_pairs(seq, 4096)) \
        == (full, window) == (134_225_920, 58_722_304)
    assert window / full == pytest.approx(0.4375, abs=1e-3)
    mixing = 3 * 2 * 2 * 128 * 28 * (full + 3 * window)
    total = family.flops_per_sample(config, traffic)
    assert total == 6.0 * 217_169_920 * seq + mixing
    assert 6.0 * 217_169_920 * seq / 1e12 == pytest.approx(21.35, abs=0.01)
    assert mixing / 1e12 == pytest.approx(13.35, abs=0.01)
    assert total / 1e12 == pytest.approx(34.70, abs=0.01)
    # the head over a quarter of the vocabulary: a last pipeline stage's
    assert 6.0 * head * seq / total == pytest.approx(0.275, abs=0.005)


def test_kernel_work_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    work = family.kernel_work(config, traffic, 1)
    assert set(work) == {"flash_win", "flash_full", "expert_matmul"}
    seq = 16384
    # seven products of 2 * 128 FLOPs a pair and query head, 28 of them
    assert work["flash_full"]["flops"] == 7 * 2 * 128 * 28 * 134_225_920
    assert work["flash_win"]["flops"] == 3 * 7 * 2 * 128 * 28 * 58_722_304
    # q, o forward and q, o, dO, dQ backward at 28 heads; k, v and k, v,
    # dK, dV once a group of 7 at the 4 key/value heads
    a_layer = 6 * seq * (28 + 4) * 128 * 2
    assert work["flash_full"]["bytes"] == a_layer
    assert work["flash_win"]["bytes"] == 3 * a_layer
    assert (work["flash_full"]["calls"], work["flash_win"]["calls"]) \
        == (3, 9)
    rows = seq * 6 * 16 / 64
    assert rows == 24576 == 16 * 1536
    assert work["expert_matmul"]["flops"] == 4 * 3 * 3 * 2 * 2560 * 768 * rows
    assert work["expert_matmul"]["bytes"] == 4 * 3 * 3 * 2 * (
        rows * (2560 + 768) + 16 * 2560 * 768)
    peaks = cells.peaks_of("TPU v5 lite")
    bound = real.spec.reader("flash_roofline").bound
    assert bound(work["flash_full"], peaks) \
        == (pytest.approx(34.19e-3, rel=1e-3), "flops")
    assert bound(work["flash_win"], peaks) \
        == (pytest.approx(44.87e-3, rel=1e-3), "flops")
    assert bound(work["expert_matmul"], peaks) \
        == (pytest.approx(17.66e-3, rel=1e-3), "flops")


# -- the files --------------------------------------------------------------


def test_the_configuration_keeps_every_published_number(real):
    """Against the catalog beside the ``model-configs`` guide where it is
    installed; the cut and the deployment either way."""
    config = real.config
    assert config["family"] == "smallthinker"
    assert config["reduced"] == REDUCED
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37984)
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_ffn_hidden_size"],
            config["moe_num_active_primary_experts"],
            config["sliding_window_size"], config["rope_theta"],
            config["rms_norm_eps"], config["max_position_embeddings"]) \
        == (2560, 28, 4, 128, 768, 6, 4096, 1500000, 1e-6, 16384)
    assert config["sliding_window_layout"][:4] == config["rope_layout"][:4] \
        == [0, 1, 1, 1]
    assert config["norm_topk_prob"] is True and config["rope_scaling"] is None
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 4
    assert deployment["experts_held_first"] == 0
    assert deployment["num_experts"] \
        == 4 * config["moe_num_primary_experts"] == 64
    assert deployment["vocab_size"] == 4 * config["vocab_size"] == 151936
    assert deployment["num_hidden_layers"] == 52
    assert deployment["router_update"].startswith("frozen")
    assert real.family.router_frozen(config)
    model = real.family.build(config)
    assert (model.num_experts, model.experts_held, model.experts_per_token,
            model.windowed, model.rotated, model.window, model.remat) \
        == (64, (0, 16), 6, (False, True, True, True),
            (False, True, True, True), 4096, True)
    assert {"router_input", "router", "mlp", "experts", "attention",
            "positions", "biases", "initializer", "dropout"} \
        <= set(config["assumed"])
    laguna = real.spec.config("laguna-xs2")
    assert config["precision"] == laguna["precision"]
    for key in ("attention", "remat"):
        assert config[key] == laguna[key]
    # AdamW as laguna-xs2's but for a fine-tune's learning rate, under which
    # the seeded weights stay the state the cell describes (assumed)
    assert config["optimizer"] == dict(laguna["optimizer"],
                                       learning_rate=1e-5)
    assert "learning_rate" in config["assumed"]
    (entry,) = [c for c in real.spec.data["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert entry["file"] == f"chipbench/configs/{CONFIG}.json"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not installed here")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "SmallThinker-21BA3B-Instruct"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key


def test_the_cell_and_its_metrics(real):
    assert (real.chips, real.per_chip_batch, real.traffic["pool"]) \
        == (1, 1, 8)
    assert real.traffic_name == "lm16384_global1"
    assert real.traffic["sample_shape"] == [16384]
    assert real.traffic["loop"] == "closed"
    names = {m["name"] for m in real.per_layer}
    assert set(NEW) <= names
    # those without a ``workloads`` key follow the cell by themselves
    assert {"norm_ms", "head_ms", "component_other_pct", "forward_ms",
            "backward_ms", "unscoped_pct", "peak_hbm_gb",
            "device_idle_pct"} <= names
    assert {m["name"] for m in real.end_to_end} == {
        "samples_per_s_per_chip", "step_ms_p95", "mfu_pct", "setup_s"}
    for other in real.spec.cell_names():
        if other != CELL:
            assert not set(NEW) & {m["name"] for m in
                                   real.spec.cell(other).per_layer}, other
    listed = {m["name"]: m for m in real.spec.data["per_layer"]}
    for name in NEW:
        m = listed[name]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "samples_per_s_per_chip"
        assert m["source"] == "device_trace"
        assert m["layer"] == ("models" if "moe" in name else "kernels")
        assert (m["unit"], m["better"]) == (
            ("%", "higher") if "roofline" in name else ("ms", "lower"))
    assert set(real.limits()) >= set(check.COMPARED)
    for number in real.limits().values():
        assert number["limit"] > 0 and number["set_from"]
    (mine,) = [w for w in real.spec.data["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and len(mine["why"]) <= 200
    (entry,) = [c for c in real.spec.data["configs"] if c["name"] == CONFIG]
    assert len(entry["why"]) <= 200


def test_every_file_the_new_entries_name_loads(real):
    spec = real.spec
    assert spec.family("smallthinker") is real.family
    for function in ("build", "optimizer", "make_step", "assemble",
                     "first_gradient", "init_model_state", "make_pool",
                     "data_spec", "flops_per_sample", "kernel_work",
                     "reference_run"):
        assert callable(getattr(real.family, function)), function
    for name in NEW:
        reader = spec.reader(name)
        assert callable(reader.read) and reader.__doc__.startswith(
            f"``{name}``"), name
    assert spec.traffic("lm16384_global1") == real.traffic
    assert spec.config(CONFIG) == real.config


def test_readers_on_a_reduced_trace(real):
    """A step's events under the names the compiled step gives them; the
    three ``st_moe_*`` leave a loop's own event out and count its body."""
    spec = real.spec

    def line(name, op_name, opcode="fusion", mosaic=False):
        call = ('custom-call(%a), custom_call_target="tpu_custom_call"'
                if mosaic else f"{opcode}(%a)")
        return (f"  %{name} = bf16[2]{{0}} {call}, metadata={{op_name="
                f'"jit(train_step)/{op_name}"}}')

    fwd, bwd = "hvd.loss", "transpose(jvp(hvd.loss))"
    moe = "block_1/moe/hvd.moe"
    hlo = "\n".join([
        "ENTRY %main {",
        line("flash_fwd.1", f"{fwd}/block_0/hvd.mixer/attn/x", mosaic=True),
        line("flash_bwd_dq", f"{bwd}/block_0/hvd.mixer/attn/x", mosaic=True),
        line("flash_bwd_dkv.2", f"{bwd}/block_0/hvd.mixer/y", mosaic=True),
        line("flash_win_fwd.3", f"{fwd}/block_1/hvd.mixer/x", mosaic=True),
        line("flash_win_bwd_dq.4", f"{bwd}/block_1/hvd.mixer/x", mosaic=True),
        line("flash_win_bwd_dkv", f"{bwd}/block_1/hvd.mixer/x", mosaic=True),
        line("expert_matmul_fwd.5", f"{fwd}/{moe}/hvd.moe.experts/w",
             mosaic=True),
        line("expert_matmul_bwd_dw.6", f"{bwd}/{moe}/hvd.moe.experts/w",
             mosaic=True),
        line("fusion.7", f"{fwd}/{moe}/hvd.moe.route/router/dot_general"),
        line("fusion.8", f"{bwd}/{moe}/hvd.moe.experts/sort"),
        line("while.9", f"{fwd}/{moe}/hvd.moe.experts/while", "while"),
        line("fusion.10", f"{fwd}/{moe}/hvd.moe.combine/convert"),
        line("fusion.11", f"{fwd}/block_1/hvd.mixer/attn/hvd.mixer.proj/q"),
        "}"])
    device = {"steps": 2, "busy_s": 1.0, "op_seconds": {
        "flash_fwd.1": 0.010, "flash_bwd_dq": 0.020, "flash_bwd_dkv.2": 0.030,
        "flash_win_fwd.3": 0.012, "flash_win_bwd_dq.4": 0.018,
        "flash_win_bwd_dkv": 0.020, "expert_matmul_fwd.5": 0.004,
        "expert_matmul_bwd_dw.6": 0.006, "fusion.7": 0.002,
        "fusion.8": 0.008, "while.9": 0.100, "fusion.10": 0.001,
        "fusion.11": 0.5}}
    peaks = cells.peaks_of("TPU v5 lite")
    work = {name: {"flops": 197e12 * least, "bytes": 1.0}
            for name, least in (("flash_full", 0.015), ("flash_win", 0.005),
                                ("expert_matmul", 0.001))}
    run = {"cell": real, "trace": {"devices": [device]}, "hlo": hlo,
           "kernel_work": work, "peaks": peaks}
    read = lambda name: spec.reader(name).read(run)  # noqa: E731
    assert read("st_flash_full_ms") == pytest.approx(30.0)
    assert read("st_flash_full_roofline") == pytest.approx(50.0)
    assert read("st_flash_win_ms") == pytest.approx(25.0)
    assert read("st_flash_win_roofline") == pytest.approx(20.0)
    assert read("st_expert_matmul_ms") == pytest.approx(5.0)
    assert read("st_expert_matmul_roofline") == pytest.approx(20.0)
    assert read("st_moe_route_ms") == pytest.approx(1.0)
    assert read("st_moe_experts_ms") == pytest.approx(9.0)    # no while.9
    assert read("st_moe_ms") == pytest.approx(10.5)
    assert spec.reader("moe_ms").read(run) == pytest.approx(60.5)   # with it
    # a program without the kernels, the scope or a trace (the parent
    # commit): nothing, no raise
    bare = dict(run, hlo="ENTRY %main {\n  %fusion.5 = bf16[2]{0} "
                "fusion(%a), kind=kLoop\n}")
    for name in NEW:
        assert spec.reader(name).read(bare) is None, name
        assert spec.reader(name).read(dict(run, trace=None)) is None, name
    for name in NEW:
        if "roofline" in name:
            assert spec.reader(name).read(dict(run, kernel_work={})) is None


def test_rehearsal_of_the_toy_cell(tmp_path):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload",
                    "toy_smallthinker_1dev", "--seed", str(2**31 + 29),
                    "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    line = last_line(proc)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["metrics"] == {} and line["rehearsal"] is True
    for number in check.COMPARED:
        assert f"correct: {number} gap" in proc.stdout
    assert "0 compilation(s) in the window" in proc.stdout


# -- the compile of the real shapes for a described v5e ---------------------


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    from chipbench import aot
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name=aot.TOPOLOGY)
    except Exception as e:  # noqa: BLE001 - whatever keeps the compiler away
        pytest.skip(f"no v5e topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.slow
def test_the_step_compiles_for_v5e_and_fits_the_chip(topo, no_compile_cache,
                                                     real):
    """As ``python3 -m chipbench.aot`` compiles it. ``slow``: it compiles
    the real step for the v5e."""
    import re

    from chipbench import aot

    compiled = aot.compile_cell(real, topo.devices)
    held = aot.device_bytes(compiled)
    hbm = cells.peaks_of("TPU v5 lite")["hbm_bytes"]
    # room for the 2.63 GB seeded copy that ``correct`` makes
    assert 0.25 * hbm < held["total"] < hbm - 4 * 656_529_920 - 0.6e9, held
    hlo = compiled.as_text()
    named = re.findall(r"%([\w\-]+?)(?:\.\d+)* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    work = real.family.kernel_work(real.config, real.traffic, 1)
    # every recomputed attention half keeps its kernel's outputs: three
    # calls a layer and no second forward
    assert sum(n.startswith("flash_win_") for n in named) \
        == work["flash_win"]["calls"] == 9
    assert sum(n in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
               for n in named) == work["flash_full"]["calls"] == 3
    assert {n for n in named if n.startswith("expert")} == {
        "expert_matmul_fwd", "expert_matmul_bwd_dx", "expert_matmul_bwd_dw"}
    for scope in ("hvd.mixer/", "hvd.mixer.proj", "hvd.norm",
                  "hvd.moe.route", "hvd.moe.experts", "hvd.head",
                  "hvd.embed"):
        assert scope in hlo, scope
    # the gauge's counting pass is no part of a step: a forward and a
    # backward loop a layer, the recomputed forward's is dead code
    assert "gate_zero" not in hlo
    assert len(set(re.findall(r"body=(%[\w.\-]+)", hlo))) <= 3 * 4 + 2
