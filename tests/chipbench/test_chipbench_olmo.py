"""Family ``olmo_hybrid``: the plain reference (the gated delta rule token
by token, dense attention, post-norm blocks) against the program at a toy
size on the CPU (a layer of each kind, 3 of 6 heads held), the program's
step through ``data_parallel_step`` against the reference trainer, the
shape functions against totals worked by hand, the configuration file
against the catalog's reading of the published config, the readers on a
reduced trace, and the rehearsal of a toy cell through the run command. The
toy benchmark file is this family's own (``tests/chipbench/olmo_toy``).

The real cell's step compiles for a described v5e in the ``slow`` test at
the end (only one process at a time may hold the TPU compiler: the topology
is described inside a fixture; under a minute of compiling)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as cells
from chipbench import check, numerics

from test_chipbench_run_cpu import last_line, run_cell

TOY = os.path.join("tests", "chipbench", "olmo_toy", "BENCHMARK.json")
CELL = "olmo_hybrid_8k_1chip"
NEW = ("gdn_ms", "gdn_roofline", "gdn_conv_ms", "olmo_flash_ms",
       "olmo_flash_roofline")
HEADS = ("num_attention_heads", "num_key_value_heads",
         "linear_num_key_heads", "linear_num_value_heads")


@pytest.fixture(scope="module")
def toy():
    return cells.Spec(os.path.join(cells.ROOT, TOY)).cell("toy_olmo_1dev")


@pytest.fixture(scope="module")
def real():
    return cells.Spec().cell(CELL)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


# -- program against reference ----------------------------------------------


@pytest.fixture(scope="module")
def gradients(toy):
    """``{dtype: (loss, grad) of the program}, reference, fp8 control`` on
    one seeded batch of 2 x 96 tokens: a chunk and a half of the rule."""
    from horovod_tpu.models import lm_loss

    family, config, traffic = toy.family, toy.config, toy.traffic
    keys = cells.seed_keys(11, 2)
    (params,) = family.init_model_state(config, keys[0])
    (tokens,) = family.make_pool(config, traffic, keys[1])[0]
    with jax.default_matmul_precision("highest"):
        reference, control = (
            jax.jit(jax.value_and_grad(functools.partial(
                family.reference_loss, config=config, num=num)))(
                    params, tokens)
            for num in (numerics.Exact, numerics.Fp8))
    program = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        model = family.build(config).clone(dtype=dtype)
        # the kernels, interpreted on the CPU backend
        assert (model.attention, model.rule) == ("flash", "chunked")
        program[dtype] = jax.jit(jax.value_and_grad(lambda p: lm_loss(
            model.apply({"params": p}, tokens), tokens)))(params)
    return program, reference, control


def test_reference_against_the_program_in_float32(gradients):
    """Loss and every gradient leaf: the convolutions, ``A_log`` and
    ``dt_bias``, the chunked rule under a decay a head with beta up to 2
    against the scan over tokens, the gated output norm, q/k-normalised
    attention over the channels held, the two post-norms."""
    program, (ref_loss, ref_grad), _ = gradients
    loss, grad = program[jnp.float32]
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    # embedding; a delta-rule mixer of 13 leaves, a full one of 6; two
    # norms and an MLP of 3 a block; final norm and head
    assert len(ref) == 1 + 13 + 6 + 2 * (2 + 3) + 1 + 1
    assert min(ref.values()) > 0
    # tolerance: float32 summation order; bfloat16 would read 0.1
    assert max(err[k] / ref[k] for k in ref) < 1e-4
    assert check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0] < 1e-5


def test_program_in_bfloat16_holds_and_the_fp8_control_fails(toy, gradients):
    limit = toy.limits()["first_gradient"]["limit"]
    program, (ref_loss, ref_grad), (_, low_grad) = gradients
    loss, grad = program[jnp.bfloat16]
    ref = numerics.leaf_norms(ref_grad)
    sound = check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0]
    control = check.worst_leaf_gap(numerics.leaf_norms(low_grad), ref)[0]
    assert float(loss) == pytest.approx(float(ref_loss), rel=5e-4)
    assert sound < limit < control
    exact = numerics.difference_norms(program[jnp.float32][1], ref_grad)
    rounded = numerics.difference_norms(grad, ref_grad)
    assert max(rounded[k] / ref[k] for k in ref) \
        > 100 * max(exact[k] / ref[k] for k in ref)


def test_the_control_leaves_the_recurrence_in_float32(toy):
    """The configuration keeps the decay and the state in float32, so the
    control does too; the reference imports nothing of the program."""
    family = toy.family
    rng = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k = (jax.random.normal(key, (96, 3, 16)) for key in rng[:2])
    v = jax.random.normal(rng[2], (96, 3, 32))
    g = -jax.nn.softplus(jax.random.normal(rng[3], (96, 3)))
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(rng[4], (96, 3)))
    out = family._delta_rule(q, k, v, g, beta)
    assert out.shape == (96, 3, 32) and out.dtype == jnp.float32
    source = open(family.__file__, encoding="utf-8").read()
    assert "horovod_tpu" not in source.split("def build")[0]
    assert source.count("from horovod_tpu") == 1      # in build alone
    assert "numerics" not in source.split("def _delta_rule")[1].split(
        "def _gdn")[0]


def test_three_steps_through_the_data_parallel_step(toy):
    """The program's step as the benchmark builds it — ``make_step``:
    ``make_lm_train_step`` over ``data_parallel_step`` with
    ``hvd.DistributedOptimizer`` — in float32, three steps from the seed,
    against the reference trainer's (its own AdamW, the moments on the
    host)."""
    import horovod_tpu as hvd

    family, config, traffic = toy.family, toy.config, toy.traffic
    keys = cells.seed_keys(13, 2)
    reference = family.reference_run(config, traffic, keys, check.STEPS)

    (params,) = family.init_model_state(config, keys[0])
    pool = family.make_pool(config, traffic, keys[1])
    model = family.build(config).clone(dtype=jnp.float32)
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
    assert gaps["loss"][0] < 1e-5
    assert gaps["first_gradient"][0] < 1e-4
    assert gaps["update"][0] < 1e-3


def test_seeded_tree_has_the_layout_of_the_programs_model(real, toy):
    """At the published widths, from shapes alone; 766.2 M parameters."""
    for cell, leaves in ((real, 68), (toy, 32)):
        family, config = cell.family, cell.config
        want = jax.eval_shape(
            family.build(config).clone(attention="dense",
                                       rule="recurrent").init,
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
        (got,) = jax.eval_shape(
            functools.partial(family.init_model_state, config),
            jax.random.PRNGKey(0))
        assert _shapes(got) == _shapes(want)
        assert len(jax.tree_util.tree_leaves(got)) == leaves
    (tree,) = jax.eval_shape(functools.partial(
        real.family.init_model_state, real.config), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == 766_241_946
    assert 16 * count / 1e9 == pytest.approx(12.26, abs=0.01)
    assert "766,241,946" in real.config["deployment"]["parameters_here"]
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in {**tree["block_3"], **tree["block_0"]}.items()}
    # half of the 88.8 M and 59.0 M of 30 heads; the MLP whole
    assert sizes["gdn"] == 44_375_262 and sizes["attn"] == 29_495_040
    assert sizes["mlp"] == 126_812_160
    assert tree["tok_embed"]["embedding"].shape == (12544, 3840)


# -- shape functions against totals worked by hand --------------------------


def test_flops_per_sample_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    d, t, heads = 3840, 8192, 15
    keys, values = heads * 96, heads * 192
    # q, k (96 a head); v, the gate, out (192); decay and beta; the taps
    gdn = d * (2 * keys + 3 * values + 2 * heads) + 4 * (2 * keys + values)
    full = 4 * d * heads * 128
    assert (gdn, full) == (44_375_040, 29_491_200)
    mlp, head = 3 * d * 11008, d * 12544
    by_hand = 3 * gdn + full + 4 * mlp + head
    assert by_hand == 718_033_920
    assert family.matmul_parameters(config) == by_hand
    causal = t * (t + 1) // 2
    attention = 3 * 2 * 2 * 128 * heads * causal
    recurrence = 3 * 3 * 7 * 96 * 192 * heads * t
    assert family.flops_per_sample(config, traffic) \
        == 6.0 * by_hand * t + attention + recurrence
    # 4.308 GFLOP a token of products, 0.094 of causal pairs, 0.017 of the
    # recurrence (which family kimi_linear counts too)
    assert 6.0 * by_hand / 1e9 == pytest.approx(4.308, abs=1e-3)
    assert attention / t / 1e9 == pytest.approx(0.0944, abs=1e-4)
    assert family.flops_per_sample(config, traffic) / 1e12 \
        == pytest.approx(36.21, abs=0.01)


def test_kernel_work_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    work = family.kernel_work(config, traffic, 1)
    assert set(work) == {"gdn", "flash"}
    t, heads = 8192, 15
    causal = t * (t + 1) // 2
    assert work["gdn"]["flops"] == 3 * 3 * 7 * 96 * 192 * heads * t
    # a token and head: q, k at 96 and v, o at 192 in 2 B, g and beta in
    # 4 B; forward once, backward the same read (dO for o) and all but o's
    # size written
    one_way = t * heads * ((2 * 96 + 2 * 192) * 2 + 8)
    assert work["gdn"]["bytes"] == 3 * (3 * one_way - t * heads * 192 * 2)
    assert work["flash"]["flops"] == 7 * 2 * 128 * heads * causal
    assert work["flash"]["bytes"] == 6 * t * 2 * heads * 128 * 2
    # a recomputed block keeps its kernels' outputs: one forward a layer
    assert (work["gdn"]["calls"], work["flash"]["calls"]) == (6, 3)
    peaks = cells.peaks_of("TPU v5 lite")
    bound = real.spec.reader("flash_roofline").bound
    assert bound(work["gdn"], peaks) \
        == (pytest.approx(1.394e-3, rel=1e-3), "bytes")
    assert bound(work["flash"], peaks) \
        == (pytest.approx(4.579e-3, rel=1e-3), "flops")


# -- the files --------------------------------------------------------------


def test_the_configuration_keeps_every_published_number(real):
    """Against the catalog beside the ``model-configs`` guide where it is
    installed; the cut and the deployment either way."""
    config = real.config
    assert config["reduced"] == ["num_hidden_layers", *HEADS, "vocab_size"]
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 12544)
    assert [config[k] for k in HEADS] == [15] * 4
    assert (config["hidden_size"], config["intermediate_size"]) \
        == (3840, 11008)
    assert (config["linear_key_head_dim"], config["linear_value_head_dim"],
            config["linear_conv_kernel_dim"], config["head_dim"]) \
        == (96, 192, 4, 128)
    assert config["linear_allow_neg_eigval"] is True
    assert config["rope_parameters"] == {"rope_theta": None}
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 2
    assert deployment["heads_held_first"] == 0
    assert [deployment[k] for k in HEADS] == [30] * 4
    assert deployment["vocab_size"] == 8 * config["vocab_size"] == 100352
    assert deployment["num_hidden_layers"] == 32
    assert real.family.layers(config) == ["linear_attention"] * 3 \
        + ["full_attention"]
    model = real.family.build(config)
    assert (model.linear_heads, model.num_heads, model.num_kv_heads,
            model.head_dim) == (15, 15, 15, 128)
    assert {"block", "qk_norm", "positions", "gdn_rule", "gdn_beta",
            "gdn_decay", "gdn_conv_init", "gdn_output", "initializer",
            "dropout", "head_dim"} <= set(config["assumed"])
    assert config["remat"] is True
    assert (config["attention"], config["rule"]) == ("flash", "chunked")
    assert config["precision"]["compute"] == "bfloat16"
    assert config["precision"]["gdn_decay_and_state"].startswith("float32")
    kimi = real.spec.config("kimi-linear-48b-a3b")
    assert config["optimizer"] == kimi["optimizer"]
    (entry,) = [c for c in real.spec.data["configs"]
                if c["name"] == "olmo-hybrid-7b"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not installed here")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Olmo-Hybrid-7B"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key


def test_the_cell_and_its_metrics(real):
    assert (real.chips, real.per_chip_batch, real.traffic["pool"]) \
        == (1, 1, 8)
    assert real.traffic["sample_shape"] == [8192]
    assert real.traffic["loop"] == "closed"
    assert real.traffic["steps_per_timing_sample"] == 1
    names = {m["name"] for m in real.per_layer}
    assert set(NEW) <= names
    # those without a ``workloads`` key read the new cell at once; the
    # lists of the others are the benchmark's to extend (PERF.md section 7)
    assert {"norm_ms", "head_ms", "component_other_pct", "forward_ms",
            "backward_ms", "unscoped_pct", "peak_hbm_gb"} <= names
    assert not {"mixer_ms", "mlp_ms", "embed_ms", "kda_ms", "flash_ms",
                "flash_full_ms", "allreduce_ms"} & names
    for other in ("gpt2m_1chip", "laguna_xs2_8k_1chip",
                  "kimi_linear_16k_1chip"):
        assert not set(NEW) & {m["name"]
                               for m in real.spec.cell(other).per_layer}
    layers = {m["layer"] for m in real.spec.data["per_layer"]
              if m["name"] not in NEW}
    for m in real.spec.data["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "samples_per_s_per_chip"
            assert m["layer"] == ("models" if m["name"] == "gdn_conv_ms"
                                  else "kernels")
            assert m["layer"] in layers     # a name that was there
    assert set(real.limits()) >= set(check.COMPARED) | set(check.OPTIONAL)
    cells_now = real.spec.data["workloads"]
    assert len(cells_now) == 6 and cells_now[-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in cells_now) == 1


def test_readers_on_a_reduced_trace(real):
    """A step's events under the names the compiled step gives them."""
    spec = real.spec

    def line(name, kind, op_name):
        call = 'custom-call(%a), custom_call_target="tpu_custom_call"' \
            if kind == "kernel" else "fusion(%a), kind=kLoop"
        return (f"  %{name} = bf16[2]{{0}} {call}, metadata={{op_name="
                f'"jit(train_step)/{op_name}"}}')

    mixer = "block_1/hvd.mixer/gdn/hvd.gdn"
    hlo = "\n".join([
        "ENTRY %main {",
        line("gdn_fwd.1", "kernel", f"hvd.loss/{mixer}/hvd.gdn.scan/gdn_fwd"),
        line("gdn_bwd", "kernel",
             f"transpose(jvp(hvd.loss))/{mixer}/hvd.gdn.scan/gdn_bwd"),
        line("fusion.3", "fusion",
             f"hvd.loss/checkpoint/{mixer}/hvd.gdn.scan/while/body/exp"),
        line("fusion.4", "fusion", f"hvd.loss/{mixer}/hvd.gdn.conv/mul"),
        # a loop as the trace shows it: one event over its body's (fusion.3)
        "  %while.6 = (s32[], bf16[2]{0}) while(%t), condition=%c, body=%b, "
        f'metadata={{op_name="jit(train_step)/hvd.loss/{mixer}/hvd.gdn.scan/'
        'while"}',
        line("fusion.5", "fusion", f"hvd.loss/{mixer}/out/dot_general"),
        line("flash_fwd.2", "kernel",
             "hvd.loss/block_3/hvd.mixer/attn/flash_fwd"),
        line("flash_bwd_dkv", "kernel",
             "transpose(jvp(hvd.loss))/block_3/hvd.mixer/attn/x"),
        line("flash_mla_fwd.7", "kernel", "hvd.loss/block_0/mla/x"),
        line("kda_fwd.7", "kernel",
             "hvd.loss/block_0/kda/hvd.kda/hvd.kda.scan/kda_fwd"),
        "}"])
    device = {"steps": 2, "busy_s": 0.4, "op_seconds": {
        "gdn_fwd.1": 0.010, "gdn_bwd": 0.020, "fusion.3": 0.010,
        "fusion.4": 0.006, "fusion.5": 0.1, "while.6": 0.012,
        "flash_fwd.2": 0.030, "flash_bwd_dkv": 0.050,
        "flash_mla_fwd.7": 0.2, "kda_fwd.7": 0.3}}
    peaks = cells.peaks_of("TPU v5 lite")
    work = {"gdn": {"flops": 1.0, "bytes": 819e9 * 0.005},
            "flash": {"flops": 197e12 * 0.010, "bytes": 1.0}}
    run = {"cell": real, "trace": {"devices": [device]}, "hlo": hlo,
           "kernel_work": work, "peaks": peaks}
    read = lambda name: spec.reader(name).read(run)  # noqa: E731
    assert read("gdn_ms") == pytest.approx(20.0)   # kernels and what feeds
    assert read("gdn_roofline") == pytest.approx(25.0)
    assert read("gdn_conv_ms") == pytest.approx(3.0)
    assert read("olmo_flash_ms") == pytest.approx(40.0)
    assert read("olmo_flash_roofline") == pytest.approx(25.0)
    # a program without the kernels, the scope or a trace (the parent
    # commit): nothing, no raise
    bare = dict(run, hlo="ENTRY %main {\n  %fusion.5 = bf16[2]{0} "
                "fusion(%a), kind=kLoop\n}")
    for name in NEW:
        assert spec.reader(name).read(bare) is None, name
        assert spec.reader(name).read(dict(run, trace=None)) is None, name
        if name.endswith("_roofline"):
            assert spec.reader(name).read(dict(run, kernel_work={})) is None


def test_rehearsal_of_the_toy_cell(tmp_path):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload",
                    "toy_olmo_1dev", "--seed", str(2**31 + 23),
                    "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    line = last_line(proc)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["metrics"] == {} and line["rehearsal"] is True
    for number in ("loss", "first_gradient", "update",
                   "first_gradient_mean"):
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
    """As ``python3 -m chipbench.aot`` compiles it: ``aot.mosaic_kernels``
    steers the flash kernels off the interpreter, and the delta rule's
    follow the platform the step is lowered for — ``gdn_fwd`` / ``gdn_bwd``
    at 15 heads of 96 and 192, whose fourth group of four reaches a head
    past the array. ``slow``: it compiles the real step for the v5e."""
    import re

    from horovod_tpu import obs

    from chipbench import aot

    compiled = aot.compile_cell(real, topo.devices)
    held = aot.device_bytes(compiled)
    hbm = cells.peaks_of("TPU v5 lite")["hbm_bytes"]
    # room for the 3.06 GB seeded copy that ``correct`` makes
    assert 0.25 * hbm < held["total"] < hbm - 4 * 766_241_946, held
    hlo = compiled.as_text()
    named = re.findall(r"%([\w\-]+?)(?:\.\d+)* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    work = real.family.kernel_work(real.config, real.traffic, 1)
    assert sum(n.startswith("gdn_") for n in named) == work["gdn"]["calls"]
    assert sum(n.startswith("flash_") for n in named) \
        == work["flash"]["calls"]
    assert set(named) == {"gdn_fwd", "gdn_bwd", "flash_fwd", "flash_bwd_dq",
                          "flash_bwd_dkv"}, set(named)
    for scope in ("hvd.gdn.scan", "hvd.gdn.conv", "hvd.mixer.proj",
                  "hvd.norm", "hvd.mlp"):
        assert scope in hlo, scope
    # no loop, the kernels by name, no forward kernel run again; the one
    # "relayout" is no tensor moved to heads on an axis of their own but
    # the compiler's copy of the first block's input gradient, bf16[8192,
    # 3840], which the count takes for being larger than q and under
    # ``hvd.gdn`` (.../block_0/hvd.mixer/gdn/hvd.gdn/hvd.mixer.proj/query/
    # add_any)
    assert obs.kda.record_scan_program(CELL, hlo) == (
        0, {"gdn_fwd": 3, "gdn_bwd": 3}, 1, 0)
    assert not re.search(r"\[[\d,]*8192,1[56],(?:96|192|90|180)\]", hlo)
