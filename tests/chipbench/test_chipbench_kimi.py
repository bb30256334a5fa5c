"""Family ``kimi_linear``: the plain reference (the delta rule token by
token) against the program at a toy size on the CPU (a layer of each kind,
16 experts, 4 held), the shape functions against totals worked by
hand, the configuration file against the catalog's reading of the published
config, the readers on a reduced trace, and the rehearsal of a toy cell
through the run command. The toy benchmark file is this family's own
(``tests/chipbench/kimi_toy``).

The real cell's step compiles for a described v5e in the ``slow`` test at
the end (only one process at a time may hold the TPU compiler: the topology
is described inside a fixture)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as cells
from chipbench import check, numerics

from test_chipbench_run_cpu import last_line, run_cell

TOY = os.path.join("tests", "chipbench", "kimi_toy", "BENCHMARK.json")
CELL = "kimi_linear_16k_1chip"
NEW = ("kda_ms", "kda_roofline", "kda_conv_ms", "flash_mla_ms",
       "flash_mla_roofline")


@pytest.fixture(scope="module")
def toy():
    return cells.Spec(os.path.join(cells.ROOT, TOY)).cell("toy_kimi_1dev")


@pytest.fixture(scope="module")
def real():
    return cells.Spec().cell(CELL)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


# -- program against reference ----------------------------------------------


@pytest.fixture(scope="module")
def gradients(toy):
    """``{dtype: ((loss, grad) of program, reference, fp8 control)}`` on one
    seeded batch of 2 x 96 tokens: a chunk and a half of the delta rule."""
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
    out = {}
    for dtype in (jnp.float32, jnp.bfloat16):
        model = family.build(config).clone(dtype=dtype)
        # the kernels, interpreted on the CPU backend
        assert (model.attention, model.kda) == ("flash", "chunked")
        out[dtype] = (jax.jit(jax.value_and_grad(lambda p: lm_loss(
            model.apply({"params": p}, tokens), tokens)))(params),
            reference, control)
    return out


def test_reference_against_the_program_in_float32(gradients):
    """Loss and every gradient leaf: the convolution, the decay's rate and
    bias, the chunked delta rule against the scan over tokens, the gated
    output norm, latent attention at 48 / 32, routing over 16 with 4 held,
    the shared expert."""
    (loss, grad), (ref_loss, ref_grad), _ = gradients[jnp.float32]
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    # embedding; 2 KDA mixers of 15 leaves, a latent one of 5; two norms a
    # block; a dense MLP and 2 expert layers of 7; final norm and head
    assert len(ref) == 1 + 2 * 15 + 5 + 3 * 2 + 3 + 2 * 7 + 1 + 1
    assert min(ref.values()) > 0
    # tolerance: float32 summation order; bfloat16 would read 0.26
    assert max(err[k] / ref[k] for k in ref) < 1e-4
    assert check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0] < 1e-5


def test_program_in_bfloat16_holds_and_the_fp8_control_fails(toy, gradients):
    limit = toy.limits()["first_gradient"]["limit"]
    (loss, grad), (ref_loss, ref_grad), (_, low_grad) = gradients[
        jnp.bfloat16]
    ref = numerics.leaf_norms(ref_grad)
    sound = check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0]
    control = check.worst_leaf_gap(numerics.leaf_norms(low_grad), ref)[0]
    assert float(loss) == pytest.approx(float(ref_loss), rel=2e-4)
    assert sound < limit < control
    exact = numerics.difference_norms(gradients[jnp.float32][0][1], ref_grad)
    rounded = numerics.difference_norms(grad, ref_grad)
    assert max(rounded[k] / ref[k] for k in ref) \
        > 100 * max(exact[k] / ref[k] for k in ref)


def test_the_control_leaves_the_recurrence_in_float32(toy):
    """The configuration keeps the decay and the state in float32, so the
    control does too: on the delta rule alone both precisions agree."""
    family = toy.family
    rng = jax.random.split(jax.random.PRNGKey(0), 5)
    q, k, v, g = (jax.random.normal(key, (96, 2, 32)) for key in rng[:4])
    beta = jax.nn.sigmoid(jax.random.normal(rng[4], (96, 2)))
    out = family._delta_rule(q, k, v, -jax.nn.softplus(g), beta)
    assert out.shape == (96, 2, 32) and out.dtype == jnp.float32
    source = open(family.__file__, encoding="utf-8").read()
    assert "horovod_tpu" not in source.split("def build")[0]
    assert source.count("from horovod_tpu") == 1      # in build alone


def test_three_adamw_steps_and_the_control_through_them(toy):
    """The reference trainer against optax on the program's model in
    float32, three steps, the routers' update withheld in both; and the
    trainer in fp8, put in the program's place, is not correct."""
    import optax

    from horovod_tpu.models import lm_loss

    family, config, traffic = toy.family, toy.config, toy.traffic
    assert family.router_frozen(config)
    keys = cells.seed_keys(13, 2)
    run = functools.partial(family.reference_run, config, traffic, keys,
                            check.STEPS)
    reference, control = run(), run(precision="fp8")
    lines = []
    assert not check.verdict(check.compare(control, reference),
                             toy.limits(), lines.append)
    assert any("> limit" in x for x in lines)
    routers = [k for k in reference["update_norms"] if "router" in k]
    assert len(routers) == 2
    assert all(reference["update_norms"][k] == 0.0 for k in routers)
    assert all(reference["grad_norms"][k] > 0.0 for k in routers)

    (params,) = family.init_model_state(config, keys[0])
    pool = family.make_pool(config, traffic, keys[1])
    model = family.build(config).clone(dtype=jnp.float32)
    opt = family.optimizer(config)
    state, seeded, losses = opt.init(params), params, []
    with jax.default_matmul_precision("highest"):
        for i in range(check.STEPS):
            loss, grad = jax.jit(jax.value_and_grad(lambda p, t: lm_loss(
                model.apply({"params": p}, t), t)))(params, pool[i][0])
            updates, state = opt.update(grad, state, params)
            params = optax.apply_updates(params, updates)
            losses.append(float(loss))
    program = {"losses": losses, "grad_norms": reference["grad_norms"],
               "update_norms": numerics.difference_norms(params, seeded)}
    gaps = check.compare(program, reference)
    assert gaps["loss"][0] < 1e-5
    assert gaps["update"][0] < 1e-3


def test_seeded_tree_has_the_layout_of_the_programs_model(real, toy):
    """At the published widths, from shapes alone; 602.4 M parameters."""
    for cell, leaves in ((real, 109), (toy, 61)):
        family, config = cell.family, cell.config
        want = jax.eval_shape(
            family.build(config).clone(attention="dense",
                                       kda="recurrent").init,
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
        (got,) = jax.eval_shape(
            functools.partial(family.init_model_state, config),
            jax.random.PRNGKey(0))
        assert _shapes(got) == _shapes(want)
        assert len(jax.tree_util.tree_leaves(got)) == leaves
    (tree,) = jax.eval_shape(functools.partial(
        real.family.init_model_state, real.config), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == 602_433_408
    assert 16 * count / 1e9 == pytest.approx(9.64, abs=0.01)
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in {**tree["block_3"], **tree["block_0"]}.items()}
    assert sizes["kda"] == 39_514_272 and sizes["mla"] == 29_114_880
    assert sizes["mlp"] == 63_700_992
    assert sizes["moe"] == 8 * 7_077_888 + 7_077_888 + 589_824


# -- shape functions against totals worked by hand --------------------------


def test_flops_per_sample_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    d, t, wide = 2304, 16384, 32 * 128
    # q, k, v, out; the decay's and the gate's two factors; beta; the taps
    kda = 4 * d * wide + 2 * (d * 128 + 128 * wide) + d * 32 + 3 * 4 * wide
    mla = d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256 + 32 * 128 * d
    assert (kda, mla) == (39_510_016, 29_114_368)
    dense = 3 * d * 9216
    # router, the shared expert, and 8 * 8 / 256 = a quarter of a routed one
    sparse = d * 256 + 3 * d * 1024 + 3 * d * 1024 // 4
    head = d * 20480
    by_hand = 4 * kda + mla + dense + 4 * sparse + head
    assert by_hand == 335_790_080
    assert family.matmul_parameters(config) == by_hand
    causal = t * (t + 1) // 2
    assert causal == 134_225_920
    attention = 3 * 2 * (192 + 128) * 32 * causal
    recurrence = 4 * 3 * 7 * 128 * 128 * 32 * t
    assert family.flops_per_sample(config, traffic) \
        == 6.0 * by_hand * t + attention + recurrence
    assert family.flops_per_sample(config, traffic) / 1e12 \
        == pytest.approx(41.98, abs=0.01)


def test_kernel_work_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    work = family.kernel_work(config, traffic, 1)
    assert set(work) == {"kda", "flash_mla"}
    t, causal = 16384, 134_225_920
    assert work["kda"]["flops"] == 4 * 3 * 7 * 128 * 128 * 32 * t
    # a token and head: q, k, v, o at 2 B, g at 4 B, beta; forward once,
    # backward the same read (dO for o) and all but o's size written
    one_way = t * 32 * (4 * 128 * 2 + 128 * 4 + 4)
    assert work["kda"]["bytes"] == 4 * (3 * one_way - t * 32 * 128 * 2)
    assert work["flash_mla"]["flops"] \
        == (2 * (192 + 128) + 2 * (3 * 192 + 2 * 128)) * 32 * causal
    assert work["flash_mla"]["bytes"] == 6 * t * 32 * (192 + 128) * 2
    # each block recomputed: the forward kernels twice a layer
    assert (work["kda"]["calls"], work["flash_mla"]["calls"]) == (12, 4)
    peaks = cells.peaks_of("TPU v5 lite")
    bound = real.spec.reader("flash_roofline").bound
    assert bound(work["kda"], peaks) \
        == (pytest.approx(11.17e-3, rel=1e-3), "bytes")
    assert bound(work["flash_mla"], peaks) \
        == (pytest.approx(50.23e-3, rel=1e-3), "flops")
    no_remat = family.kernel_work(dict(config, remat=False), traffic, 1)
    assert (no_remat["kda"]["calls"], no_remat["flash_mla"]["calls"]) \
        == (8, 3)


# -- the files --------------------------------------------------------------


def test_the_configuration_keeps_every_published_number(real):
    """Against the catalog beside the ``model-configs`` guide where it is
    installed; the cut and the deployment either way."""
    config = real.config
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["kv_lora_rank"]) \
        == (2304, 9216, 1024, 512)
    assert (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"]) == (128, 64, 128)
    linear = config["linear_attn_config"]
    assert (linear["num_heads"], linear["head_dim"],
            linear["short_conv_kernel_size"]) == (32, 128, 4)
    assert (config["num_experts_per_token"], config["num_shared_experts"],
            config["routed_scaling_factor"]) == (8, 1, 2.446)
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 32
    assert deployment["num_experts"] == 32 * config["num_experts"] == 256
    assert deployment["vocab_size"] == 8 * config["vocab_size"] == 163840
    assert deployment["num_hidden_layers"] == 27
    assert real.family.layers(config) == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    assert real.family.held(config) == (0, 8)
    assert {"kda_low_rank", "kda_decay_init", "kda_conv", "mla", "router",
            "initializer"} <= set(config["assumed"])
    assert deployment["router_update"].startswith("frozen")
    assert real.family.router_frozen(config)
    assert config["remat"] is True
    assert (config["attention"], config["kda"]) == ("flash", "chunked")
    assert config["precision"]["compute"] == "bfloat16"
    assert config["precision"]["kda_decay_and_state"].startswith("float32")
    (entry,) = [c for c in real.spec.data["configs"]
                if c["name"] == "kimi-linear-48b-a3b"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not installed here")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key


def test_the_cell_and_its_metrics(real):
    assert (real.chips, real.per_chip_batch, real.traffic["pool"]) \
        == (1, 1, 8)
    assert real.traffic["sample_shape"] == [16384]
    assert real.traffic["loop"] == "closed"
    assert real.traffic["steps_per_timing_sample"] == 1
    names = {m["name"] for m in real.per_layer}
    assert set(NEW) <= names
    # the expert layer's metrics list Laguna's cell alone (PERF.md, Open
    # questions: appending this cell is a benchmark PR's edit)
    assert not {"flash_ms", "flash_win_ms", "flash_full_ms", "moe_ms",
                "allreduce_ms"} & names
    for other in ("gpt2m_1chip", "laguna_xs2_8k_1chip"):
        assert not set(NEW) & {m["name"]
                               for m in real.spec.cell(other).per_layer}
    layers = {m["layer"] for m in real.spec.data["per_layer"]
              if m["name"] not in NEW}
    for m in real.spec.data["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "samples_per_s_per_chip"
            assert m["layer"] == ("models" if m["name"] == "kda_conv_ms"
                                  else "kernels")
            assert m["layer"] in layers     # a name that was there
    assert set(real.limits()) >= set(check.COMPARED)


def test_readers_on_a_reduced_trace(real):
    """A step's events under the names the compiled step gives them."""
    spec = real.spec

    def line(name, kind, op_name):
        call = 'custom-call(%a), custom_call_target="tpu_custom_call"' \
            if kind == "kernel" else "fusion(%a), kind=kLoop"
        return (f"  %{name} = bf16[2]{{0}} {call}, metadata={{op_name="
                f'"jit(train_step)/{op_name}"}}')

    mixer = "block_1/kda/hvd.kda"
    hlo = "\n".join([
        "ENTRY %main {",
        line("kda_fwd.1", "kernel", f"hvd.loss/{mixer}/hvd.kda.scan/kda_fwd"),
        line("kda_bwd", "kernel",
             f"transpose(jvp(hvd.loss))/{mixer}/hvd.kda.scan/kda_bwd"),
        line("fusion.3", "fusion",
             f"hvd.loss/checkpoint/{mixer}/hvd.kda.scan/while/body/exp"),
        line("fusion.4", "fusion", f"hvd.loss/{mixer}/hvd.kda.conv/mul"),
        # a loop as the trace shows it: one event over its body's (fusion.3)
        "  %while.6 = (s32[], bf16[2]{0}) while(%t), condition=%c, body=%b, "
        f'metadata={{op_name="jit(train_step)/hvd.loss/{mixer}/hvd.kda.scan/'
        'while"}',
        line("fusion.5", "fusion", f"hvd.loss/{mixer}/out/dot_general"),
        line("flash_mla_fwd.2", "kernel",
             "hvd.loss/block_3/mla/hvd.mla/hvd.mla.attn/flash_mla_fwd"),
        line("flash_mla_bwd_dkv", "kernel",
             "transpose(jvp(hvd.loss))/block_3/mla/hvd.mla/hvd.mla.attn/x"),
        line("flash_fwd.7", "kernel", "hvd.loss/block_0/attn/flash_fwd"),
        "}"])
    device = {"steps": 2, "busy_s": 0.4, "op_seconds": {
        "kda_fwd.1": 0.010, "kda_bwd": 0.020, "fusion.3": 0.010,
        "fusion.4": 0.006, "fusion.5": 0.1, "while.6": 0.012,
        "flash_mla_fwd.2": 0.030,
        "flash_mla_bwd_dkv": 0.050, "flash_fwd.7": 0.2}}
    peaks = cells.peaks_of("TPU v5 lite")
    work = {"kda": {"flops": 1.0, "bytes": 819e9 * 0.005},
            "flash_mla": {"flops": 197e12 * 0.010, "bytes": 1.0}}
    run = {"cell": real, "trace": {"devices": [device]}, "hlo": hlo,
           "kernel_work": work, "peaks": peaks}
    read = lambda name: spec.reader(name).read(run)  # noqa: E731
    assert read("kda_ms") == pytest.approx(20.0)   # kernels and what feeds
    assert read("kda_roofline") == pytest.approx(25.0)
    assert read("kda_conv_ms") == pytest.approx(3.0)
    assert read("flash_mla_ms") == pytest.approx(40.0)
    assert read("flash_mla_roofline") == pytest.approx(25.0)
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
                    "toy_kimi_1dev", "--seed", str(2**31 + 23),
                    "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    line = last_line(proc)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["metrics"] == {} and line["rehearsal"] is True
    for number in ("loss", "first_gradient", "update"):
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
    steers the flash kernels off the interpreter, and the delta rule's and
    the experts' kernels follow the platform the step is lowered for."""
    import re

    from chipbench import aot

    compiled = aot.compile_cell(real, topo.devices)
    held = aot.device_bytes(compiled)
    hbm = cells.peaks_of("TPU v5 lite")["hbm_bytes"]
    # room for the 2.41 GB seeded copy that ``correct`` makes
    assert 0.25 * hbm < held["total"] < hbm - 4 * 602_433_408, held
    hlo = compiled.as_text()
    named = re.findall(r"%([\w\-]+?)(?:\.\d+)* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    work = real.family.kernel_work(real.config, real.traffic, 1)
    assert sum(n.startswith("kda_") for n in named) == work["kda"]["calls"]
    assert sum(n.startswith("flash_mla") for n in named) \
        == work["flash_mla"]["calls"]
    assert set(named) == {
        "kda_fwd", "kda_bwd", "flash_mla_fwd", "flash_mla_bwd_dq",
        "flash_mla_bwd_dkv", "expert_matmul_fwd", "expert_matmul_bwd_dx",
        "expert_matmul_bwd_dw"}, set(named)
    for scope in ("hvd.kda.scan", "hvd.kda.conv", "hvd.mla.attn",
                  "hvd.moe.experts"):
        assert scope in hlo, scope
