"""Family ``sdar``: the plain reference (the three-part mask from its
definition, a softmax router over held experts, the weighted loss at the
masked positions) against the program at a toy size on the CPU — 4 of 16
experts held, the kernels interpreted, a tile run whole, each half of a
block recomputed —, the program's step through ``data_parallel_step``
against the reference trainer, the pool's noise against the program's
``block_diffusion_noise``, the shape functions against totals worked by
hand, the configuration file against the catalog's reading of the published
config, the readers on a reduced trace, and the rehearsal of a toy cell
through the run command. The toy benchmark file is this family's own
(``tests/chipbench/sdar_toy``).

The real cell's step compiles for a described v5e in the ``slow`` test at
the end (only one process at a time may hold the TPU compiler: the topology
is described inside a fixture; two to three minutes of compiling)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import cell as cells
from chipbench import check, numerics

from test_chipbench_run_cpu import last_line, run_cell

TOY = os.path.join("tests", "chipbench", "sdar_toy", "BENCHMARK.json")
CELL = "sdar_moe_8k_1chip"
NEW = ("flash_bd_ms", "flash_bd_roofline", "flash_bd_fwd_ms",
       "flash_bd_dq_ms", "flash_bd_dkv_ms")
REDUCED = ["num_hidden_layers", "num_experts", "vocab_size"]


@pytest.fixture(scope="module")
def toy():
    return cells.Spec(os.path.join(cells.ROOT, TOY)).cell("toy_sdar_1dev")


@pytest.fixture(scope="module")
def real():
    return cells.Spec().cell(CELL)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


# -- program against reference ----------------------------------------------


def test_reference_against_the_program_in_float32(toy):
    """Loss and every gradient leaf on one seeded batch of 2 x 64 clean
    tokens with their noise: the ``flash_bd_*`` kernels (interpreted; the
    rows ``[clean ; noisy]``) against dense attention under the mask from
    its definition (the rows ``[noisy ; clean]``), q/k norms and rotation,
    the softmax router's held experts, the last layer's clean half left
    out against every row computed, the blocked weighted loss against whole
    logits."""
    family, config, traffic = toy.family, toy.config, toy.traffic
    keys = cells.seed_keys(11, 2)
    (params,) = family.init_model_state(config, keys[0])
    batch = family.make_pool(config, traffic, keys[1])[0]
    model = family.build(config).clone(dtype=jnp.float32)
    assert (model.attention, model.remat, model.block_length) \
        == ("flash", True, 4)
    with jax.default_matmul_precision("highest"):
        ref_loss, ref_grad = jax.jit(jax.value_and_grad(functools.partial(
            family.reference_loss, config=config)))(params, *batch)
        loss, grad = jax.jit(jax.value_and_grad(lambda p: model.apply(
            {"params": p}, *batch[:2], weights=batch[2])))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    # embedding; a block's two norms, six attention leaves, router and
    # three expert tensors; final norm and head
    assert len(ref) == 1 + 2 * (2 + 6 + 4) + 1 + 1
    assert min(ref.values()) > 0
    # tolerance: float32 summation order; bfloat16 would read 0.02
    assert max(err[k] / ref[k] for k in ref) < 1e-4
    assert check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0] < 1e-5
    source = open(family.__file__, encoding="utf-8").read()
    assert "horovod_tpu" not in source.split("def build")[0]
    assert source.count("from horovod_tpu") == 1      # in build alone
    assert source.count("from benchmarks") == 1       # in make_step alone


def test_three_steps_through_the_data_parallel_step(toy):
    """The program's step as the benchmark builds it — ``make_step``:
    ``make_bd_train_step`` over ``data_parallel_step`` with
    ``hvd.DistributedOptimizer``, three data arrays a batch — in float32,
    three steps from the seed, against the reference trainer's (its own
    AdamW, the routers' update withheld, the moments on the host)."""
    import horovod_tpu as hvd

    family, config, traffic = toy.family, toy.config, toy.traffic
    assert family.router_frozen(config)
    keys = cells.seed_keys(13, 2)
    reference = family.reference_run(config, traffic, keys, check.STEPS)

    (params,) = family.init_model_state(config, keys[0])
    pool = family.make_pool(config, traffic, keys[1])
    # the kernels stand against the reference in the test above; here the
    # step's wiring does, with the attention written out (half the compile)
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
    assert gaps["loss"][0] < 1e-5
    assert gaps["first_gradient"][0] < 1e-4
    assert gaps["update"][0] < 1e-3
    routers = [k for k in program["update_norms"] if "router" in k]
    assert len(routers) == 2
    assert all(program["update_norms"][k] == 0.0 for k in routers)


def test_the_pool_draws_the_noise_the_program_documents(real):
    """``make_pool``'s own draw and ``models.sdar.block_diffusion_noise``
    are one law: the same keys give the same arrays; clean ids never draw
    the mask token; a batch is three arrays split alike."""
    from horovod_tpu.models.sdar import block_diffusion_noise

    family, config = real.family, real.config
    traffic = dict(real.traffic, sample_shape=[512], pool=2)
    pool = family.make_pool(config, traffic, jax.random.PRNGKey(3))
    assert len(pool) == 2 and len(family.data_spec("data")) == 3
    clean, noisy, weights = pool[1]
    assert clean.shape == noisy.shape == weights.shape == (1, 512)
    assert (clean.dtype, noisy.dtype, weights.dtype) \
        == (jnp.int32, jnp.int32, jnp.float32)
    assert int(clean.max()) < config["mask_token_id"] == 18991
    assert not bool(jnp.array_equal(pool[0][0], clean))
    key = jax.random.split(jax.random.split(jax.random.PRNGKey(3), 2)[1])[1]
    want = block_diffusion_noise(key, clean, config["block_length"],
                                 config["mask_token_id"],
                                 config["noise_eps"])
    np.testing.assert_array_equal(noisy, want[0])
    np.testing.assert_allclose(weights, want[1], rtol=1e-6)
    assert 0.3 < float((weights > 0).mean()) < 0.7


def test_seeded_tree_has_the_layout_of_the_programs_model(real, toy):
    """At the published widths, from shapes alone; 645.6 M parameters."""
    tokens = jnp.zeros((1, 16), jnp.int32)
    for cell, leaves in ((real, 75), (toy, 27)):
        family, config = cell.family, cell.config
        want = jax.eval_shape(
            family.build(config).clone(attention="dense").init,
            jax.random.PRNGKey(0), tokens, tokens)["params"]
        (got,) = jax.eval_shape(
            functools.partial(family.init_model_state, config),
            jax.random.PRNGKey(0))
        assert _shapes(got) == _shapes(want)
        assert len(jax.tree_util.tree_leaves(got)) == leaves
    (tree,) = jax.eval_shape(functools.partial(
        real.family.init_model_state, real.config), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == 645_623_296 == 6 * 94_638_336 + 77_793_280
    assert 16 * count / 1e9 == pytest.approx(10.33, abs=0.01)
    assert "645,623,296" in real.config["deployment"]["parameters_here"]
    sizes = {k: sum(x.size for x in jax.tree_util.tree_leaves(v))
             for k, v in tree["block_5"].items()}
    assert sizes["attn"] + sizes["ln_attn"] + sizes["ln_mlp"] \
        + 2048 * 128 == 19_140_864
    assert sizes["moe"] == 2048 * 128 + 16 * 4_718_592
    assert tree["block_0"]["moe"]["router"]["kernel"].shape == (2048, 128)
    assert tree["tok_embed"]["embedding"].shape == (18992, 2048)


def test_the_mask_token_is_placed_where_the_deployment_says(toy):
    """Of the experts the mask token uses in a layer one is held, the first,
    and no other held one: its logits over the held experts are 4 and zeros
    before normalisation, in every layer; the other rows are as drawn
    (unit-variance embeddings, residual writes scaled by the published
    depth)."""
    family, config = toy.family, toy.config
    (params,) = family.init_model_state(config, jax.random.PRNGKey(5))
    first, count = family.held(config)
    row = params["tok_embed"]["embedding"][config["mask_token_id"]]
    for i in range(config["num_hidden_layers"]):
        logits = row @ params[f"block_{i}"]["moe"]["router"]["kernel"]
        np.testing.assert_allclose(
            logits[first:first + count], [4.0] + [0.0] * (count - 1),
            atol=1e-4)
        chosen = jax.lax.top_k(logits, config["num_experts_per_tok"])[1]
        assert sum(first <= int(e) < first + count for e in chosen) == 1
    table = params["tok_embed"]["embedding"]
    assert float(jnp.std(table[:-1])) == pytest.approx(1.0, abs=0.02)
    # the move is small at the published width (router columns of norm 0.9
    # against a row of norm 45: 3 %), large at the toy's 64
    assert 0.9 < float(jnp.std(row)) < 6.0
    out = params["block_0"]["attn"]["out"]["kernel"]
    assert float(jnp.std(out)) == pytest.approx(0.02 / 2.0, rel=0.1)
    assert "mask_token_experts_here" in config["deployment"] \
        or "all" in config["assumed"]


# -- shape functions against totals worked by hand --------------------------


def test_flops_per_sample_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    d, seq = 2048, 8192
    attention = d * 4096 + 2 * d * 512 + 4096 * d
    kv = 2 * d * 512
    router, expert = d * 128, 3 * d * 768
    assert (attention, kv, router, expert) \
        == (18_874_368, 2_097_152, 262_144, 4_718_592)
    # an expected 8 * 16 / 128 = 1 held expert a row
    layer = attention + router + expert
    # layers 0-4 take 2 L rows; layer 5 its noisy half whole and the clean
    # half's K/V projections; the head reads L rows
    rows = 11 * seq * layer + seq * kv + seq * d * 18992
    pairs = seq * (seq + 4)
    assert family.needed_pairs(seq, 4) == pairs == 67_141_632
    mixing = 3 * 2 * 2 * 128 * 32 * 5.5 * pairs
    assert family.flops_per_sample(config, traffic) == 6.0 * rows + mixing
    assert 6.0 * (rows - seq * d * 18992) / 1e12 \
        == pytest.approx(13.00, abs=0.01)
    assert mixing / 1e12 == pytest.approx(18.15, abs=0.01)
    assert 6.0 * seq * d * 18992 / 1e12 == pytest.approx(1.91, abs=0.01)
    total = family.flops_per_sample(config, traffic)
    assert total / 1e12 == pytest.approx(33.06, abs=0.01)
    assert mixing / total == pytest.approx(0.55, abs=0.01)


def test_kernel_work_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    work = family.kernel_work(config, traffic, 1)
    assert set(work) == {"flash_bd", "expert_matmul"}
    seq, pairs = 8192, 8192 * 8196
    assert work["flash_bd"]["flops"] == 7 * 2 * 128 * 32 * 5.5 * pairs
    # q, o forward and q, o, dO, dQ backward over the querying rows (2 L; L
    # in the last layer), k, v and k, v, dK, dV over all 2 L rows of 4 heads
    assert work["flash_bd"]["bytes"] == 6 * 128 * 2 * (
        11 * seq * 32 + 6 * 2 * seq * 4)
    assert work["flash_bd"]["calls"] == 18
    rows = 11 * seq * 8 * 16 / 128
    assert work["expert_matmul"]["flops"] == 3 * 3 * 2 * 2048 * 768 * rows
    assert work["expert_matmul"]["bytes"] == 3 * 3 * 2 * (
        rows * (2048 + 768) + 6 * 16 * 2048 * 768)
    peaks = cells.peaks_of("TPU v5 lite")
    bound = real.spec.reader("flash_roofline").bound
    assert bound(work["flash_bd"], peaks) \
        == (pytest.approx(107.5e-3, rel=1e-3), "flops")


# -- the files --------------------------------------------------------------


def test_the_configuration_keeps_every_published_number(real):
    """Against the catalog beside the ``model-configs`` guide where it is
    installed; the cut and the deployment either way."""
    config = real.config
    assert config["reduced"] == REDUCED
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    assert (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["moe_intermediate_size"], config["num_experts_per_tok"]) \
        == (2048, 128, 32, 4, 768, 8)
    assert config["norm_topk_prob"] is True and config["rope_scaling"] is None
    assert config["rope_theta"] == 1000000
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["experts_held_first"] == 0
    assert deployment["num_experts"] == 8 * config["num_experts"] == 128
    assert deployment["vocab_size"] == 8 * config["vocab_size"] == 151936
    assert deployment["num_hidden_layers"] == 48
    assert deployment["router_update"].startswith("frozen")
    assert real.family.router_frozen(config)
    model = real.family.build(config)
    assert (model.num_experts, model.experts_held, model.experts_per_token,
            model.num_layers, model.block_length, model.remat) \
        == (128, (0, 16), 8, 6, 4, True)
    assert {"block_length", "noise_schedule", "objective", "qk_norm",
            "rotation", "router", "mask_token", "initializer",
            "learning_rate", "dropout"} <= set(config["assumed"])
    assert (config["block_length"], config["mask_token_id"],
            config["noise_eps"]) == (4, 18991, 0.001)
    laguna = real.spec.config("laguna-xs2")
    # AdamW as laguna-xs2's but for a conversion's learning rate, under which
    # the seeded weights stay the state the cell describes (assumed)
    assert config["optimizer"] == dict(laguna["optimizer"],
                                       learning_rate=1e-5)
    assert config["precision"] == laguna["precision"]
    (entry,) = [c for c in real.spec.data["configs"]
                if c["name"] == "sdar-30b-a3b"]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not installed here")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
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
    assert not {"mixer_ms", "moe_ms", "embed_ms", "expert_matmul_ms",
                "flash_ms", "flash_full_ms", "allreduce_ms"} & names
    for other in ("gpt2m_1chip", "laguna_xs2_8k_1chip",
                  "olmo_hybrid_8k_1chip"):
        assert not set(NEW) & {m["name"]
                               for m in real.spec.cell(other).per_layer}
    for m in real.spec.data["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "samples_per_s_per_chip"
            assert m["layer"] == "kernels"
            assert m["unit"] == ("%" if "roofline" in m["name"] else "ms")
    assert set(real.limits()) >= set(check.COMPARED) | set(check.OPTIONAL)
    # no count of cells and no "mine is the last": a later PR adds one (as
    # this one broke ``test_chipbench_olmo.py``'s pin of six, which is the
    # benchmark's to re-pin: PERF.md section 7)
    (mine,) = [w for w in real.spec.data["workloads"] if w["name"] == CELL]
    assert mine["chips"] == 1 and len(mine["why"]) <= 200


def test_readers_on_a_reduced_trace(real):
    """A step's events under the names the compiled step gives them."""
    spec = real.spec

    def line(name, op_name):
        return (f"  %{name} = bf16[2]{{0}} custom-call(%a), "
                'custom_call_target="tpu_custom_call", metadata={op_name='
                f'"jit(train_step)/{op_name}"}}')

    mixer = "block_1/hvd.mixer/attn/hvd.bd/hvd.bd.attn"
    hlo = "\n".join([
        "ENTRY %main {",
        line("flash_bd_fwd.1", f"hvd.loss/{mixer}/flash_bd_fwd"),
        line("flash_bd_fwd.2", f"hvd.loss/block_2/{mixer}/flash_bd_fwd"),
        line("flash_bd_bwd_dq", f"transpose(jvp(hvd.loss))/{mixer}/x"),
        line("flash_bd_bwd_dkv.3", f"transpose(jvp(hvd.loss))/{mixer}/y"),
        line("flash_fwd.7", "hvd.loss/block_0/attn/x"),
        line("flash_win_fwd.8", "hvd.loss/block_0/attn/x"),
        "}"])
    device = {"steps": 2, "busy_s": 0.4, "op_seconds": {
        "flash_bd_fwd.1": 0.010, "flash_bd_fwd.2": 0.014,
        "flash_bd_bwd_dq": 0.020, "flash_bd_bwd_dkv.3": 0.036,
        "flash_fwd.7": 0.2, "flash_win_fwd.8": 0.3}}
    peaks = cells.peaks_of("TPU v5 lite")
    work = {"flash_bd": {"flops": 197e12 * 0.010, "bytes": 1.0}}
    run = {"cell": real, "trace": {"devices": [device]}, "hlo": hlo,
           "kernel_work": work, "peaks": peaks}
    read = lambda name: spec.reader(name).read(run)  # noqa: E731
    assert read("flash_bd_fwd_ms") == pytest.approx(12.0)
    assert read("flash_bd_dq_ms") == pytest.approx(10.0)
    assert read("flash_bd_dkv_ms") == pytest.approx(18.0)
    assert read("flash_bd_ms") == pytest.approx(40.0)
    assert read("flash_bd_roofline") == pytest.approx(25.0)
    # a program without the kernels or a trace (the parent commit):
    # nothing, no raise
    bare = dict(run, hlo="ENTRY %main {\n  %fusion.5 = bf16[2]{0} "
                "fusion(%a), kind=kLoop\n}")
    for name in NEW:
        assert spec.reader(name).read(bare) is None, name
        assert spec.reader(name).read(dict(run, trace=None)) is None, name
    assert spec.reader("flash_bd_roofline").read(
        dict(run, kernel_work={})) is None


def test_rehearsal_of_the_toy_cell(tmp_path):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload",
                    "toy_sdar_1dev", "--seed", str(2**31 + 23),
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
    steers the flash kernels off the interpreter, the grouped products
    follow the platform the step is lowered for. ``slow``: it compiles the
    real step for the v5e."""
    import re

    from horovod_tpu import obs

    from chipbench import aot

    compiled = aot.compile_cell(real, topo.devices)
    held = aot.device_bytes(compiled)
    hbm = cells.peaks_of("TPU v5 lite")["hbm_bytes"]
    # room for the 2.58 GB seeded copy that ``correct`` makes
    assert 0.25 * hbm < held["total"] < hbm - 4 * 645_623_296 - 0.6e9, held
    hlo = compiled.as_text()
    named = re.findall(r"%([\w\-]+?)(?:\.\d+)* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    work = real.family.kernel_work(real.config, real.traffic, 1)
    assert sum(n.startswith("flash_bd_") for n in named) \
        == work["flash_bd"]["calls"] == 18
    assert {n for n in named if n.startswith("flash")} == {
        "flash_bd_fwd", "flash_bd_bwd_dq", "flash_bd_bwd_dkv"}
    assert {n for n in named if n.startswith("expert")} == {
        "expert_matmul_fwd", "expert_matmul_bwd_dx", "expert_matmul_bwd_dw"}
    for scope in ("hvd.bd/", "hvd.bd.attn", "hvd.mixer.proj", "hvd.norm",
                  "hvd.moe.route", "hvd.moe.experts", "hvd.head",
                  "hvd.embed"):
        assert scope in hlo, scope
    # every recomputed attention half keeps its kernel's outputs: no
    # ``flash_bd_fwd`` beyond one a layer
    assert obs.kda.record_scan_program(CELL, hlo)[3] == 0
