"""Family ``laguna``: the plain reference against the program at a toy size
on the CPU (2 layer periods, 16 experts, 4 held), the shape functions
against totals worked by hand, the configuration file against the catalog's
reading of the published config, the readers on a reduced trace, and the
rehearsal of a toy cell through the run command. The toy benchmark file is
this family's own (``tests/chipbench/laguna_toy``).

The real cell's step and the two 8k kernel calls compile for a described
v5e in the ``slow`` tests at the end (only one process at a time may hold
the TPU compiler: the topology is described inside a fixture)."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import pytest

from chipbench import cell as cells
from chipbench import check, numerics

from test_chipbench_run_cpu import last_line, run_cell

TOY = os.path.join("tests", "chipbench", "laguna_toy", "BENCHMARK.json")
CELL = "laguna_xs2_8k_1chip"


@pytest.fixture(scope="module")
def toy():
    return cells.Spec(os.path.join(cells.ROOT, TOY)).cell("toy_laguna_1dev")


@pytest.fixture(scope="module")
def real():
    return cells.Spec().cell(CELL)


def _shapes(tree):
    return jax.tree_util.tree_map(lambda x: (x.shape, x.dtype), tree)


# -- program against reference ----------------------------------------------


def _gradients(toy, config, dtypes):
    """``{dtype: ((loss, grad) of program, reference, fp8 control)}`` on one
    seeded batch."""
    from horovod_tpu.models import lm_loss

    family, traffic = toy.family, toy.traffic
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
    for dtype in dtypes:
        model = family.build(config).clone(dtype=dtype)
        assert model.attention == "flash"  # interpreted on the CPU backend
        out[dtype] = (jax.jit(jax.value_and_grad(lambda p: lm_loss(
            model.apply({"params": p}, tokens), tokens)))(params),
            reference, control)
    return out


@pytest.fixture(scope="module")
def gradients(toy):
    """Program and reference form the router's gradient too."""
    return _gradients(toy, toy.config, (jnp.float32, jnp.bfloat16))


def test_reference_against_the_program_in_float32(gradients):
    """Loss and every gradient leaf: rotary (plain and YaRN), grouped heads,
    the window, the gate, routing over 16 with 4 held (the router's own
    gradient through the selected weights), the shared expert."""
    (loss, grad), (ref_loss, ref_grad), _ = gradients[jnp.float32]
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-6)
    ref = numerics.leaf_norms(ref_grad)
    err = numerics.difference_norms(grad, ref_grad)
    assert len(ref) == 1 + 8 * 7 + 3 + 7 * 7 + 1 + 1
    assert min(ref.values()) > 0
    # tolerance: float32 summation order; bfloat16 would read 0.25
    assert max(err[k] / ref[k] for k in ref) < 1e-4
    assert check.worst_leaf_gap(numerics.leaf_norms(grad), ref)[0] < 1e-5


def test_program_in_bfloat16_holds_and_the_fp8_control_fails(toy, gradients):
    """The stated precision stays inside the toy limit, the next one below
    does not: a bfloat16-for-float32 swap fails the float32 tolerance
    above, an fp8-for-bfloat16 swap this one."""
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


def test_the_cells_router_update_is_withheld(toy, gradients):
    """The routers' gradient is formed (and reaches AdamW's moments, from
    which ``first_gradient`` reads it), their update is zero, in the
    program's optimizer and in the reference's alike; every other leaf
    moves."""
    import optax

    family, config = toy.family, toy.config
    assert family.router_frozen(config)
    assert not family.router_frozen(dict(config, num_experts=16))
    (_, grad), _, _ = gradients[jnp.float32]
    (params,) = family.init_model_state(config, cells.seed_keys(11, 2)[0])
    opt = family.optimizer(config)
    updates, state = opt.update(grad, opt.init(params), params)
    moments = numerics.leaf_norms(state[0][0].mu)
    moved = numerics.leaf_norms(updates)
    routers = [k for k in moved if "router" in k]
    assert len(routers) == 7
    assert all(moved[k] == 0.0 and moments[k] > 0.0 for k in routers)
    assert all(moved[k] > 0.0 for k in moved if k not in routers)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, grad)
    after, mu, _ = family._adamw(params, grad, zeros, zeros, 1.0,
                                 config["optimizer"], frozen=True)
    reference = numerics.difference_norms(after, params)
    program = numerics.difference_norms(
        optax.apply_updates(params, updates), params)
    assert all(reference[k] == 0.0 for k in routers)
    assert numerics.leaf_norms(mu) == pytest.approx(moments, rel=1e-6)
    assert check.worst_leaf_gap(program, reference)[0] < 1e-5


def test_three_adamw_steps_and_the_control_through_them(toy):
    """The reference trainer against optax on the program's model in
    float32, three steps; and the trainer in fp8, put in the program's
    place, is not correct."""
    import optax

    from horovod_tpu.models import lm_loss

    family, config, traffic = toy.family, toy.config, toy.traffic
    keys = cells.seed_keys(13, 2)
    run = functools.partial(family.reference_run, config, traffic, keys,
                            check.STEPS)
    reference, control = run(), run(precision="fp8")
    lines = []
    assert not check.verdict(check.compare(control, reference),
                             toy.limits(), lines.append)
    assert any("> limit" in x for x in lines)

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
    """At the published widths, from shapes alone; 691.6 M parameters."""
    for cell, leaves in ((real, 1 + 10 + 4 * 14 + 2), (toy, 1 + 10 + 7 * 14 + 2)):
        family, config = cell.family, cell.config
        want = jax.eval_shape(
            family.build(config).clone(attention="dense").init,
            jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))["params"]
        (got,) = jax.eval_shape(
            functools.partial(family.init_model_state, config),
            jax.random.PRNGKey(0))
        assert _shapes(got) == _shapes(want)
        assert len(jax.tree_util.tree_leaves(got)) == leaves
    (tree,) = jax.eval_shape(functools.partial(
        real.family.init_model_state, real.config), jax.random.PRNGKey(0))
    count = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    assert count == 691_623_936
    assert 16 * count / 1e9 == pytest.approx(11.07, abs=0.01)


# -- shape functions against totals worked by hand --------------------------


def test_yarn_inv_freq_of_the_reference_against_hand_worked_values(real):
    rope = real.config["rope_parameters"]["full_attention"]
    freq = real.family.yarn_inv_freq(rope, 64)
    # low 5, high 16 (test_laguna_model works them out); i = 10: ramp 5/11
    assert float(freq[10]) == pytest.approx(0.0090329 + 0.0001176, rel=1e-4)
    assert float(freq[3]) == pytest.approx(5e5 ** (-3 / 32), rel=1e-5)
    assert float(freq[20]) == pytest.approx(5e5 ** (-20 / 32) / 64, rel=1e-5)
    plain = real.family.yarn_inv_freq(
        real.config["rope_parameters"]["sliding_attention"], 128)
    assert float(plain[1]) == pytest.approx(10000 ** (-1 / 64), rel=1e-6)


def test_flops_per_sample_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    d, t = 2048, 8192
    full = 2 * d * 48 * 128 + 2 * d * 8 * 128 + d * 48      # q, out, k, v, g
    sliding = 2 * d * 64 * 128 + 2 * d * 8 * 128 + d * 64
    assert (full, sliding) == (29_458_432, 37_879_808)
    dense = 3 * d * 8192
    # router, the shared expert, and 8 * 32 / 256 = one expected routed one
    sparse = d * 256 + 3 * d * 512 + 3 * d * 512
    head = d * 12544
    by_hand = 2 * full + 3 * sliding + dense + 4 * sparse + head
    assert by_hand == 275_841_024
    assert family.matmul_parameters(config) == by_hand
    causal = t * (t + 1) // 2
    window = 512 * 513 // 2 + (t - 512) * 512
    assert (causal, window) == (33_558_528, 4_063_488)
    assert family.visible_pairs(t) == causal
    assert family.visible_pairs(t, 512) == window
    assert family.visible_pairs(256, 512) == 256 * 257 // 2
    attention = 12 * 128 * (2 * 48 * causal + 3 * 64 * window)
    assert family.flops_per_sample(config, traffic) \
        == 6.0 * by_hand * t + attention
    assert family.flops_per_sample(config, traffic) / t / 1e9 \
        == pytest.approx(2.405, abs=1e-3)


def test_kernel_work_against_totals_worked_by_hand(real):
    family, config, traffic = real.family, real.config, real.traffic
    work = family.kernel_work(config, traffic, 2)
    assert set(work) == {"flash_win", "flash_full", "expert_matmul"}
    causal, window = 33_558_528, 4_063_488
    assert work["flash_full"]["flops"] == 2 * 7 * 2 * 128 * 48 * 2 * causal
    assert work["flash_win"]["flops"] == 3 * 7 * 2 * 128 * 64 * 2 * window
    # 6 tensors at the query heads' width, 6 at the 8 key/value heads'
    assert work["flash_full"]["bytes"] == 2 * 6 * 2 * 8192 * 56 * 128 * 2
    assert work["flash_win"]["bytes"] == 3 * 6 * 2 * 8192 * 72 * 128 * 2
    # each block recomputed: the forward kernel twice a layer
    assert (work["flash_full"]["calls"], work["flash_win"]["calls"]) == (8, 12)
    rows = 2 * 8192 * 8 * 32 // 256
    assert rows == 16384
    assert work["expert_matmul"]["flops"] \
        == 4 * 9 * 2 * 2048 * 512 * rows
    assert work["expert_matmul"]["bytes"] \
        == 4 * 9 * 2 * (rows * 2560 + 32 * 2048 * 512)
    peaks = cells.peaks_of("TPU v5 lite")
    bound = real.spec.reader("flash_roofline").bound
    assert bound(work["flash_full"], peaks)[1] == "flops"
    assert bound(work["flash_win"], peaks)[1] == "flops"
    assert bound(work["expert_matmul"], peaks) \
        == (pytest.approx(6.637e-3, rel=1e-3), "bytes")
    no_remat = family.kernel_work(dict(config, remat=False), traffic, 2)
    assert no_remat["flash_win"]["calls"] == 9


# -- the files --------------------------------------------------------------


def test_the_configuration_keeps_every_published_number(real):
    """Against the catalog beside the ``model-configs`` guide where it is
    installed; the cut and the deployment either way."""
    config = real.config
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 32, 12544)
    deployment = config["deployment"]
    assert deployment["chips_sharing_a_layer"] == 8
    assert deployment["num_experts"] == 8 * config["num_experts"] == 256
    assert deployment["vocab_size"] == 8 * config["vocab_size"] == 100352
    assert real.family.layers(config) == [
        ("full_attention", 48, "dense"), ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"),
        ("sliding_attention", 64, "sparse"), ("full_attention", 48, "sparse")]
    assert real.family.held(config) == (0, 32)
    assert {"gating", "router", "mlp", "qk_norm", "initializer"} <= set(
        config["assumed"])
    assert "router_training" not in config
    assert deployment["router_update"].startswith("frozen")
    assert real.family.router_frozen(config)
    assert config["remat"] is True and config["attention"] == "flash"
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(catalog):
        pytest.skip("the catalog is not installed here")
    with open(catalog, encoding="utf-8") as fh:
        rows = [json.loads(line) for line in fh if line.strip()]
    (row,) = [r for r in rows if r["name"] == "Laguna-XS.2"]
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value, key
        else:
            assert config[key] == value, key


def test_the_cell_and_its_metrics(real):
    assert (real.chips, real.per_chip_batch, real.traffic["pool"]) \
        == (1, 2, 8)
    assert real.traffic["sample_shape"] == [8192]
    assert real.traffic["steps_per_timing_sample"] == 1
    names = {m["name"] for m in real.per_layer}
    new = {"flash_win_ms", "flash_win_roofline", "flash_full_ms",
           "flash_full_roofline", "expert_matmul_ms",
           "expert_matmul_roofline", "moe_ms", "moe_route_ms",
           "moe_experts_ms", "moe_combine_ms"}
    assert new <= names
    assert not {"flash_ms", "flash_roofline", "flash_fwd_ms", "allreduce_ms"} \
        & names
    other = {m["name"] for m in real.spec.cell("gpt2m_1chip").per_layer}
    assert not new & other
    for m in real.spec.data["per_layer"]:
        if m["name"] in new:
            assert m["workloads"] == [CELL]
            assert m["moves"] == "samples_per_s_per_chip"
            assert m["layer"] == ("models" if m["name"].startswith("moe_")
                                  else "kernels")
    limits = real.limits()
    assert set(limits) >= set(check.COMPARED)


def test_readers_on_a_reduced_trace(real):
    """A step's events under the names the compiled step gives them."""
    spec = real.spec
    hlo = "\n".join([
        "ENTRY %main {",
        '  %flash_win_fwd.1 = bf16[2]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(train_step)/hvd.loss/block_1/attn/flash_win_fwd"}',
        '  %flash_win_bwd_dkv = bf16[2]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(train_step)/transpose(jvp(hvd.loss))/block_1/attn/x"}',
        '  %flash_fwd.3 = bf16[2]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(train_step)/hvd.loss/block_0/attn/flash_fwd"}',
        '  %expert_matmul_bwd_dw.7 = bf16[2]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(train_step)/hvd.loss/block_1/moe/hvd.moe/hvd.moe.experts/r"}',
        '  %fusion.9 = bf16[2]{0} fusion(%a), kind=kLoop, metadata={op_name='
        '"jit(train_step)/hvd.loss/block_1/moe/hvd.moe/hvd.moe.route/s"}',
        '  %fusion.10 = bf16[2]{0} fusion(%a), kind=kLoop, metadata={op_name='
        '"jit(train_step)/hvd.loss/block_1/attn/gate/dot_general"}',
        "}"])
    device = {"steps": 2, "busy_s": 0.2, "op_seconds": {
        "flash_win_fwd.1": 0.010, "flash_win_bwd_dkv": 0.006,
        "flash_fwd.3": 0.040, "expert_matmul_bwd_dw.7": 0.008,
        "fusion.9": 0.004,
        "fusion.10": 0.1}}
    peaks = cells.peaks_of("TPU v5 lite")
    work = {"flash_win": {"flops": 197e12 * 0.002, "bytes": 1.0},
            "flash_full": {"flops": 1.0, "bytes": 819e9 * 0.005},
            "expert_matmul": {"flops": 197e12 * 0.001, "bytes": 1.0}}
    run = {"cell": real, "trace": {"devices": [device]}, "hlo": hlo,
           "kernel_work": work, "peaks": peaks}
    read = lambda name: spec.reader(name).read(run)  # noqa: E731
    assert read("flash_win_ms") == pytest.approx(8.0)
    assert read("flash_win_roofline") == pytest.approx(25.0)
    assert read("flash_full_ms") == pytest.approx(20.0)
    assert read("flash_full_roofline") == pytest.approx(25.0)
    assert read("expert_matmul_ms") == pytest.approx(4.0)
    assert read("expert_matmul_roofline") == pytest.approx(25.0)
    assert read("moe_ms") == pytest.approx(6.0)   # the kernel and the fusion
    assert read("moe_experts_ms") == pytest.approx(4.0)
    assert read("moe_route_ms") == pytest.approx(2.0)
    assert read("moe_combine_ms") == 0.0   # the scope is there, nothing under
    # a program without the kernels, the scope or a trace: nothing, no raise
    bare = dict(run, hlo="ENTRY %main {\n  %fusion.10 = bf16[2]{0} "
                "fusion(%a), kind=kLoop\n}")
    for name in ("flash_win_ms", "flash_win_roofline", "flash_full_ms",
                 "flash_full_roofline", "expert_matmul_ms",
                 "expert_matmul_roofline", "moe_ms", "moe_route_ms",
                 "moe_experts_ms", "moe_combine_ms"):
        assert spec.reader(name).read(bare) is None, name
        assert spec.reader(name).read(dict(run, trace=None)) is None, name
        if name.endswith("_roofline"):
            assert spec.reader(name).read(dict(run, kernel_work={})) is None


def test_rehearsal_of_the_toy_cell(tmp_path):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload",
                    "toy_laguna_1dev", "--seed", str(2**31 + 17),
                    "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    line = last_line(proc)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["metrics"] == {} and line["rehearsal"] is True
    for number in ("loss", "first_gradient", "update"):
        assert f"correct: {number} gap" in proc.stdout
    assert "0 compilation(s) in the window" in proc.stdout


# -- compiles of the real shapes for a described v5e ------------------------


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
@pytest.mark.parametrize("heads,window,names", [
    (64, 512, ("flash_win_fwd", "flash_win_bwd_dq", "flash_win_bwd_dkv")),
    (48, None, ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"))])
def test_the_8k_grouped_kernels_compile_for_v5e(topo, no_compile_cache,
                                                heads, window, names):
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.pallas_attention import flash_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    q = jax.ShapeDtypeStruct((2, 8192, heads, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, 8192, 8, 128), jnp.bfloat16,
                              sharding=one_chip)
    hlo = jax.jit(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=True, window=window,
            interpret=False).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 3
    for name in names:
        assert f"%{name}" in hlo, name


@pytest.mark.slow
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_the_expert_kernels_compile_for_v5e(topo, no_compile_cache, dtype):
    """At the cell's shapes: a pass of 32,768 slots of 2048, 32 experts of
    width 512; forward, the rows' gradient and the weights' gradient; in
    float32 too, where a matrix is 4 MB."""
    from jax.sharding import SingleDeviceSharding

    from horovod_tpu.ops.grouped_matmul import ROW_TILE, grouped_matmul

    one_chip = SingleDeviceSharding(topo.devices[0])

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(rows, w_in, w_out, group, active):
        hidden = grouped_matmul(rows, w_in, group, active, interpret=False)
        return grouped_matmul(hidden, w_out, group, active,
                              interpret=False).astype(jnp.float32).sum()

    hlo = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        placed((32768, 2048), dtype), placed((32, 2048, 512), dtype),
        placed((32, 512, 2048), dtype),
        placed((32768 // ROW_TILE,), jnp.int32),
        placed((), jnp.int32)).compile().as_text()
    # the second product's own result is not needed for the gradients
    assert hlo.count('custom_call_target="tpu_custom_call"') == 5
    for name in ("expert_matmul_fwd", "expert_matmul_bwd_dx",
                 "expert_matmul_bwd_dw"):
        assert f"%{name}" in hlo, name


@pytest.mark.slow
def test_the_step_compiles_for_v5e_and_fits_the_chip(topo, no_compile_cache,
                                                     real):
    """As ``python3 -m chipbench.aot`` compiles it: ``aot.mosaic_kernels``
    steers the flash kernels off the interpreter, and the expert kernels
    follow the platform the step is lowered for."""
    import re

    from chipbench import aot

    compiled = aot.compile_cell(real, topo.devices)
    held = aot.device_bytes(compiled)
    hbm = cells.peaks_of("TPU v5 lite")["hbm_bytes"]
    # room for the 2.77 GB seeded copy that ``correct`` makes
    assert 0.25 * hbm < held["total"] < hbm - 4 * 691_623_936, held
    hlo = compiled.as_text()
    named = re.findall(r"%([\w\-]+?)(?:\.\d+)* = [^\n]*"
                       r'custom_call_target="tpu_custom_call"', hlo)
    work = real.family.kernel_work(real.config, real.traffic, 2)
    flash = [n for n in named if n.startswith("flash")]
    assert len(flash) == work["flash_win"]["calls"] \
        + work["flash_full"]["calls"]
    assert sum(n.startswith("flash_win") for n in flash) \
        == work["flash_win"]["calls"]
    assert set(named) == {
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "flash_win_fwd",
        "flash_win_bwd_dq", "flash_win_bwd_dkv", "expert_matmul_fwd",
        "expert_matmul_bwd_dx", "expert_matmul_bwd_dw"}, set(named)
    assert "hvd.moe.experts" in hlo and "hvd.moe.route" in hlo
