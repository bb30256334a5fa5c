"""The shape functions against hand-worked totals, the step-time sampler
on a fake step, and every file that ``BENCHMARK.json`` names."""

import json
import math
import os

import pytest

from chipbench import cell as cells
from chipbench import check, timing

ROOT = cells.ROOT


@pytest.fixture(scope="module")
def spec():
    return cells.Spec()


def test_gpt2_medium_flops_per_token(spec):
    cell = spec.cell("gpt2m_1chip")
    family, config, traffic = cell.family, cell.config, cell.traffic
    # 24 layers x (4 x 1024^2 + 2 x 1024 x 4096) + 1024 x 50257
    assert family.matmul_parameters(config) == 24 * 12_582_912 + 51_463_168
    assert family.matmul_parameters(config) == 353_453_056
    # attention: 2 products x 2 FLOPs x 1024 wide x 3 (fwd + bwd) per
    # causal pair and layer; 1024 x 1025 / 2 pairs
    attention = 24 * 12 * 1024 * 524_800
    per_sequence = 6 * 353_453_056 * 1024 + attention
    assert family.flops_per_sample(config, traffic) == per_sequence
    assert per_sequence / 1024 / 1e9 == pytest.approx(2.272, abs=1e-3)


def test_resnet50_forward_flops(spec):
    cell = spec.cell("resnet50_1chip")
    forward = cell.family.forward_flops(cell.config)
    # torchvision's v1.5 ResNet-50: 4.09 G multiply-adds an image
    assert forward / 2e9 == pytest.approx(4.09, abs=0.01)
    # the stem by hand: 7x7x3x64 at 112x112, and the classifier
    stem = 2 * 7 * 7 * 3 * 64 * 112 * 112
    assert cell.family._convolutions(cell.config)[0][1:] == (7, 3, 64, 2, 224)
    assert forward > stem + 2 * 2048 * 1000
    assert cell.family.flops_per_sample(cell.config, cell.traffic) \
        == 3 * forward
    assert len(cell.family._convolutions(cell.config)) == 53


def test_flash_kernel_work_and_its_bound(spec):
    cell = spec.cell("gpt2m_1chip")
    work = cell.family.kernel_work(cell.config, cell.traffic, 4)["flash"]
    assert work["calls"] == 72
    assert work["flops"] == 24 * 7 * 2 * 1024 * 524_800 * 4
    assert work["bytes"] == 24 * 12 * 4 * 1024 * 1024 * 2
    least, by = spec.reader("flash_roofline").bound(
        work, cells.peaks_of("TPU v5 lite"))
    assert by == "flops" and least == pytest.approx(3.666e-3, rel=1e-3)
    assert cell.family.kernel_work is not None
    assert spec.cell("resnet50_1chip").family.kernel_work(
        spec.cell("resnet50_1chip").config, {}, 128) == {}


def test_unknown_device_kind_is_an_error():
    assert cells.peaks_of("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(cells.SpecError, match="no peaks on record"):
        cells.peaks_of("TPU v9 imaginary")


def test_step_sampler_on_a_fake_step():
    """A fake device that finishes a step every 10 ms of a fake clock;
    dispatch costs 1 ms; reading a loss waits for its step."""
    now = [0.0]
    done_at = []

    def step():
        now[0] += 0.001
        start = max(now[0], done_at[-1] if done_at else 0.0)
        done_at.append(start + 0.010)
        return len(done_at) - 1

    def read(i):
        now[0] = max(now[0], done_at[i])
        return 1.0 if i != 5 else math.nan

    w = timing.run_window(step, 0.0, read=read, clock=lambda: now[0],
                          max_steps=21)
    assert w.steps == 21 and w.failed == 1
    assert len(w.dispatch_s) == 21
    assert all(d == pytest.approx(0.001) for d in w.dispatch_s)
    # the host runs LAG steps ahead: the window closes when the last
    # step is done, 21 x 10 ms after the first began
    assert w.seconds == pytest.approx(0.001 + 0.210)
    samples = timing.step_samples(w.read_at, 2)
    assert len(samples) == 10
    assert all(s == pytest.approx(0.010) for s in samples)
    out = timing.end_to_end(w, global_batch=4, chips=2, steps_per_sample=2,
                            flops_per_sample=1e9, peak_flops_per_s=1e12)
    assert out["samples_per_s_per_chip"] == pytest.approx(
        21 * 4 / 0.211 / 2)
    assert out["step_ms_p95"] == pytest.approx(10.0)
    assert out["mfu_pct"] == pytest.approx(
        100 * out["samples_per_s_per_chip"] * 1e9 / 1e12)
    assert out["facts"]["step_time_samples"] == 10


def test_window_by_time_stops_dispatching_at_the_deadline():
    now = [0.0]

    def step():
        now[0] += 0.3
        return 0

    w = timing.run_window(step, 1.0, read=float, clock=lambda: now[0])
    assert w.steps == 4  # dispatched at 0, .3, .6, .9


@pytest.mark.parametrize("q,expected", [(95, 95), (50, 50), (100, 100),
                                        (1, 1), (0.5, 1)])
def test_percentile_nearest_rank(q, expected):
    assert timing.percentile(list(range(100, 0, -1)), q) == expected


def test_percentile_of_nothing():
    with pytest.raises(ValueError):
        timing.percentile([], 95)


def test_worst_leaf_gap_measures_norms_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "tiny": 1e-9}
    got = {"a": 1.1, "b": 2.0, "tiny": 1e-3}
    gap, where = check.worst_leaf_gap(got, ref)
    assert where == "a" and gap == pytest.approx(0.1)
    # the all-but-zero leaf is held to the median leaf's norm, not its own
    assert check.worst_leaf_gap({**ref, "tiny": 0.5}, ref)[0] \
        == pytest.approx(0.5)
    assert check.worst_leaf_gap({"a": 1.0}, ref)[0] == math.inf
    assert check.worst_leaf_gap({**ref, "a": math.nan}, ref)[0] == math.inf
    assert check.worst_leaf_gap(got, ref, skip={"a"})[0] \
        == pytest.approx(1e-3, rel=1e-3)


def test_exact_zeros_stay_in_the_comparison_and_out_of_the_median():
    """Blocks that start as the identity: most first-gradient leaves are
    exact zeros on both sides."""
    ref = {"a": 1.0, "b": 3.0, "z1": 0.0, "z2": 0.0, "z3": 0.0}
    assert check.median_leaf(ref) == 2.0
    assert check.median_leaf({"z": 0.0}) == 0.0
    assert check.worst_leaf_gap(dict(ref), ref) == (0.0, "")
    gap, where = check.worst_leaf_gap({**ref, "z2": 0.2}, ref)
    assert where == "z2" and gap == pytest.approx(0.1)
    assert check.dead_leaves({"a": 1.0, "b": 1.0, "n": 1e-9, "z": 0.0}) \
        == {"n"}
    assert check.worst_leaf_gap({"z": 1e-9}, {"z": 0.0})[0] == math.inf


def test_mean_leaf_gap_is_held_only_where_a_limit_is_set():
    ref = {"losses": [1.0], "grad_norms": {"a": 1.0, "b": 2.0, "z": 0.0},
           "update_norms": {"a": 1.0, "b": 2.0, "z": 0.0}}
    got = {"losses": [1.0], "grad_norms": {"a": 1.3, "b": 2.0, "z": 0.0},
           "update_norms": {"a": 1.0, "b": 2.0, "z": 0.0}}
    gaps = check.compare(got, ref)
    # a: 0.3 / max(1.0, median 1.5); b: 0; the exact zero is not averaged
    assert gaps["first_gradient_mean"] == (pytest.approx(0.1),
                                           "mean of 2 leaves")
    assert gaps["first_gradient"][0] == pytest.approx(0.2)
    loose = {k: {"limit": 0.5} for k in check.COMPARED}
    lines = []
    assert check.verdict(gaps, loose, lines.append) and len(lines) == 3
    held = {**loose, "first_gradient_mean": {"limit": 0.05}}
    assert not check.verdict(gaps, held, lines.append)
    assert "first_gradient_mean gap 0.1" in lines[-1]
    assert check.mean_leaf_gap({"a": 1.0}, {"b": 1.0})[0] == math.inf


def test_compare_and_verdict():
    ref = {"losses": [10.0, 9.0, 8.0],
           "grad_norms": {"w": 1.0, "b": 1.0, "dead": 1e-12},
           "update_norms": {"w": 0.1, "b": 0.1, "dead": 0.0}}
    same = {"losses": [10.0, 9.0, 8.0],
            "grad_norms": {"w": 1.0, "b": 1.0, "dead": 1e-7},
            "update_norms": {"w": 0.1, "b": 0.1, "dead": 0.1}}
    limits = {k: {"limit": 0.01} for k in check.COMPARED}
    lines = []
    assert check.verdict(check.compare(same, ref), limits, lines.append)
    assert len(lines) == 3 and all("<= limit 0.01" in x for x in lines)
    unchanged = dict(same, update_norms={"w": 0.0, "b": 0.0, "dead": 0.0})
    gaps = check.compare(unchanged, ref)
    assert gaps["update"][0] == pytest.approx(1.0)
    assert not check.verdict(gaps, limits, lines.append)
    part_left_out = dict(same, losses=[10.0, 9.2, 8.0])
    assert check.compare(part_left_out, ref)["loss"] \
        == (pytest.approx(0.2 / 9.0), "step 1")


def test_every_file_named_in_benchmark_json_loads(spec):
    data = spec.data
    assert set(data) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert isinstance(data["run_seconds"], int)
    assert sum(w["chips"] == 4 for w in data["workloads"]) \
        <= max(1, len(data["workloads"]) // 4)
    used = set()
    for name in spec.cell_names():
        cell = spec.cell(name)
        used.add(cell.config_name)
        assert cell.chips in (1, 4)
        assert cell.per_chip_batch * cell.chips \
            == cell.traffic["global_batch"]
        for attr in ("build", "optimizer", "make_step", "assemble",
                     "first_gradient", "init_model_state", "make_pool",
                     "data_spec", "flops_per_sample", "kernel_work",
                     "reference_run"):
            assert callable(getattr(cell.family, attr)), (name, attr)
        assert cell.family.flops_per_sample(cell.config, cell.traffic) > 0
        limits = cell.limits()
        assert set(limits) >= set(check.COMPARED)
        assert all(limits[k]["limit"] > 0 and limits[k]["set_from"]
                   for k in check.COMPARED)
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e
            assert callable(spec.reader(m["name"]).read)
    assert used == {c["name"] for c in data["configs"]}
    for c in data["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        with open(os.path.join(ROOT, c["file"]), encoding="utf-8") as fh:
            body = json.load(fh)
        assert body["reduced"] == c["reduced"] and len(c["source"]) <= 200
    for m in data["end_to_end"]:
        assert 0 < m["bound"] <= 0.1 and m["source"] in (
            "host_clock", "device_trace")
    layers = {m["layer"] for m in data["per_layer"]}
    assert layers == {"entry and compile cache", "step builders",
                      "gradient exchange", "kernels", "device"}


def test_metrics_of_a_cell_follow_the_workloads_key(spec):
    one = {m["name"] for m in spec.cell("gpt2m_1chip").per_layer}
    four = {m["name"] for m in spec.cell("gpt2m_4chip").per_layer}
    conv = {m["name"] for m in spec.cell("resnet50_1chip").per_layer}
    assert four - one == {"allreduce_ms", "exchange_exposed_pct"}
    assert one - conv == {"flash_ms", "flash_roofline"}
    assert {"compile_s", "compiles_in_window", "dispatch_ms",
            "device_idle_pct", "peak_hbm_gb"} <= conv
    with pytest.raises(cells.SpecError, match="no workload"):
        spec.cell("no_such_cell")


def test_readers_return_nothing_when_there_is_nothing_to_read(spec):
    run = {"cell": spec.cell("gpt2m_4chip"), "trace": None,
           "kernel_work": {"flash": {"flops": 1.0, "bytes": 1.0}},
           "peaks": cells.peaks_of("TPU v5 lite"), "peak_bytes": 0}
    for name in ("allreduce_ms", "exchange_exposed_pct", "flash_ms",
                 "flash_roofline", "device_idle_pct", "peak_hbm_gb"):
        assert spec.reader(name).read(run) is None, name


def test_readers_on_a_reduced_trace(spec):
    device = {"window_s": 0.2, "steps": 2, "busy_s": 0.19,
              "collective_s": 0.04, "exposed_collective_s": 0.03,
              "mosaic_s": 0.03, "op_seconds": {"fusion.1": 0.1, "custom-call.5": 0.02,
                             "custom-call.6": 0.01, "all-reduce.1": 0.04}}
    run = {"cell": spec.cell("gpt2m_4chip"), "trace": {"devices": [device]},
           "kernel_work": {"flash": {"flops": 197e12 * 0.003, "bytes": 1.0}},
           "peaks": cells.peaks_of("TPU v5 lite"), "peak_bytes": 12.5e9}
    assert spec.reader("allreduce_ms").read(run) == pytest.approx(20.0)
    assert spec.reader("exchange_exposed_pct").read(run) \
        == pytest.approx(15.0)
    assert spec.reader("flash_ms").read(run) == pytest.approx(15.0)
    assert spec.reader("flash_roofline").read(run) == pytest.approx(20.0)
    assert spec.reader("device_idle_pct").read(run) == pytest.approx(5.0)
    assert spec.reader("peak_hbm_gb").read(run) == pytest.approx(12.5)


@pytest.mark.parametrize("seed", [0, 7, 2**31 - 1, 2**31, 2**31 + 12345])
def test_seed_keys_take_seeds_beyond_32_signed_bits(seed):
    import jax

    keys = cells.seed_keys(seed, 2)
    other = cells.seed_keys(seed + 1, 2)
    assert keys.shape[0] == 2
    assert not bool((jax.random.key_data(keys)
                     == jax.random.key_data(other)).all())
