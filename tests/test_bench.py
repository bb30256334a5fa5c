"""bench.py and benchmarks/lm_bench.py must always run end to end.

These tests execute the REAL scripts (tiny sizes, a CPU run asked for
explicitly with ``HOROVOD_BENCH_PLATFORM=cpu``) and assert the
machine-readable result line, so any refactor that breaks an artifact fails
CI — and that without that explicit request a machine with no TPU gets a
non-zero exit and no result line at all.
"""

import json
import os
import subprocess
import sys

import pytest

# Subprocess/soak-heavy by design: excluded from the quick tier (-m "not soak").
pytestmark = pytest.mark.soak

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_end_to_end_cpu(tmp_path):
    """One CPU run covers the whole artifact: the result line (including
    the MFU additions — achieved TFLOP/s from the compiled module's cost
    analysis; mfu_pct only appears on real accelerators) and the
    HOROVOD_BENCH_DUMP_HLO audit dump, so the multi-minute AOT compile is
    paid once."""
    hlo_path = str(tmp_path / "step_hlo.txt")
    env = dict(os.environ)
    env.update({"HOROVOD_BENCH_PLATFORM": "cpu",
                "HOROVOD_BENCH_DUMP_HLO": hlo_path})
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "bench.py"),
         "--batch-size", "2", "--num-warmup-batches", "1",
         "--num-batches-per-iter", "1", "--num-iters", "1"],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=560)
    assert result.returncode == 0, (
        f"bench.py failed\nstdout:\n{result.stdout}\n"
        f"stderr:\n{result.stderr}")
    # one process, one result: nothing but the final line on stdout
    assert len(result.stdout.strip().splitlines()) == 1
    line = json.loads(result.stdout.strip().splitlines()[-1])
    assert (line["platform"], line["device_kind"]) == ("cpu", "cpu")
    assert line["n_devices"] >= 1
    assert "live" not in line and "captured_by" not in line
    assert line["metric"] == \
        "resnet50_synthetic_train_images_per_sec_per_device"
    assert line["value"] > 0
    assert line["unit"] == "img/s"
    assert isinstance(line["vs_baseline"], float)
    assert line["tflops_per_device"] > 0
    assert "mfu_pct" not in line  # meaningless on CPU, by design
    with open(hlo_path) as f:
        hlo = f.read()
    assert "ENTRY" in hlo or "HloModule" in hlo


def test_onchip_path_bench_cpu():
    """The single-device residency bench (docs/benchmarks.md) must run and
    produce its comparison row."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["HOROVOD_BENCH_PLATFORM"] = "cpu"
    result = subprocess.run(
        [sys.executable,
         os.path.join(_ROOT, "benchmarks", "onchip_path_bench.py"),
         "--tensors", "8", "--elems", "1024", "--rounds", "3"],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=560)
    assert result.returncode == 0, result.stderr
    line = json.loads(result.stdout.strip().splitlines()[-1])
    assert line["host_tensors_per_s"] > 0
    assert line["onchip_tensors_per_s"] > 0


@pytest.mark.parametrize("script", ["bench.py",
                                    os.path.join("benchmarks", "lm_bench.py")])
def test_bench_without_tpu_fails_without_result(script):
    """A measurement path that finds no TPU fails; it does not fall back
    to the host. JAX_PLATFORMS=cpu alone (this sandbox's environment) is
    not a request to measure the CPU — HOROVOD_BENCH_PLATFORM=cpu is."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("HOROVOD_BENCH_PLATFORM", None)
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, script)],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert result.returncode != 0
    assert result.stdout.strip() == ""
    assert "no TPU" in result.stderr and "'cpu'" in result.stderr


def test_lm_bench_end_to_end_cpu():
    """The Transformer-LM benchmark (second flagship workload) must run
    end to end on CPU for both attention backends and emit the JSON line,
    stamped with where it ran."""
    for attention in ("dense", "flash"):
        env = dict(os.environ)
        env["HOROVOD_BENCH_PLATFORM"] = "cpu"
        result = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "benchmarks",
                                          "lm_bench.py"),
             "--num-layers", "1", "--num-heads", "2", "--d-model", "32",
             "--d-ff", "64", "--vocab-size", "128", "--seq-len", "128",
             "--batch-size", "1", "--num-warmup-batches", "1",
             "--num-batches-per-iter", "1", "--num-iters", "1",
             "--attention", attention],
            cwd=_ROOT, env=env, capture_output=True, text=True, timeout=560)
        assert result.returncode == 0, (attention, result.stderr)
        line = json.loads(result.stdout.strip().splitlines()[-1])
        assert line["metric"] == "transformer_lm_tokens_per_sec_per_device"
        assert line["value"] > 0
        assert line["attention"] == attention
        assert line["tflops_per_device"] > 0
        assert (line["platform"], line["device_kind"]) == ("cpu", "cpu")
        assert line["n_devices"] >= 1
