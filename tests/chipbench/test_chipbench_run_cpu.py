"""The run command end to end at toy size on the CPU backend, one process
a run as on the chip: 1 and 4 virtual devices, the last line's keys, no
device metric, and the refusals."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import cell as cells

ROOT = cells.ROOT
TOY = os.path.join("tests", "chipbench", "toy", "BENCHMARK.json")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(tmp_path, *args, devices=1, cwd=ROOT, program=None):
    """``python3 -m chipbench.run *args`` (or ``program`` in its place) in
    a process of its own; returns the finished process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=cwd,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env.pop("BENCH_RUN", None)
    head = ["-c", program] if program else ["-m", "chipbench.run"]
    return subprocess.run([sys.executable, *head, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=600)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices,trace", [
    ("toy_lm_1dev", 1, 0), ("toy_lm_4dev", 4, 1), ("toy_resnet_1dev", 1, 0)])
def test_rehearsal_prints_the_contract_line_and_no_device_metric(
        tmp_path, cell, devices, trace):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload", cell,
                    "--seed", str(2**31 + 17), "--seconds", "1", "--trace",
                    str(trace), "--rehearse-cpu", devices=devices)
    line = last_line(proc)
    assert LINE_KEYS <= set(line)
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # every number compared is printed beside its limit
    for number in ("loss", "first_gradient", "update"):
        assert f"correct: {number} gap" in proc.stdout
    assert "0 compilation(s) in the window" in proc.stdout
    if devices > 1:
        assert "replica group sizes [4]" in proc.stdout
        assert f"bit-identical on all {devices} devices after the window: " \
               "True" in proc.stdout


def test_without_the_rehearsal_flag_the_cpu_is_refused(tmp_path):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload",
                    "toy_lm_1dev", "--seed", "1", "--seconds", "1",
                    "--trace", "0")
    assert proc.returncode != 0 and proc.stdout == ""
    assert "needs a TPU" in proc.stderr and "'cpu'" in proc.stderr


def test_fewer_devices_than_the_cell_asks_for_is_refused(tmp_path):
    proc = run_cell(tmp_path, "--benchmark", TOY, "--workload",
                    "toy_lm_4dev", "--seed", "1", "--seconds", "1",
                    "--trace", "0", "--rehearse-cpu", devices=2)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "asks for 4 chip(s), JAX found 2" in proc.stderr


def test_alone_with_benchmark_json_it_fails_and_prints_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    ``paths``: the system under test is missing."""
    spec = cells.Spec()
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), alone)
    for path in spec.data["paths"]:
        shutil.copytree(os.path.join(ROOT, path), alone / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = run_cell(tmp_path, "--workload", "gpt2m_1chip", "--seed", "1",
                    "--seconds", "1", "--trace", "0", "--rehearse-cpu",
                    cwd=str(alone))
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
    assert "horovod_tpu" in proc.stderr
