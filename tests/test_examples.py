"""Example smoke tests — every example must actually run (the reference's
examples are its de-facto integration suite; SURVEY §2.8).

Examples are executed in subprocesses with the platform pinned to CPU via
jax.config, so they stay off any real chip whatever the environment says."""

import os
import subprocess
import sys

import pytest


# Subprocess/soak-heavy by design: excluded from the quick tier (-m "not soak").
pytestmark = pytest.mark.soak

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(script: str, argv, timeout: float = 300.0, env=None):
    bootstrap = (
        "import jax; jax.config.update('jax_platforms', 'cpu'); "
        "import runpy, sys; "
        f"sys.argv = [{script!r}] + {list(argv)!r}; "
        f"runpy.run_path({os.path.join(_ROOT, 'examples', script)!r}, "
        "run_name='__main__')"
    )
    full_env = dict(os.environ)
    full_env.pop("JAX_PLATFORMS", None)
    full_env.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count=2")
    if env:
        full_env.update(env)
    result = subprocess.run(
        [sys.executable, "-c", bootstrap], cwd=_ROOT, env=full_env,
        capture_output=True, text=True, timeout=timeout)
    assert result.returncode == 0, (
        f"{script} failed:\nstdout:\n{result.stdout}\n"
        f"stderr:\n{result.stderr}")
    return result


def test_jax_mnist_eager():
    out = _run_example("jax_mnist_eager.py",
                       ["--steps", "12", "--batch-size", "16"])
    assert "step 0: loss=" in out.stdout
    assert "done" in out.stdout


@pytest.mark.parametrize("mode", ["dp", "ring", "ulysses"])
def test_jax_transformer_lm(mode):
    out = _run_example(
        "jax_transformer_lm.py",
        ["--mode", mode, "--steps", "12", "--seq-len", "64",
         "--batch-size", "8"],
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"})
    lines = [l for l in out.stdout.splitlines() if l.startswith("step")]
    losses = [float(l.split("loss=")[1].split()[0]) for l in lines]
    assert losses[-1] < losses[0], (mode, losses)
    assert "done" in out.stdout


def test_flax_mnist_frontend():
    out = _run_example("flax_mnist.py",
                       ["--epochs", "1", "--batch-size", "8"])
    assert "epoch 0: loss" in out.stdout
    assert "restored at step" in out.stdout


def test_flax_mnist_advanced_callbacks():
    out = _run_example(
        "flax_mnist_advanced.py",
        ["--epochs", "3", "--batch-size", "8", "--warmup-epochs", "2"])
    lines = [l for l in out.stdout.splitlines() if l.startswith("epoch")]
    assert len(lines) == 3
    # warmup must raise the LR from base toward base * num_devices
    lrs = [float(l.split("lr=")[1].split()[0]) for l in lines]
    assert lrs[-1] > lrs[0]


def test_pytorch_synthetic_benchmark():
    out = _run_example(
        "pytorch_synthetic_benchmark.py",
        ["--batch-size", "4", "--image-size", "32", "--num-iters", "2",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "1"])
    assert "Img/sec per rank" in out.stdout


def test_pytorch_synthetic_benchmark_device_plane_json():
    """The torch front-end on the explicit size-1 XLA data plane (grad
    bytes ride H2D -> compiled reduce -> D2H) and a self-describing JSON
    result line in the bench.py protocol."""
    import json

    out = _run_example(
        "pytorch_synthetic_benchmark.py",
        ["--batch-size", "4", "--image-size", "32", "--num-iters", "2",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "1",
         "--json"],
        env={"HOROVOD_DATA_PLANE": "xla"})
    line = [l for l in out.stdout.splitlines() if l.startswith("{")][-1]
    rec = json.loads(line)
    assert rec["metric"] == "torch_synthetic_train_images_per_sec_per_rank"
    assert rec["data_plane"] == "xla"
    assert rec["front_end"] == "torch"
    assert rec["value"] > 0
    assert rec["n_ranks"] == 1
    assert rec["git_sha"]


def test_run_fn_job():
    out = _run_example("run_fn_job.py", [],
                       env={"EXAMPLE_PLATFORM": "cpu"})
    assert "OK" in out.stdout


def test_jax_tabular_job():
    """The end-to-end data job (keras_spark_rossmann analog): driver
    feature engineering -> run_fn training world with sharded rows,
    warmup, metric averaging, rank-0 checkpoint -> driver restore +
    submission CSV."""
    out = _run_example("jax_tabular_job.py",
                       ["--rows", "768", "--epochs", "2",
                        "--batch-size", "96"],
                       env={"EXAMPLE_PLATFORM": "cpu"}, timeout=420.0)
    assert "submission written" in out.stdout
    assert "OK" in out.stdout


def test_jax_mnist():
    out = _run_example("jax_mnist.py",
                       ["--epochs", "1", "--batch-size", "8"])
    assert out.returncode == 0


def test_tensorflow_mnist():
    out = _run_example(
        "tensorflow_mnist.py",
        ["--epochs", "1", "--batch-size", "32", "--samples", "64"])
    assert "epoch 0: loss=" in out.stdout
    assert "done" in out.stdout


def test_tensorflow_mnist_eager():
    out = _run_example(
        "tensorflow_mnist_eager.py",
        ["--batches", "12", "--batch-size", "16"])
    assert "Step #0\tLoss:" in out.stdout
    assert "done" in out.stdout


def test_pytorch_imagenet_resnet50(tmp_path):
    """The production-loop example: gradient accumulation, fp16 wire
    compression, checkpoint save — then a second run that must resume from
    the broadcast epoch instead of retraining."""
    fmt = str(tmp_path / "ckpt-{epoch}.pth.tar")
    argv = ["--epochs", "1", "--image-size", "64", "--train-batches", "2",
            "--batch-size", "8", "--batches-per-allreduce", "2",
            "--num-classes", "10", "--fp16-allreduce",
            "--checkpoint-format", fmt]
    out = _run_example("pytorch_imagenet_resnet50.py", argv, timeout=600.0)
    assert "epoch 0: loss=" in out.stdout
    assert os.path.exists(fmt.format(epoch=1))
    # resume: epoch 1 checkpoint exists -> nothing left to train
    out2 = _run_example("pytorch_imagenet_resnet50.py", argv, timeout=600.0)
    assert "epoch 0" not in out2.stdout
    assert "done" in out2.stdout


def test_pytorch_mnist():
    out = _run_example("pytorch_mnist.py",
                       ["--epochs", "1", "--batch-size", "8"])
    assert "epoch 0: loss=" in out.stdout


def test_jax_imagenet_resnet50():
    out = _run_example(
        "jax_imagenet_resnet50.py",
        ["--epochs", "1", "--steps-per-epoch", "2", "--batch-size", "4",
         "--image-size", "64", "--warmup-epochs", "1"],
        timeout=600.0)
    assert "epoch 0: loss=" in out.stdout


def test_jax_word2vec():
    out = _run_example(
        "jax_word2vec.py",
        ["--vocab-size", "200", "--embedding-dim", "16",
         "--batch-size", "32", "--steps", "12"])
    assert "loss=" in out.stdout


def test_haiku_mnist():
    out = _run_example("haiku_mnist.py",
                       ["--steps", "10", "--batch-size", "8"])
    assert out.returncode == 0


def test_scaling_bench_smoke():
    """The scaling-curve harness (BASELINE.md north star) must produce a
    point per device count and the efficiency table."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks",
                                      "scaling_bench.py"),
         "--devices", "1,2", "--batch-size", "4", "--iters", "1",
         "--batches-per-iter", "1"],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    assert '"devices": 2' in result.stdout
    assert "efficiency" in result.stdout


def test_fusion_bench_smoke():
    """The fusion micro-benchmark (docs/benchmarks.md) must run end to end
    on tiny sizes; its workers spawn their own 2-process worlds."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "benchmarks",
                                      "fusion_bench.py"),
         "--tensors", "4", "--elems", "256", "--rounds", "2"],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    assert "xla" in result.stdout and "host" in result.stdout
