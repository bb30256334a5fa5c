"""chip_smoke.py is the quickest proof that the system still starts on the
chip; here its phase functions run at toy sizes on the virtual 8-device CPU
mesh (Pallas kernels interpreted), and the script itself must refuse a
machine without a TPU."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke  # noqa: E402

sys.path.remove(_ROOT)


@pytest.fixture()
def mesh(hvd):
    return hvd.parallel.data_parallel_mesh()


def test_train_resnet_toy_size(mesh):
    """The ResNet phase end to end: on-mesh init, broadcast, AOT compile,
    steps with falling loss, no compile after warm-up, params replicated
    on all 8 devices, batch split 8 ways, all-reduces over groups of 8."""
    from horovod_tpu.models import ResNet
    from horovod_tpu.models.resnet import ResNetBlock

    model = ResNet(stage_sizes=[1], num_filters=8, num_classes=10,
                   block_cls=ResNetBlock, dtype=jnp.float32)
    facts = chip_smoke.train_resnet(model, mesh, batch_per_device=2,
                                    image_side=16, num_classes=10, steps=3)
    assert facts["global_batch"] == 16
    assert facts["all_reduces"] >= 1
    assert facts["compiles_after_warmup"] == 0
    assert len(facts["losses"]) == len(facts["step_seconds"]) == 3
    assert facts["losses"][-1] < facts["losses"][0]


def test_train_lm_toy_size(mesh):
    """The LM phase with attention='flash' (interpreted on CPU, so no
    Mosaic call is demanded — and none may be claimed)."""
    facts = chip_smoke.train_lm(
        mesh, num_layers=1, num_heads=2, d_model=32, d_ff=64, vocab_size=128,
        seq_len=128, batch_per_device=1, steps=3, require_mosaic=False)
    assert facts["global_batch"] == 8
    assert facts["mosaic_custom_calls"] == 0
    assert facts["compiles_after_warmup"] == 0
    assert facts["losses"][-1] < facts["losses"][0]
    with pytest.raises(chip_smoke.SmokeFailure, match="Mosaic"):
        chip_smoke.train_lm(
            mesh, num_layers=1, num_heads=2, d_model=32, d_ff=64,
            vocab_size=128, seq_len=128, batch_per_device=1, steps=2,
            require_mosaic=True)


def test_flash_vs_dense_toy_size():
    facts = chip_smoke.check_flash_vs_dense(
        batch=1, seq_len=128, num_heads=2, head_dim=16, dtype=jnp.float32,
        interpret=True, tolerance=1e-4)
    assert set(facts["max_error_over_max_reference"]) == {
        "out", "dq", "dk", "dv"}
    with pytest.raises(chip_smoke.SmokeFailure, match="beyond"):
        chip_smoke.check_flash_vs_dense(
            batch=1, seq_len=128, num_heads=2, head_dim=16,
            dtype=jnp.float32, interpret=True, tolerance=0.0)


def test_run_steps_fails_on_rising_loss_and_late_compiles():
    def rising(step, x):
        return step + 1, jnp.float32(step)

    with pytest.raises(chip_smoke.SmokeFailure, match="did not go down"):
        chip_smoke.run_steps(rising, (0,), (None,), steps=3)

    def recompiling(step, x):
        # a new shape each call: every step compiles
        return step + 1, -jax.jit(jnp.sum)(jnp.ones(step + 1))

    with pytest.raises(chip_smoke.SmokeFailure, match="after the warm-up"):
        chip_smoke.run_steps(recompiling, (0,), (None,), steps=3)


def test_count_compiles_sees_new_programs_only():
    fn = jax.jit(lambda x: x * 3 + 1)
    with chip_smoke.count_compiles() as first:
        fn(jnp.ones(7))
    with chip_smoke.count_compiles() as again:
        fn(jnp.ones(7))
    assert first[0] >= 1
    assert again[0] == 0


def test_allreduce_group_sizes_reads_both_hlo_spellings():
    hlo = "\n".join([
        "  %ar.1 = f32[8]{0} all-reduce(f32[8]{0} %p), channel_id=1, "
        "replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add",
        "  %ars = f32[8]{0} all-reduce-start(f32[8]{0} %p), channel_id=2, "
        "replica_groups=[1,4]<=[4], to_apply=%add",
        "  %ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)",
        "  %all = f32[8]{0} all-reduce(f32[8]{0} %p), replica_groups={}, "
        "to_apply=%add",
        "  %pair = f32[8]{0} all-reduce(f32[8]{0} %p), "
        "replica_groups={{0,1},{2,3}}, to_apply=%add",
    ])
    assert chip_smoke.allreduce_group_sizes(hlo) == [4, 4, 0, 2]

    class FourDevices:
        size = 4

    with pytest.raises(chip_smoke.SmokeFailure, match="sizes"):
        chip_smoke.check_allreduce_spans_mesh(hlo, FourDevices, "step")
    with pytest.raises(chip_smoke.SmokeFailure, match="no all-reduce"):
        chip_smoke.check_allreduce_spans_mesh("ENTRY main {}", FourDevices,
                                              "step")


def test_placement_checks_catch_single_device_arrays(mesh):
    """Code that has only met one device may leave everything on the
    first: the placement checks must reject exactly that."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    on_first = jax.device_put(jnp.ones((16, 4)), jax.devices()[0])
    with pytest.raises(chip_smoke.SmokeFailure, match="not replicated"):
        chip_smoke.check_replicated({"w": on_first}, mesh, "params")
    with pytest.raises(chip_smoke.SmokeFailure, match="not split"):
        chip_smoke.check_batch_split(on_first, mesh, "batch")
    chip_smoke.check_replicated(
        {"w": jax.device_put(on_first, NamedSharding(mesh, P()))}, mesh,
        "params")
    chip_smoke.check_batch_split(
        jax.device_put(on_first, NamedSharding(mesh, P("data"))), mesh,
        "batch")


def test_script_refuses_a_machine_without_tpu():
    """As the driver runs it in a sandbox: non-zero, names the platform it
    found, trains nothing and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    result = subprocess.run(
        [sys.executable, os.path.join(_ROOT, "chip_smoke.py")], cwd=_ROOT,
        env=env, capture_output=True, text=True, timeout=120)
    assert result.returncode != 0
    assert "'cpu'" in result.stderr
    assert result.stdout.strip() == ""
