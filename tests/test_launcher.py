"""Launcher tests (reference: ``test/test_spark.py:41-110`` — happy path
with per-rank results, fast failure on a broken command, failure
propagation when a rank dies)."""

import os
import sys

import pytest

from horovod_tpu.runner import LaunchError, launch, run

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_mp_worker.py")


def test_launch_allreduce_world():
    rc = launch([sys.executable, _WORKER, "allreduce"], np=2,
                host_data_plane=True)
    assert rc == 0


def test_launch_propagates_rank_failure():
    with pytest.raises(LaunchError) as excinfo:
        launch([sys.executable, "-c",
                "import os, sys; sys.exit(3 if os.environ['HOROVOD_RANK'] == '1' else 0)"],
               np=2)
    assert excinfo.value.rank == 1
    assert excinfo.value.returncode == 3


def test_launch_missing_binary_fails_fast():
    with pytest.raises(FileNotFoundError):
        launch(["definitely-not-a-real-binary-xyz"], np=2)


def test_launch_error_names_rank_code_and_stderr_tail():
    """A dead worker's LaunchError must carry the failed rank, its exit
    code, and the tail of its captured stderr — not surface later as an
    opaque result-wait timeout."""
    with pytest.raises(LaunchError) as excinfo:
        launch([sys.executable, "-c",
                "import os, sys\n"
                "if os.environ['HOROVOD_RANK'] == '1':\n"
                "    print('boom: synthetic worker crash', file=sys.stderr)\n"
                "    sys.exit(7)\n"
                "import time; time.sleep(30)\n"],
               np=2, capture_stderr=True, job_timeout_s=60.0)
    err = excinfo.value
    assert err.rank == 1 and err.returncode == 7
    assert "boom: synthetic worker crash" in str(err)
    assert "code 7" in str(err)


def test_launch_controller_listener_is_prebound():
    """TOCTOU fix: rank 0 receives the launcher's LIVE listening socket
    (HOROVOD_CONTROLLER_FD) on the advertised controller port."""
    probe = (
        "import os, socket\n"
        "fd = int(os.environ['HOROVOD_CONTROLLER_FD'])\n"
        "s = socket.socket(fileno=fd)\n"
        "port = s.getsockname()[1]\n"
        "assert port == int(os.environ['HOROVOD_CONTROLLER_PORT']), port\n"
        "s.listen(128)\n"  # already listening: re-listen is a no-op\n
        "s.close()\n"
    )
    rc = launch([sys.executable, "-c", probe], np=1, job_timeout_s=60.0)
    assert rc == 0


def test_launch_allreduce_world_python_controller_adopts_fd():
    """End to end on the Python controller service: rank 0's
    ControllerService must adopt the inherited listener (no rebind) and
    the world must still negotiate and reduce correctly."""
    rc = launch([sys.executable, _WORKER, "allreduce"], np=2,
                host_data_plane=True, job_timeout_s=120.0,
                env_extra={"HOROVOD_NATIVE_CONTROLLER": "0"})
    assert rc == 0


def _silent_exit_fn():
    import os

    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    if hvd.rank() == 1:
        os._exit(0)  # dies without reporting a result, exit code 0
    hvd.shutdown()
    return "ok"


def test_run_fn_names_silent_exit_instead_of_timing_out():
    """A worker that exits 0 WITHOUT registering a result used to eat the
    whole result timeout; now the driver names the silent ranks as soon
    as the launcher observes every process gone."""
    import time

    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as excinfo:
        run(_silent_exit_fn, np=2, timeout_s=300.0)
    assert "without reporting a result" in str(excinfo.value)
    assert "[1]" in str(excinfo.value)
    assert time.monotonic() - t0 < 120.0


def _worker_fn(scale):
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    out = hvd.allreduce(np.full(3, float(hvd.rank() + 1), np.float32),
                        average=False, name="runfn.sum")
    total = float(np.asarray(out)[0])
    return {"rank": hvd.rank(), "sum": total, "scaled": hvd.rank() * scale}


def test_run_fn_collects_rank_results():
    results = run(_worker_fn, args=(10,), np=2, timeout_s=120.0)
    assert [r["rank"] for r in results] == [0, 1]
    assert all(r["sum"] == 3.0 for r in results)  # 1 + 2
    assert [r["scaled"] for r in results] == [0, 10]


def _failing_fn():
    import jax

    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd

    hvd.init()
    if hvd.rank() == 1:
        raise RuntimeError("intentional rank failure")
    return "ok"


def test_run_fn_propagates_worker_exception():
    with pytest.raises((RuntimeError, LaunchError)) as excinfo:
        run(_failing_fn, np=2, timeout_s=120.0)
    assert "rank 1" in str(excinfo.value) or "intentional" in str(excinfo.value)


def test_horovodrun_cli():
    import subprocess

    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--host-data-plane", sys.executable, _WORKER, "broadcast"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert rc.returncode == 0, rc.stderr
    # which plane carries the bytes is said once, at default verbosity
    plane_lines = [line for line in rc.stderr.splitlines()
                   if "eager data plane for 2 ranks" in line]
    assert len(plane_lines) == 1, rc.stderr
    assert "host" in plane_lines[0]
    assert "HOROVOD_DATA_PLANE=host" in plane_lines[0]


def test_parse_hosts():
    from horovod_tpu.runner.launcher import parse_hosts

    assert parse_hosts("a:2,b:3") == [("a", 2), ("b", 3)]
    assert parse_hosts("solo") == [("solo", 1)]
    with pytest.raises(ValueError):
        parse_hosts("a:x")
    with pytest.raises(ValueError):
        parse_hosts("a:0")
    with pytest.raises(ValueError):
        parse_hosts("")


def test_launch_hosts_topology():
    """-H localhost:2,localhost:2 = a 2x2 virtual cluster: global ranks
    0..3, local ranks 0..1 per entry, cross ranks 0..1 (the comm-split
    structure of ``operations.cc:1760-1797``), with a real allreduce."""
    from horovod_tpu.runner.launcher import launch_hosts

    probe = (
        "import os, sys, json\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "out = hvd.allreduce(np.ones(2, np.float32), average=False,\n"
        "                    name='mh.sum')\n"
        "assert float(np.asarray(out)[0]) == 4.0, np.asarray(out)\n"
        "expect_local = hvd.rank() % 2\n"
        "expect_cross = hvd.rank() // 2\n"
        "assert hvd.local_rank() == expect_local, (hvd.rank(), hvd.local_rank())\n"
        "assert hvd.local_size() == 2\n"
        "assert hvd.cross_rank() == expect_cross, (hvd.rank(), hvd.cross_rank())\n"
        "assert hvd.cross_size() == 2\n"
        "hvd.shutdown()\n"
    )
    rc = launch_hosts([sys.executable, "-c", probe],
                      [("localhost", 2), ("localhost", 2)],
                      host_data_plane=True, job_timeout_s=120.0)
    assert rc == 0


def test_launch_hosts_rsh_agent(tmp_path):
    """A custom rsh agent (mpirun's plm_rsh_agent hook, the seam the
    reference's Spark integration uses — ``spark/driver/mpirun_rsh.py``)
    must be invoked once per rank with the host and the env-wrapped
    command, and the job must still work end to end."""
    from horovod_tpu.runner.launcher import launch_hosts

    log = tmp_path / "rsh_calls"
    agent = tmp_path / "fake_rsh.py"
    agent.write_text(
        "#!/usr/bin/env python\n"
        "import subprocess, sys\n"
        f"open({str(log)!r}, 'a').write(sys.argv[1] + '\\n')\n"
        "host, remote = sys.argv[1], sys.argv[2]\n"
        "sys.exit(subprocess.call(['bash', '-c', remote]))\n")
    probe = (
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "hvd.init()\n"
        "out = hvd.allreduce(np.ones(1, np.float32), average=False, name='r')\n"
        "assert float(np.asarray(out)[0]) == 2.0\n"
        "hvd.shutdown()\n"
    )
    rc = launch_hosts(
        [sys.executable, "-c", probe], [("localhost", 1), ("localhost", 1)],
        rsh_agent=[sys.executable, str(agent)],
        controller_addr="127.0.0.1",
        host_data_plane=True, job_timeout_s=120.0)
    assert rc == 0
    calls = log.read_text().splitlines()
    assert calls == ["localhost", "localhost"]


def test_launch_hosts_remote_simulation(tmp_path):
    """A simulated REMOTE 2x2 world: hosts named by hostname (not
    localhost), so the launcher must derive the controller address from
    hosts[0], export a non-loopback controller bind for rank 0, and
    forward world env + env_extra through the rsh line — the fake rsh
    scrubs its inherited environment the way a real ssh session would
    start clean (ADVICE round-1 items + reference
    ``spark/util/network.py:117-141`` NIC advertisement)."""
    import socket

    from horovod_tpu.runner.launcher import launch_hosts

    hostname = socket.gethostname()
    try:
        socket.gethostbyname(hostname)
    except OSError:
        pytest.skip("hostname does not resolve locally")

    agent = tmp_path / "fake_rsh.py"
    agent.write_text(
        "#!/usr/bin/env python\n"
        "import os, subprocess, sys\n"
        "# simulate a clean remote login shell: only the env assignments\n"
        "# embedded in the remote command line may carry the world\n"
        "env = {k: v for k, v in os.environ.items()\n"
        "       if not k.startswith(('HOROVOD_', 'HVD_TEST_'))}\n"
        "sys.exit(subprocess.call(['bash', '-c', sys.argv[2]], env=env))\n")
    probe = (
        "import os\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import horovod_tpu as hvd\n"
        "assert os.environ.get('HVD_TEST_EXTRA') == '42', 'env_extra lost'\n"
        "hvd.init()\n"
        "out = hvd.allreduce(np.ones(2, np.float32), average=False,\n"
        "                    name='remote.sum')\n"
        "assert float(np.asarray(out)[0]) == 4.0, np.asarray(out)\n"
        "hvd.shutdown()\n"
    )
    rc = launch_hosts(
        [sys.executable, "-c", probe],
        [(hostname, 2), (hostname, 2)],
        rsh_agent=[sys.executable, str(agent)],
        env_extra={"HVD_TEST_EXTRA": "42"},
        host_data_plane=True, job_timeout_s=180.0)
    assert rc == 0


def test_horovodrun_cli_hosts():
    import subprocess

    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-H",
         "localhost:2", "--host-data-plane",
         sys.executable, _WORKER, "allreduce"],
        capture_output=True, text=True, timeout=120,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert rc.returncode == 0, rc.stderr


def test_horovodrun_cli_np_and_hosts_conflict():
    import subprocess

    rc = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2", "-H",
         "localhost:2", sys.executable, "-c", "pass"],
        capture_output=True, text=True, timeout=60)
    assert rc.returncode != 0
    assert "exactly one of" in rc.stderr


def test_build_rank_env_pins_tpu_chip_per_slot():
    """Several slots on one host -> one chip per process (the TPU analog
    of the reference's one-GPU-per-process model: the runtime locks chips
    to the first process that initializes them, so the pin must come from
    the launcher env, not user code)."""
    from horovod_tpu.runner.launcher import build_rank_env

    env = build_rank_env(5, 8, 1234, "s", base_env={}, local_rank=1,
                         local_size=4)
    assert env["TPU_VISIBLE_DEVICES"] == "1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # one process per host (the TPU-native model): all chips stay visible
    env1 = build_rank_env(0, 4, 1234, "s", base_env={}, local_rank=0,
                          local_size=1)
    assert "TPU_VISIBLE_DEVICES" not in env1
    # explicit user topology wins over the launcher's default pin
    env2 = build_rank_env(0, 4, 1234, "s",
                          base_env={"TPU_PROCESS_BOUNDS": "2,2,1"},
                          local_rank=0, local_size=4)
    assert "TPU_VISIBLE_DEVICES" not in env2
    assert env2["TPU_PROCESS_BOUNDS"] == "2,2,1"
    # documented opt-out
    env3 = build_rank_env(
        0, 4, 1234, "s",
        base_env={"HOROVOD_LAUNCHER_PIN_DEVICES": "0"},
        local_rank=0, local_size=4)
    assert "TPU_VISIBLE_DEVICES" not in env3
    # programmatic env_extra merges BEFORE the pin: the opt-out and user
    # topology passed via launch(env_extra=...) must also be honored
    env4 = build_rank_env(
        0, 4, 1234, "s", base_env={}, local_rank=0, local_size=4,
        env_extra={"HOROVOD_LAUNCHER_PIN_DEVICES": "0"})
    assert "TPU_VISIBLE_DEVICES" not in env4
    env5 = build_rank_env(
        0, 4, 1234, "s", base_env={}, local_rank=0, local_size=4,
        env_extra={"TPU_PROCESS_BOUNDS": "2,2,1"})
    assert "TPU_VISIBLE_DEVICES" not in env5
    assert env5["TPU_PROCESS_BOUNDS"] == "2,2,1"


def test_cli_example_composition():
    """The documented user flow, end to end: the CLI launcher driving a
    real example across 2 ranks (the exact command in
    examples/pytorch_mnist.py's header), steered onto CPU via
    HOROVOD_PLATFORM."""
    import subprocess

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["HOROVOD_PLATFORM"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--host-data-plane", sys.executable,
         os.path.join(root, "examples", "pytorch_mnist.py"),
         "--epochs", "1"],
        cwd=root, env=env, capture_output=True, text=True, timeout=420)
    assert result.returncode == 0, result.stdout + result.stderr
    assert "epoch 0: loss=" in result.stdout


def test_rsh_wrap_forwards_pin_and_steering_vars():
    """Remote workers must receive the chip pin and platform steering —
    they are part of the world description, not local-only state."""
    from horovod_tpu.runner.launcher import _rsh_wrap, build_rank_env

    env = build_rank_env(1, 4, 1234, "s", base_env={"HOROVOD_PLATFORM": "cpu"},
                         local_rank=1, local_size=4)
    argv = _rsh_wrap(["ssh"], "remotehost", env, ["python", "train.py"])
    remote = argv[-1]
    assert "TPU_VISIBLE_DEVICES=1" in remote
    assert "TPU_CHIPS_PER_PROCESS_BOUNDS=1,1,1" in remote
    assert "TPU_PROCESS_BOUNDS=1,1,1" in remote
    assert "HOROVOD_PLATFORM=cpu" in remote
