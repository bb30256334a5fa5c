"""The driver artifacts must stay runnable: ``entry()`` (single-chip
compile check) and ``dryrun_multichip`` (virtual-mesh sharding check) gate
external credit for the build, so their contracts are pinned here."""

import numpy as np
import pytest

# Full-model compiles in subprocesses (~3 min): excluded from the quick
# tier (-m "not soak").
pytestmark = pytest.mark.soak


def test_entry_compiles_and_runs():
    import jax

    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 1000)
    assert np.isfinite(np.asarray(out)).all()


def test_dryrun_multichip_subprocess():
    """The multi-chip gate artifact, exactly as the driver invokes it
    (own process: dryrun pins its own platform/device-count globals)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(8); print('OK')"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    assert "OK" in result.stdout
    # The default dryrun certifies BOTH collective routes (round-4 verdict
    # next #2): the driver artifact's tail must show the flat step, the
    # forced-hierarchical step, and the factored HLO evidence.
    assert "DP step OK (hierarchical allreduce: off (flat psum))" \
        in result.stderr
    assert "DP step OK (hierarchical allreduce: ON)" in result.stderr
    assert "factored-step HLO" in result.stderr


import pytest


@pytest.mark.slow
def test_dryrun_elastic_restart_subprocess():
    """The elastic-restart certification, exactly as the driver invokes
    it. Slow-tier: the same kill→relaunch→restore machinery is pinned in
    tier-1 by test_elastic.py's acceptance test."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_elastic_restart(); "
         "print('OK')"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    assert "OK" in result.stdout
    assert "elastic restart OK" in result.stderr


@pytest.mark.slow
def test_dryrun_chaos_subprocess():
    """The chaos certification, exactly as the driver invokes it.
    Slow-tier: the same drop→reconnect→dedup machinery is pinned in
    tier-1 by test_chaos.py's acceptance matrix."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_chaos(); print('OK')"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert result.returncode == 0, result.stderr
    assert "OK" in result.stdout
    assert "chaos OK" in result.stderr


@pytest.mark.slow
@pytest.mark.integrity
def test_dryrun_integrity_subprocess():
    """The data-plane integrity certification, exactly as the driver
    invokes it. Slow-tier: the same sentry/consensus machinery is pinned
    by tests/test_wire_integrity.py's acceptance cells."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    result = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_integrity(); print('OK')"],
        cwd=root, env=env, capture_output=True, text=True, timeout=420)
    assert result.returncode == 0, result.stderr
    assert "OK" in result.stdout
    assert "integrity OK" in result.stderr


def test_dryrun_multichip_hierarchical_16():
    """The hierarchical dryrun twin (round-3 verdict next #5): at 16
    virtual devices with HOROVOD_HIERARCHICAL_ALLREDUCE=1 the full DP
    step must compile and execute through the factored two-level route
    (the HLO shape itself is pinned in test_spmd)."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)
    env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1"
    result = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(16); print('OK')"],
        cwd=root, env=env, capture_output=True, text=True, timeout=500)
    assert result.returncode == 0, result.stderr
    assert "OK" in result.stdout
    assert "hierarchical allreduce: ON" in result.stderr
