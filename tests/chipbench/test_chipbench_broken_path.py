"""``correct`` comes out false when the timed path is broken underneath:
the harness's look for a chip is skipped (``--rehearse-cpu``), the rest of
a run is driven as it stands."""

import os

import pytest

from test_chipbench_run_cpu import TOY, last_line, run_cell

# Wraps the compiled step that set-up builds and the window drives.
BROKEN = '''
import sys
import jax
from chipbench import run

FAULT = sys.argv.pop(1)
build = run.Trainer.__init__


def broken(self, *args, **kwargs):
    build(self, *args, **kwargs)
    real, n_state = self.compiled, len(self.state)

    def state_unchanged(*call):
        kept = jax.tree_util.tree_map(lambda x: x.copy(), call[:n_state])
        *_, loss = real(*call)
        return (*kept, loss)

    def half_the_batch(*call):
        data = [x.at[x.shape[0] // 2:].set(x[:x.shape[0] // 2])
                for x in call[n_state:]]
        return real(*call[:n_state], *data)

    self.compiled = {"state_unchanged": state_unchanged,
                     "half_the_batch": half_the_batch}[FAULT]


run.Trainer.__init__ = broken
sys.exit(run.main(sys.argv[1:]))
'''


@pytest.mark.parametrize("fault,cell,number", [
    ("state_unchanged", "toy_lm_1dev", "update"),
    ("half_the_batch", "toy_resnet_1dev", "first_gradient")])
def test_a_broken_step_is_not_correct(tmp_path, fault, cell, number):
    proc = run_cell(tmp_path, fault, "--benchmark", TOY, "--workload", cell,
                    "--seed", "5", "--seconds", "1", "--trace", "0",
                    "--rehearse-cpu", program=BROKEN)
    line = last_line(proc)
    assert line["correct"] is False, proc.stdout[-3000:]
    failed = [x for x in proc.stdout.splitlines() if "> limit" in x]
    assert any(f"correct: {number} gap" in x for x in failed), proc.stdout
    assert os.path.basename(TOY) == "BENCHMARK.json"
