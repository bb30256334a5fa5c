"""``kda_ms``: device time per step of every operation traced under the
program's scope ``hvd.kda.scan`` — the delta rule between the projections
and the output norm: the ``kda_fwd`` / ``kda_bwd`` kernels and what XLA
computes to feed them (the short convolutions apart: the normalisation, the
decay and its cumulative sums, the chunks' decayed products and triangular
solves), forward, recomputed and backward — first device. An operation
without metadata of its own (a copy the compiler scheduled) is not counted,
nor is a loop or a branch as such: a trace shows a ``while`` as one event
that spans its body's, and those are counted."""

import re

from chipbench import scopes

SCOPE = "hvd.kda"
_CONTAINER = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? (?:while|conditional|call)\(", re.M)


def scope_ms(run, scope: str):
    """Milliseconds per step, device 0, of the traced operations whose
    ``op_name`` holds ``scope``, loops and branches themselves left out;
    ``None`` without a trace of steady steps or where the program has no
    ``hvd.kda`` scope at all."""
    if run.get("trace") is None or not run.get("hlo"):
        return None
    device = run["trace"]["devices"][0]
    if not device["steps"]:
        return None
    op_names = {name: op_name for name, (_, op_name, _)
                in scopes.instructions(run["hlo"]).items()}
    if not any(SCOPE in op_name for op_name in op_names.values()):
        return None
    containers = set(_CONTAINER.findall(run["hlo"]))
    seconds = sum(s for name, s in device["op_seconds"].items()
                  if scope in op_names.get(name, "")
                  and name not in containers)
    return 1e3 * seconds / device["steps"]


def read(run):
    return scope_ms(run, "hvd.kda.scan")
