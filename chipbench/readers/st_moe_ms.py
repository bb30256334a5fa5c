"""``st_moe_ms``: device time per step of every operation traced under the
program's scope ``hvd.moe`` — the router's product on the block's input, the
sort, the grouped products and the combine, forward, recomputed and backward
— first device, a loop or a branch as such left out as
``kda_ms.scope_ms`` leaves them (a trace shows a ``while`` as one event that
spans its body's; ``moe_ms`` counts both). An operation without metadata of
its own (a copy the compiler scheduled) is not counted."""

import re

from chipbench import scopes

SCOPE = "hvd.moe"
CONTAINER = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = .*? (?:while|conditional|call)\(", re.M)


def scope_ms(run, scope: str):
    """Milliseconds per step, device 0, of the traced operations whose
    ``op_name`` holds ``scope``, loops and branches themselves left out;
    ``None`` without a trace of steady steps or where the program has no
    ``hvd.moe`` scope at all."""
    if run.get("trace") is None or not run.get("hlo"):
        return None
    device = run["trace"]["devices"][0]
    if not device["steps"]:
        return None
    op_names = {name: op_name for name, (_, op_name, _)
                in scopes.instructions(run["hlo"]).items()}
    if not any(SCOPE in op_name for op_name in op_names.values()):
        return None
    containers = set(CONTAINER.findall(run["hlo"]))
    seconds = sum(s for name, s in device["op_seconds"].items()
                  if scope in op_names.get(name, "")
                  and name not in containers)
    return 1e3 * seconds / device["steps"]


def read(run):
    return scope_ms(run, SCOPE)
