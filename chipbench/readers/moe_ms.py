"""``moe_ms``: device time per step of every operation traced under the
program's scope ``hvd.moe`` — routing, the sort, the grouped products, the
shared expert and the combine, forward, recomputed and backward — first
device. An operation without metadata of its own (a copy the compiler
scheduled) is not counted."""

from chipbench import scopes

SCOPE = "hvd.moe"


def scope_ms(run, scope: str):
    """Milliseconds per step, device 0, of the traced operations whose
    ``op_name`` holds ``scope``; ``None`` without a trace of steady steps or
    where the program has no ``hvd.moe`` scope at all; 0.0 where it has one
    and the compiler left no operation of its own under ``scope``."""
    if run.get("trace") is None or not run.get("hlo"):
        return None
    device = run["trace"]["devices"][0]
    if not device["steps"]:
        return None
    op_names = {name: op_name for name, (_, op_name, _)
                in scopes.instructions(run["hlo"]).items()}
    if not any(SCOPE in op_name for op_name in op_names.values()):
        return None
    seconds = sum(s for name, s in device["op_seconds"].items()
                  if scope in op_names.get(name, ""))
    return 1e3 * seconds / device["steps"]


def read(run):
    return scope_ms(run, SCOPE)
