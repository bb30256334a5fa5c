"""What the program names from inside, read back: the phase scopes of the
step, the flash kernels' names, the compile ledger.

A v5e trace names a device event by its HLO text *without* metadata, and
the compiled text (``run["hlo"]``) has the metadata: each instruction's
``op_name`` is the path of ``jax.named_scope``s and transformations it was
traced under, e.g. ``jit(train_step)/shard_map/transpose(jvp(hvd.loss))/
TransformerLM/block_3/mlp/...``. Joining the two by instruction name puts
the ten traced steady steps' device time (device 0) under the phases the
program names (``docs/tracing.md``, "Scopes in a compiled step"). An
operation belongs to the first of:

1. ``collective`` — a collective by opcode, whatever its scope: that time
   is ``allreduce_ms``'s and is left out of every phase here;
2. ``exchange_compute`` — ``hvd.exchange`` or ``hvd.sync_stats``: the
   averaging divide, casts, a codec;
3. ``optimizer`` — ``hvd.optimizer`` or ``hvd.apply_updates``;
4. ``backward`` — ``transpose(jvp(hvd.loss))``;
5. ``forward`` — ``hvd.loss`` otherwise;
6. ``unscoped`` — under none of the program's scopes, or an instruction
   that the HLO text does not hold.

The compiler's own copies, slices and concatenations have no metadata;
they count with the operation they move data for (``instructions``).

A fusion counts under its own instruction's ``op_name`` (its root's), so
work XLA fuses across a phase boundary goes where the fusion's result
belongs: the optimizer's moments fused into ``apply_updates``' add is why
those two are one phase. A program without the scopes (an older commit,
an executable fetched from a cache that an older commit filled) gives
``None`` everywhere, and the metric is left out of the line.

The compile ledger is ``horovod_tpu.obs.compile_events()``; a program
without one gives ``None`` too.
"""

from __future__ import annotations

import functools
import re
from typing import Optional

from chipbench import trace_reduce

PHASES = ("forward", "backward", "optimizer", "exchange_compute",
          "collective", "unscoped")
STEP_PROGRAM = "train_step"     # the builders' step function, in fun_name

_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_MAX_CHAIN = 8      # copy-start -> copy-done -> bitcast -> ... -> fusion
_BY_SCOPE = (
    ("exchange_compute", ("hvd.exchange", "hvd.sync_stats")),
    ("optimizer", ("hvd.optimizer", "hvd.apply_updates")),
    ("backward", ("transpose(jvp(hvd.loss))",)),
    ("forward", ("hvd.loss",)),
)


def phase_of(op_name: str, collective: bool = False) -> str:
    """The phase of an operation traced under ``op_name``."""
    if collective:
        return "collective"
    for phase, scopes in _BY_SCOPE:
        if any(s in op_name for s in scopes):
            return phase
    return "unscoped"


@functools.lru_cache(maxsize=1)
def instructions(hlo: str) -> dict:
    """``{instruction name: (phase, op_name, is a Mosaic call)}`` for every
    instruction of compiled HLO text, the fused computations' own
    included (a trace never shows those; names are unique in a module).

    The compiler's own data movement — the copies, slices and
    concatenations it schedules round an operation, thousands a step —
    carries no metadata at all. Such an instruction counts where the
    first operation that consumes its result counts (as exchange compute
    where that is a collective), and one whose result nothing consumes (a
    copy into the step's output) where its operand's producer counts."""
    own, operands_of, first_user = {}, {}, {}
    for line in hlo.splitlines():
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        text = line.strip()
        if text.startswith("ROOT "):
            text = text[5:]
        found = _OP_NAME.search(line)
        own[name] = (found.group(1) if found else None,
                     trace_reduce.is_collective(text),
                     trace_reduce.is_mosaic(text))
        operands_of[name] = _OPERAND.findall(text.partition(" = ")[2])
        for operand in operands_of[name]:
            first_user.setdefault(operand, name)

    def inherited(name, towards):
        """The phase of the nearest instruction with metadata along the
        chain of first users (or first operands) of ``name``."""
        for _ in range(_MAX_CHAIN):
            step = towards(name)
            if step is None or step not in own:
                return None
            name = step
            op_name, collective, _ = own[name]
            if collective:   # a copy into an all-reduce's buffers
                return "exchange_compute"
            if op_name is not None:
                return phase_of(op_name)
        return None

    out = {}
    for name, (op_name, collective, mosaic) in own.items():
        if op_name is not None or collective:
            phase = phase_of(op_name or "", collective)
        else:
            phase = inherited(name, first_user.get) or inherited(
                name, lambda n: next(iter(operands_of[n]), None)) \
                or "unscoped"
        out[name] = (phase, op_name or "", mosaic)
    return out


def _joined(run) -> Optional[tuple]:
    """``(device 0 of the reduced trace, instructions of the HLO text)``,
    or ``None`` without a trace of steady steps."""
    if run.get("trace") is None or not run.get("hlo"):
        return None
    device = run["trace"]["devices"][0]
    if not device["steps"]:
        return None
    return device, instructions(run["hlo"])


def _traced(run) -> Optional[tuple]:
    """``_joined``, or ``None`` where the program has none of the scopes."""
    joined = _joined(run)
    if joined is None or not any(
            "hvd." in op_name for _, op_name, _ in joined[1].values()):
        return None
    return joined


def phase_seconds(run) -> Optional[dict]:
    """Seconds per step of each of ``PHASES`` on device 0, plus ``busy``
    (the device-busy time per step that the shares are of)."""
    traced = _traced(run)
    if traced is None:
        return None
    device, known = traced
    out = dict.fromkeys(PHASES, 0.0)
    for name, seconds in device["op_seconds"].items():
        if name in known:
            phase = known[name][0]
        else:
            phase = phase_of("", trace_reduce.is_collective(name))
        out[phase] += seconds / device["steps"]
    out["busy"] = device["busy_s"] / device["steps"]
    return out


def phase_ms(run, phase: str) -> Optional[float]:
    seconds = phase_seconds(run)
    return None if seconds is None else 1e3 * seconds[phase]


def unscoped_operations(run, top: int = 10) -> list:
    """``[[instruction, seconds per step], ...]`` of the unscoped
    operations that took most time: what ``unscoped_pct`` is made of."""
    traced = _traced(run)
    if traced is None:
        return []
    device, known = traced
    rows = [(name, s / device["steps"])
            for name, s in device["op_seconds"].items()
            if known.get(name, ("unscoped",))[0] == "unscoped"]
    return [list(r) for r in sorted(rows, key=lambda r: -r[1])[:top]]


def kernel_ms(run, kernel: str) -> Optional[float]:
    """Device milliseconds per step of the Mosaic custom calls that the
    program named ``kernel`` (instructions ``<kernel>.<n>``), device 0."""
    joined = _joined(run)
    if joined is None:
        return None
    device, known = joined
    seconds = [s for name, s in device["op_seconds"].items()
               if re.fullmatch(re.escape(kernel) + r"(\.\d+)*", name)
               and known.get(name, (None, None, False))[2]]
    if not seconds:
        return None
    return 1e3 * sum(seconds) / device["steps"]


def step_compile_seconds(run, stages: tuple) -> Optional[float]:
    """Seconds the program's compile ledger holds for the step program
    (``fun_name`` holds ``train_step``) in ``stages``, before the window
    opened; the reference's programs come after it. ``None`` where the
    program has no ledger or the ledger no such program."""
    try:
        from horovod_tpu.obs import compile_events
    except ImportError:
        return None
    opened_at = run["window"].opened_at
    mine = [e for e in compile_events()
            if STEP_PROGRAM in e.fun_name and e.at < opened_at]
    if not mine:
        return None
    return sum(e.seconds for e in mine if e.stage in stages)
