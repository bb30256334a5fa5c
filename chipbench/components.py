"""Whose the forward and the backward pass's device time is: the component
scopes the models set at their blocks' call sites, read back.

The program's models name each operation's owner in its ``op_name``
(``horovod_tpu/models/scopes.py``; docs/tracing.md, "Scopes in a compiled
step"): ``hvd.embed``, ``hvd.norm``, ``hvd.mixer`` (and inside it
``hvd.mixer.proj``), ``hvd.mlp``, ``hvd.head``, beside the ``hvd.moe`` of
the expert layer. This module splits what ``scopes.py`` puts under the
phases ``forward`` and ``backward`` — the same instructions, the same
seconds of the reduced trace, first device — by the outermost of those
names in the instruction's ``op_name``, so that from one trace

    embed + norm + mixer + mlp + head + moe + other
        == forward_ms + backward_ms

whatever the compiler fused. ``mixer`` holds its kernels and its
projections; ``other`` is what no component owns: the attribution's own
check. Forward, recomputed and backward count together, and a loop counts
as ``scopes.py`` counts it (its own event beside its body's).

The compiler's copies carry no metadata. ``scopes.instructions`` gives
such an instruction the phase of the nearest instruction with metadata
along the chain of its first users (else of its first operands); the same
walk, written again here on ``scopes.py``'s own patterns (that file keeps
the chain to itself), gives it that instruction's owner.

A program without the component scopes (an older commit) gives ``None``
everywhere, and the metrics are left out of the line.
"""

from __future__ import annotations

import functools
import re
from typing import Optional

from chipbench import scopes, trace_reduce

OWNERS = ("embed", "norm", "mixer", "mlp", "head", "moe")
PROJ = "hvd.mixer.proj"
_OWNER = re.compile(r"hvd\.(embed|norm|mixer|mlp|head|moe)\b")
_SPLIT = ("forward", "backward")    # the phases that are split


def owner_of(op_name: str) -> str:
    """The component an operation traced under ``op_name`` belongs to: the
    outermost of the owners' scopes in the path, else ``other``."""
    found = _OWNER.search(op_name)
    return found.group(1) if found else "other"


@functools.lru_cache(maxsize=1)
def inherited_op_names(hlo: str) -> dict:
    """``{instruction: op_name}`` for the instructions of compiled HLO text
    that have no metadata of their own and are no collectives: the
    ``op_name`` of the nearest instruction that has, along the chain of
    first users, else of first operands (none found: left out)."""
    own, operands_of, first_user = {}, {}, {}
    for line in hlo.splitlines():
        m = scopes._INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(1)
        text = line.strip()
        if text.startswith("ROOT "):
            text = text[5:]
        found = scopes._OP_NAME.search(line)
        own[name] = (found.group(1) if found else None,
                     trace_reduce.is_collective(text))
        operands_of[name] = scopes._OPERAND.findall(
            text.partition(" = ")[2])
        for operand in operands_of[name]:
            first_user.setdefault(operand, name)

    def nearest(name, towards):
        for _ in range(scopes._MAX_CHAIN):
            name = towards(name)
            if name is None or name not in own:
                return None
            op_name, collective = own[name]
            if collective:      # exchange compute: no component's
                return ""
            if op_name is not None:
                return op_name
        return None

    out = {}
    for name, (op_name, collective) in own.items():
        if op_name is not None or collective:
            continue
        found = nearest(name, first_user.get)
        if found is None:
            found = nearest(
                name, lambda n: next(iter(operands_of[n]), None))
        if found:
            out[name] = found
    return out


def _split(run) -> Optional[list]:
    """``[(instruction, op_name it counts under, seconds per step), ...]``
    of the traced operations in the forward and backward phases, device 0;
    ``None`` without a trace of steady steps or where the program has none
    of the component scopes."""
    joined = scopes._joined(run)
    if joined is None:
        return None
    device, known = joined
    # ``hvd.moe`` alone is the parent's: none of the models' six
    if not any(owner_of(op_name) not in ("moe", "other")
               for _, op_name, _ in known.values()):
        return None
    inherited = inherited_op_names(run["hlo"])
    return [(name, known[name][1] or inherited.get(name, ""),
             s / device["steps"])
            for name, s in device["op_seconds"].items()
            if name in known and known[name][0] in _SPLIT]


def seconds(run) -> Optional[dict]:
    """Seconds per step under each of ``OWNERS``, under ``other``, under
    ``mixer_proj`` (a part of ``mixer``) and in ``total`` (the forward and
    backward phases: the sum of the owners and ``other``)."""
    rows = _split(run)
    if rows is None:
        return None
    out = dict.fromkeys(OWNERS + ("other", "mixer_proj", "total"), 0.0)
    for _, op_name, s in rows:
        owner = owner_of(op_name)
        out[owner] += s
        out["total"] += s
        if owner == "mixer" and PROJ in op_name:
            out["mixer_proj"] += s
    return out


def component_ms(run, component: str) -> Optional[float]:
    found = seconds(run)
    return None if found is None else 1e3 * found[component]


def other_operations(run, top: int = 8) -> list:
    """``[[instruction, end of its op_name, ms per step], ...]`` of the
    operations no component owns that took most time: what
    ``component_other_pct`` is made of. No reader calls it; it is for
    whoever holds a ``run`` and has to say why that share is what it is."""
    rows = [(name, op_name, s) for name, op_name, s in _split(run) or ()
            if owner_of(op_name) == "other"]
    return [[name, "/".join(op_name.split("/")[-3:]), 1e3 * s]
            for name, op_name, s in sorted(rows, key=lambda r: -r[2])[:top]]
