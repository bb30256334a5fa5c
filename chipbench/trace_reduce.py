"""From a ``jax.profiler`` trace (``.xplane.pb``) to per-device numbers.

Read with ``jax.profiler.ProfileData`` alone. On a TPU each chip is a
plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO operation (the TensorCore runs them one after another) and
whose line ``XLA Modules`` holds one event per executed program (as a v5e
trace of this JAX shows them; asynchronous copies sit on a line of their
own, ``Async XLA Ops``, and are not TensorCore time). An operation's event
is named by its whole HLO text, ``%name = type opcode(operands), ...``. The
host is the plane ``/host:CPU``, one line per thread, where the benchmark's
own ``TraceAnnotation`` spans land on the same clock.

The reduction works on plain ``Event`` lists, so the tests drive it with
synthetic events; only ``load`` touches a file.

    python3 -m chipbench.trace_reduce --dump <trace dir or file>

prints what a trace holds (planes, lines, the commonest event names with
their stats), to look at one by hand before trusting a reader.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?(?=$|[.\s(])")
MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIX = "chipbench."


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    stats: tuple = ()

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def parse_op(text: str) -> tuple:
    """``(name, opcode, output type)`` of an operation's event name. A name
    that is not HLO text (``all-reduce.3``) is its own name, its opcode the
    name without its number, its type empty."""
    head, sep, rest = text.partition(" = ")
    name = head.lstrip("%")
    if not sep:
        return name, re.sub(r"\.\d+$", "", name), ""
    depth, i = 0, 0
    if rest.startswith("("):
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        i += 1
    else:
        i = rest.find(" ")
    out_type = re.sub(r"\{[^{}]*\}", "", rest[:i])
    opcode = rest[i:].lstrip().partition("(")[0]
    return name, opcode, out_type


def group_of(text: str) -> str:
    """What the breakdown adds an operation up under: its name without
    the number, its opcode and its output type, so that the same operation
    of every layer falls together."""
    name, opcode, out_type = parse_op(text)
    stem = re.sub(r"\.\d+$", "", name)
    label = stem if stem == opcode else f"{stem} {opcode}"
    if len(out_type) > 70:
        out_type = out_type[:67] + "..."
    return f"{label} {out_type}".strip()


def find_xplane(path: str) -> str:
    """The newest ``.xplane.pb`` under ``path`` (or ``path`` itself)."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def load(path: str, with_stats: bool = False) -> dict:
    """``{plane name: {line name: [Event, ...]}}`` of a trace."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(find_xplane(path))
    planes: dict = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for e in line.events:
                stats = tuple((k, v) for k, v in e.stats) if with_stats \
                    else ()
                events.append(Event(e.name, float(e.start_ns),
                                    float(e.duration_ns), stats))
    return planes


def merge(intervals: Iterable) -> list:
    """Sorted, disjoint ``(start, end)`` covering the same points."""
    out: list = []
    for start, end in sorted((s, e) for s, e in intervals if e > s):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: list) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """The points of merged ``a`` that merged ``b`` does not cover."""
    out, j = [], 0
    for start, end in a:
        while j < len(b) and b[j][1] <= start:
            j += 1
        k, at = j, start
        while k < len(b) and b[k][0] < end:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < end:
            out.append((at, end))
    return out


def clip(events: list, start: float, end: float) -> list:
    """``(start, end)`` of every event, cut to the window."""
    return [(max(e.start_ns, start), min(e.end_ns, end)) for e in events
            if e.end_ns > start and e.start_ns < end]


def is_collective(text: str) -> bool:
    return COLLECTIVE.match(parse_op(text)[1]) is not None


def is_mosaic(text: str) -> bool:
    """A Pallas kernel: a custom call that targets Mosaic."""
    return parse_op(text)[1] == "custom-call" and MOSAIC_TARGET in text


def collective_intervals(ops: list) -> list:
    """When a collective is under way. A synchronous one is its own event;
    an asynchronous one runs from the start of its ``-start`` event to the
    end of the ``-done`` event that follows with the same stem and number."""
    intervals, open_starts = [], {}
    for e in sorted(ops, key=lambda e: e.start_ns):
        name, opcode, _ = parse_op(e.name)
        m = COLLECTIVE.match(opcode)
        if not m:
            continue
        key = name.replace("-start", "").replace("-done", "")
        if m.group(2) == "-start":
            open_starts[key] = e.start_ns
            intervals.append((e.start_ns, e.end_ns))
        elif m.group(2) == "-done":
            intervals.append((open_starts.pop(key, e.start_ns), e.end_ns))
        else:
            intervals.append((e.start_ns, e.end_ns))
    return merge(intervals)


def steady_window(modules: list, ops: list, skip_first: int = 1):
    """``(start, end, steps)``: the stretch the reduction looks at. With a
    modules line, from the start of the most time-consuming program's
    ``skip_first``-th run (the first follows a drained device) to the end
    of its last; without one, from the first operation to the last."""
    by_name: dict = {}
    for m in modules:
        by_name.setdefault(m.name, []).append(m)
    if by_name:
        runs = sorted(max(by_name.values(),
                          key=lambda ms: sum(m.dur_ns for m in ms)),
                      key=lambda m: m.start_ns)
        runs = runs[skip_first:] if len(runs) > skip_first else runs
        return runs[0].start_ns, runs[-1].end_ns, len(runs)
    if not ops:
        raise ValueError("the trace holds no device operation")
    return (min(e.start_ns for e in ops), max(e.end_ns for e in ops), 0)


def reduce_device(ops: list, modules: list, host_spans: list) -> dict:
    """One device's numbers over its steady window (seconds)."""
    start, end, steps = steady_window(modules, ops)
    inside = [e for e in ops if e.end_ns > start and e.start_ns < end]
    busy = merge(clip(inside, start, end))
    compute = merge(clip([e for e in inside if not is_collective(e.name)],
                         start, end))
    collective = [(max(s, start), min(e, end))
                  for s, e in collective_intervals(inside)]
    collective = merge(collective)
    by_name: dict = {}
    by_group: dict = {}
    mosaic = 0.0
    for e in inside:
        spent = min(e.end_ns, end) - max(e.start_ns, start)
        name = parse_op(e.name)[0]
        by_name[name] = by_name.get(name, 0.0) + spent
        group = group_of(e.name)
        by_group[group] = by_group.get(group, 0.0) + spent
        if is_mosaic(e.name):
            mosaic += spent
    gaps = subtract([(start, end)], busy)
    spans = [s for s in host_spans if s.name.startswith(SPAN_PREFIX)]
    return {
        "window_s": (end - start) / 1e9,
        "steps": steps,
        "busy_s": total(busy) / 1e9,
        "compute_s": total(compute) / 1e9,
        "collective_s": total(collective) / 1e9,
        "exposed_collective_s": total(subtract(collective, compute)) / 1e9,
        "mosaic_s": mosaic / 1e9,
        "op_seconds": {n: t / 1e9 for n, t in by_name.items()},
        "group_seconds": {g: t / 1e9 for g, t in by_group.items()},
        "idle_gaps": sorted(
            ((_host_activity(s, e, spans), (e - s) / 1e9) for s, e in gaps),
            key=lambda g: -g[1]),
    }


def _host_activity(start: float, end: float, spans: list) -> str:
    """The benchmark's host span that covers most of a device gap."""
    best, best_overlap = "host:outside_any_span", 0.0
    for s in spans:
        overlap = min(end, s.end_ns) - max(start, s.start_ns)
        if overlap > best_overlap:
            best, best_overlap = "host:" + s.name[len(SPAN_PREFIX):], overlap
    return best


def reduce_trace(path: str, chips: Optional[int] = None) -> dict:
    """Every TPU plane of the trace reduced; ``devices`` is in device
    order. Raises if the trace shows no operation on a device."""
    planes = load(path)
    host_spans = [e for name, lines in planes.items()
                  if name.startswith("/host:")
                  for events in lines.values() for e in events
                  if e.name.startswith(SPAN_PREFIX)]
    devices = []
    for name in sorted(planes, key=lambda n: (len(n), n)):
        if not DEVICE_PLANE.match(name):
            continue
        lines = planes[name]
        ops = lines.get(OPS_LINE, [])
        if not ops:
            raise ValueError(f"{name} has no event on its {OPS_LINE!r} line "
                             f"(lines: {sorted(lines)})")
        devices.append(reduce_device(ops, lines.get(MODULES_LINE, []),
                                     host_spans))
    if not devices:
        raise ValueError(f"no TPU plane in the trace (planes: "
                         f"{sorted(planes)})")
    if chips is not None and len(devices) != chips:
        raise ValueError(f"trace holds {len(devices)} TPU planes, the cell "
                         f"uses {chips} chips")
    return {"devices": devices}


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: device 0's operations that took most
    time over the traced steady window (the same operation of every layer
    added up, see ``group_of``) and its longest idle gaps, by what the
    host was doing."""
    dev = reduced["devices"][0]
    ops = sorted(dev["group_seconds"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, t] for n, t in ops],
            "idle_gaps": [[n, t] for n, t in dev["idle_gaps"][:top]]}


def dump(path: str, out=print, names: int = 40) -> None:
    """What a trace holds, for reading by hand."""
    planes = load(path, with_stats=True)
    for pname, lines in planes.items():
        out(f"plane {pname!r}: {len(lines)} line(s)")
        for lname, events in lines.items():
            if not events:
                out(f"  line {lname!r}: empty")
                continue
            t0 = min(e.start_ns for e in events)
            t1 = max(e.end_ns for e in events)
            out(f"  line {lname!r}: {len(events)} events, "
                f"{t0 / 1e9:.6f}..{t1 / 1e9:.6f} s, busy "
                f"{total(merge((e.start_ns, e.end_ns) for e in events)) / 1e9:.6f} s")
            agg: dict = {}
            for e in events:
                n, t, _ = agg.get(e.name, (0, 0.0, e))
                agg[e.name] = (n + 1, t + e.dur_ns, e)
            for name, (n, t, e) in sorted(
                    agg.items(), key=lambda kv: -kv[1][1])[:names]:
                stats = {k: (v if len(str(v)) < 100 else str(v)[:100] + "...")
                         for k, v in e.stats}
                out(f"    {t / 1e9:10.6f} s  x{n:<5d} {name[:120]!r} {stats}")


if __name__ == "__main__":
    import argparse

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--dump", required=True, metavar="TRACE")
    dump(parser.parse_args().dump)
