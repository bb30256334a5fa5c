"""The measured window of a closed-loop trainer and its arithmetic.

One trainer: step *i* is dispatched as soon as step *i-1*'s call returned,
and the loss of step *i-LAG* is then read, as a trainer logs — so the
device is never drained inside the window and the host stays ``LAG`` steps
ahead. The times at which losses were read give the per-step times: each
sample spans ``steps_per_sample`` successive steps, so that a sample is a
quarter of a second or more of host clock.
"""

from __future__ import annotations

import collections
import dataclasses
import math
import statistics
import time
from typing import Callable

LAG = 2


@dataclasses.dataclass
class Window:
    seconds: float          # opening to the last step's result being ready
    dispatch_s: list        # host time of each step call, in order
    read_at: list           # clock at which each step's loss was read
    losses: list            # every step's loss, in order
    opened_at: float

    @property
    def steps(self) -> int:
        return len(self.losses)

    @property
    def failed(self) -> int:
        return sum(1 for x in self.losses if not math.isfinite(x))


def run_window(step: Callable[[], object], seconds: float,
               read: Callable[[object], float] = float,
               clock: Callable[[], float] = time.perf_counter,
               max_steps: int = 0) -> Window:
    """Drive ``step()`` (which dispatches one training step and returns
    its loss, not yet ready) for ``seconds`` from now, or for exactly
    ``max_steps`` steps when that is given. The caller opens the window on
    a drained device; it closes when the last step's loss has been read."""
    pending = collections.deque()
    dispatch_s, read_at, losses = [], [], []
    opened = clock()
    while (len(dispatch_s) < max_steps if max_steps
           else clock() - opened < seconds):
        t = clock()
        pending.append(step())
        dispatch_s.append(clock() - t)
        if len(pending) > LAG:
            losses.append(read(pending.popleft()))
            read_at.append(clock())
    while pending:
        losses.append(read(pending.popleft()))
        read_at.append(clock())
    return Window(seconds=read_at[-1] - opened, dispatch_s=dispatch_s,
                  read_at=read_at, losses=losses, opened_at=opened)


def step_samples(read_at: list, steps_per_sample: int) -> list:
    """Seconds per step, one sample per ``steps_per_sample`` successive
    steps: the gap between the reads that bracket them, divided by their
    number. The steps before the first read have no opening read and are
    left out; a last group that is not full is left out too."""
    k = max(1, int(steps_per_sample))
    marks = read_at[::k]
    return [(b - a) / k for a, b in zip(marks, marks[1:])]


def percentile(samples: list, q: float) -> float:
    """The nearest-rank ``q``-th percentile (0 < q <= 100) of ``samples``."""
    if not samples:
        raise ValueError("no samples")
    ordered = sorted(samples)
    return ordered[max(0, math.ceil(q / 100.0 * len(ordered)) - 1)]


def end_to_end(window: Window, global_batch: int, chips: int,
               steps_per_sample: int, flops_per_sample: float,
               peak_flops_per_s: float) -> dict:
    """The end-to-end metrics of one window, and the facts printed beside
    them. Rates are over all the work and all the time of the window."""
    samples = step_samples(window.read_at, steps_per_sample)
    rate = window.steps * global_batch / window.seconds / chips
    return {
        "samples_per_s_per_chip": rate,
        "step_ms_p95": 1e3 * percentile(samples, 95),
        "mfu_pct": 100.0 * rate * flops_per_sample / peak_flops_per_s,
        "facts": {
            "steps": window.steps,
            "window_s": window.seconds,
            "step_time_samples": len(samples),
            "steps_per_sample": steps_per_sample,
            "step_ms_median": 1e3 * statistics.median(samples),
            "step_ms_max": 1e3 * max(samples),
            "samples_beyond_p95": len(samples) - math.ceil(
                0.95 * len(samples)),
        },
    }
