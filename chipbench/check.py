"""The comparison that decides ``correct``.

The compiled step, the same object the window then drives, is driven
from the seed through its first ``STEPS`` steps; the plain reference
trainer follows them on the same seeded weights and batches. Compared,
each against a limit of its own (``limits/<cell>.json``):

* ``loss``: each step's loss, as ``|program - reference| / |reference|``,
  the largest of the steps;
* ``first_gradient``: the norm of the first gradient as the optimizer got
  it (worked out from its state after one step), by the worst leaf;
* ``update``: the norm of the parameters' change after the steps, by the
  worst leaf;
* ``first_gradient_mean``, where the cell's limits file holds it: the same
  gaps of the first gradient, averaged over the leaves whose reference
  norm is not zero. The worst of some fifty noisy leaves swings from seed
  to seed by more than the step from bfloat16 to fp8 moves it (ResNet-50,
  my chip run, PR 23); their mean is steady, and is what the control fails
  there.

A leaf's gap is the distance between the two *norms* (not the norm of the
difference), measured against the reference's norm of that leaf or of the
median leaf, whichever is larger — some gradients are all but zero. The
median is taken over the leaves whose reference norm is not exactly zero:
where every residual block starts as the identity most leaves of the first
gradient are exact zeros, on both sides, and a leaf that is zero in the
reference and not in the program is an infinite gap.

Some are zero by construction (a key projection's bias: softmax does not
see a shift of every score in a row). What reaches the optimizer there is
rounding noise, and Adam scales noise up to a full-sized update, in the
program and in the reference alike but not to the same one. So ``update``
leaves out the leaves whose reference gradient is under ``DEAD_GRADIENT``
of the median leaf's; ``first_gradient`` still holds them to the median.
"""

from __future__ import annotations

import math
import statistics

STEPS = 3
DEAD_GRADIENT = 1e-4
COMPARED = ("loss", "first_gradient", "update")   # every cell
OPTIONAL = ("first_gradient_mean",)                # where a limit is set


def median_leaf(norms: dict) -> float:
    """The median of the norms that are not exactly zero (0 if none)."""
    positive = [n for n in norms.values() if n > 0]
    return statistics.median(positive) if positive else 0.0


def dead_leaves(reference_grad_norms: dict) -> set:
    """Leaves whose reference gradient is zero but for rounding (an exact
    zero is not rounding: it stays in the comparison)."""
    floor = DEAD_GRADIENT * median_leaf(reference_grad_norms)
    return {leaf for leaf, n in reference_grad_norms.items()
            if 0 < n < floor}


def worst_leaf_gap(program: dict, reference: dict, skip=frozenset()):
    """``(gap, leaf)`` of the leaf whose norm is farthest from the
    reference's, the leaves in ``skip`` left out. A leaf missing on either
    side, or a norm that is not finite, is an infinite gap."""
    if set(program) != set(reference) or not reference:
        return math.inf, "<trees differ>"
    floor = median_leaf(reference)
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        if leaf in skip:
            continue
        got = program[leaf]
        if not (math.isfinite(got) and math.isfinite(ref)):
            return math.inf, leaf
        scale = max(ref, floor)
        gap = abs(got - ref) / scale if scale > 0 else (
            0.0 if got == ref else math.inf)
        if gap > worst:
            worst, where = gap, leaf
    return worst, where


def mean_leaf_gap(program: dict, reference: dict):
    """``(gap, "")``: the leaves' gaps as ``worst_leaf_gap`` takes them,
    averaged over the leaves whose reference norm is not zero."""
    if set(program) != set(reference):
        return math.inf, "<trees differ>"
    floor = median_leaf(reference)
    gaps = [abs(program[leaf] - ref) / max(ref, floor)
            for leaf, ref in reference.items() if ref > 0]
    if not gaps or not all(map(math.isfinite, gaps)):
        return math.inf, "<no finite leaf>"
    return statistics.fmean(gaps), f"mean of {len(gaps)} leaves"


def loss_gap(program: list, reference: list):
    """Largest ``|program - reference| / |reference|`` over the steps."""
    if len(program) != len(reference) or not reference:
        return math.inf, "<step counts differ>"
    worst, where = 0.0, ""
    for i, (got, ref) in enumerate(zip(program, reference)):
        if not (math.isfinite(got) and math.isfinite(ref)) or ref == 0:
            return math.inf, f"step {i}"
        gap = abs(got - ref) / abs(ref)
        if gap > worst:
            worst, where = gap, f"step {i}"
    return worst, where


def compare(program: dict, reference: dict) -> dict:
    """``{number: (gap, where)}`` for every number ``verdict`` may hold."""
    return {
        "first_gradient_mean": mean_leaf_gap(program["grad_norms"],
                                             reference["grad_norms"]),
        "loss": loss_gap(program["losses"], reference["losses"]),
        "first_gradient": worst_leaf_gap(program["grad_norms"],
                                         reference["grad_norms"]),
        "update": worst_leaf_gap(program["update_norms"],
                                 reference["update_norms"],
                                 dead_leaves(reference["grad_norms"])),
    }


def verdict(gaps: dict, limits: dict, say=print) -> bool:
    """Print each number compared beside its limit; true if all hold. The
    numbers of ``COMPARED`` need a limit; one of ``OPTIONAL`` is held only
    where the cell's limits give it one."""
    ok = True
    for name in COMPARED + tuple(n for n in OPTIONAL if n in limits):
        gap, where = gaps[name]
        limit = float(limits[name]["limit"])
        holds = gap <= limit
        ok = ok and holds
        say(f"correct: {name} gap {gap:.6g} (worst at {where}) "
            f"{'<=' if holds else '>'} limit {limit:g}")
    return ok
