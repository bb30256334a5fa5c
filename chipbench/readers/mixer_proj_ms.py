"""``mixer_proj_ms``: device time per step of the forward and backward
operations traced under ``hvd.mixer.proj`` — the mixers' input and output
projections, a part of ``mixer_ms`` (``chipbench/components.py``), first
device."""

from chipbench import components


def read(run):
    return components.component_ms(run, "mixer_proj")
