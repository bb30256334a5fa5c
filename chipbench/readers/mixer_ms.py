"""``mixer_ms``: device time per step of the forward and backward operations
traced under the models' scope ``hvd.mixer`` — a block's whole mixer: its
projections (``mixer_proj_ms``), rotary, gate, its own norms, the flash or
delta-rule kernels, its residual add (``chipbench/components.py``), first
device."""

from chipbench import components


def read(run):
    return components.component_ms(run, "mixer")
