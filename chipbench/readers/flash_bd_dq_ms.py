"""``flash_bd_dq_ms``: device time per step of the Mosaic custom calls the
program names ``flash_bd_bwd_dq`` (the dQ flash-attention kernel under
block diffusion's mask, every layer's), first device."""

from chipbench import scopes


def read(run):
    return scopes.kernel_ms(run, "flash_bd_bwd_dq")
