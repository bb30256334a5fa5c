"""``flash_fwd_ms``: device time per step of the Mosaic custom calls the
program names ``flash_fwd`` (the forward flash-attention kernel of every
layer), first device."""

from chipbench import scopes


def read(run):
    return scopes.kernel_ms(run, "flash_fwd")
