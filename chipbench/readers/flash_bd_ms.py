"""``flash_bd_ms``: device time per step of the Mosaic custom calls the
program names ``flash_bd_fwd``, ``flash_bd_bwd_dq`` and
``flash_bd_bwd_dkv`` (the flash-attention kernels under block diffusion's
three-part mask, a forward's recomputation included where there is one),
first device."""

BLOCK_DIFFUSION = ("flash_bd_fwd", "flash_bd_bwd_dq", "flash_bd_bwd_dkv")


def read(run):
    return run["cell"].spec.reader("flash_win_ms").kernels_ms(
        run, BLOCK_DIFFUSION)
