"""``flash_full_ms``: device time per step of the Mosaic custom calls the
program names ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` (the
flash-attention kernels of the full-attention layers, the forward's
recomputation included), first device."""

FULL = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def read(run):
    return run["cell"].spec.reader("flash_win_ms").kernels_ms(run, FULL)
