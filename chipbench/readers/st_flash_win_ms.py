"""``st_flash_win_ms``: device time per step of ``flash_win_fwd``,
``flash_win_bwd_dq`` and ``flash_win_bwd_dkv`` — the flash-attention kernels
of SmallThinker's window layers (a window of 4,096, a group of 7 query heads
a key/value head, q and k rotated), once each a layer: the recomputed block
keeps its forward kernel's outputs. First device."""


def read(run):
    return run["cell"].spec.reader("flash_win_ms").read(run)
