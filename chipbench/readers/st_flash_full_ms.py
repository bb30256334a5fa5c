"""``st_flash_full_ms``: device time per step of ``flash_fwd``,
``flash_bwd_dq`` and ``flash_bwd_dkv`` — the flash-attention kernels of
SmallThinker's full layers (every earlier token, no positions, a group of 7),
once each a layer. First device."""


def read(run):
    return run["cell"].spec.reader("flash_full_ms").read(run)
