"""``flash_mla_ms``: device time per step of the Mosaic custom calls the
program names ``flash_mla_fwd``, ``flash_mla_bwd_dq`` and
``flash_mla_bwd_dkv`` (the flash-attention kernels of the latent-attention
layers, whose v is narrower than their q and k; the forward's
recomputation included), first device."""

MLA = ("flash_mla_fwd", "flash_mla_bwd_dq", "flash_mla_bwd_dkv")


def read(run):
    return run["cell"].spec.reader("flash_win_ms").kernels_ms(run, MLA)
