"""``olmo_flash_ms``: device time per step of the Mosaic custom calls the
program names ``flash_fwd``, ``flash_bwd_dq`` and ``flash_bwd_dkv`` — the
flash-attention kernels of Olmo-Hybrid's full-attention layers (as many
K/V as query heads, no window), a forward's recomputation included where
there is one — first device."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").kernels_ms(
        run, spec.reader("flash_full_ms").FULL)
