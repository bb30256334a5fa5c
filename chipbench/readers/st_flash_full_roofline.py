"""``st_flash_full_roofline``: the least time the chip could take for the
full layers' needed attention FLOPs and bytes (``kernel_work``'s
``flash_full``) over ``st_flash_full_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("st_flash_full_ms").read(run), "flash_full")
