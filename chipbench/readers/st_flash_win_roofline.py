"""``st_flash_win_roofline``: the least time the chip could take for the
window layers' needed attention FLOPs and bytes (``kernel_work``'s
``flash_win``: the pairs inside the window only) over ``st_flash_win_ms``, in
percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("st_flash_win_ms").read(run), "flash_win")
