"""``flash_win_roofline``: the least time the chip could take for the
sliding layers' needed attention FLOPs and bytes (``kernel_work``'s
``flash_win``: the pairs inside the window only) over ``flash_win_ms``,
in percent."""


def read(run):
    shared = run["cell"].spec.reader("flash_win_ms")
    return shared.roofline(run, shared.read(run), "flash_win")
