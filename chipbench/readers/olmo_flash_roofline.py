"""``olmo_flash_roofline``: the least time the chip could take for the full
layers' needed attention FLOPs and bytes (``kernel_work``'s ``flash``: seven
products over the visible pairs, each operand once each way) over
``olmo_flash_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("olmo_flash_ms").read(run), "flash")
