"""``flash_bd_roofline``: the least time the chip could take for the
attention FLOPs and bytes block diffusion's mask *needs*
(``kernel_work``'s ``flash_bd``: seven products over the pairs the mask
keeps, the last layer's clean queries left out, K and V once a group) over
``flash_bd_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("flash_bd_ms").read(run), "flash_bd")
