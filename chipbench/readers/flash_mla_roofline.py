"""``flash_mla_roofline``: the least time the chip could take for the
latent layers' needed attention FLOPs and bytes (``kernel_work``'s
``flash_mla``) over ``flash_mla_ms``, in percent."""


def read(run):
    spec = run["cell"].spec
    return spec.reader("flash_win_ms").roofline(
        run, spec.reader("flash_mla_ms").read(run), "flash_mla")
