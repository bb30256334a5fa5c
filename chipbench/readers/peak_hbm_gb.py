"""``peak_hbm_gb``: the most the fullest chip held at once, from
``memory_stats()`` after the window (live buffers at their peak plus the
region reserved for the programs' temporaries, see
``run.device_peak_bytes``), in 1e9 bytes."""


def read(run):
    return run["peak_bytes"] / 1e9 if run["peak_bytes"] else None
