"""``kda_conv_ms``: device time per step under the program's scope
``hvd.kda.conv`` — the short causal convolutions on q, k and v and their
SiLU, forward, recomputed and backward. First device."""


def read(run):
    return run["cell"].spec.reader("kda_ms").scope_ms(run, "hvd.kda.conv")
