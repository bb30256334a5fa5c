"""``gdn_conv_ms``: device time per step under the program's scope
``hvd.gdn.conv`` — the short causal convolutions on q, k and v of the
gated-delta-rule layers and their SiLU, forward, recomputed and backward.
First device."""


def read(run):
    return run["cell"].spec.reader("gdn_ms").scope_ms(run, "hvd.gdn.conv")
