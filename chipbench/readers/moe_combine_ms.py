"""``moe_combine_ms``: the part of ``moe_ms`` under the expert layer's scope
``hvd.moe.combine`` — the sum of the shared expert's output and the routed
one. The compiler may fuse it into a neighbour's operation: 0.0 then.
First device, per step."""


def read(run):
    return run["cell"].spec.reader("moe_ms").scope_ms(run, "hvd.moe.combine")
