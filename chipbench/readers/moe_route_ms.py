"""``moe_route_ms``: the part of ``moe_ms`` under the expert layer's scope
``hvd.moe.route`` — the router's product in float32, the sigmoid, the top-k
and the selected weights, forward, recomputed and backward. First device,
per step."""


def read(run):
    return run["cell"].spec.reader("moe_ms").scope_ms(run, "hvd.moe.route")
