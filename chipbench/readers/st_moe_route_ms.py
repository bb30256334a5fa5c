"""``st_moe_route_ms``: the part of ``st_moe_ms`` under ``hvd.moe.route`` —
the router's float32 product on the block's own input, the softmax, the
top-k and the selected weights, forward, recomputed and backward, wherever
in the block they are issued. First device, per step."""


def read(run):
    return run["cell"].spec.reader("st_moe_ms").scope_ms(
        run, "hvd.moe.route")
