"""Device time of the MLP router with its carried state (scope moe_router: router_down, router_mix, router_mlp; three float32 products a layer) over busy time."""

from harness import readers_cca


def read(run):
    return readers_cca.scope_share(run, "moe_router")
