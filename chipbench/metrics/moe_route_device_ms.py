"""Device time a step spends routing and combining (scope ``moe_route``:
the router's product, top-k, the sort of the (token, choice) pairs by
expert, the gather into expert order; scope ``moe_combine``: the gather
back and the weighted sum; forward, backward and rematerialised). Source:
device trace (ops' metadata, by innermost named scope). Layer:
sparse-expert trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("moe_route", "moe_combine"))
