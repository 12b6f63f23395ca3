"""Device time a step spends in the grouped matrix products of the expert
layers (scope ``moe_experts``: three products a layer over the (token,
choice) pairs on held experts; forward, both gradients, and the forwards
rematerialised in the backward pass). Source: device trace (ops' metadata,
by innermost named scope, ``chipbench/trace_scopes.py``). Layer:
sparse-expert trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("moe_experts",))
