"""Device time a step spends in the routed feed-forward of the window trunk
(scopes ``moe_route`` + ``moe_experts`` + ``moe_combine`` +
``shared_expert``: pre-norm, router, sort and gathers, the grouped products
over the pairs on held experts, the weighted sum, and the shared expert
every token passes; forward, both gradients and the rematerialised
forwards). Source: device trace, by innermost named scope. Layer: window
trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(
        run, ("moe_route", "moe_experts", "moe_combine", "shared_expert"))
