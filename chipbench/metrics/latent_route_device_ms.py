"""Device time a step of the latent trunk spends routing and combining
(scope ``moe_route``: the sigmoid router's product, top-k over the biased
scores, the sort of the (token, choice) pairs by expert, the gather into
expert order; scope ``moe_combine``: the gather back and the weighted sum;
forward, backward and rematerialised). The part of
``latent_experts_device_ms`` that is neither the grouped products nor the
shared expert. Source: device trace, by innermost named scope. Layer:
latent trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("moe_route", "moe_combine"))
