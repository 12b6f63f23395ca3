"""Device time a step of the window trunk spends routing and combining
(scope ``moe_route``: the feed-forward's pre-norm, the sigmoid router's
product over 256 experts, top-8 over the biased scores, the sort of the
(token, choice) pairs by expert, the gather into expert order; scope
``moe_combine``: the gather back and the weighted sum; forward, backward and
rematerialised). The part of ``window_experts_device_ms`` that is neither
the grouped products nor the shared expert. Source: device trace, by
innermost named scope. Layer: window trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("moe_route", "moe_combine"))
