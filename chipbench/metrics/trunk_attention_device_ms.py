"""Device time a step spends in the trunk's attention (scope
``trunk_attention``: pre-norm, the four projections, rotary, the masked
grouped-query core, the residual; forward, backward and rematerialised).
Source: device trace (ops' metadata, by innermost named scope). Layer:
sparse-expert trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("trunk_attention",))
