"""Device time a step spends in the trunk's latent attention (scope
``latent_attention``: pre-norm, both bottlenecks and their norms, rotary
with YaRN's frequencies, the masked core over 192-wide keys, the output
projection; forward, backward and rematerialised). Source: device trace
(ops' metadata, by innermost named scope, ``chipbench/trace_scopes.py``).
Layer: latent trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("latent_attention",))
