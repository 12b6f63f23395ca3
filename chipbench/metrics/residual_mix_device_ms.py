"""Device time a step spends mixing the residual streams (scope
``residual_mix``: the norm of a token's n*d vector, the three maps, Sinkhorn's
iterations, reading the streams into a sublayer, writing its output back,
mixing the streams, the final merge; forward, backward and rematerialised).
Memory-bound work over a state four times the hidden size. Source: device
trace, by innermost named scope. Layer: latent trunk. Moves
``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("residual_mix",))
