"""Device time a step spends in the window trunk's attention (scope
``window_attention``: pre-norm, the three projections, the norms of q and k
over a head, the layer kind's rotary, the softplus gate a head and the
output projection; and, inside it, scope ``attention_core``: the blocked
scores, softmax and values; forward, backward and rematerialised). Source:
device trace (ops' metadata, by innermost named scope,
``chipbench/trace_scopes.py``). Layer: window trunk. Moves
``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("window_attention", "attention_core"))
