"""Device time a step spends in the window trunk's blocked attention core
alone (scope ``attention_core``: per query block the scores against the key
blocks its band allows, the masks of the diagonal and band-edge blocks, the
softmax and the context; forward, the rematerialised forwards and the
backward's second pass). Source: device trace, by innermost named scope.
Layer: window trunk. Moves ``train_samples_per_s``."""

from chipbench import trace_scopes


def read(run: dict):
    return trace_scopes.scope_ms_per_step(run, ("attention_core",))
