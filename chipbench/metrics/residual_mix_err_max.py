"""How far from doubly stochastic the residual mixing matrices still are
after their last Sinkhorn iteration: the largest distance from 1 of a row or
column sum, over tokens, sublayers, steps and clients of the last round. The
program computes it inside the step, returns it with the step's metrics and
publishes it as the gauge ``trunk.residual_mix_err_max``
(``obs/registry.py``). Sinkhorn contracts slowly on a matrix with a few
dominant entries, so the reading says whether the published 20 iterations
still do their work where training has moved the mixers. Source: program
counter. Layer: latent trunk. Moves ``train_samples_per_s`` (more iterations
are the cure, and they cost device time)."""


def read(run: dict):
    return run.get("mixer")
