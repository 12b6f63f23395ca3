"""How uneven the window trunk's routing is: the tokens on the fullest held
expert of a routed layer over the mean of the held experts, averaged over
the last round's steps, clients and layers, in per cent (100 = even). The
program counts the (token, choice) pairs on each held expert inside the step
and publishes the ratio as the gauge ``moe.expert_load_max_over_mean``
(``obs/registry.py``), as for the other routed trunks. A property of the
first weights and the traffic, not of the chip: the grouped products' tiles
and the sorted buffer's size follow the fullest expert. Source: program
counter. Layer: window trunk. Moves ``train_samples_per_s``."""


def read(run: dict):
    routing = run.get("routing")
    return None if not routing else 100.0 * routing["load_max_over_mean"]
