"""Peak device memory of the fullest chip after the window, in GB (1e9
bytes), read before the reference runs: ``peak_bytes_in_use`` (live arrays:
table, client states, batches) plus ``peak_bytes_reserved`` (the region the
runtime reserves for the compiled programs' temporaries, which
``peak_bytes_in_use`` does not include), both from
``device.memory_stats()``. Source: program counter (the runtime's
allocator). Layer: device. Moves ``train_samples_per_s``: it bounds the
clients and the batch that fit."""


def read(run: dict):
    return run["peak_bytes"] / 1e9 if run["peak_bytes"] else None
