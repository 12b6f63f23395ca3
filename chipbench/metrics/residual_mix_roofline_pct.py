"""The residual mixers' share of their memory roofline in the latent trunk,
whatever implements them: the least time the chip could take for the bytes
the mixers must move in a step (``chipbench/bytes_residual_mix.py``: every
sublayer's read-in, write-back and their backward once, the opening tile and
the closing merge, from the shapes alone) at the chip's published memory
bandwidth (``chipbench/peaks_memory.py``), divided by the device time of
the scope ``residual_mix`` in a step. The mixers compute a few operations a
byte, so bytes bound them. The scope's time holds the read-in run again by
the rematerialised forward, which is not required work. Source: device
trace. Layer: latent trunk. Moves ``train_samples_per_s``."""

from chipbench import bytes_residual_mix, peaks_memory, trace_scopes


def read(run: dict):
    ms = trace_scopes.scope_ms_per_step(run, ("residual_mix",))
    if not (ms and run.get("trunk") and run.get("device")):
        return None
    bandwidth = run.get("hbm_bytes_per_s") or peaks_memory.hbm_bytes_per_s(run["device"]["kind"])
    least_s = (bytes_residual_mix.bytes_per_step(run["shapes"], run["trunk"])
               / (bandwidth * run["device"]["count"]))
    return 100.0 * least_s / (ms / 1e3)
