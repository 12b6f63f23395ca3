"""The pair sum's share of its roofline: the least time the chip could take
for the bytes one step's pair sums must move (``chipbench/bytes_pair_sum.py``:
the combine's forward and the route's backward in every routed layer, over
the held share of pairs by the program's gauge ``moe.absent_share``) at the
chip's published memory bandwidth, divided by the device time a step of
the kernel's ops. Those are the trace's ``custom-call`` ops named after the
kernel, ``pair_sum`` and the copy of the held rows it runs first,
``pair_sum_rows``: both are the kernel's work. The sums move a few bytes a
row and compute nothing, so bytes bound them. Source: device trace. Layer:
sparse-expert trunk. Moves ``train_samples_per_s``."""

import re

from chipbench import bytes_pair_sum, peaks_memory

KERNEL_OP = re.compile(r"^pair_sum(_rows)?(\.\d+)* custom-call ")


def read(run: dict):
    trace = run.get("trace")
    if not (trace and trace.get("ops") and run.get("trunk") and run.get("routing")):
        return None
    seconds = sum(s for op, s in trace["ops"].items() if KERNEL_OP.match(op))
    m = trace.get("modules", {}).get(run.get("module_names", {}).get("train_step"))
    if not seconds or not m or not m["count"] or run["device"].get("platform", "tpu") != "tpu":
        return None
    bandwidth = run.get("hbm_bytes_per_s") or peaks_memory.hbm_bytes_per_s(run["device"]["kind"])
    held_share = 1.0 - run["routing"]["absent_share"]
    least_s = (bytes_pair_sum.bytes_per_step(run["shapes"], run["trunk"], held_share)
               / (bandwidth * run["device"]["count"]))
    return 100.0 * least_s / (seconds / m["count"])
