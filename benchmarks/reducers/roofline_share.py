"""A kernel family's share of its compute roofline, in percent.

The operations the forwards of the traced window need (patches per second
the client saw x the configuration's FLOPs per patch x the traced window)
over the time the trace shows in ops of ``pattern`` x the device's peak,
summed over the cell's devices. Compute-bound against ``peak``; padding
rows of a batch are not counted as work.
"""
from cfbench import catalog, peaks, trace


def reduce(record, pattern: str, peak: str = "bf16_flops"):
    rate = record.client.get("patches_per_s")
    if not record.trace or not rate:
        return None
    seconds = sum(trace.category_total_seconds(record.trace, pattern))
    if seconds <= 0:
        return None
    flops = catalog.load_module("flops", record.config["flops"])
    needed = rate * flops.flops_per_patch(record.config) \
        * record.trace["window_s"]
    return 100.0 * needed / (
        seconds * peaks.peaks_for(record.device["kind"])[peak])
