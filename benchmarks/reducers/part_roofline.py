"""The share of the compute roofline that named parts of the program
reach, in percent.

The operations a patch needs in those parts, from shapes
(``flops_per_patch(config)`` of the module ``flops/<flops>.py``: the whole
patch's, whatever the program computes of it, as ``forward_roofline``
counts them), over the device time a patch that ``trace_part_ms`` reads
for the same ``parts``, ``scope`` and categories, times the device's
``peak``. Forwards and batch cancel: both numbers are a patch's. Compute
bound. None where ``trace_part_ms`` has nothing to read.
"""
from cfbench import catalog, peaks


def reduce(record, parts: str, flops: str, scope: str = "forward",
           category: str = None, not_category: str = None,
           peak: str = "bf16_flops"):
    if not record.trace or record.device.get("kind") not in peaks.PEAKS:
        return None
    ms = catalog.load_module("reducers", "trace_part_ms").reduce(
        record, parts=parts, scope=scope, category=category,
        not_category=not_category)
    if not ms:
        return None
    needed = catalog.load_module("flops", flops).flops_per_patch(
        record.config)
    return 100.0 * needed / (
        1e-3 * ms * peaks.PEAKS[record.device["kind"]][peak])
