"""The whole step's share of the chip's peak, in percent, from the device
trace.

The operations the forwards that ran in the traced window need (forwards
counted on the device: every convolution of the patch program runs once
a forward, so the mean number of events an op name of the
``convolution`` category has in the trace (an op in which a program
lists a convolution: ``cfbench.trace.file_by_contents``) is the number
of forwards,
with the part of a forward that a window's edge cuts off counted by the
share of its convolutions that lie inside; x the configuration's batch x
its FLOPs per patch, from shapes: the whole patch's, as the forward's
roofline share counts them) over the traced window x the cell's chips x
the device's peak. The time is all of the window's: idle gaps, copies, blending and
transfers included, where a kernel's roofline share divides by the
kernel's own time alone. So a change that takes a kernel off the path,
and leaves its roofline silent, still has this number over it; and a
window in which the device waited reads lower whatever the client saw.

On several chips every chip runs the program once a batch: the forwards
are the mean over the chips, the batch is the whole mesh's.
"""
import statistics

from cfbench import catalog, peaks


def forwards_in(device: dict) -> float:
    """How many times the patch program's forward ran on one device: the
    mean number of events a convolution has, over the convolutions seen
    more than half as often as the one seen most (a program that ran once
    beside the patch program's does not move the count)."""
    events: dict = {}
    for name, category, _, _ in device["ops"]:
        if category == "convolution":
            events[name] = events.get(name, 0) + 1
    most = max(events.values(), default=0)
    return statistics.fmean(
        [n for n in events.values() if 2 * n > most]) if most else 0.0


def reduce(record, peak: str = "bf16_flops"):
    tables = record.trace
    # a rehearsal's CPU has no device plane, no row of peaks and no share
    # of one to report; a measuring run on a device without a row has
    # ended in run.py
    if not tables or not tables["devices"] or tables["window_s"] <= 0 \
            or record.device["kind"] not in peaks.PEAKS:
        return None
    forwards = statistics.fmean(map(forwards_in, tables["devices"]))
    if forwards <= 0:
        return None
    flops = catalog.load_module("flops", record.config["flops"])
    needed = forwards * int(record.config["batch"]) \
        * flops.flops_per_patch(record.config)
    return 100.0 * needed / (
        tables["window_s"] * int(record.cell["chips"])
        * peaks.PEAKS[record.device["kind"]][peak])
