"""Device time a patch in the ops of named parts of the program, in ms.

A part is the first name on an op's path below its scope's root module: a
flax module of the model (``enc0``, ``up1``) or a scope the model opens
around what its own ``__call__`` emits (``pool0``, ``skip1``, ``post``).
The trace keeps an op's name and nothing of its metadata, so the part is
joined on the name, as the scope shares are: each ``programs.json`` entry
of a traced run carries ``op_parts``, ``{scope: {part: [op names]}}``,
which the program reads out of its compiled module (a fusion by the
convolution with the most taps inside it, not by its root, after which
XLA names and shapes it: ``chunkflow_tpu/core/profiling.py``).

The number: innermost-op seconds of the ops under ``scope`` whose part
matches ``parts`` (a regular expression, matched against the whole part
name) and whose category (``convolution`` where a program lists one
inside the op, whatever its opcode: ``cfbench.trace.file_by_contents``)
matches ``category`` and not ``not_category``,
summed over the cell's devices, over the forwards the trace holds
(``step_mfu.forwards_in``, the mean over the devices) times the
configuration's batch. A unit of time and not a share of busy time, so
that a change to one part moves one number.

With ``unnamed`` it is the time of the ops under ``scope`` that have no
part (``""``) or that two programs put in different places: what the
named parts leave over. The named parts and the unnamed time add up to the
time under ``scope``. With ``share`` the number is a percentage of that
time instead of ms a patch: with both, how far the parts can be trusted.

None where no program carries ``op_parts`` (a program from before the
parts) or the trace holds no forward, so the line leaves the metric out.
"""
import re
import statistics

from cfbench import catalog, trace


def scope_seconds(record, scope: str):
    """``{(part, category): seconds}`` of innermost-op time under
    ``scope``, summed over the devices; the part is None for an op
    without one and for one the programs place differently. None if no
    program carries ``op_parts``."""
    places = trace.places_of_ops(record.programs)
    if places is None:
        return None
    out: dict = {}
    for device in record.trace["devices"]:
        for (short, category), seconds in \
                trace._leaf_seconds(device).items():
            found = places.get(short.split(" ", 1)[0], ())
            if not any(place[0] == scope for place in found):
                continue
            part = next(iter(found))[1] if len(found) == 1 else ""
            key = (part or None, category)
            out[key] = out.get(key, 0.0) + seconds
    return out


def patches_in(record) -> float:
    """Patches whose forward the traced window holds."""
    forwards_in = catalog.load_module("reducers", "step_mfu").forwards_in
    devices = record.trace["devices"]
    if not devices:
        return 0.0
    return statistics.fmean(map(forwards_in, devices)) \
        * int(record.config["batch"])


def reduce(record, parts: str = None, scope: str = "forward",
           category: str = None, not_category: str = None,
           unnamed: bool = False, share: bool = False):
    if not record.trace:
        return None
    seconds = scope_seconds(record, scope)
    patches = patches_in(record)
    if seconds is None or patches <= 0:
        return None
    wanted = re.compile(parts) if parts else None
    keep = re.compile(category, re.I) if category else None
    skip = re.compile(not_category, re.I) if not_category else None
    hit = 0.0
    for (part, op_category), spent in seconds.items():
        if unnamed != (part is None):
            continue
        if part is not None and wanted and not wanted.fullmatch(part):
            continue
        if (keep and not keep.search(op_category)) \
                or (skip and skip.search(op_category)):
            continue
        hit += spent
    if share:
        total = sum(seconds.values())
        return 100.0 * hit / total if total > 0 else None
    return 1e3 * hit / patches
