"""The share of its memory roofline that the work under named scopes of
the program reaches, in percent.

The bytes the traced window's patches have to move (patches per second
the client saw x ``function(config)`` of the module ``flops/<name>.py``
that the configuration names under ``module_key`` x the traced window)
over the time the trace shows in ops under ``scopes`` x the device's peak
``peak``, summed over the cell's devices. Ops are joined to scopes on
their names through ``programs.json`` ``op_scopes``, as the scope shares
are. None where the configuration names no such module, no program
carries ``op_scopes``, or no op ran under the scopes.
"""
from cfbench import catalog, peaks, trace


def reduce(record, scopes: list, module_key: str, function: str,
           peak: str = "hbm_bytes_s"):
    rate = record.client.get("patches_per_s")
    module_name = record.config.get(module_key)
    if not record.trace or not rate or not module_name:
        return None
    shares = catalog.load_module("reducers", "trace_scope_share")
    names = shares.scope_of_ops(record.programs)
    if names is None:
        return None
    seconds = 0.0
    for device in record.trace["devices"]:
        for (short, _), spent in trace._leaf_seconds(device).items():
            if names.get(short.split(" ", 1)[0]) in scopes:
                seconds += spent
    if seconds <= 0:
        return None
    per_patch = getattr(catalog.load_module("flops", module_name),
                        function)(record.config)
    needed = rate * per_patch * record.trace["window_s"]
    return 100.0 * needed / (
        seconds * peaks.peaks_for(record.device["kind"])[peak])
