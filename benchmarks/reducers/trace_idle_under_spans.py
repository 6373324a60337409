"""Of the device's idle time in the traced window, the share during which
one of the named program spans was open on the host, in percent, mean
over devices. The program's spans are ``TraceAnnotation`` events of the
same name on the trace's host plane, on the profiler's clock, so an idle
interval can be laid against them; with ``complement`` the share under
none of the names is given instead. Spans on different threads overlap,
so the shares of several groups of names can sum past 100. The tables
keep host events of 1 ms and more: shorter spans are not seen. None
where the host plane has no event of any program span (``any_of``: a
program from before its spans were annotations), so the line leaves the
metric out.
"""
from cfbench import trace


def idle_intervals(tables: dict, device: dict) -> list:
    edges = [tables["t0_ns"]]
    for start, end in trace._busy(device):
        edges += [start, end]
    edges.append(tables["t1_ns"])
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def span_intervals(tables: dict, names) -> list:
    """Merged intervals of the host events whose name, after the
    thread's, is one of ``names``."""
    names = set(names)
    return trace._union(
        (start, start + dur) for name, start, dur in tables["host"]
        if name.rsplit(": ", 1)[-1] in names)


def overlap(idle: list, spans: list) -> int:
    total, j = 0, 0
    for start, end in idle:
        while j < len(spans) and spans[j][1] <= start:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < end:
            total += min(end, spans[k][1]) - max(start, spans[k][0])
            k += 1
    return total


def reduce(record, names: list, any_of: list, complement: bool = False):
    tables = record.trace
    if not tables or not tables["devices"]:
        return None
    if not span_intervals(tables, any_of):
        return None
    spans = span_intervals(tables, names)
    shares = []
    for device in tables["devices"]:
        idle = idle_intervals(tables, device)
        total = sum(end - start for start, end in idle)
        if total <= 0:
            continue
        under = overlap(idle, spans) / total
        shares.append(1.0 - under if complement else under)
    return 100.0 * sum(shares) / len(shares) if shares else None
