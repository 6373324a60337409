"""The ``task_extent`` reducer over the tasks that one span tags: those
with a span of ``name`` whose attribute ``attr`` equals ``value`` (the
blank check's ``blank=1``: the tasks that took the blank path). The
extent, in ms, from a task's first span's start to its last span's end,
the ``q`` quantile over the tagged tasks that ended inside the window.
None where no span carries the attribute (a program from before it was
added) or no tagged task ended in the window."""
from cfbench import catalog, stats


def reduce(record, name: str, attr: str, value, q: float = 0.5,
           ignore: list = ()):
    extent = catalog.load_module("reducers", "task_extent")
    tagged = {span["trace_id"] for span in record.spans
              if span.get("name") == name and span.get(attr) == value
              and span.get("trace_id") is not None}
    start, end = record.window
    values = []
    for trace_id, intervals in extent.task_intervals(
            record.spans, set(ignore)).items():
        last = max(b for _, b in intervals)
        if trace_id in tagged and start <= last <= end:
            values.append(last - min(a for a, _ in intervals))
    value = stats.quantile(values, q)
    return None if value is None else 1000.0 * value
