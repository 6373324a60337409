"""Per task, from the spans that carry its ``trace_id``: the extent from
its first span's start to its last span's end (``part: "inside"``), or
the part of that extent that no span of the task covers (``part:
"wait"``: the task sat in a queue between stages), in ms; the ``q``
quantile over the steady tasks, those whose last span ended inside the
measured window. ``ignore`` names spans that wait *for* a task and can
begin before the task was claimed (the scheduler's load wait is given
the task it ended with): they are neither extent nor cover. Spans from
before the span record had a start (``t0``) are not read: None where
there are none, so the line leaves the metric out.
"""
from cfbench import stats, trace


def task_intervals(spans: list, ignore=()) -> dict:
    """``{trace_id: [(start, end), ...]}`` of the spans that have both."""
    tasks: dict = {}
    for span in spans:
        if span.get("trace_id") is None or "t0" not in span \
                or span["name"] in ignore:
            continue
        tasks.setdefault(span["trace_id"], []).append(
            (span["t0"], span["t0"] + span["dur_s"]))
    return tasks


def covered(intervals: list) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    return sum(end - start for start, end in trace._union(intervals))


def reduce(record, part: str, q: float = 0.5, ignore: list = ()):
    if part not in ("inside", "wait"):
        raise ValueError(f"part is 'inside' or 'wait', not {part!r}")
    start, end = record.window
    values = []
    for intervals in task_intervals(record.spans, set(ignore)).values():
        first = min(a for a, _ in intervals)
        last = max(b for _, b in intervals)
        if not start <= last <= end:
            continue
        inside = last - first
        values.append(inside if part == "inside"
                      else inside - covered(intervals))
    value = stats.quantile(values, q)
    return None if value is None else 1000.0 * value
