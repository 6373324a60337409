"""A quantile of the durations of the spans of ``name`` that ended inside
the window."""
from cfbench import stats


def reduce(record, name: str, q: float, scale: float = 1.0):
    if not record.spans:
        return None
    value = stats.quantile(record.spans_in_window(name), q)
    return None if value is None else value * scale
