"""Seconds of the named spans that fall inside the measured window, over
the window, in percent. Spans of different threads may overlap, so the
share can pass 100."""


def reduce(record, names: list):
    if not record.spans:
        return None
    start, end = record.window
    total = sum(sum(record.spans_in_window(name)) for name in names)
    return 100.0 * total / (end - start)
