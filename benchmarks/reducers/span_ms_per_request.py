"""Seconds of the named spans inside the window per request whose
``per`` span ended inside the window, in ms."""


def reduce(record, names: list, per: str):
    requests = len(record.spans_in_window(per)) if record.spans else 0
    if not requests:
        return None
    total = sum(sum(record.spans_in_window(name)) for name in names)
    return 1000.0 * total / requests
