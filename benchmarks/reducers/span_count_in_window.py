"""How many spans of ``name`` ended inside the measured window."""


def reduce(record, name: str):
    if not record.spans:
        return None
    return float(len(record.spans_in_window(name)))
