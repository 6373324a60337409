"""Share of device busy time in ops whose category (read from the op's
text by ``cfbench.trace.parse_op``) matches ``pattern``, in percent."""
from cfbench import trace


def reduce(record, pattern: str):
    if not record.trace:
        return None
    share = trace.category_share(record.trace, pattern)
    return None if share is None else 100.0 * share
