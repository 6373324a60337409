"""Time in collective ops over device busy time, in percent."""
from cfbench import trace


def reduce(record):
    if not record.trace:
        return None
    share = trace.collective_share(record.trace)
    return None if share is None else 100.0 * share
