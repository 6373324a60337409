"""1 - union of device-op intervals over the traced window, in percent:
the mean over the cell's devices, or with ``worst`` the idlest one."""
from cfbench import trace


def reduce(record, worst: bool = False):
    shares = trace.idle_shares(record.trace) if record.trace else []
    if not shares:
        return None
    return 100.0 * (max(shares) if worst else sum(shares) / len(shares))
