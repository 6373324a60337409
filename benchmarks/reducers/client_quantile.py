"""A quantile of a list of readings the driver took from outside."""
from cfbench import stats


def reduce(record, key: str, q: float, scale: float = 1.0):
    value = stats.quantile(record.client.get(key) or [], q)
    return None if value is None else value * scale
