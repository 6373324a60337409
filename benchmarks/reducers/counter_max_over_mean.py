"""Largest over mean of the counters whose names match ``pattern``."""
import re


def reduce(record, pattern: str):
    rule = re.compile(pattern)
    values = [v for name, v in record.counters.items() if rule.fullmatch(name)]
    if not values or sum(values) <= 0:
        return None
    return max(values) / (sum(values) / len(values))
