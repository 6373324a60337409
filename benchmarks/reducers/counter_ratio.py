"""One counter over another, the divisor optionally scaled by a number of
the configuration (``per_config`` names its key)."""


def reduce(record, numerator: str, denominator: str, per_config: str = None):
    top = record.counters.get(numerator)
    bottom = record.counters.get(denominator)
    if top is None or not bottom:
        return None
    if per_config:
        bottom *= record.config[per_config]
    return top / bottom
