"""One counter as a share of another, in percent. None where the program
has neither (it is from before they were added)."""


def reduce(record, numerator: str, denominator: str):
    bottom = record.counters.get(denominator)
    if not bottom:
        return None
    return 100.0 * record.counters.get(numerator, 0.0) / bottom
