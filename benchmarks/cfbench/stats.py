"""Order statistics, one way, for every metric."""


def quantile(values, q: float):
    """Linear-interpolated ``q``-quantile (0..1) of ``values``; None when
    there are none."""
    data = sorted(values)
    if not data:
        return None
    pos = q * (len(data) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(data) - 1)
    return data[lo] + (data[hi] - data[lo]) * (pos - lo)
