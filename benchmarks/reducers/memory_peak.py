"""A per-device peak of ``device.memory_stats()`` read after the window
(``client[key]``: bytes in use, or bytes the runtime reserved), the
largest over the cell's devices."""


def reduce(record, key: str, scale: float = 1e-9):
    peaks = record.client.get(key) or []
    return max(peaks) * scale if peaks and max(peaks) > 0 else None
