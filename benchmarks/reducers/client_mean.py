"""The mean of a list of readings the driver took from outside."""


def reduce(record, key: str, scale: float = 1.0):
    values = record.client.get(key) or []
    return sum(values) / len(values) * scale if values else None
