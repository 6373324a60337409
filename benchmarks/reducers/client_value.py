"""A number the driver saw from outside, as it is: ``client[key]``."""


def reduce(record, key: str, scale: float = 1.0):
    value = record.client.get(key)
    return None if value is None else value * scale
