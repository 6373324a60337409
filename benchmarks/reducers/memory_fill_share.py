"""How full the fullest device was, in percent of what it can hold: the
peak of the buffers in use plus the peak the runtime reserved for the
loaded programs' scratch (``client[in_use_key]``, ``client[reserved_key]``,
per device, as ``memory_stats()`` gave them after the window) over the
device's ``bytes_limit`` (``client[limit_key]``, which the driver read
from the same ``memory_stats()``). None where any of the three is missing
or zero, as on a device that keeps no such statistics."""


def reduce(record, in_use_key: str, reserved_key: str, limit_key: str):
    in_use = record.client.get(in_use_key) or []
    reserved = record.client.get(reserved_key) or []
    limit = record.client.get(limit_key) or []
    shares = [(used + held) / cap
              for used, held, cap in zip(in_use, reserved, limit)
              if cap and used + held > 0]
    if not shares or len(shares) != len(in_use):
        return None
    return 100.0 * max(shares)
