"""Sum of one field over the run's ``programs.json`` entries."""


def reduce(record, field: str = "compile_s"):
    if not record.programs:
        return None
    return float(sum(p.get(field) or 0.0 for p in record.programs))
