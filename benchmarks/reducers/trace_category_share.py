"""Share of device busy time in ops whose category matches ``pattern``,
in percent. The category is read from the op's text
(``cfbench.trace.parse_op``) and, where the run's programs say what
their ops hold, decided by that (``cfbench.trace.file_by_contents``,
applied once by ``run.py``): an op is a ``convolution`` where a program
lists one inside it."""
from cfbench import trace


def reduce(record, pattern: str):
    if not record.trace:
        return None
    share = trace.category_share(record.trace, pattern)
    return None if share is None else 100.0 * share
