"""Share of device busy time in ops that lie under the given named scopes
of the program (``jax.named_scope``), in percent, mean over devices.

The trace's tables keep an op's name and category, not its metadata, so
the scope is joined on the name: each ``programs.json`` entry of a traced
run carries ``op_scopes``, ``{scope: [op names]}`` read by the program
out of its compiled module, the ops under no scope listed under ``""``.
An op name that two programs put under different scopes counts as
ambiguous, and so does one that no program lists (an op of a program
outside the program cache): with ``unscoped`` the reducer gives the share
of those and of the ops under ``""``, which says how far the scope shares
can be trusted. ``not_category`` (a regex) drops ops of a category, as
``"^convolution$"`` does to read a scope's copies and elementwise passes.
Returns None where no program carries ``op_scopes`` (a program from
before the scopes), so the line leaves the metric out.
"""
import re

from cfbench import trace

AMBIGUOUS = object()


def scope_of_ops(programs: list):
    """``{op name: scope}`` over every program's ``op_scopes``; a name
    mapped two ways maps to ``AMBIGUOUS``. None if no program has any."""
    names: dict = {}
    found = False
    for program in programs:
        for scope, ops in (program.get("op_scopes") or {}).items():
            found = True
            for op in ops:
                if names.setdefault(op, scope) != scope:
                    names[op] = AMBIGUOUS
    return names if found else None


def reduce(record, scopes: list = (), unscoped: bool = False,
           not_category: str = None):
    if not record.trace:
        return None
    names = scope_of_ops(record.programs)
    if names is None:
        return None
    skip = re.compile(not_category, re.I) if not_category else None
    shares = []
    for device, busy in zip(record.trace["devices"],
                            trace.busy_seconds(record.trace)):
        if busy <= 0:
            continue
        hit = 0.0
        for (short, category), seconds in \
                trace._leaf_seconds(device).items():
            scope = names.get(short.split(" ", 1)[0], AMBIGUOUS)
            if skip is not None and skip.search(category):
                continue
            if unscoped:
                hit += seconds if scope in (AMBIGUOUS, "") else 0.0
            elif scope in scopes:
                hit += seconds
        shares.append(hit / busy)
    return 100.0 * sum(shares) / len(shares) if shares else None
