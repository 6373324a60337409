#!/usr/bin/env python3
"""Record a trace fixture for ``test_trace.py``:

    python3 benchmarks/tests/record_trace.py <profile dir> <chips> \\
        <start s> <seconds> <out.json.gz>

``<profile dir>`` is what ``jax.profiler.start_trace`` wrote (it holds
``plugins/profile/<session>/*.xplane.pb``). The script reads it into the
tables every reduction works on (``cfbench.trace.load_run_trace``), cuts
``<seconds>`` from ``<start s>`` after the first event, and writes them
as gzipped JSON, op names already shortened by ``parse_op``. Look at the
planes and a few raw events by hand first (``jax.profiler.ProfileData``),
and write what they are called into PERF.md.
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cfbench import trace  # noqa: E402


def main(profile_dir, chips, start_s, seconds, out) -> int:
    tables = trace.load_run_trace(profile_dir, int(chips))
    if not tables["devices"]:
        raise SystemExit(f"no device plane under {profile_dir}")
    with gzip.open(out, "wt") as f:
        json.dump(trace.cut(tables, float(start_s), float(seconds)), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
