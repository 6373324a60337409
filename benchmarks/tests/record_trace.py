#!/usr/bin/env python3
"""Record a trace fixture for ``test_trace.py``:

    python3 benchmarks/tests/record_trace.py <profile dir> <chips> \\
        <start s> <seconds> <out.json.gz> [<programs.json> <out.json>]

``<profile dir>`` is what ``jax.profiler.start_trace`` wrote (it holds
``plugins/profile/<session>/*.xplane.pb``). The script reads it into the
tables every reduction works on (``cfbench.trace.load_run_trace``), cuts
``<seconds>`` from ``<start s>`` after the first event, and writes them
as gzipped JSON, op names already shortened by ``parse_op`` and filed by
its text rule (``trace.file_by_contents`` is applied by whoever reads
them, with the programs). Look at the planes and a few raw events by hand
first (``jax.profiler.ProfileData``), and write what they are called into
PERF.md. With the run's ``programs.json`` it also keeps, of every entry,
the family, the label and the three op maps the reducers join on.
"""
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cfbench import trace  # noqa: E402


KEPT = ("family", "label", "op_scopes", "op_parts", "op_convolutions")


def main(profile_dir, chips, start_s, seconds, out,
         programs=None, programs_out=None) -> int:
    tables = trace.load_run_trace(profile_dir, int(chips))
    if not tables["devices"]:
        raise SystemExit(f"no device plane under {profile_dir}")
    with gzip.open(out, "wt") as f:
        json.dump(trace.cut(tables, float(start_s), float(seconds)), f)
    if programs:
        with open(programs) as f:
            entries = json.load(f)["programs"]
        with open(programs_out, "w") as f:
            json.dump({"programs": [
                {key: entry.get(key) for key in KEPT} for entry in entries]},
                f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(*sys.argv[1:]))
