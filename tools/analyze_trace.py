"""Offline op-level analysis of a jax.profiler trace.

tensorboard's profile plugin is not installed in this image, so this
parser extracts the op-level story directly from the
``*.trace.json.gz`` event files a ``jax.profiler`` capture writes: top
ops by total device time, grouped by XLA op category (convolution /
fusion / copy / all-reduce / ...), with per-category totals. That
attribution is what decides the next forward-pass lever.

Since PR 8 this is also the summarizer for the device-performance
plane's bounded captures (core/profiling.py: windowed ``--profile-dir``
runs, anomaly captures, the ``POST /profile`` route): importable
(:func:`summarize_trace_dir`), machine-readable (``--json``), and an
empty or missing trace dir is a warning, not a crash — ``log-summary``
calls through here for every ``profile-*`` dir it finds under a
metrics dir.

Usage: python tools/analyze_trace.py trace_dir [--top N] [--json]
"""
from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys


def find_trace_files(trace_dir: str):
    pattern = os.path.join(
        trace_dir, "**", "*.trace.json.gz"
    )
    return sorted(glob.glob(pattern, recursive=True))


def load_events(path: str):
    with gzip.open(path, "rt") as f:
        data = json.load(f)
    return data.get("traceEvents", [])


_CATEGORY_RULES = (
    ("convolution", re.compile(r"conv", re.I)),
    ("matmul", re.compile(r"dot|gemm|matmul", re.I)),
    ("copy/transpose", re.compile(r"copy|transpose|reshape|bitcast", re.I)),
    ("scatter", re.compile(r"scatter", re.I)),
    ("gather/slice", re.compile(r"gather|slice", re.I)),
    ("reduce", re.compile(r"reduce|all-reduce|psum", re.I)),
    ("fusion", re.compile(r"fusion", re.I)),
    ("infeed/outfeed", re.compile(r"infeed|outfeed|transfer", re.I)),
)


def categorize(name: str) -> str:
    for cat, rx in _CATEGORY_RULES:
        if rx.search(name):
            return cat
    return "other"


def device_op_durations(events):
    """name -> total device-lane microseconds. Device lanes are the pids
    whose process_name metadata mentions TPU/device; fall back to 'every
    complete event with a duration' when metadata is absent."""
    device_pids = set()
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            name = str(e.get("args", {}).get("name", ""))
            if re.search(r"tpu|device|/device:", name, re.I):
                device_pids.add(e.get("pid"))
    durations = collections.Counter()
    counts = collections.Counter()
    host_rx = re.compile(r"\.py:|PjitFunction|^trace$")
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        if device_pids:
            if e.get("pid") not in device_pids:
                continue
        elif host_rx.search(e.get("name", "")):
            # no device metadata (CPU traces): drop python-frame events
            continue
        name = e.get("name", "?")
        durations[name] += e["dur"]
        counts[name] += 1
    return durations, counts


def summarize_trace_dir(trace_dir: str, top: int = 25) -> dict:
    """Aggregate every ``*.trace.json.gz`` under ``trace_dir`` (an
    empty or missing dir yields ``files == 0``, never raises)::

        {"trace_dir": ..., "files": n, "total_device_us": x,
         "categories": [{"category", "us", "share"}, ...],   # sorted
         "top_ops": [{"name", "us", "share", "count"}, ...]}
    """
    files = find_trace_files(trace_dir)
    durations = collections.Counter()
    counts = collections.Counter()
    for path in files:
        try:
            d, c = device_op_durations(load_events(path))
        except (OSError, ValueError):
            continue  # a torn/corrupt trace file is skippable evidence
        durations.update(d)
        counts.update(c)
    total_us = sum(durations.values())
    by_cat = collections.Counter()
    for name, dur in durations.items():
        by_cat[categorize(name)] += dur
    return {
        "trace_dir": trace_dir,
        "files": len(files),
        "total_device_us": total_us,
        "categories": [
            {"category": cat, "us": dur,
             "share": dur / total_us if total_us else 0.0}
            for cat, dur in by_cat.most_common()
        ],
        "top_ops": [
            {"name": name, "us": dur,
             "share": dur / total_us if total_us else 0.0,
             "count": counts[name]}
            for name, dur in durations.most_common(top)
        ],
    }


def print_summary(summary: dict) -> None:
    """Human rendering of a :func:`summarize_trace_dir` result."""
    print(f"{summary['files']} trace file(s); total device-op time "
          f"{summary['total_device_us'] / 1e3:.2f} ms\n")
    print("== by category ==")
    for row in summary["categories"]:
        print(f"{row['us'] / 1e3:10.2f} ms  {100 * row['share']:5.1f}%"
              f"  {row['category']}")
    print(f"\n== top {len(summary['top_ops'])} ops ==")
    for row in summary["top_ops"]:
        print(f"{row['us'] / 1e3:10.2f} ms  {100 * row['share']:5.1f}%"
              f"  x{row['count']:<5d} {row['name'][:90]}")


def main(argv=None) -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("trace_dir")
    parser.add_argument("--top", type=int, default=25)
    parser.add_argument(
        "--json", action="store_true",
        help="emit the summary as one JSON object (log-summary "
             "consumption) instead of the human tables",
    )
    args = parser.parse_args(argv)

    summary = summarize_trace_dir(args.trace_dir, top=args.top)
    if summary["files"] == 0:
        # a missing/empty dir is an answer (nothing captured here), not
        # a crash: log-summary sweeps every profile-* candidate dir
        print(f"warning: no *.trace.json.gz under {args.trace_dir}",
              file=sys.stderr)
        if args.json:
            print(json.dumps(summary))
        return 0
    if args.json:
        print(json.dumps(summary))
    else:
        print_summary(summary)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
