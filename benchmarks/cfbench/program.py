"""What the benchmark takes from the program: its command line, run in
this process as a user's shell would run it, and the spans, counters and
program records it leaves under ``--metrics-dir``. Nothing else of the
program is read for a metric."""
import glob
import json
import os


def chunkflow(*args) -> None:
    """One ``chunkflow`` command line through the CLI entry point, in this
    process (one process holds the chip). Nothing is caught."""
    from chunkflow_tpu.flow.cli import main

    main([str(a) for a in args], standalone_mode=False)


def read_events(metrics_dir: str) -> list:
    """Every JSONL event of the run's telemetry stream, in file order."""
    events = []
    for path in sorted(glob.glob(os.path.join(metrics_dir, "*.jsonl*"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if line:
                    events.append(json.loads(line))
    return events


def read_spans(events: list) -> list:
    """``{"name", "t" (end, time.time()), "dur_s"}`` per span event."""
    return [e for e in events if e.get("kind") == "span"]


def read_counters(events: list) -> dict:
    """Final counter and gauge values: the last snapshot wins."""
    counters: dict = {}
    for event in events:
        if event.get("kind") == "snapshot":
            counters.update(event.get("counters") or {})
            counters.update(event.get("gauges") or {})
    return counters


def read_programs(metrics_dir: str) -> list:
    """The ``programs.json`` entries: family, label, compile_s, calls."""
    programs = []
    for path in glob.glob(os.path.join(metrics_dir, "programs*.json")):
        with open(path) as f:
            programs += json.load(f)["programs"]
    return programs
