"""The benchmark's own tests: not tier-1, run by whoever changes the
benchmark. CPU only; nothing here is a measurement."""
import json
import os
import shutil
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(TESTS)
CHECKOUT = os.path.dirname(BENCH_DIR)
for path in (BENCH_DIR, CHECKOUT):
    if path not in sys.path:
        sys.path.insert(0, path)


def bench() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def parked() -> dict:
    with open(os.path.join(BENCH_DIR, "parked.json")) as f:
        return json.load(f)


def merged_bench() -> dict:
    """``BENCHMARK.json`` with the parked cells' entries appended: what a
    later PR that admits them would commit."""
    merged, extra = bench(), parked()
    for group in ("workloads", "end_to_end", "per_layer"):
        merged[group] = merged[group] + extra[group]
    return merged


def make_checkout(directory: str, benchmark: dict) -> str:
    """A checkout that differs from the tree by its ``BENCHMARK.json``
    alone: a copy of ``benchmarks/``, the program linked in."""
    shutil.copytree(BENCH_DIR, os.path.join(directory, "benchmarks"),
                    ignore=shutil.ignore_patterns(".work", "__pycache__"))
    os.symlink(os.path.join(CHECKOUT, "chunkflow_tpu"),
               os.path.join(directory, "chunkflow_tpu"))
    with open(os.path.join(directory, "BENCHMARK.json"), "w") as f:
        json.dump(benchmark, f)
    return directory
