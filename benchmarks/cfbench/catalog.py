"""Find what a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a data file under
``configs/`` or ``traffic/``. A traffic mix names the ``kind`` of driver
that can run it (``drivers/<kind>.py``). A metric is a data file under
``end_to_end/`` or ``layer_metrics/`` that names a reducer
(``reducers/<reducer>.py``) and its arguments. A configuration names its
plain reference (``reference/<name>.py``) and its FLOPs function
(``flops/<name>.py``). So a later PR adds files and entries, and edits
nothing that is here.
"""
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH_DIR)


def load_json(*parts) -> dict:
    path = os.path.join(BENCH_DIR, *parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SystemExit(f"benchmarks: {os.path.relpath(path, CHECKOUT)} "
                         f"not found") from None


def load_module(directory: str, name: str):
    """``benchmarks/<directory>/<name>.py`` as a module, found by name."""
    path = os.path.join(BENCH_DIR, directory, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmarks: no {directory}/{name}.py")
    module_name = f"cfbench_{directory}_{name}".replace("-", "_")
    if module_name in sys.modules:
        return sys.modules[module_name]
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(bench: dict, name: str) -> dict:
    for entry in bench["workloads"]:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"benchmarks: no workload {name!r} in BENCHMARK.json; "
                     f"it has {[w['name'] for w in bench['workloads']]}")


def config_of(bench: dict, cell_entry: dict) -> dict:
    for entry in bench["configs"]:
        if entry["name"] == cell_entry["config"]:
            with open(os.path.join(CHECKOUT, entry["file"])) as f:
                return json.load(f)
    raise SystemExit(f"benchmarks: no config {cell_entry['config']!r}")


def metrics_of(bench: dict, cell_name: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    with no ``workloads`` key, or with the cell in it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell_name in m["workloads"]]
