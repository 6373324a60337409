"""Every cell end to end with ``--rehearse`` on CPU devices (four virtual
ones for a four-chip cell), the last line parsed against the contract:
the cells of ``BENCHMARK.json`` in this tree, and the parked cells
(``benchmarks/parked.json``) in a copy whose ``BENCHMARK.json`` has their
entries merged in. A rehearsal of the control flow: its numbers mean
nothing."""
import json
import os
import subprocess
import sys

import pytest

from conftest import CHECKOUT, bench, make_checkout, merged_bench, parked


def run_cell(checkout, cell, trace, seconds=4, extra=("--rehearse",)):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    return subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "5", "--seconds", str(seconds),
         "--trace", str(trace), *extra],
        cwd=checkout, env=env, capture_output=True, text=True, timeout=600)


def check_line(bench, cell, trace, line):
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # each number compared beside its limit, last in the line
    assert list(line)[-1] == "checks" and line["checks"]
    for check in line["checks"].values():
        assert 0 <= check["value"] <= check["limit"]
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == cell)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    assert "memory_peak_bytes" in line["device"]
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in bench[group]
               if cell in m.get("workloads", [cell])}
    assert line["metrics"], "no metric reported"
    for name, metric in line["metrics"].items():
        assert metric["unit"] == allowed[name]
        assert isinstance(metric["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert line["metrics"][next(
            n for n in allowed if n.endswith("compiles_in_window"))][
                "value"] == 0.0
    else:
        assert set(line["metrics"]) == set(allowed)


@pytest.fixture(scope="module")
def with_parked(tmp_path_factory):
    return make_checkout(str(tmp_path_factory.mktemp("parked")),
                         merged_bench())


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize(
    "cell, is_parked",
    [(w["name"], False) for w in bench()["workloads"]]
    + [(w["name"], True) for w in parked()["workloads"]])
def test_cell_rehearses(cell, is_parked, trace, request):
    checkout = (request.getfixturevalue("with_parked") if is_parked
                else CHECKOUT)
    work = os.path.join(checkout, "benchmarks", ".work")
    before = set(os.listdir(work)) if os.path.isdir(work) else set()
    done = run_cell(checkout, cell, trace)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    check_line(merged_bench(), cell, trace, line)
    # a traced run says once how its ops' categories were decided: by
    # what the programs list inside them (a CPU's programs carry the map)
    assert ("note: op categories by contents" in done.stderr) == bool(trace)
    # the run's work directory is gone; only the rehearsals' cache stays
    assert set(os.listdir(work)) - before <= {"rehearsal-cache"}


def test_without_a_tpu_no_result_line():
    cell = bench()["workloads"][0]["name"]
    done = run_cell(CHECKOUT, cell, 0, extra=())
    assert done.returncode != 0
    assert "correct" not in done.stdout
    assert "needs a TPU" in done.stderr
