"""``BENCHMARK.json`` and the files it names, held to the limits the driver
checks before it makes a single run; and the same limits on what it
would be with the parked cells (``benchmarks/parked.json``) merged in."""
import json
import os
import re

import pytest

from conftest import BENCH_DIR, CHECKOUT, bench, merged_bench, parked

BOTH = pytest.mark.parametrize("load", [bench, merged_bench])

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmarks"]
    assert len(b["command"]) <= 32 and all(one_line(w) for w in b["command"])
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(CHECKOUT, "BENCHMARK.json")) < 65536
    # a full check of 24 cells must fit into 43200 s
    runs = 2 + 14 * 24
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    b = bench()
    files = set()
    used = {w["config"] for w in b["workloads"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            data = json.load(f)
        assert data["name"] == c["name"]
        assert data["reduced"] == c["reduced"]
        assert data["tolerance"]["why"]
    assert len({c["name"] for c in b["configs"]}) == len(b["configs"])


@BOTH
def test_workloads(load):
    b = load()
    cells = b["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    configs = {c["name"] for c in b["configs"]}
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert one_line(w["why"]), (w["name"], len(w["why"]))
        assert os.path.exists(os.path.join(
            BENCH_DIR, "traffic", w["traffic"] + ".json"))
    four = sum(w["chips"] == 4 for w in cells)
    assert four <= max(1, len(cells) // 4)


@BOTH
def test_metrics(load):
    b = load()
    cells = {w["name"] for w in b["workloads"]}
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(b["end_to_end"]) <= 16 and 1 <= len(b["per_layer"]) <= 128
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert os.path.exists(os.path.join(
            BENCH_DIR, "end_to_end", m["name"] + ".json"))
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and one_line(m["layer"])
        assert os.path.exists(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".json"))
        # reported only where the metric it moves is
        where = set(m.get("workloads", cells))
        assert where <= set(e2e[m["moves"]].get("workloads", cells))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    for cell in cells:
        mine = [m for m in b["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])


def test_file_names_under_paths():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    skip = {".work", "__pycache__"}
    for root, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in skip]
        for name in files:
            rel = os.path.relpath(os.path.join(root, name), CHECKOUT)
            assert allowed.match(rel), rel


def test_the_harness_names_no_cell_config_or_metric():
    """No `if name ==` anywhere: the code under cfbench/, drivers/,
    reducers/ and run.py mentions no name BENCHMARK.json defines."""
    b = merged_bench()
    names = ([w["name"] for w in b["workloads"]]
             + [c["name"] for c in b["configs"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]
                if m["name"] != "setup_s"])
    sources = [os.path.join(BENCH_DIR, "run.py")]
    for sub in ("cfbench", "drivers", "reducers"):
        for name in os.listdir(os.path.join(BENCH_DIR, sub)):
            if name.endswith(".py"):
                sources.append(os.path.join(BENCH_DIR, sub, name))
    for path in sources:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(rf"[\"']{re.escape(name)}[\"']", text), \
                (os.path.relpath(path, CHECKOUT), name)
        assert "CHUNKFLOW_" not in text, path


def test_parked_metrics_belong_to_parked_cells():
    """``parked.json`` holds every entry of a parked cell, the span metrics
    of PR 23 among them (until PR 35 in a file beside it), and none that
    names an accepted cell."""
    extra = parked()
    cells = {w["name"] for w in extra["workloads"]}
    for m in extra["end_to_end"] + extra["per_layer"]:
        assert m["workloads"] and set(m["workloads"]) <= cells, m["name"]
    names = {m["name"] for m in extra["per_layer"]}
    assert {"data4_task_inside_p50_ms", "data4_idle_unattributed_share",
            "serve_codec_ms_request"} <= names
    assert not os.path.exists(os.path.join(BENCH_DIR, "parked_spans.json"))


def test_every_parked_cell_says_why():
    extra = parked()
    assert {w["name"] for w in extra["workloads"]} == set(extra["why"])
    listed = {w["name"] for w in bench()["workloads"]}
    assert not listed & set(extra["why"])
