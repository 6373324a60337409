"""A configuration, a traffic mix and a per-layer metric dropped in as new
files and new entries are found with no code edited: the copy of the
benchmark this test makes differs from the tree by added files and by
entries appended to ``BENCHMARK.json`` alone."""
import json
import os

from conftest import bench as load_bench, make_checkout
from test_rehearsal import check_line, run_cell


def test_new_files_and_entries_are_enough(tmp_path):
    bench = load_bench()
    checkout = make_checkout(str(tmp_path / "checkout"), bench)
    before = {}
    for root, _, files in os.walk(os.path.join(checkout, "benchmarks")):
        for name in files:
            path = os.path.join(root, name)
            with open(path, "rb") as f:
                before[path] = f.read()
    bdir = os.path.join(checkout, "benchmarks")

    def put(rel, obj):
        with open(os.path.join(bdir, rel), "w") as f:
            json.dump(obj, f)

    # a configuration: the same family at other widths, as its own file
    with open(os.path.join(bdir, "configs", "rsunet-deepem.json")) as f:
        config = json.load(f)
    config["name"] = "rsunet-narrow"
    put("configs/rsunet-narrow.json", config)
    # a traffic mix: the volume stream with another patch grid
    with open(os.path.join(bdir, "traffic", "volume.json")) as f:
        traffic = json.load(f)
    traffic["rehearse"]["patch_grid"] = [2, 3, 2]
    put("traffic/volume-tall.json", traffic)
    # a per-layer metric over a span no metric reads yet
    put("layer_metrics/stage_ms_task.json",
        {"reducer": "span_ms_per_task", "args": {"name": "pipeline/stage"}})
    cell = "rsunet-narrow.volume-tall"
    bench["configs"].append({
        "name": "rsunet-narrow", "source": "test",
        "file": "benchmarks/configs/rsunet-narrow.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": cell, "config": "rsunet-narrow", "traffic": "volume-tall",
        "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "rsunet-deepem.volume" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "stage_ms_task", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "inferencer, program cache",
        "moves": "volume_mvox_s", "workloads": [cell]})
    with open(os.path.join(checkout, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    for trace in (0, 1):
        done = run_cell(checkout, cell, trace)
        assert done.returncode == 0, done.stderr[-2000:]
        line = json.loads(done.stdout.strip().splitlines()[-1])
        check_line(bench, cell, trace, line)
        if trace:
            assert line["metrics"]["stage_ms_task"]["value"] > 0
    # nothing that was there has been edited
    for path, content in before.items():
        with open(path, "rb") as f:
            assert f.read() == content, path
