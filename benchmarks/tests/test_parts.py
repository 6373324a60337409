"""The reducers that read the program's parts (``programs.json``
``op_parts``, PR 40) on a hand-made table, the level-0 FLOPs against a
count by hand, and the seven metrics' files through the harness's own
reduction."""
import json
import os

import pytest

import run
from cfbench import catalog
from cfbench.run_record import RunRecord
from conftest import BENCH_DIR, bench
from test_flops import FACTORS, by_hand

MS = 1_000_000   # ns

# one patch program and a second program that puts fusion.50 elsewhere
PROGRAMS = [
    {"family": "scatter", "op_parts": {
        "forward": {"enc0": ["fusion.10", "copy.1", "fusion.50"],
                    # named `out` after its root by XLA, `dec0` by the
                    # program: its widest convolution is dec0/conv3
                    "dec0": ["fusion.11"], "enc1": ["fusion.20"],
                    "bridge": ["fusion.30"], "pool0": ["reduce.1"],
                    "": ["fusion.40"]},
        "accumulate": {"": ["while.1", "fusion.60"]},
        "": {"": ["copy.8"]}}},
    {"family": "serve_forward", "op_parts": {
        "accumulate": {"": ["fusion.50"]}}},
    {"family": "from before the parts", "op_parts": None},
]
# what one forward of the patch program leaves on the device, ms
FORWARD = [("fusion.10 bf16[4,8]", "convolution", 4.0),
           ("copy.1 bf16[4,8]", "copy", 1.0),
           ("fusion.11 bf16[4,12]", "convolution", 3.0),
           ("fusion.20 bf16[4,8]", "convolution", 2.0),
           ("fusion.30 bf16[4,8]", "convolution", 0.5),
           ("reduce.1 bf16[4,8]", "reduce", 0.7),
           ("fusion.40 bf16[4,8]", "loop fusion", 0.2),   # no part
           ("fusion.50 bf16[4,8]", "loop fusion", 0.1),   # two places
           ("copy.9 f32[8]", "copy", 0.3)]                # no program's
BATCH = 4


def tables(forwards=2):
    ops, at = [], 0
    for _ in range(forwards):
        for name, category, ms in FORWARD:
            ops.append([name, category, at, int(ms * MS)])
            at += int(ms * MS)
        # the blend: a loop whose body op lies inside it
        ops.append(["while.1 s32[]", "while", at, MS])
        ops.append(["fusion.60 f32[8]", "loop fusion", at + MS // 4, MS // 2])
        at += MS
    return {"window_s": at / 1e9, "t0_ns": 0, "t1_ns": at,
            "devices": [{"name": "/device:TPU:0", "ops": ops}], "host": []}


def record(programs=PROGRAMS, **kw):
    return RunRecord(cell={"chips": 1}, config={"batch": BATCH}, traffic={},
                     device={"kind": "TPU v5 lite"}, trace=tables(),
                     programs=programs, **kw)


def metric_args(name):
    definition = catalog.load_json("layer_metrics", name + ".json")
    return catalog.load_module("reducers", definition["reducer"]), \
        definition.get("args", {})


MS_METRICS = {"level0_conv_ms_patch": 7.0 / BATCH,
              "level0_rest_ms_patch": 1.0 / BATCH,
              "level1_ms_patch": 2.0 / BATCH,
              "deep_ms_patch": 0.5 / BATCH,
              "glue_ms_patch": 0.7 / BATCH}


@pytest.mark.parametrize("name, want", sorted(MS_METRICS.items()))
def test_ms_a_patch_of_a_group_of_parts(name, want):
    reducer, args = metric_args(name)
    assert reducer.reduce(record(), **args) == pytest.approx(want)


def test_the_parts_and_the_unnamed_time_add_up_to_the_scope():
    parts = catalog.load_module("reducers", "trace_part_ms")
    named = sum(metric_args(name)[0].reduce(record(), **metric_args(name)[1])
                for name in MS_METRICS)
    # an op with no part and one that two programs place differently
    unnamed = parts.reduce(record(), unnamed=True)
    assert unnamed == pytest.approx((0.2 + 0.1) / BATCH)
    # every op under `forward` in some program, and no other: not the
    # blend's loop, not the copy no program lists
    under_forward = sum(ms for name, _, ms in FORWARD
                        if not name.startswith("copy.9"))
    assert named + unnamed == pytest.approx(under_forward / BATCH)
    assert parts.reduce(record(), parts=".*") + unnamed == pytest.approx(
        under_forward / BATCH)
    reducer, args = metric_args("forward_unnamed_share")
    assert reducer.reduce(record(), **args) == pytest.approx(
        100.0 * 0.3 / under_forward)
    # another scope's time is its own
    assert parts.reduce(record(), scope="accumulate", unnamed=True) \
        == pytest.approx((1.0 + 0.1) / BATCH)


def test_the_parts_roofline_divides_a_patchs_flops_by_its_time():
    reducer, args = metric_args("level0_conv_roofline")
    config = {"batch": BATCH, "patch": [20, 256, 256], "model": {
        "width": [28, 36, 48, 64], "pooling": FACTORS,
        "in_channels": 1, "out_channels": 3}}
    one = record()
    one.config = config
    level0 = catalog.load_module("flops", "rsunet_level0")
    assert reducer.reduce(one, **args) == pytest.approx(
        100.0 * level0.flops_per_patch(config)
        / (1e-3 * MS_METRICS["level0_conv_ms_patch"] * 197e12))
    # no row of peaks for the device (a rehearsal's CPU): no share of one
    one.device = {"kind": "cpu"}
    assert reducer.reduce(one, **args) is None


@pytest.mark.parametrize("name", sorted(MS_METRICS) + [
    "forward_unnamed_share", "level0_conv_roofline"])
def test_none_where_no_program_carries_the_parts(name):
    reducer, args = metric_args(name)
    before = [{"family": "scatter", "op_scopes": {"forward": ["fusion.10"]}}]
    assert reducer.reduce(record(programs=before), **args) is None
    assert reducer.reduce(record(programs=[]), **args) is None
    untraced = record()
    untraced.trace = None
    assert reducer.reduce(untraced, **args) is None


@pytest.mark.parametrize("config", [
    "rsunet-superhuman", "rsunet-deepem", "rsunet-superhuman-prod",
    "rsunet-superhuman-masked"])
def test_level0_flops_are_the_level0_terms_of_the_whole_count(config):
    with open(os.path.join(BENCH_DIR, "configs", config + ".json")) as f:
        config = json.load(f)
    w0, w1, w2, w3 = width = config["model"]["width"]
    cin, cout = config["model"]["in_channels"], config["model"]["out_channels"]
    assert config["model"]["pooling"] == FACTORS
    v0 = config["patch"][0] * config["patch"][1] * config["patch"][2]
    level0 = (2 * 25 * cin * w0 + 2 * 63 * w0 * w0 + 2 * 63 * w0 * w0
              + 2 * w0 * cout) * v0      # embed, enc0, dec0, out
    flops = catalog.load_module("flops", "rsunet_level0")
    assert flops.flops_per_patch(config) == level0
    # and the rest of the whole count is the other levels and the glue
    whole = catalog.load_module("flops", config["flops"]) \
        .flops_per_patch(config)
    v1, v2, v3 = v0 // 4, v0 // 32, v0 // 256
    rest = (2 * (9 * w0 * w1 + 54 * w1 * w1) * v1          # enc1
            + 2 * (9 * w1 * w2 + 54 * w2 * w2) * v2        # enc2
            + 2 * (9 * w2 * w3 + 54 * w3 * w3) * v3        # bridge
            + 2 * w3 * w2 * v2 + 2 * 63 * w2 * w2 * v2     # up2, dec2
            + 2 * w2 * w1 * v1 + 2 * 63 * w1 * w1 * v1     # up1, dec1
            + 2 * w1 * w0 * v0)                            # up0
    assert whole == level0 + rest
    if cout == 3 and cin == 1:
        assert whole == by_hand(width, config["patch"])
    if width[0] == 28:   # 0.261 of the 0.396 TFLOP a patch
        assert (round(level0 / 1e12, 3), round(whole / 1e12, 3)) == (
            0.261, 0.396)


def test_the_harness_prints_the_seven_metrics_in_every_cell():
    """A rehearsal's CPU has no device plane, so its line leaves every
    ``device_trace`` metric out; here the harness's own reduction runs
    the seven entries of ``BENCHMARK.json`` over the hand-made table."""
    benchmark = bench()
    new = list(MS_METRICS) + ["forward_unnamed_share", "level0_conv_roofline"]
    config = {"batch": BATCH, "patch": [20, 256, 256], "model": {
        "width": [28, 36, 48, 64], "in_channels": 1, "out_channels": 3}}
    for cell in benchmark["workloads"]:
        entries = [m for m in catalog.metrics_of(
            benchmark, cell["name"], "per_layer") if m["name"] in new]
        assert [m["name"] for m in entries] == new
        assert all(m["layer"] == "patch program"
                   and m["source"] == "device_trace"
                   and m["moves"] == "volume_mvox_s" for m in entries)
        one = record()
        one.config = config
        line = run.reduce_metrics(entries, "layer_metrics", one)
        assert list(line) == new
        assert {m["unit"] for m in line.values()} == {"ms", "%"}
        # and on a program from before the parts the line leaves them out
        assert run.reduce_metrics(
            entries, "layer_metrics", record(programs=[])) == {}
