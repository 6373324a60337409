"""An op is a convolution by what the run's programs say is inside it
(``programs.json`` ``op_convolutions``), not by its name or opcode
(``cfbench.trace.file_by_contents``, PR 46): on hand-made tables whose
categories come from ``parse_op`` of event texts, through the metric
files the cells use; on the two recorded traces from before the map,
which have to read as they did; and on a recorded v5e trace of a program
that holds an XLA convolution, a Pallas kernel the program lists as a
convolution and one it does not (``data/v5e_kernel_probe.*``)."""
import copy
import json
import os

import pytest

from cfbench import catalog, trace
from cfbench.run_record import RunRecord
from conftest import TESTS

DATA = os.path.join(TESTS, "data")
MS = 1_000_000   # ns
BATCH = 4


def text(name, shape, opcode, more=""):
    return (f"%{name} = {shape}{{1,0:T(8,128)(2,1)}} {opcode}({shape} "
            f"%p.1){more}")


# one forward of a patch program, as the device plane words it: ms each
FORWARD = [
    # a convolution with its epilogue; a program lists it
    (text("fusion.10", "bf16[4,8]", "fusion", ", kind=kOutput, calls=%fc"),
     4.0),
    # named and shaped after its root, the head; dec0/conv3 is inside
    (text("fusion.11", "bf16[4,12]", "fusion", ", kind=kOutput, calls=%fc"),
     3.0),
    # the pool's window on the folded array: kOutput, no convolution
    (text("fusion.218", "bf16[4,8]", "fusion", ", kind=kOutput, calls=%fc"),
     0.8),
    # a kernel the program lists as dec0/conv2's convolution
    (text("kernel_convolution_3x3x3.1", "bf16[4,8]", "custom-call",
          ', custom_call_target="tpu_custom_call"'), 2.0),
    # a kernel nobody lists, and XLA's own custom call
    (text("pool0.1", "bf16[4,8]", "custom-call",
          ', custom_call_target="tpu_custom_call"'), 0.5),
    (text("custom-call", "bf16[4,8]", "custom-call",
          ', custom_call_target="ConcatBitcast"'), 0.1),
    # one program lists a convolution under the name, another knows the
    # name without one
    (text("fusion.50", "bf16[4,8]", "fusion", ", kind=kLoop, calls=%fc"),
     0.2),
    (text("copy.1", "bf16[4,8]", "copy"), 1.0),
    # a bare instruction of a program outside the program cache
    (text("convolution.3", "f32[8]", "convolution", ", window={size=3}"),
     0.3),
]
PROGRAMS = [
    {"family": "scatter",
     "op_scopes": {"forward": [
         "fusion.10", "fusion.11", "fusion.218",
         "kernel_convolution_3x3x3.1", "pool0.1", "custom-call",
         "fusion.50", "copy.1"]},
     "op_parts": {"forward": {
         "enc0": ["fusion.10", "copy.1", "fusion.50"],
         "dec0": ["fusion.11", "kernel_convolution_3x3x3.1"],
         "pool0": ["fusion.218", "pool0.1", "custom-call"]}},
     "op_convolutions": {
         "fusion.10": [["enc0/conv2", "3x3x3"]],
         "fusion.11": [["dec0/conv3", "3x3x3"], ["out", "1x1x1"]],
         "kernel_convolution_3x3x3.1": [["dec0/conv2", "3x3x3"]],
         "fusion.50": [["enc0/conv1", "1x1x1"]]}},
    {"family": "serve_forward",
     "op_scopes": {"accumulate": ["fusion.50"]},
     "op_parts": {"accumulate": {"": ["fusion.50"]}},
     "op_convolutions": {}},
    {"family": "from before the maps", "op_scopes": None, "op_parts": None,
     "op_convolutions": None},
]
# the same run as a program from before PR 40 would describe it
BEFORE = [{key: value for key, value in program.items()
           if key != "op_convolutions"} for program in PROGRAMS]


def tables(forwards=2):
    ops, at = [], 0
    for _ in range(forwards):
        for event, ms in FORWARD:
            ops.append([*trace.parse_op(event), at, int(ms * MS)])
            at += int(ms * MS)
    return {"window_s": at / 1e9, "t0_ns": 0, "t1_ns": at,
            "devices": [{"name": "/device:TPU:0", "ops": ops}], "host": []}


def record(programs=PROGRAMS, file=True):
    filed = trace.file_by_contents(tables(), programs) if file else tables()
    return RunRecord(cell={"chips": 1}, config={"batch": BATCH}, traffic={},
                     device={"kind": "TPU v5 lite"}, trace=filed,
                     programs=programs)


def categories(filed):
    return {short.split(" ", 1)[0]: category
            for short, category, _, _ in filed["devices"][0]["ops"]}


def metric(name, one):
    definition = catalog.load_json("layer_metrics", name + ".json")
    return catalog.load_module("reducers", definition["reducer"]).reduce(
        one, **definition.get("args", {}))


BUSY = sum(ms for _, ms in FORWARD)


@pytest.mark.parametrize("name, by_text, by_contents", [
    # what a program lists a convolution for, whatever its opcode
    ("fusion.10", "convolution", "convolution"),
    ("fusion.11", "convolution", "convolution"),
    ("kernel_convolution_3x3x3.1", "custom-call", "convolution"),
    # a kOutput fusion without one
    ("fusion.218", "convolution", "output fusion"),
    # kernels nobody lists stay what their text says
    ("pool0.1", "custom-call", "custom-call"),
    ("custom-call", "custom-call", "custom-call"),
    # two programs disagree: the text rule's answer
    ("fusion.50", "loop fusion", "loop fusion"),
    ("copy.1", "copy", "copy"),
    # a bare convolution is one by its own text
    ("convolution.3", "convolution", "convolution"),
])
def test_an_ops_category_is_what_a_program_lists_inside_it(
        name, by_text, by_contents):
    assert categories(tables())[name] == by_text
    assert categories(trace.file_by_contents(tables(), PROGRAMS))[name] \
        == by_contents


def test_what_moved_and_what_is_ambiguous_is_counted():
    filing = trace.file_by_contents(tables(), PROGRAMS)["filing"]
    assert filing == {
        "by": "contents",
        "moved": {
            "fusion.218 bf16[4,8]": ["convolution", "output fusion"],
            "kernel_convolution_3x3x3.1 bf16[4,8]": ["custom-call",
                                                    "convolution"]},
        "ambiguous": ["fusion.50"]}
    note = trace.filing_note(filing)
    assert note.startswith("op categories by contents: 2 moved (fusion.218")
    assert note.endswith("1 ambiguous (fusion.50)")


def test_an_ambiguous_kOutput_fusion_keeps_the_text_rule():
    programs = copy.deepcopy(PROGRAMS)
    programs[1]["op_scopes"]["accumulate"].append("fusion.10")
    filed = trace.file_by_contents(tables(), programs)
    assert categories(filed)["fusion.10"] == "convolution"
    assert filed["filing"]["ambiguous"] == ["fusion.10", "fusion.50"]
    assert "fusion.10 bf16[4,8]" not in filed["filing"]["moved"]


@pytest.mark.parametrize("programs", [BEFORE, [], [{"family": "scatter"}]],
                         ids=["before-the-map", "no-programs", "bare-entry"])
def test_without_the_map_the_text_rule_stands(programs):
    plain = tables()
    filed = trace.file_by_contents(plain, programs)
    assert filed["filing"] == {"by": "text", "moved": {}, "ambiguous": []}
    assert filed["devices"] == plain["devices"]
    assert trace.filing_note(filed["filing"]) == \
        "op categories by text: 0 moved, 0 ambiguous"


def test_the_tables_handed_in_are_left_as_they_were():
    plain = tables()
    kept = copy.deepcopy(plain)
    trace.file_by_contents(plain, PROGRAMS)
    assert plain == kept


# by the text rule / by contents, ms of one forward
CONV_TEXT = 4.0 + 3.0 + 0.8 + 0.3
CONV_CONTENTS = 4.0 + 3.0 + 2.0 + 0.3


@pytest.mark.parametrize("name, by_text, by_contents", [
    # the window fusion leaves, the listed kernel enters
    ("forward_busy_share", 100 * CONV_TEXT / BUSY,
     100 * CONV_CONTENTS / BUSY),
    # under `forward`: all but the bare convolution no program knows and
    # fusion.50, which two programs put under different scopes
    ("scope_forward_nonconv_share",
     100 * (BUSY - 0.3 - 0.2 - (CONV_TEXT - 0.3)) / BUSY,
     100 * (BUSY - 0.3 - 0.2 - (CONV_CONTENTS - 0.3)) / BUSY),
    # the kernel enters under its part, dec0
    ("level0_conv_ms_patch", (4.0 + 3.0) / BATCH, (4.0 + 3.0 + 2.0) / BATCH),
    ("level0_rest_ms_patch", (2.0 + 1.0) / BATCH, 1.0 / BATCH),
    # pool0's time is the glue's in every category
    ("glue_ms_patch", (0.8 + 0.5 + 0.1) / BATCH, (0.8 + 0.5 + 0.1) / BATCH),
    # fusion.50, which two programs place differently, whatever it holds
    ("forward_unnamed_share", 100 * 0.2 / (BUSY - 0.3),
     100 * 0.2 / (BUSY - 0.3)),
])
def test_the_cells_metrics_read_the_filed_categories(
        name, by_text, by_contents):
    assert metric(name, record(file=False)) == pytest.approx(by_text)
    assert metric(name, record()) == pytest.approx(by_contents)


def test_the_forwards_are_counted_over_what_is_filed():
    forwards_in = catalog.load_module("reducers", "step_mfu").forwards_in
    by_text, by_contents = record(file=False), record()
    # every op runs once a forward: the count does not move, whichever
    # ops are the convolutions
    assert forwards_in(by_text.trace["devices"][0]) == 2.0
    assert forwards_in(by_contents.trace["devices"][0]) == 2.0
    names = {short.split(" ", 1)[0]
             for short, category, _, _ in by_contents.trace["devices"][0][
                 "ops"] if category == "convolution"}
    assert names == {"fusion.10", "fusion.11", "kernel_convolution_3x3x3.1",
                     "convolution.3"}


def test_the_parts_and_the_unnamed_time_still_add_up_to_the_scope():
    parts = catalog.load_module("reducers", "trace_part_ms")
    for one in (record(file=False), record()):
        named = sum(metric(name, one) for name in (
            "level0_conv_ms_patch", "level0_rest_ms_patch",
            "level1_ms_patch", "deep_ms_patch", "glue_ms_patch"))
        unnamed = parts.reduce(one, unnamed=True)
        assert named + unnamed == pytest.approx((BUSY - 0.3) / BATCH)
        assert parts.reduce(one, parts=".*", category="^convolution$") \
            + parts.reduce(one, parts=".*", not_category="^convolution$") \
            + unnamed == pytest.approx((BUSY - 0.3) / BATCH)


def test_the_breakdown_says_what_is_inside_an_op():
    one = record()
    top = dict(trace.top_ops(one.trace, 10, one.programs))
    assert set(top) == {
        "fusion.10 bf16[4,8] [convolution] enc0 enc0/conv2 3x3x3",
        # XLA names and shapes it after the head; the time is dec0/conv3's
        "fusion.11 bf16[4,12] [convolution] dec0 dec0/conv3 3x3x3 "
        "out 1x1x1",
        "kernel_convolution_3x3x3.1 bf16[4,8] [convolution] dec0 "
        "dec0/conv2 3x3x3",
        "fusion.218 bf16[4,8] [output fusion] pool0",
        "pool0.1 bf16[4,8] [custom-call] pool0",
        "custom-call bf16[4,8] [custom-call] pool0",
        # no part where two programs place it differently
        "fusion.50 bf16[4,8] [loop fusion] enc0/conv1 1x1x1",
        "copy.1 bf16[4,8] [copy] enc0",
        "convolution.3 f32[8] [convolution]"}
    assert top["fusion.11 bf16[4,12] [convolution] dec0 dec0/conv3 3x3x3 "
               "out 1x1x1"] == pytest.approx(2 * 3.0e-3)
    assert trace.breakdown(one.trace, one.programs)["device_ops"] \
        == trace.top_ops(one.trace, 10, one.programs)
    # without the programs an entry is the name and the category, as it was
    assert dict(trace.top_ops(one.trace, 1)) == {
        "fusion.10 bf16[4,8] [convolution]": pytest.approx(2 * 4.0e-3)}


# ---------------------------------------------------------------------------
# the recorded traces from before the map read as they did
# ---------------------------------------------------------------------------
def scopes_programs():
    with open(os.path.join(
            DATA, "v5e_superhuman_volume_scopes.programs.json")) as f:
        return json.load(f)["programs"]


@pytest.mark.parametrize("recorded, programs", [
    ("v5e_superhuman_volume.trace.json.gz", lambda: []),
    ("v5e_superhuman_volume_scopes.trace.json.gz", scopes_programs),
])
def test_a_recorded_run_without_the_map_reads_as_before(recorded, programs):
    plain = trace.load(os.path.join(DATA, recorded))
    filed = trace.file_by_contents(plain, programs())
    assert filed["filing"]["by"] == "text"
    assert filed["devices"] == plain["devices"]
    assert trace.category_seconds(filed) == trace.category_seconds(plain)
    assert trace.top_ops(filed, 10, programs()) == trace.top_ops(plain, 10)


# ---------------------------------------------------------------------------
# a recorded v5e trace of a cell, with the map (PR 46)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def anchor():
    """1.5 steady seconds cut (``record_trace.py``'s way) out of the 6 s
    trace of a ``rsunet-superhuman.volume`` run on the chip (PR 46, the
    tree of PR 43), filed by the text rule as every recorded table is, and
    the three op maps of that run's one ``programs.json`` entry."""
    with open(os.path.join(
            DATA, "v5e_superhuman_volume_contents.programs.json")) as f:
        programs = json.load(f)["programs"]
    plain = trace.load(os.path.join(
        DATA, "v5e_superhuman_volume_contents.trace.json.gz"))
    return plain, programs


def test_the_pools_windows_leave_the_convolutions_of_a_recorded_cell(anchor):
    plain, programs = anchor
    assert len(plain["devices"][0]["ops"]) == 14386
    filed = trace.file_by_contents(plain, programs)
    assert filed["filing"] == {"by": "contents", "ambiguous": [], "moved": {
        "fusion.1015 bf16[20,128,32,9,112]": ["convolution", "output fusion"],
        "fusion.1025 bf16[10,64,32,9,72]": ["convolution", "output fusion"]}}
    places = trace.places_of_ops(programs)
    assert places["fusion.1015"] == {("forward", "pool0")}
    assert places["fusion.1025"] == {("forward", "pool1")}
    assert trace.busy_seconds(filed) == trace.busy_seconds(plain) == [
        pytest.approx(1.499990222, abs=1e-9)]
    assert trace.category_seconds(plain)[0]["convolution"] == \
        pytest.approx(1.166225717, abs=1e-8)
    seconds = trace.category_seconds(filed)[0]
    assert seconds["convolution"] == pytest.approx(1.141631052, abs=1e-8)
    assert seconds["output fusion"] == pytest.approx(0.024594665, abs=1e-8)


@pytest.mark.parametrize("name, by_text, by_contents", [
    ("forward_busy_share", 77.74982, 76.11016),
    ("scope_forward_nonconv_share", 18.08822, 19.72788),
    ("scope_forward_busy_share", 95.83711, 95.83711),
    # the forwards are a mean over the convolutions' event counts (22 or 23
    # in this cut): two names fewer move it in the fourth digit
    ("level0_conv_ms_patch", 8.386437, 8.388447),
    ("level0_rest_ms_patch", 0.819193, 0.819389),
    ("level1_ms_patch", 3.593481, 3.594343),
    ("deep_ms_patch", 0.749626, 0.749806),
    ("glue_ms_patch", 2.125454, 2.125964),
    ("forward_unnamed_share", 0.0, 0.0),
])
def test_a_recorded_cells_metrics_by_text_and_by_contents(
        anchor, name, by_text, by_contents):
    plain, programs = anchor

    def one(tables):
        return RunRecord(cell={"chips": 1}, config={"batch": BATCH},
                         traffic={}, device={"kind": "TPU v5 lite"},
                         trace=tables, programs=programs)
    assert metric(name, one(plain)) == pytest.approx(by_text, abs=1e-5)
    assert metric(name, one(trace.file_by_contents(plain, programs))) == \
        pytest.approx(by_contents, abs=1e-5)


def test_a_recorded_cells_breakdown_names_what_is_inside(anchor):
    plain, programs = anchor
    filed = trace.file_by_contents(plain, programs)
    forwards_in = catalog.load_module("reducers", "step_mfu").forwards_in
    assert forwards_in(plain["devices"][0]) == pytest.approx(22.928571)
    assert forwards_in(filed["devices"][0]) == pytest.approx(22.923077)
    top = trace.top_ops(filed, 4, programs)
    assert [name for name, _ in top] == [
        "fusion.1012 bf16[20,256,32,9,112] [convolution] enc0 "
        "enc0/conv3 3x3x3",
        "fusion.1010 bf16[20,256,4,8,9,112] [convolution] enc0 "
        "enc0/conv2 3x3x3",
        "fusion.1066 bf16[20,256,4,8,9,112] [convolution] dec0 "
        "dec0/conv2 3x3x3",
        # what PR 38 took for the 12-lane head
        "fusion.1068 bf16[20,256,32,9,12] [convolution] dec0 "
        "dec0/conv3 3x3x3 out 1x1x1"]
    assert top[0][1] == pytest.approx(0.168504184, abs=1e-8)
    by_name = dict(trace.top_ops(filed, 400, programs))
    assert by_name["fusion.1015 bf16[20,128,32,9,112] [output fusion] "
                   "pool0"] == pytest.approx(0.0188, abs=1e-3)


# ---------------------------------------------------------------------------
# a recorded v5e trace with a kernel in it
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def probe():
    """1.5 s cut out of a 2.5 s trace, on the chip (PR 46), of one jitted
    program under scope ``forward``: an XLA convolution (part ``enc0``), a
    Pallas matmul whose wrapper states that it computes ``dec0/conv2``'s
    3x3x3 convolution, and the same kernel unmarked under ``pool0``; and
    the program's entry as the program's own sink wrote it, but for the
    kernel's ``op_convolutions`` line, which a prototype of the marker's
    parser wrote (``core/profiling.py`` lists no custom-call yet:
    PERF.md section 7). Mosaic names a kernel's instruction after the
    innermost scope, and the v5e's event is the whole instruction."""
    with open(os.path.join(DATA, "v5e_kernel_probe.programs.json")) as f:
        programs = json.load(f)["programs"]
    plain = trace.load(os.path.join(DATA, "v5e_kernel_probe.trace.json.gz"))
    return plain, programs


def test_the_v5e_names_a_kernels_event_so_that_the_join_finds_it(probe):
    plain, programs = probe
    assert len(plain["devices"][0]["ops"]) == 17570
    # the text rule: three custom calls, one of them XLA's own
    assert {name: category for name, category in categories(plain).items()
            if category == "custom-call"} == {
        "kernel_convolution_3x3x3.1": "custom-call",
        "pool0.1": "custom-call", "custom-call": "custom-call"}
    assert programs[0]["op_convolutions"] == {
        "fusion.5": [["enc0/conv1", "3x3x3"]],
        "kernel_convolution_3x3x3.1": [["dec0/conv2", "3x3x3"]]}
    filed = trace.file_by_contents(plain, programs)
    assert filed["filing"] == {"by": "contents", "ambiguous": [], "moved": {
        "kernel_convolution_3x3x3.1 bf16[8192,512]": ["custom-call",
                                                      "convolution"]}}
    by_contents = categories(filed)
    assert by_contents["kernel_convolution_3x3x3.1"] == "convolution"
    assert by_contents["fusion.5"] == "convolution"
    # the unmarked kernel and XLA's ConcatBitcast stay what they were
    assert by_contents["pool0.1"] == by_contents["custom-call"] \
        == "custom-call"
    assert trace.category_seconds(plain)[0]["convolution"] == \
        pytest.approx(1.023085729, abs=1e-8)
    assert trace.category_seconds(filed)[0]["convolution"] == \
        pytest.approx(1.037184924, abs=1e-8)
    assert trace.category_seconds(filed)[0]["custom-call"] == \
        pytest.approx(0.014733649, abs=1e-8)


@pytest.mark.parametrize("part, conv_text, rest_text, conv, rest", [
    ("enc0", 1.7137114, 0.5334759, 1.7151479, 0.5339230),
    # the marked kernel's time is its part's convolution time
    ("dec0", 0.0, 0.0236167, 0.0236365, 0.0),
    ("pool0", 0.0, 0.0246790, 0.0, 0.0246997),
])
def test_a_listed_kernels_time_is_its_parts_convolution_time(
        probe, part, conv_text, rest_text, conv, rest):
    plain, programs = probe
    parts = catalog.load_module("reducers", "trace_part_ms")

    def read(tables):
        one = RunRecord(cell={"chips": 1}, config={"batch": 1}, traffic={},
                        device={"kind": "TPU v5 lite"}, trace=tables,
                        programs=programs)
        return (parts.reduce(one, parts=part, category="^convolution$"),
                parts.reduce(one, parts=part,
                             not_category="^convolution$"))
    assert read(plain) == pytest.approx((conv_text, rest_text), abs=1e-6)
    assert read(trace.file_by_contents(plain, programs)) == pytest.approx(
        (conv, rest), abs=1e-6)


def test_a_listed_kernel_counts_the_forwards_and_shows_in_the_breakdown(
        probe):
    plain, programs = probe
    filed = trace.file_by_contents(plain, programs)
    forwards_in = catalog.load_module("reducers", "step_mfu").forwards_in
    # 597 events of the XLA convolution, 596 of the kernel in this cut
    assert forwards_in(plain["devices"][0]) == 597.0
    assert forwards_in(filed["devices"][0]) == 596.5
    top = dict(trace.top_ops(filed, 12, programs))
    assert top["kernel_convolution_3x3x3.1 bf16[8192,512] [convolution] "
               "dec0 dec0/conv2 3x3x3"] == pytest.approx(0.014099195)
    assert top["pool0.1 bf16[8192,512] [custom-call] pool0"] == \
        pytest.approx(0.014733364)
    assert top["fusion.5 bf16[16,128,16,17,32] [convolution] enc0 "
               "enc0/conv1 3x3x3"] > 1.0
