"""The reduction from trace tables to numbers: on small hand-made tables,
and pinned on a recorded trace of a TPU v5 lite.

``data/v5e_superhuman_volume.trace.json.gz`` is 1.5 steady seconds cut
(``record_trace.py``, beside this file) out of the 6 s trace of a
``rsunet-superhuman.volume`` run on the chip (PR 22), op names shortened
by ``parse_op`` as every run's are."""
import os

import pytest

from cfbench import trace
from conftest import TESTS

RECORDED = os.path.join(TESTS, "data",
                        "v5e_superhuman_volume.trace.json.gz")

HLO = ("%fusion.960 = bf16[20,256,32,34,3]{2,4,3,1,0:T(4,128)(2,1)} "
       "fusion(bf16[1,1,1,28,3]{3,4,2,1,0} %copy-done.62, bf16[3,3,3,28,28]"
       "{4,3,2,1,0} %copy-done.14), kind=kOutput, "
       "calls=%fused_computation.140.clone.clone")


@pytest.mark.parametrize("text, want", [
    (HLO, ("fusion.960 bf16[20,256,32,34,3]", "convolution")),
    ("%reduce-window_bitcast_fusion.4 = bf16[10,64]{1,0} fusion(bf16[2] "
     "%fusion.930), kind=kOutput, calls=%fc",
     ("reduce-window_bitcast_fusion.4 bf16[10,64]", "output fusion")),
    ("%add_select_fusion = f32[4]{0} fusion(f32[4] %p), kind=kLoop, calls=%f",
     ("add_select_fusion f32[4]", "loop fusion")),
    ("%copy.295 = bf16[20,256]{1,0} copy(bf16[20,256]{0,1} %reshape.534)",
     ("copy.295 bf16[20,256]", "copy")),
    ("%while.18 = (s32[]{:T(128)}, f32[3,36]{1,0}) while((s32[], f32[3,36]) "
     "%tuple), condition=%c, body=%b", ("while.18 s32[]", "while")),
    ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8] %x), replica_groups={}",
     ("all-reduce.1 f32[8]", "all-reduce")),
    ("%convolution.3 = f32[8]{0} convolution(f32[8] %x, f32[3] %k), window={}",
     ("convolution.3 f32[8]", "convolution")),
    # a bare op name, as other versions write: the name rule
    ("fusion.12", ("fusion.12", "fusion")),
    ("conv_general_dilated.3", ("conv_general_dilated.3", "convolution")),
])
def test_parse_op(text, want):
    assert trace.parse_op(text) == want


def tables(ops_by_device, host=(), t0=0, t1=1000):
    return {"window_s": (t1 - t0) / 1e9, "t0_ns": t0, "t1_ns": t1,
            "devices": [{"name": f"/device:TPU:{i}", "ops": ops}
                        for i, ops in enumerate(ops_by_device)],
            "host": [list(h) for h in host]}


def test_busy_union_idle_and_innermost_attribution():
    # a while [100, 700) holding a conv [100, 400) and a copy [450, 650);
    # then a collective [800, 900): busy = 600 + 100 of 1000 ns
    t = tables([[["while.1", "while", 100, 600],
                 ["fusion.1", "convolution", 100, 300],
                 ["copy.1", "copy", 450, 200],
                 ["all-reduce.1", "all-reduce", 800, 100]]])
    assert trace.busy_seconds(t) == [pytest.approx(700e-9)]
    assert trace.idle_shares(t) == [pytest.approx(0.3)]
    seconds = trace.category_seconds(t)[0]
    assert seconds == pytest.approx({"while": 100e-9, "convolution": 300e-9,
                                     "copy": 200e-9, "all-reduce": 100e-9})
    assert trace.category_share(t, "^convolution$") == pytest.approx(3 / 7)
    assert trace.collective_share(t) == pytest.approx(1 / 7)
    assert trace.top_ops(t, 2) == [
        ["fusion.1 [convolution]", pytest.approx(300e-9)],
        ["copy.1 [copy]", pytest.approx(200e-9)]]


def test_several_devices_are_averaged_and_the_worst_is_kept():
    t = tables([[["fusion.1", "convolution", 0, 500]],
                [["fusion.1", "convolution", 0, 900]]])
    assert trace.idle_shares(t) == [pytest.approx(0.5), pytest.approx(0.1)]
    assert trace.category_share(t, "conv") == pytest.approx(1.0)
    assert trace.top_ops(t, 1) == [["fusion.1 [convolution]",
                                    pytest.approx(700e-9)]]


def test_idle_gaps_are_named_by_the_most_specific_host_event():
    t = tables([[["fusion.1", "convolution", 0, 100],
                 ["fusion.1", "convolution", 600, 100],
                 ["fusion.1", "convolution", 900, 100]]],
               host=[("main: whole run", 0, 1000),
                     ("python3: np.asarray(jax.Array)", 150, 400)])
    gaps = trace.idle_gaps(t)
    assert gaps == [["python3: np.asarray(jax.Array)", pytest.approx(500e-9)],
                    ["main: whole run", pytest.approx(200e-9)]]
    no_host = tables([[["fusion.1", "convolution", 0, 100]]])
    assert trace.idle_gaps(no_host) == [["unattributed",
                                         pytest.approx(900e-9)]]


def test_cut_clips_events_at_the_edges():
    t = tables([[["fusion.1", "convolution", 100, 300],
                 ["copy.1", "copy", 450, 200]]], t0=0, t1=1000)
    small = trace.cut(t, 200e-9, 300e-9)
    assert small["window_s"] == pytest.approx(300e-9)
    assert small["devices"][0]["ops"] == [
        ["fusion.1", "convolution", 200, 200], ["copy.1", "copy", 450, 50]]


def test_no_device_plane_gives_nothing_to_read():
    empty = tables([])
    assert trace.idle_shares(empty) == []
    assert trace.category_share(empty, "conv") is None
    assert trace.collective_share(empty) is None
    assert trace.breakdown(empty) == {"device_ops": [], "idle_gaps": []}


def test_recorded_v5e_trace_is_pinned():
    t = trace.load(RECORDED)
    assert [d["name"] for d in t["devices"]] == ["/device:TPU:0"]
    assert len(t["devices"][0]["ops"]) == 4548
    assert t["window_s"] == pytest.approx(1.5)
    assert trace.busy_seconds(t) == [pytest.approx(1.431068526, abs=1e-9)]
    assert trace.idle_shares(t) == [pytest.approx(0.045954316, abs=1e-8)]
    assert trace.category_share(t, "^convolution$") == pytest.approx(
        0.67354725, abs=1e-7)
    assert trace.collective_share(t) == 0.0
    seconds = trace.category_seconds(t)[0]
    assert seconds["copy"] == pytest.approx(0.201152, abs=1e-6)
    assert seconds["loop fusion"] == pytest.approx(0.14619, abs=1e-6)
    assert seconds["while"] < 1e-4      # its time is its children's
    assert sum(seconds.values()) == pytest.approx(1.431068526, abs=1e-6)
    top = trace.top_ops(t, 10)
    assert top[0] == ["fusion.960 bf16[20,256,32,34,3] [convolution]",
                      pytest.approx(0.18803931, abs=1e-8)]
    gaps = trace.idle_gaps(t)
    assert gaps[0] == ["python3: np.asarray(jax.Array)",
                       pytest.approx(0.043192024, abs=1e-8)]
