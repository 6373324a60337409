"""The comparison's judge under one bound and under two, and the whole
step's share of the peak (PR 35)."""
import numpy as np
import pytest

from cfbench import catalog, check
from cfbench.run_record import RunRecord


def record(tolerance=None, **kw):
    return RunRecord(cell=kw.pop("cell", {}),
                     config={"tolerance": tolerance, **kw.pop("config", {})},
                     traffic={}, device={"kind": "TPU v5 lite"}, **kw)


WANT = np.random.default_rng(0).random((3, 4, 8, 8))


def off_by(mean, peak=0.0):
    got = WANT + mean
    got[0, 0, 0, 0] += peak
    return got


@pytest.mark.parametrize("tolerance, got, correct, failed", [
    ({"max_abs_diff": 1e-2}, off_by(5e-3), True, set()),
    # one bound: a difference of 5e-3 everywhere passes; two: it does not
    ({"max_abs_diff": 1e-2, "mean_abs_diff": 8e-4}, off_by(5e-3), False,
     {"mean_abs_diff"}),
    ({"max_abs_diff": 1e-2, "mean_abs_diff": 8e-4}, off_by(3e-4), True,
     set()),
    ({"max_abs_diff": 1e-2, "mean_abs_diff": 8e-4}, off_by(1e-4, 0.1), False,
     {"max_abs_diff"}),
    ({"max_abs_diff": 1e-2, "mean_abs_diff": 8e-4}, off_by(0.1), False,
     {"max_abs_diff", "mean_abs_diff"}),
])
def test_judge_with_and_without_the_mean_bound(tolerance, got, correct,
                                               failed):
    r = record(tolerance)
    check.judge(r, got, WANT, "a block", {"queue empty": True})
    assert r.correct is correct
    assert set(r.checks) == set(tolerance)
    for name, c in r.checks.items():
        assert c["limit"] == tolerance[name]
        assert (c["value"] > c["limit"]) == (name in failed)
    assert {n for n in r.notes if n.startswith("not correct")} == {
        f"not correct: failed '{name} within the bound'" for name in failed}
    # the last note names every number, beside its bound where it has one
    for name in ("max_abs_diff", "mean_abs_diff"):
        assert f"{name} " in r.notes[-1]
    assert ("(no bound)" in r.notes[-1]) == ("mean_abs_diff"
                                             not in tolerance)


def test_judge_holds_the_other_conditions():
    r = record({"max_abs_diff": 1e-2, "mean_abs_diff": 8e-4})
    check.judge(r, WANT, WANT, "a block", {"queue empty": False})
    assert r.correct is False
    assert "not correct: failed 'queue empty'" in r.notes
    r = record({"max_abs_diff": 1e-2})
    check.judge(r, WANT[:, :2], WANT, "a block", {})
    assert r.correct is False and r.checks["max_abs_diff"]["value"] == np.inf
    r = record({"max_abs_diff": 1e-2})
    check.judge(r, np.zeros_like(WANT), np.zeros_like(WANT), "a block", {})
    assert r.correct is False
    assert "not correct: failed 'not constant'" in r.notes


def forwards(n, period_ns, conv_ns, names=3):
    """A trace of ``n`` forwards, one every ``period_ns``: ``names``
    convolutions of ``conv_ns`` each and a copy in every one."""
    ops = []
    for i in range(n):
        t = i * period_ns
        for k in range(names):
            ops.append([f"fusion.{k} bf16[4]", "convolution",
                        t + k * conv_ns, conv_ns])
        ops.append(["copy.1 bf16[4]", "copy", t + names * conv_ns, conv_ns])
    return {"window_s": n * period_ns / 1e9, "t0_ns": 0,
            "t1_ns": n * period_ns,
            "devices": [{"name": "/device:TPU:0", "ops": ops}], "host": []}


@pytest.mark.parametrize("name, batch_ms, want", [
    # a batch of 4 every 1030 / 12 ms and every 720 / 12 ms; of 6 every
    # 8760 / 81 ms
    ("rsunet-superhuman", 1030 / 12, 9.37),
    ("rsunet-deepem", 720 / 12, 7.49),
    ("rsunet-superhuman-prod", 8760 / 81, 11.16),
])
def test_step_mfu(name, batch_ms, want):
    config = catalog.load_json("configs", name + ".json")
    reduce = catalog.load_module("reducers", "step_mfu").reduce
    period = int(batch_ms * 1e6)
    r = record(config=config, cell={"chips": 1},
               trace=forwards(50, period, period // 5))
    assert reduce(r) == pytest.approx(want, abs=0.02)
    flops = catalog.load_module("flops", config["flops"])
    assert reduce(r) == pytest.approx(
        100 * config["batch"] / (period / 1e9)
        * flops.flops_per_patch(config) / 197e12)
    # what the client saw is not read: the forwards are the trace's
    r.client = {"patches_per_s": 1.0}
    assert reduce(r) == pytest.approx(want, abs=0.02)
    # four chips doing the same work are a quarter as busy
    r.cell = {"chips": 4}
    assert reduce(r) == pytest.approx(want / 4, abs=0.01)
    # nothing to read (an untraced run, a trace with no device plane or
    # with no convolution in it): the metric is left out
    assert reduce(record(config=config, cell={"chips": 1})) is None
    empty = forwards(0, period, 1)
    assert reduce(record(config=config, cell={"chips": 1},
                         trace=empty)) is None
    copies = forwards(5, period, 1000, names=0)
    assert reduce(record(config=config, cell={"chips": 1},
                         trace=copies)) is None
    # a program that ran once beside the forward's does not move the count
    r = record(config=config, cell={"chips": 1},
               trace=forwards(50, period, period // 5))
    r.trace["devices"][0]["ops"].append(
        ["fusion.99 f32[2]", "convolution", 5, 10])
    assert reduce(r) == pytest.approx(want, abs=0.02)
    # it lies under the forward's roofline share, whose time is the
    # convolutions' alone (3/5 of the window here): the same count of
    # FLOPs over less time
    roofline = catalog.load_module("reducers", "roofline_share").reduce
    r.client = {"patches_per_s": config["batch"] / (period / 1e9)}
    assert reduce(r) == pytest.approx(0.6 * roofline(r, pattern="conv"),
                                      rel=1e-3)


def test_step_mfu_on_the_recorded_trace():
    """Pinned on 1.5 s of a v5e trace (PR 22: a program of batch 4 every
    134 ms, before the x-fold): 25 convolutions, 21 of them seen 11 times
    and 4 of them 10 times, so 10.84 forwards (1.5 s / 134 ms = 11.2 less
    what the cut clipped away)."""
    import os

    from cfbench import trace
    from conftest import TESTS

    tables = trace.load(os.path.join(
        TESTS, "data", "v5e_superhuman_volume.trace.json.gz"))
    step_mfu = catalog.load_module("reducers", "step_mfu")
    assert step_mfu.forwards_in(tables["devices"][0]) == pytest.approx(
        (21 * 11 + 4 * 10) / 25)
    config = catalog.load_json("configs", "rsunet-superhuman.json")
    r = record(config=config, cell={"chips": 1}, trace=tables)
    flops = catalog.load_module("flops", "rsunet").flops_per_patch(config)
    assert step_mfu.reduce(r) == pytest.approx(
        100 * 10.84 * 4 * flops / (1.5 * 197e12))
    assert 5.7 < step_mfu.reduce(r) < 5.9
