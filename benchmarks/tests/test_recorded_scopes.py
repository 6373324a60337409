"""The scope and idle-under-span reductions pinned on a recorded trace of
a TPU v5 lite.

``data/v5e_superhuman_volume_scopes.trace.json.gz`` is 1.5 steady seconds
cut (``record_trace.py``'s way: ``trace.cut`` of the run's tables) out of
the 6 s trace of a ``rsunet-superhuman.volume`` run on the chip (PR 23,
from a ``git archive`` copy with an empty compile cache), and
``..._scopes.programs.json`` the ``op_scopes`` of that run's
``programs.json``: what the chip's first trace with named scopes showed.
The raw ``XLA Ops`` events carry neither ``op_name`` nor a scope (PERF.md,
"Reading a v5e trace"), so the join on the op's name is the route."""
import json
import os

import pytest

from cfbench import catalog, trace
from cfbench.run_record import RunRecord
from conftest import TESTS

DATA = os.path.join(TESTS, "data")
HOST_SPANS = ["pipeline/drain", "pipeline/stage", "pipeline/dispatch",
              "scheduler/load", "queue/fetch"]


@pytest.fixture(scope="module")
def record():
    with open(os.path.join(
            DATA, "v5e_superhuman_volume_scopes.programs.json")) as f:
        programs = json.load(f)["programs"]
    tables = trace.load(os.path.join(
        DATA, "v5e_superhuman_volume_scopes.trace.json.gz"))
    return RunRecord(cell={}, config={}, traffic={}, device={},
                     trace=tables, programs=programs)


def test_scope_shares_of_the_recorded_trace(record):
    scope = catalog.load_module("reducers", "trace_scope_share")
    forward = scope.reduce(record, scopes=["forward"])
    blend = scope.reduce(record, scopes=["gather", "accumulate", "normalize"])
    unscoped = scope.reduce(record, unscoped=True)
    assert forward == pytest.approx(98.09, abs=0.01)
    assert blend == pytest.approx(1.59, abs=0.01)
    assert unscoped == pytest.approx(0.32, abs=0.01)
    assert forward + blend + unscoped == pytest.approx(100.0, abs=1e-6)
    # the model's own copies and elementwise passes: what is under
    # `forward` and is no convolution
    assert scope.reduce(record, scopes=["forward"],
                        not_category="^convolution$") == \
        pytest.approx(30.24, abs=0.01)
    # the blend, part by part: the scatter-add's loops are nearly all
    assert scope.reduce(record, scopes=["accumulate"]) == \
        pytest.approx(1.40, abs=0.01)
    assert scope.reduce(record, scopes=["gather"]) == \
        pytest.approx(0.04, abs=0.01)
    assert scope.reduce(record, scopes=["normalize"]) == \
        pytest.approx(0.15, abs=0.01)
    # no cross-chip exchange on one chip
    assert scope.reduce(record, scopes=["collective"]) == 0.0
    # the same convolutions the category metric counts lie under forward
    assert trace.category_share(record.trace, "conv") * 100 == \
        pytest.approx(forward - 30.24, abs=0.01)


def test_the_program_spans_lie_on_the_recorded_host_plane(record):
    names = {name.rsplit(": ", 1)[-1] for name, _, _ in record.trace["host"]}
    assert {"pipeline/dispatch", "inference/blank_check", "storage/read",
            "storage/decode", "storage/write", "queue/fetch",
            "op/load-precomputed", "op/save-precomputed"} <= names
    idle = catalog.load_module("reducers", "trace_idle_under_spans")
    # 0.27% of this cut is idle, all of it while a dispatch span was open
    assert trace.idle_shares(record.trace)[0] == pytest.approx(0.00266,
                                                               abs=1e-5)
    assert idle.reduce(record, names=["pipeline/stage", "pipeline/dispatch"],
                       any_of=HOST_SPANS) == pytest.approx(100.0)
    assert idle.reduce(record, names=HOST_SPANS, any_of=HOST_SPANS,
                       complement=True) == pytest.approx(0.0)
    assert idle.reduce(record, names=["pipeline/drain"],
                       any_of=HOST_SPANS) == pytest.approx(0.0)
    # and the harness's own breakdown names a gap after a program span
    gaps = dict(trace.idle_gaps(record.trace))
    assert gaps["python3: pipeline/dispatch"] > 0
