"""Pipeline stall attribution (ISSUE 3): a synthetic slow-stage /
slow-compute / slow-drain pipeline must attribute >80% of the injected
delay to the correct phase, telemetry-off runs must be bit-identical to
telemetry-on runs, and telemetry must cost ~nothing on the pipelined
path (the overhead gate)."""
import time

import numpy as np
import pytest

from chunkflow_tpu.core import telemetry
from chunkflow_tpu.flow.pipeline import pipeline_chunks


@pytest.fixture(autouse=True)
def clean_registry(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


class FakeArray:
    """Mimics a jax array's drain-side surface: block_until_ready is the
    compute wait, nothing else is touched."""

    def __init__(self, compute_s):
        self.compute_s = compute_s

    def block_until_ready(self):
        time.sleep(self.compute_s)


class FakeOut:
    def __init__(self, payload, compute_s, drain_s):
        self.array = FakeArray(compute_s)
        self.payload = payload
        self.drain_s = drain_s

    def host(self):
        time.sleep(self.drain_s)
        return self.payload


class FakeInferencer:
    """Injects a controlled delay into exactly one pipeline phase."""

    def __init__(self, stage_s=0.0, compute_s=0.0, drain_s=0.0):
        self.stage_s = stage_s
        self.compute_s = compute_s
        self.drain_s = drain_s

    def stage(self, chunk):
        time.sleep(self.stage_s)
        return ("staged", chunk)  # distinct object -> pipeline-owned

    def infer_async(self, slot, crop=None, consume=False):
        _, chunk = slot
        return FakeOut(chunk, self.compute_s, self.drain_s)


N_CHUNKS = 5
DELAY_S = 0.03


def _run(inferencer):
    return list(pipeline_chunks(inferencer, list(range(N_CHUNKS)), ring=2))


def _phase_totals():
    hists = telemetry.snapshot()["hists"]
    return {
        phase: hists.get(f"pipeline/{phase}", {}).get("total", 0.0)
        for phase in ("stage", "dispatch", "compute", "drain")
    }


@pytest.mark.parametrize("slow_phase", ["stage", "compute", "drain"])
def test_injected_delay_lands_in_the_right_phase(slow_phase):
    injected = N_CHUNKS * DELAY_S
    inferencer = FakeInferencer(**{f"{slow_phase}_s": DELAY_S})
    out = _run(inferencer)
    assert out == list(range(N_CHUNKS))  # order preserved
    totals = _phase_totals()
    # >80% of the injected delay attributed to the right phase, and no
    # other phase absorbs a comparable share
    assert totals[slow_phase] >= 0.8 * injected, totals
    for phase, total in totals.items():
        if phase != slow_phase:
            assert total <= 0.2 * injected, totals


def test_ring_occupancy_gauge_recorded():
    _run(FakeInferencer())
    snap = telemetry.snapshot()
    occ = snap["hists"]["pipeline/ring_occupancy"]
    assert occ["count"] == N_CHUNKS
    assert 1 <= occ["max"] <= 2  # ring=2 bounds staged-ahead inputs
    assert snap["hists"]["pipeline/inflight"]["max"] <= 2


def test_summary_reports_drain_bound(tmp_path):
    """End to end: JSONL from a drain-bound run must say so."""
    from chunkflow_tpu.flow.log_summary import (
        load_telemetry_dir,
        summarize_telemetry,
    )

    telemetry.configure(str(tmp_path))
    _run(FakeInferencer(drain_s=DELAY_S))
    telemetry.flush()
    agg = summarize_telemetry(load_telemetry_dir(str(tmp_path)))
    stall = agg["stall"]
    assert stall["pipeline/drain"]["share"] > 0.5
    dominant = max(stall, key=lambda p: stall[p]["share"])
    assert dominant == "pipeline/drain"
    assert agg["gauges"]["pipeline/ring_occupancy"]["mean"] >= 1


def test_telemetry_off_run_is_bit_identical():
    """The real executor over the real identity engine: telemetry on vs
    off must produce byte-for-byte the same outputs (telemetry never
    touches data, only clocks)."""
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.inference import Inferencer

    inferencer = Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=3,
        framework="identity",
        batch_size=2,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    chunks = [
        Chunk(rng.random((8, 32, 32), dtype=np.float32)) for _ in range(3)
    ]

    def run_all():
        return [
            np.asarray(out.array)
            for out in pipeline_chunks(inferencer, iter(chunks), ring=2)
        ]

    on = run_all()
    import os

    os.environ["CHUNKFLOW_TELEMETRY"] = "0"
    try:
        off = run_all()
    finally:
        del os.environ["CHUNKFLOW_TELEMETRY"]
    for a, b in zip(on, off):
        assert np.array_equal(a, b)


def test_overhead_gate():
    """Telemetry-on wall time within noise of telemetry-off on a
    sleep-calibrated synthetic pipeline. 25% is a deliberately loose
    bound for a shared test box: it catches a lock or an fsync on the
    per-event path, not a percentage; what telemetry costs on the chip
    is a traced run of a benchmark cell against an untraced one (PERF.md)."""
    import os

    def timed_run():
        t0 = time.perf_counter()
        _run(FakeInferencer(stage_s=0.01, compute_s=0.005, drain_s=0.005))
        return time.perf_counter() - t0

    timed_run()  # warm both paths
    on = min(timed_run() for _ in range(2))
    os.environ["CHUNKFLOW_TELEMETRY"] = "0"
    try:
        off = min(timed_run() for _ in range(2))
    finally:
        del os.environ["CHUNKFLOW_TELEMETRY"]
    assert on <= off * 1.25, (on, off)
