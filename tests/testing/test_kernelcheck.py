"""Unit tests for the interpret-mode kernel sanitizer
(chunkflow_tpu/testing/kernelcheck.py): switch semantics, registry
mechanics, the three host-side checks, and end-to-end runs through the
SHIPPING kernels — clean data must pass with zero violations (and
bit-identical results), bad data must trip the right violation kind.
"""
import numpy as np
import pytest

from chunkflow_tpu.testing import kernelcheck


@pytest.fixture(autouse=True)
def _clean_registry():
    kernelcheck.reset_state()
    yield
    kernelcheck.reset_state()


# ---------------------------------------------------------------------------
# switch semantics
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("value", ["", "0", "off", "false", "no",
                                   "OFF", "False", "No"])
def test_off_values(monkeypatch, value):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", value)
    assert not kernelcheck.enabled()
    assert kernelcheck.key_suffix() == ""
    assert not kernelcheck.active(True)


@pytest.mark.parametrize("value", ["1", "on", "yes", "raise"])
def test_on_values(monkeypatch, value):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", value)
    assert kernelcheck.enabled()
    assert kernelcheck.key_suffix() == "+kc"


def test_unset_is_off(monkeypatch):
    monkeypatch.delenv("CHUNKFLOW_KERNELCHECK", raising=False)
    assert not kernelcheck.enabled()


def test_active_requires_interpret(monkeypatch):
    # compiled Mosaic legs are never instrumented, whatever the env says
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    assert kernelcheck.active(True)
    assert not kernelcheck.active(False)


# ---------------------------------------------------------------------------
# registry mechanics
# ---------------------------------------------------------------------------
def test_report_and_reset(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    kernelcheck._registry.count_check()
    kernelcheck._registry.violation("oob-slice", "synthetic")
    snap = kernelcheck.report()
    assert snap["enabled"] and snap["checks"] == 1
    assert [v["kind"] for v in snap["violations"]] == ["oob-slice"]
    kernelcheck.reset_state()
    snap = kernelcheck.report()
    assert snap["checks"] == 0 and snap["violations"] == []


def test_violation_raises_in_raise_mode(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "raise")
    with pytest.raises(kernelcheck.KernelCheckError, match="synthetic"):
        kernelcheck._registry.violation("oob-slice", "synthetic detail")
    # recorded even when it raises
    assert len(kernelcheck.report()["violations"]) == 1


def test_grid_trace_only_records_when_armed():
    kernelcheck._record_visit(0, label="k")
    kernelcheck._record_visit(1, label="k")
    assert kernelcheck._registry.take_trace("k") == []
    kernelcheck.arm_grid_trace("k")
    kernelcheck._record_visit(0, label="k")
    kernelcheck._record_visit(1, label="k")
    assert kernelcheck._registry.take_trace("k") == [0, 1]
    # take_trace consumed it
    assert kernelcheck._registry.take_trace("k") == []


def test_rmw_order_violation_from_descending_walk(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    kernelcheck.arm_grid_trace("k")
    for idx in (0, 2, 1):
        kernelcheck._record_visit(idx, label="k")
    kernelcheck._host_check_result(False, label="k")
    kinds = [v["kind"] for v in kernelcheck.report()["violations"]]
    assert kinds == ["rmw-order"]


def test_ascending_walk_passes(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    kernelcheck.arm_grid_trace("k")
    for idx in (0, 0, 1, 2):  # repeats are fine (multi-channel grids)
        kernelcheck._record_visit(idx, label="k")
    kernelcheck._host_check_result(False, label="k")
    assert kernelcheck.report()["violations"] == []


def test_nan_canary_violation(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    kernelcheck._host_check_result(True, label="k")
    kinds = [v["kind"] for v in kernelcheck.report()["violations"]]
    assert kinds == ["scratch-canary"]


def test_host_check_bounds(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    starts = np.array([[0, 0], [8, 128]], np.int32)
    kernelcheck._host_check_bounds(
        starts, window=(8, 128), extent=(16, 256), label="k")
    assert kernelcheck.report()["violations"] == []
    kernelcheck._host_check_bounds(
        starts, window=(8, 256), extent=(16, 256), label="k")
    viols = kernelcheck.report()["violations"]
    assert [v["kind"] for v in viols] == ["oob-slice"]
    assert "batch 1 dim 1" in viols[0]["detail"]


def test_host_check_bounds_negative_start(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    starts = np.array([[-8, 0]], np.int32)
    kernelcheck._host_check_bounds(
        starts, window=(8, 128), extent=(16, 256), label="k")
    assert [v["kind"] for v in kernelcheck.report()["violations"]] == [
        "oob-slice"]


def test_publish_gauges(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    from chunkflow_tpu.core import telemetry

    kernelcheck._registry.count_check()
    kernelcheck.publish()
    gauges = telemetry.snapshot()["gauges"]
    assert gauges["kernelcheck/checks"] == 1
    assert gauges["kernelcheck/violations"] == 0


# ---------------------------------------------------------------------------
# end-to-end through the shipping kernels (interpret mode on CPU)
# ---------------------------------------------------------------------------
def _gather_args(starts_rows):
    import jax.numpy as jnp

    from chunkflow_tpu.ops import pallas_gather

    ci, shape, pin = 2, (9, 40, 50), (3, 12, 18)
    raw = np.ones((ci,) + shape, np.float32)
    pad_y, pad_x = pallas_gather.gather_buffer_padding(pin, raw.dtype)
    padded = np.pad(raw, [(0, 0), (0, 0), (0, pad_y), (0, pad_x)])
    return (jnp.asarray(padded),
            jnp.asarray(np.array(starts_rows, np.int32)), pin)


def test_gather_patches_clean_run_counts_checks(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    from chunkflow_tpu.ops import pallas_gather

    chunk, starts, pin = _gather_args([[0, 0, 0], [6, 28, 32]])
    pallas_gather.gather_patches(
        chunk, starts, pin, interpret=True).block_until_ready()
    snap = kernelcheck.report()
    assert snap["violations"] == []
    assert snap["checks"] >= 2  # bounds + result sweep both fired


def test_gather_patches_oob_starts_detected(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    from chunkflow_tpu.ops import pallas_gather

    # z start 8 + window 3 runs past the 9-deep chunk
    chunk, starts, pin = _gather_args([[8, 0, 0]])
    pallas_gather.gather_patches(
        chunk, starts, pin, interpret=True).block_until_ready()
    kinds = [v["kind"] for v in kernelcheck.report()["violations"]]
    assert "oob-slice" in kinds


def test_fused_blend_armed_walk_is_ascending(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    import jax.numpy as jnp

    from chunkflow_tpu.ops import pallas_blend

    kernelcheck.arm_grid_trace("fused_blend")
    co, Z, Y, X, B, pz, py, px = 2, 5, 32, 40, 3, 3, 12, 16
    pad_y, pad_x = pallas_blend.buffer_padding((pz, py, px))
    out = jnp.zeros((co, Z, Y + pad_y, X + pad_x), jnp.float32)
    weight = jnp.zeros((Z, Y + pad_y, X + pad_x), jnp.float32)
    preds = jnp.ones((B, co, pz, py, px), jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    bump = jnp.ones((pz, py, px), jnp.float32)
    starts = jnp.asarray(
        np.array([[0, 0, 0], [1, 6, 8], [2, 12, 16]], np.int32))
    res_out, _ = pallas_blend.fused_accumulate_patches(
        out, weight, preds, valid, bump, starts, interpret=True)
    res_out.block_until_ready()
    snap = kernelcheck.report()
    assert snap["violations"] == []
    # check_result consumed the trace; nothing left behind
    assert kernelcheck._registry.take_trace("fused_blend") == []


def _random_kernel_run(kernel):
    """One run of a shipping kernel in interpret mode on random data, at
    starts that are neither aligned nor apart: overlapping windows."""
    import jax.numpy as jnp

    from chunkflow_tpu.ops import pallas_blend, pallas_gather

    rng = np.random.default_rng(0)
    B, co, pout = 8, 3, (3, 16, 32)
    Z, Y, X = pout[0] + 4, pout[1] * 3, pout[2] * 3
    starts = np.stack([rng.integers(0, n - p, B) for n, p in
                       zip((Z, Y, X), pout)], axis=1).astype(np.int32)
    if kernel == "gather":
        raw = rng.integers(0, 256, (2, Z, Y, X), dtype=np.uint8)
        pad_y, pad_x = pallas_gather.gather_buffer_padding(pout, np.uint8)
        chunk = np.pad(raw, [(0, 0), (0, 0), (0, pad_y), (0, pad_x)])
        return [pallas_gather.gather_patches(
            jnp.asarray(chunk), jnp.asarray(starts), pout, interpret=True)]
    pad_y, pad_x = pallas_blend.buffer_padding(pout)
    return list(pallas_blend.fused_accumulate_patches(
        jnp.zeros((co, Z, Y + pad_y, X + pad_x), jnp.float32),
        jnp.zeros((Z, Y + pad_y, X + pad_x), jnp.float32),
        jnp.asarray(rng.standard_normal((B, co) + pout), jnp.float32),
        jnp.ones((B,), jnp.float32),
        jnp.asarray(rng.random(pout) * 5 + 1, jnp.float32),
        jnp.asarray(starts), interpret=True))


@pytest.mark.parametrize("kernel", ["gather", "fused_blend"])
def test_sanitized_kernel_gives_the_unsanitized_outputs(monkeypatch, kernel):
    """The sanitizer rides the traced program (poison writes, a bounds
    and a NaN callback): a clean workload raises nothing and every output
    is bit for bit the one the kernel gives with the sanitizer off."""
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "0")
    plain = [np.asarray(a) for a in _random_kernel_run(kernel)]
    assert kernelcheck.report()["checks"] == 0
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    checked = [np.asarray(a) for a in _random_kernel_run(kernel)]
    snap = kernelcheck.report()
    assert snap["violations"] == [] and snap["checks"] >= 2
    for a, b in zip(plain, checked):
        np.testing.assert_array_equal(a, b)
    assert plain[0].std() > 0


def test_disabled_is_strict_noop(monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "0")
    from chunkflow_tpu.ops import pallas_gather

    chunk, starts, pin = _gather_args([[8, 0, 0]])  # OOB — must NOT trip
    pallas_gather.gather_patches(
        chunk, starts, pin, interpret=True).block_until_ready()
    snap = kernelcheck.report()
    assert snap == {"enabled": False, "checks": 0, "violations": []}
