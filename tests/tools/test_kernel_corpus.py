"""The seeded-defect corpus (tests/tools/fixtures/): every planted
kernel defect must be DETECTED by its plane — GL020-GL024 by the lint,
the runtime pair by the kernelcheck sanitizer — and every twin must be
quiet. This is the regression harness that keeps the detectors honest:
a refactor that stops catching a seed fails here, not on the chip.
"""
from pathlib import Path

import pytest

from tools.graftlint.config import Config
from tools.graftlint.engine import lint_file

FIXTURES = Path(__file__).parent / "fixtures"

LINT_SEEDS = [
    ("gl020_unaligned_slice.py", "GL020"),
    ("gl021_vmem_overflow.py", "GL021"),
    ("gl022_unaliased_rmw.py", "GL022"),
    ("gl023_unwaited_copy.py", "GL023"),
    ("gl024_unguarded_call.py", "GL024"),
]


def lint(name):
    path = FIXTURES / name
    return lint_file(str(path), path.read_text(), Config())


@pytest.mark.parametrize("name,code", LINT_SEEDS)
def test_lint_seed_detected(name, code):
    findings, _ = lint(name)
    hits = [f.code for f in findings]
    # exactly the planted defect, nothing else: a seed that trips a
    # second rule would blur which detector the corpus pins
    assert hits == [code], (name, [(f.code, f.message) for f in findings])


@pytest.mark.parametrize("name,code", LINT_SEEDS)
def test_lint_seed_suppressed_twin_is_quiet(name, code):
    twin = name.replace(".py", "_suppressed.py")
    findings, suppressed = lint(twin)
    assert [f.code for f in findings] == [], twin
    assert suppressed == 1, twin


# ---------------------------------------------------------------------------
# runtime seeds: only the kernelcheck sanitizer sees these
# ---------------------------------------------------------------------------
@pytest.fixture
def kernelcheck_log(monkeypatch):
    from chunkflow_tpu.testing import kernelcheck

    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "log")
    kernelcheck.reset_state()
    yield kernelcheck
    kernelcheck.reset_state()


def test_runtime_seeds_are_lint_clean():
    # the whole point of the runtime pair: statically sound, only the
    # sanitizer catches them
    for name in ("rt_scratch_read_before_write.py", "rt_oob_slice.py"):
        findings, _ = lint(name)
        gl02x = [f.code for f in findings if f.code.startswith("GL02")]
        assert gl02x == [], (name, gl02x)


def test_scratch_read_before_write_detected(kernelcheck_log):
    import jax.numpy as jnp

    from tests.tools.fixtures import rt_scratch_read_before_write as fx

    x = jnp.ones((4, 16, 128), jnp.float32)
    fx.build(x, interpret=True).block_until_ready()
    kinds = [v["kind"] for v in kernelcheck_log.report()["violations"]]
    assert "scratch-canary" in kinds


def test_oob_slice_detected(kernelcheck_log):
    import jax.numpy as jnp

    from tests.tools.fixtures import rt_oob_slice as fx

    x = jnp.ones((16, 256), jnp.float32)
    fx.build(x, interpret=True).block_until_ready()
    kinds = [v["kind"] for v in kernelcheck_log.report()["violations"]]
    assert "oob-slice" in kinds


def test_runtime_seeds_silent_with_sanitizer_off(monkeypatch):
    # the strict no-op twin: CHUNKFLOW_KERNELCHECK=0 -> the defects run
    # to completion, nothing is recorded, no callback ever fires
    import jax.numpy as jnp

    from chunkflow_tpu.testing import kernelcheck
    from tests.tools.fixtures import rt_oob_slice
    from tests.tools.fixtures import rt_scratch_read_before_write

    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "0")
    kernelcheck.reset_state()
    rt_scratch_read_before_write.build(
        jnp.ones((4, 16, 128), jnp.float32), interpret=True
    ).block_until_ready()
    rt_oob_slice.build(
        jnp.ones((16, 256), jnp.float32), interpret=True
    ).block_until_ready()
    snap = kernelcheck.report()
    assert snap["violations"] == []
    assert snap["checks"] == 0


def test_scratch_seed_detected_in_raise_mode(monkeypatch):
    # default mode: the violation raises out of the host callback and
    # surfaces through the runtime instead of passing silently
    import jax.numpy as jnp

    from chunkflow_tpu.testing import kernelcheck
    from tests.tools.fixtures import rt_scratch_read_before_write as fx

    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK", "1")
    monkeypatch.setenv("CHUNKFLOW_KERNELCHECK_MODE", "raise")
    kernelcheck.reset_state()
    x = jnp.ones((4, 16, 128), jnp.float32)
    with pytest.raises(Exception, match="canary|KernelCheck"):
        fx.build(x, interpret=True).block_until_ready()
    kernelcheck.reset_state()
