"""Compile-cache layer (core/compile_cache.py): ragged-edge chunks that
shape-bucket into the same run geometry must trigger exactly one trace,
and the keyed program cache must count builds/hits as invariants a test
can assert (not a benchmark)."""
import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core.compile_cache import ProgramCache
from chunkflow_tpu.inference import Inferencer
from chunkflow_tpu.inference.engines import Engine, create_identity_engine


def test_program_cache_counts_and_eviction():
    cache = ProgramCache(maxsize=2)
    built = []

    def make(tag):
        def build():
            built.append(tag)
            return tag
        return build

    assert cache.get("a", make("a")) == "a"
    assert cache.get("a", make("a2")) == "a"  # hit: builder not invoked
    assert cache.get("b", make("b")) == "b"
    assert (cache.builds, cache.hits) == (2, 1)
    assert built == ["a", "b"]
    cache.get("c", make("c"))  # evicts "a" (FIFO)
    assert "a" not in cache and "b" in cache and "c" in cache
    assert cache.peek("a") is None
    with pytest.raises(ValueError):
        ProgramCache(maxsize=0)


def test_retrace_watchdog_warns_past_expected_builds():
    from chunkflow_tpu.core.compile_cache import RetraceWarning

    cache = ProgramCache(expected_builds=2, label="test")
    cache.get("a", lambda: "a")
    cache.get("b", lambda: "b")
    with pytest.warns(RetraceWarning, match="expected bucket count"):
        cache.get("c", lambda: "c")
    # once per cache: a warning per retrace would swamp the log
    import warnings as _warnings

    with _warnings.catch_warnings():
        _warnings.simplefilter("error", RetraceWarning)
        cache.get("d", lambda: "d")


def test_cache_counters_feed_telemetry(monkeypatch):
    from chunkflow_tpu.core import telemetry

    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    cache = ProgramCache()
    cache.get("a", lambda: "a")
    cache.get("a", lambda: "a")
    cache.get("b", lambda: "b")
    counters = telemetry.snapshot()["counters"]
    assert counters["compile_cache/builds"] == 2
    assert counters["compile_cache/hits"] == 1
    # per-instance counters stay live even with telemetry off
    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    cache.get("c", lambda: "c")
    assert cache.builds == 3
    telemetry.reset()


def _counting_engine(input_patch, num_output_channels):
    """Identity engine whose apply counts TRACES: the body runs under
    jit tracing only, so the counter advances once per program
    compilation and never on cached executions."""
    inner = create_identity_engine(
        input_patch, input_patch,
        num_output_channels=num_output_channels,
    )
    traces = []

    def apply(params, batch):
        traces.append(batch.shape)
        return inner.apply(params, batch)

    return Engine(
        params=(),
        apply=apply,
        num_input_channels=1,
        num_output_channels=num_output_channels,
    ), traces


@pytest.mark.parametrize("blend", ["scatter", "fold"])
def test_same_bucket_chunks_trace_once(blend):
    """Two ragged chunks in the same shape bucket run ONE compiled
    program: the second chunk is a pure cache hit (zero traces)."""
    engine, traces = _counting_engine((4, 16, 16), 1)
    inferencer = Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=1,
        framework="prebuilt",
        engine=engine,
        batch_size=2,
        shape_bucket=(8, 16, 16),
        blend=blend,
        crop_output_margin=False,
    )
    rng = np.random.default_rng(0)
    first = rng.random((5, 17, 18)).astype(np.float32)
    np.asarray(inferencer(Chunk(first)).array)
    n_traces = len(traces)
    assert n_traces >= 1
    # same bucket (8, 32, 32): bit-for-bit program reuse, no retrace
    second = rng.random((7, 30, 20)).astype(np.float32)
    out = np.asarray(inferencer(Chunk(second)).array)
    assert len(traces) == n_traces, "same-bucket chunk retraced"
    np.testing.assert_allclose(out[0], second, atol=1e-5)
    # a different bucket is a genuine new geometry: exactly one more trace
    third = rng.random((8, 40, 40)).astype(np.float32)
    np.asarray(inferencer(Chunk(third)).array)
    assert len(traces) == 2 * n_traces


def test_fold_family_shares_program_cache():
    """The fold path keys per padded shape in the shared ProgramCache:
    three ragged shapes, one bucket, one build."""
    inferencer = Inferencer(
        input_patch_size=(4, 16, 16),
        output_patch_overlap=(2, 8, 8),
        num_output_channels=1,
        framework="identity",
        batch_size=2,
        blend="fold",
        crop_output_margin=False,
    )
    rng = np.random.default_rng(2)
    for shape in ((8, 30, 30), (7, 27, 32), (8, 32, 32)):
        np.asarray(inferencer(Chunk(rng.random(shape, dtype=np.float32))).array)
    assert inferencer._programs.builds == 1
    assert inferencer._programs.hits == 2


# ---------------------------------------------------------------------------
# the persistent cache is placed from outside (ISSUE 21)
# ---------------------------------------------------------------------------
@pytest.fixture()
def fresh_cache_state(monkeypatch):
    """enable_persistent_cache() as a new process would see it, with
    jax's cache directory restored afterwards."""
    import jax

    from chunkflow_tpu.core import compile_cache

    before = jax.config.jax_compilation_cache_dir
    restore = jax.config.update  # tests below replace it
    monkeypatch.setattr(compile_cache, "_PERSISTENT_DIR", None)
    yield compile_cache
    restore("jax_compilation_cache_dir", before)


def test_cache_dir_from_environment_is_not_set_in_code(
        fresh_cache_state, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set (tests/conftest.py sets it before jax
    loads): the program sets no cache directory at all and reports the
    one jax made of the variable."""
    import os

    import jax

    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    updated = []
    real_update = jax.config.update

    def recording_update(name, value):
        updated.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", recording_update)
    assert fresh_cache_state.enable_persistent_cache() == placed
    assert fresh_cache_state.enable_persistent_cache() == placed  # idempotent
    assert "jax_compilation_cache_dir" not in updated
    assert jax.config.jax_compilation_cache_dir == placed
    assert fresh_cache_state.persistent_cache_dir() == placed


def test_cache_dir_defaults_to_the_checkout(fresh_cache_state, monkeypatch):
    """Variable unset: <checkout>/.jax_cache exactly — no $HOME, no temp
    name, no pid, no time in the path, so a second process finds the
    first one's entries."""
    import os

    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    want = os.path.join(repo, ".jax_cache")
    assert fresh_cache_state.CHECKOUT_CACHE_DIR == want
    assert fresh_cache_state.enable_persistent_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_cache_enable_failure_is_not_swallowed(fresh_cache_state,
                                               monkeypatch):
    import jax

    def broken_update(name, value):
        raise RuntimeError("no cache for you")

    monkeypatch.setattr(jax.config, "update", broken_update)
    with pytest.raises(RuntimeError, match="no cache for you"):
        fresh_cache_state.enable_persistent_cache()
    assert fresh_cache_state.persistent_cache_dir() is None


# ---------------------------------------------------------------------------
# a kernel's lowering is kept beside the executables (ISSUE 47): a process
# that starts traces and lowers no kernel an earlier one has lowered
# ---------------------------------------------------------------------------
_TRACES = []  # the static argument of each trace of _scaled


def _scaled(x, y, *, factor: int):
    """A stand-in for a kernel: what it is lowered from is its arrays'
    shapes and its static arguments."""
    _TRACES.append(factor)
    return (x * factor + y,)


@pytest.fixture()
def lowered_dir(tmp_path, monkeypatch):
    """jax's cache directory at an empty place, this process's lowered
    functions forgotten, both restored afterwards."""
    import jax

    from chunkflow_tpu.core import compile_cache, telemetry

    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    monkeypatch.setattr(compile_cache, "_LOWERED", {})
    telemetry.reset()
    del _TRACES[:]
    yield tmp_path
    telemetry.reset()
    jax.config.update("jax_compilation_cache_dir", before)


def _call(factor=3, shape=(4,)):
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.core import compile_cache

    x, y = jnp.arange(shape[0], dtype=jnp.float32), jnp.ones(shape)
    (out,) = compile_cache.lowered_once(
        _scaled, dict(factor=factor), x, y, platform=jax.default_backend())
    return np.asarray(out), np.asarray(x * factor + y)


def test_a_lowering_is_written_once_and_read_by_the_next_process(
        lowered_dir, monkeypatch):
    from chunkflow_tpu.core import compile_cache, telemetry

    got, want = _call()
    assert np.array_equal(got, want) and _TRACES == [3]
    (entry,) = (lowered_dir / "lowered").iterdir()
    assert telemetry.snapshot()["counters"] == {
        "compile_cache/lowered_builds": 1}
    # the same process again: neither the file nor the function is read
    got, _ = _call()
    assert np.array_equal(got, want) and _TRACES == [3]
    # a process that starts: the function is not traced at all
    monkeypatch.setattr(compile_cache, "_LOWERED", {})
    got, _ = _call()
    assert np.array_equal(got, want) and _TRACES == [3]
    assert telemetry.snapshot()["counters"][
        "compile_cache/lowered_hits"] == 1
    assert [p.name for p in (lowered_dir / "lowered").iterdir()] \
        == [entry.name]


@pytest.mark.parametrize("change", ["static", "shape", "source", "version"])
def test_what_a_lowering_is_made_from_is_its_key(lowered_dir, monkeypatch,
                                                 change):
    """Another static argument, another shape, an edited file or another
    jax: another entry, never a stale one."""
    import jax

    from chunkflow_tpu.core import compile_cache

    _call()
    monkeypatch.setattr(compile_cache, "_LOWERED", {})
    kwargs = {}
    if change == "static":
        kwargs["factor"] = 5
    elif change == "shape":
        kwargs["shape"] = (8,)
    elif change == "source":
        monkeypatch.setattr(compile_cache, "_source_digest",
                            lambda fn: "edited")
    else:
        monkeypatch.setattr(jax, "__version__", "0.0.0")
    got, want = _call(**kwargs)
    assert np.array_equal(got, want)
    assert len(_TRACES) == 2
    assert len(list((lowered_dir / "lowered").iterdir())) == 2


def test_a_broken_entry_is_lowered_again(lowered_dir, monkeypatch):
    from chunkflow_tpu.core import compile_cache

    _call()
    (entry,) = (lowered_dir / "lowered").iterdir()
    entry.write_bytes(b"half a file")
    monkeypatch.setattr(compile_cache, "_LOWERED", {})
    got, want = _call()
    assert np.array_equal(got, want) and len(_TRACES) == 2
    assert entry.read_bytes() != b"half a file"


@pytest.mark.parametrize("directory, platform", [
    (None, "cpu"), ("placed", None)])
def test_without_a_directory_or_a_platform_nothing_is_kept(
        lowered_dir, directory, platform):
    """No cache directory (a library user who enabled none), or an
    interpreted kernel: the calls that agree are still one function of
    the program, and no file is written."""
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.core import compile_cache

    if directory is None:
        jax.config.update("jax_compilation_cache_dir", None)

    def program(x):
        for _ in range(3):
            (x,) = compile_cache.lowered_once(
                _scaled, dict(factor=2), x, x, platform=platform)
        return x

    x = jnp.ones((4,))
    assert np.array_equal(np.asarray(jax.jit(program)(x)), np.full(4, 27.0))
    assert _TRACES == [2]
    assert not (lowered_dir / "lowered").exists()
