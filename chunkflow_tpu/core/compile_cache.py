"""Compile-cache layer: persistent XLA artifacts + in-process programs.

Two tiers, attacking two different retrace costs:

1. **Persistent compilation cache** (:func:`enable_persistent_cache`):
   jax's on-disk cache, so a process restart skips the UNet compile.
   Where it lives is decided outside the program: with
   ``JAX_COMPILATION_CACHE_DIR`` set, jax reads the variable itself and
   this module sets no directory at all; without it the cache is
   ``<checkout>/.jax_cache`` — one fixed path, because the directory is
   part of what makes an entry hit. Entries below
   ``min_compile_time_secs`` are not persisted, so CPU test-suite
   micro-programs never churn the disk.

2. **In-process keyed program cache** (:class:`ProgramCache`): one bounded
   FIFO map from geometry key -> built (jit-wrapped) program, shared by
   every program family the :class:`~chunkflow_tpu.inference.inferencer.
   Inferencer` builds (scatter, fold, patch-sharded, spatial, spatial2d).
   The key is derived from the *bucketed* run shape (``shape_bucket``), so
   ragged edge chunks that pad into the same bucket hit the same entry and
   never retrace. ``builds``/``hits`` counters make trace counts a
   testable invariant (tests/inference/test_compile_cache.py).

3. **Lowered kernels** (:func:`lowered_once`): a Pallas kernel is traced
   to a jaxpr and lowered to Mosaic's MLIR in Python by every process
   whose program holds it, before jax can even ask the persistent cache
   for the executable (tens of ms a distinct kernel; 4 s of a warm start
   for the RSUNet's, PERF.md, PR 47). The lowered function is kept beside
   the executables (``<cache dir>/lowered/``) as ``jax.export`` writes
   it, keyed on everything it was lowered from, and a later process
   reads it back in a millisecond.

Donation note: programs cached here donate their chunk buffer
(``donate_argnums=(0,)``, GL005) — see docs/performance.md for the
buffer-lifetime contract. When XLA cannot alias the donated input to the
output (e.g. 1 input channel, 3 affinity output channels) it emits a
"donated buffers were not usable" warning on every compile; that is the
expected, harmless half of the donation bargain, so it is silenced
process-wide on import of this module.
"""
from __future__ import annotations

import os
import threading
import warnings
from typing import Callable, Hashable, Optional

from chunkflow_tpu.core import profiling, telemetry

# Donation is best-effort by design: a chunk buffer that cannot alias the
# program's output is simply dropped, and the warning would otherwise fire
# once per compiled geometry (ops/fold_blend.py, parallel/*, inferencer).
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable"
)

_LOCK = threading.Lock()
_PERSISTENT_DIR: Optional[str] = None


class RetraceWarning(UserWarning):
    """More program builds than the planned bucket count (see
    :class:`ProgramCache`)."""


def persistent_cache_dir() -> Optional[str]:
    """The on-disk XLA cache directory in effect, or None before the
    first :func:`enable_persistent_cache` (CLI end-of-run summary)."""
    return _PERSISTENT_DIR


#: ``<checkout>/.jax_cache`` (gitignored): the cache's place when
#: ``JAX_COMPILATION_CACHE_DIR`` does not give one
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_persistent_cache() -> Optional[str]:
    """Enable jax's on-disk compilation cache; returns the directory in
    effect. Idempotent. A failure to enable raises: a worker that
    recompiles the UNet on every start must not look healthy.

    With ``JAX_COMPILATION_CACHE_DIR`` in the environment the directory
    is whatever jax made of that variable — no directory is set here.
    Otherwise it is :data:`CHECKOUT_CACHE_DIR`.
    """
    global _PERSISTENT_DIR
    import jax

    with _LOCK:
        if _PERSISTENT_DIR is not None:
            return _PERSISTENT_DIR
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              CHECKOUT_CACHE_DIR)
        # persist everything that took real compile time; tiny CPU
        # test programs stay in-memory only
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        _PERSISTENT_DIR = jax.config.jax_compilation_cache_dir
    return _PERSISTENT_DIR


_LOWERED: dict = {}  # key -> the lowered function's call, this process


def _source_digest(fn) -> str:
    """Of the file that defines ``fn``: a kernel edited is another key."""
    import hashlib
    import inspect

    with open(inspect.getsourcefile(fn), "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def lowered_once(fn, static: dict, *args, platform: Optional[str] = "tpu"):
    """``fn(*args, **static)``, lowered for ``platform`` once per cache
    directory and not once per process: the calls of one program that
    agree in key are one function of it (a ``jit`` of their own), and the
    function's lowering is read from ``<cache dir>/lowered/<key>`` where
    an earlier process left it (``jax.export``; written through a
    temporary name behind its own digest: half a file, or another's, is
    no entry and is lowered again). The key is what
    the lowering is made from: the function's name and its file's digest,
    jax's and jaxlib's versions, the platform, ``static`` (everything the
    function reads that is no array belongs there) and the arrays' tree,
    shapes and dtypes. Without a cache directory (jax's own,
    ``jax_compilation_cache_dir``) or a ``platform`` (an interpreted
    kernel is the process's own backend's) the function is lowered in
    place as any other."""
    import hashlib

    import jax
    import jaxlib
    from jax import export

    leaves, tree = jax.tree_util.tree_flatten(args)
    key = hashlib.sha256(repr((
        fn.__module__, fn.__qualname__, _source_digest(fn), jax.__version__,
        jaxlib.__version__, platform, sorted(static.items()), str(tree),
        [(leaf.shape, str(leaf.dtype)) for leaf in leaves])).encode()
    ).hexdigest()
    with _LOCK:
        call = _LOWERED.get(key)
    if call is None:
        directory = jax.config.jax_compilation_cache_dir
        def lowered(*arrays):
            return fn(*arrays, **static)

        # the kernel's instruction is named after the innermost jit
        lowered.__name__ = lowered.__qualname__ = fn.__name__.lstrip("_")
        one = jax.jit(lowered)
        if directory and platform:
            path = os.path.join(directory, "lowered", key)
            try:
                with open(path, "rb") as f:
                    digest, kept = f.read(32), f.read()
                if hashlib.sha256(kept).digest() != digest:
                    raise ValueError(f"{path}: not what was written")
                exported = export.deserialize(bytearray(kept))
                telemetry.inc("compile_cache/lowered_hits")
            except (OSError, ValueError):
                exported = export.export(one, platforms=(platform,))(
                    *jax.tree_util.tree_map(
                        lambda leaf: jax.ShapeDtypeStruct(leaf.shape,
                                                          leaf.dtype), args))
                telemetry.inc("compile_cache/lowered_builds")
                try:
                    os.makedirs(os.path.dirname(path), exist_ok=True)
                    kept = bytes(exported.serialize())
                    with open(f"{path}.{os.getpid()}", "wb") as f:
                        f.write(hashlib.sha256(kept).digest() + kept)
                    os.replace(f.name, path)
                except OSError:
                    pass  # a cache that cannot be written is no cache
            one = jax.jit(exported.call)
        with _LOCK:
            call = _LOWERED.setdefault(key, one)
    return call(*args)


class ProgramCache:
    """Bounded FIFO cache of built programs keyed on trace geometry.

    Each entry's closure pins its engine (and params) alive, so the cache
    is bounded: past ``maxsize`` the oldest entry is dropped (same policy
    as parallel/distributed._PROGRAM_CACHE). ``builds`` counts builder
    invocations — i.e. traces of new program geometry — and ``hits``
    counts reuses, so tests can assert "two same-bucket chunks, one
    trace" as an invariant instead of a benchmark. Both also feed the
    process-global telemetry counters (``compile_cache/builds``,
    ``compile_cache/hits``) the CLI surfaces at end of run.

    Retrace watchdog: ``expected_builds`` is the bucket count the owner
    planned for (with shape bucketing, ragged chunks collapse into a
    handful of buckets). The first build past it raises a
    ``RetraceWarning`` — the signature of a silent retrace-per-chunk
    (e.g. bucketing misconfigured, a key deriving from the RAW rather
    than bucketed shape) that would otherwise only show up as an
    unexplained N-minute compile stall per task.
    """

    def __init__(self, maxsize: int = 16,
                 expected_builds: Optional[int] = None,
                 label: str = "programs"):
        if maxsize < 1:
            raise ValueError(f"maxsize must be >= 1, got {maxsize}")
        self.maxsize = maxsize
        self.expected_builds = expected_builds
        self.label = label
        self.builds = 0
        self.hits = 0
        self._warned = False
        self._entries: dict = {}
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, key: Hashable) -> bool:
        return key in self._entries

    def items(self):
        """Snapshot of (key, program) pairs (debugging, tests)."""
        with self._lock:
            return list(self._entries.items())

    def peek(self, key: Hashable, default=None):
        """The cached program for ``key`` without building or counting."""
        return self._entries.get(key, default)

    def get(self, key: Hashable, build: Callable[[], object]):
        """Return the cached program for ``key``, building (and counting a
        trace) on first sight. Eviction is FIFO by insertion order."""
        with self._lock:
            if key in self._entries:
                self.hits += 1
                hit = self._entries[key]
            else:
                hit = None
        if hit is not None:
            telemetry.inc("compile_cache/hits")
            return hit
        # build outside the lock: builders jit-trace, which can re-enter
        # (a fold program build may consult the same Inferencer)
        with telemetry.span("compile_cache/build", label=self.label) as sp:
            program = build()
        # cost ledger (core/profiling.py): the wrapper times the first
        # invocation — the one that pays trace + XLA compile — and
        # captures the program's XLA cost analysis; a no-op passthrough
        # under CHUNKFLOW_TELEMETRY=0 or for non-jit cache entries
        program = profiling.instrument_program(
            program, key, label=self.label,
            build_s=getattr(sp, "duration", 0.0),
        )
        raced = False
        with self._lock:
            if key not in self._entries:
                self.builds += 1
                self._entries[key] = program
                while len(self._entries) > self.maxsize:
                    self._entries.pop(next(iter(self._entries)))
            else:
                # lost a race: keep the first-published program so every
                # caller shares one compiled executable
                self.hits += 1
                raced = True
            result = self._entries[key]
        telemetry.inc("compile_cache/hits" if raced else
                      "compile_cache/builds")
        if not raced:
            self._watchdog()
        return result

    def _watchdog(self) -> None:
        """Warn (once per cache) when builds exceed the planned bucket
        count — the retrace-per-chunk signature."""
        if (self.expected_builds is None or self._warned
                or self.builds <= self.expected_builds):
            return
        self._warned = True
        telemetry.inc("compile_cache/retrace_warnings")
        # a retrace-per-chunk in flight is the highest-value moment for
        # device evidence: one bounded profiler window (cooldown-gated,
        # core/profiling.py) captures what the extra compiles cost
        profiling.note_retrace(self.label)
        warnings.warn(
            f"ProgramCache[{self.label}]: {self.builds} program builds "
            f"exceed the expected bucket count "
            f"({self.expected_builds}) — likely a retrace per chunk "
            f"(check --shape-bucket / key derivation); every extra "
            f"build pays a full XLA compile",
            RetraceWarning,
            stacklevel=3,
        )

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
