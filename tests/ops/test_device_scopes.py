"""The named scopes every patch program traces its parts under (ISSUE
23): they reach the compiled module's ``op_name`` metadata in every
program family, where core/profiling.py reads them back for
``programs.json``; and they are metadata only, so the optimized code is
the same with and without them."""
import contextlib
import re

import numpy as np
import pytest

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.core import profiling, telemetry
from chunkflow_tpu.inference import Inferencer, engines

PIN = (4, 16, 16)
OVERLAP = (2, 8, 8)
FOUR = {"gather", "forward", "accumulate", "normalize"}


@pytest.fixture(scope="module")
def conv_engine():
    """A real conv engine: its forward leaves ops of its own in the
    compiled module (the identity oracle's fuse into the blend's)."""
    return engines.create_flax_engine(
        "", None, PIN, num_input_channels=1, num_output_channels=3)


def make_inferencer(engine, **kw):
    return Inferencer(
        input_patch_size=PIN, output_patch_overlap=OVERLAP,
        num_output_channels=3, framework="prebuilt", batch_size=2,
        engine=engine, crop_output_margin=False, **kw)


def chunk():
    return Chunk(np.random.default_rng(0).random((8, 32, 32),
                                                 dtype=np.float32))


# what selects each program family the tier-1 suite builds
FAMILIES = {
    "scatter": ({}, {}),
    "scatter_fused": ({}, {"CHUNKFLOW_PALLAS": "interpret",
                           "CHUNKFLOW_GATHER": "interpret"}),
    "fold": ({"blend": "fold"}, {}),
    "shard-data": ({"mesh": "data=2"}, {}),
    "shard-spatial": ({"mesh": "y=2"}, {}),
}


@pytest.fixture(scope="module")
def ledger(conv_engine, tmp_path_factory):
    """One chunk through each family with a sink configured: the ledger
    entry of every program then carries ``op_scopes``, read out of the
    module XLA compiled."""
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path_factory.mktemp("metrics")))
    entries = {}
    try:
        for name, (kwargs, env) in FAMILIES.items():
            with monkeypatch.context() as patch:
                for key, value in env.items():
                    patch.setenv(key, value)
                make_inferencer(conv_engine, **kwargs)(chunk())
            for entry in profiling.catalog():
                entries.setdefault((entry["family"], entry["key"]),
                                   (name, entry))
    finally:
        telemetry.reset()
        monkeypatch.undo()
    return {name: entry for name, entry in entries.values()}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_patch_program_carries_the_four_scopes(ledger, family):
    scopes = ledger[family]["op_scopes"]
    assert FOUR <= set(scopes), (family, sorted(scopes))
    assert set(scopes) <= set(profiling.DEVICE_SCOPES) | {""}
    # the model is most of the program, whichever family runs it
    assert len(scopes["forward"]) > len(scopes.get("", []))
    if family.startswith("shard"):
        assert scopes["collective"]
    else:
        assert "collective" not in scopes


def _stripped(hlo: str) -> str:
    """Optimized HLO text with what named scopes can touch taken out:
    each instruction's ``metadata={...}``, the module's tables of
    source files, functions and stack frames (they hold this file's own
    line numbers), and the instructions' names."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"(?m)^(FileNames|FunctionNames|FileLocations|StackFrames)"
                 r"\n(?:.+\n)*", "", hlo)
    # instruction names are labels whose numbering is not the code's:
    # rename them in order of first appearance
    names: dict = {}
    return re.sub(r"%[\w.\-]+",
                  lambda m: names.setdefault(m.group(0), f"%n{len(names)}"),
                  hlo)


def test_scopes_are_metadata_only(conv_engine, monkeypatch):
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.inference.patching import (
        enumerate_patches,
        pad_to_batch,
    )

    grid = enumerate_patches((8, 32, 32), PIN, PIN, OVERLAP)
    in_starts, out_starts, valid = pad_to_batch(grid, 2)
    inferencer = make_inferencer(conv_engine)
    args = (jnp.zeros((1, 8, 32, 32), jnp.float32), jnp.asarray(in_starts),
            jnp.asarray(out_starts), jnp.asarray(valid),
            inferencer.engine.params)

    def compiled_text():
        return inferencer._build_program().lower(*args).compile().as_text()

    # JAX leaves metadata out of the compile-cache key: with the cache
    # on, the second compile would be handed the first one's executable,
    # scopes and all (which is why a cache entry from before the scopes
    # has none: PERF.md)
    from jax.experimental.compilation_cache import compilation_cache

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        scoped = compiled_text()
        monkeypatch.setattr(jax, "named_scope",
                            lambda name: contextlib.nullcontext())
        bare = compiled_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()
    assert FOUR <= set(profiling.op_scopes(scoped))
    assert not set(profiling.op_scopes(bare)) & FOUR
    assert _stripped(scoped) == _stripped(bare)
