"""The named scopes every patch program traces its parts under (ISSUE
23), and those the RSUNet opens around what its own ``__call__`` emits
(ISSUE 40): they reach the compiled module's ``op_name`` metadata in
every program family, where core/profiling.py reads them back for
``programs.json``; and they are metadata only, so the optimized code is
the same with and without them."""
import contextlib
import glob
import json
import os
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


# the RSUNet pools three times: a patch of its own, and an output patch
# smaller than it, so that the decoder's cone cuts (`crop{i}`)
RSUNET_PIN = (8, 32, 32)
RSUNET_POUT = (4, 16, 16)
RSUNET_OVERLAP = (2, 8, 8)


@pytest.fixture(scope="module")
def rsunet_engine():
    return engines.create_flax_engine(
        "", None, RSUNET_PIN, num_input_channels=1, num_output_channels=3,
        dtype="bfloat16", model_variant="rsunet",
        output_patch_size=RSUNET_POUT)


def make_inferencer(engine, pin=PIN, overlap=OVERLAP, **kw):
    return Inferencer(
        input_patch_size=pin, output_patch_overlap=overlap,
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


@pytest.mark.parametrize("which", ["conv", "rsunet"])
def test_scopes_are_metadata_only(which, request, monkeypatch):
    import jax
    import jax.numpy as jnp

    from chunkflow_tpu.inference.patching import (
        enumerate_patches,
        pad_to_batch,
    )

    if which == "conv":
        inferencer = make_inferencer(request.getfixturevalue("conv_engine"))
        chunk_shape, pin, pout, overlap = (8, 32, 32), PIN, PIN, OVERLAP
    else:  # the model's own scopes too: in, pool, crop, skip, post
        inferencer = make_inferencer(
            request.getfixturevalue("rsunet_engine"), RSUNET_PIN,
            RSUNET_OVERLAP, output_patch_size=RSUNET_POUT)
        chunk_shape, pin, pout, overlap = (
            (12, 48, 48), RSUNET_PIN, RSUNET_POUT, RSUNET_OVERLAP)
    grid = enumerate_patches(chunk_shape, pin, pout, overlap)
    in_starts, out_starts, valid = pad_to_batch(grid, 2)
    args = (jnp.zeros((1, *chunk_shape), jnp.float32),
            jnp.asarray(in_starts), jnp.asarray(out_starts),
            jnp.asarray(valid), inferencer.engine.params)

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
    # (the RSUNet's cast fuses the gather's ops into its own on the CPU)
    assert FOUR - {"gather"} <= set(profiling.op_scopes(scoped))
    assert which == "rsunet" or "gather" in profiling.op_scopes(scoped)
    assert not set(profiling.op_scopes(bare)) & FOUR
    if which == "rsunet":
        # the model's own names are in the executable's metadata, fused
        # instructions' too; bare there is none (flax names its modules
        # through jax.named_scope as well)
        for name in ("enc0", "in", "pool0", "skip1", "post"):
            assert f"/forward/RSUNet/{name}/" in scoped
        assert {"enc0", "pool0"} <= set(
            profiling.op_parts(scoped)[0]["forward"])
        assert not profiling.op_parts(bare)[0].get("forward")
        assert "RSUNet/" not in bare
    assert _stripped(scoped) == _stripped(bare)


# ---------------------------------------------------------------------------
# the RSUNet's parts in its patch program (ISSUE 40)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def rsunet_program(rsunet_engine, tmp_path_factory):
    """The RSUNet's patch program's ledger entry and the run's counters,
    with a sink configured."""
    monkeypatch = pytest.MonkeyPatch()
    monkeypatch.delenv("CHUNKFLOW_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.configure(str(tmp_path_factory.mktemp("metrics")))
    try:
        make_inferencer(rsunet_engine, RSUNET_PIN, RSUNET_OVERLAP,
                        output_patch_size=RSUNET_POUT)(
            Chunk(np.random.default_rng(0).random((12, 48, 48),
                                                  dtype=np.float32)))
        (entry,) = [e for e in profiling.catalog()
                    if "forward" in (e["op_parts"] or {})]
        return entry, telemetry.snapshot()["counters"]
    finally:
        telemetry.reset()
        monkeypatch.undo()


def part_patterns() -> dict:
    """``{metric: regex}`` of the per-layer metrics that read
    ``op_parts``: the part names live in their files' ``args``."""
    found = {}
    for path in glob.glob(os.path.join(
            os.path.dirname(__file__), "..", "..", "benchmarks",
            "layer_metrics", "*.json")):
        with open(path) as f:
            parts = json.load(f).get("args", {}).get("parts")
        if parts:
            found[os.path.basename(path)[:-len(".json")]] = parts
    return found


def test_every_part_of_the_rsunet_is_read_by_one_group_of_metrics(
        rsunet_program):
    entry, _ = rsunet_program
    parts = entry["op_parts"]["forward"]
    # every flax module and both branches of the pool leave ops of their
    # own on the CPU too (what else the model names fuses into those here)
    assert {"embed", "enc0", "enc1", "enc2", "bridge", "dec2", "dec1",
            "dec0", "out", "up0", "up1", "up2", "pool0", "pool1",
            "pool2"} <= set(parts)
    # the groups the metric files name: level 0 (twice: its convolutions
    # and the rest), level 1, the deep levels, the glue. Every part the
    # model emits, and every name it gives (also where XLA fused the op
    # away), lies in exactly one
    groups = set(part_patterns().values())
    assert len(groups) == 4
    named = {"in", "post"} | {f"{kind}{i}" for i in range(3)
                              for kind in ("pool", "skip")} \
        | {f"crop{i}" for i in range(4)}
    for part in (set(parts) - {""}) | named:
        assert sum(bool(re.fullmatch(group, part)) for group in groups) \
            == 1, part


def test_no_convolution_of_the_rsunet_is_left_without_a_part(rsunet_program):
    entry, counters = rsunet_program
    convolutions = entry["op_convolutions"]
    assert convolutions
    for scope, by_part in entry["op_parts"].items():
        held = [op for op in by_part.get("", []) if op in convolutions]
        assert not held or scope != "forward", held
    # every convolution of the program is the model's
    assert set(convolutions) <= {
        op for ops in entry["op_parts"]["forward"].values() for op in ops}
    for held in convolutions.values():
        assert all(re.fullmatch(r"\d+x\d+x\d+", window)
                   for _, window in held)


@pytest.mark.parametrize("part,windows", [
    # `up0` (1,2,2): one convolution of the zero-dilated input, two taps
    # in y; the bias and the skip sum ride behind it where XLA fuses them
    ("up0", ["1x2x1"]),
    # `up1` (2,2,2): one such convolution a z plane
    ("up1", ["1x2x1"] * 2),
    # `up2`, below the folded levels, is flax's own transposed convolution
    ("up2", ["2x2x2"]),
])
def test_an_upsamplings_convolutions_lie_under_its_own_part(
        rsunet_program, part, windows):
    entry, _ = rsunet_program
    by_part = entry["op_parts"]["forward"]
    held = [tuple(conv) for op in by_part[part]
            for conv in entry["op_convolutions"].get(op, [])]
    assert sorted(held) == sorted((part, window) for window in windows)
    # and under no other part: a fusion goes by its widest convolution,
    # and no other convolution is as narrow as an up-sampling's
    for other, ops in by_part.items():
        if other != part:
            assert not any(path == part for op in ops for path, _ in
                           entry["op_convolutions"].get(op, []))
    # the cone's cuts leave no op of their own: they are the padding of
    # `up0`'s and `up1`'s convolutions (level 3 is never cut)
    assert not {"crop1", "crop2", "crop3"} & set(by_part)
    assert [entry[f"up{i}_convolutions"] for i in range(3)] == [1, 2, 1]


def test_the_pools_say_on_the_programs_entry_where_they_run(rsunet_program):
    """``forward/pool{i}_folded`` lands in ``programs.json`` like
    ``x_fold``: the two pools of the folded levels take their maximum on
    the folded array, under their own part; the third unfolds nothing."""
    entry, _ = rsunet_program
    assert [entry[f"pool{i}_folded"] for i in range(3)] == [1, 1, 0]
    assert {"pool0", "pool1", "pool2"} <= set(entry["op_parts"]["forward"])


def test_a_fresh_executable_is_not_called_stale(rsunet_program):
    """`crop{i}` is one slice each, which XLA folds into its reader: the
    executable lacks the name and is still today's."""
    _, counters = rsunet_program
    assert "program/stale_cache_entries" not in counters
    assert counters["program/op_map_seconds"] > 0
