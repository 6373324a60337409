"""Unified multi-chip engine: ONE parametrized parity matrix.

Replaces the per-variant test trios (test_distributed / test_spatial /
test_spatial2d): every mesh shape the spec grammar can express runs the
same traffic — plain, ragged, uint8, crop-margin, packed-serve — against
the single-device reference program and must match **bitwise** (the
engine's contract: forward sharded, reference accumulation replayed;
chunkflow_tpu/parallel/engine.py). Runs on the 8-device virtual CPU mesh
(tests/conftest.py)."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

from chunkflow_tpu.chunk.base import Chunk
from chunkflow_tpu.inference import engines
from chunkflow_tpu.inference.inferencer import Inferencer
from chunkflow_tpu.parallel.engine import (
    MeshSpec,
    parse_mesh_spec,
    sharded_inference,
)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs 8 virtual devices (see tests/conftest.py)",
)

PIN = (4, 16, 16)
OVERLAP = (2, 8, 8)

# the matrix: every engine kind and several shapes of each — mesh
# shapes 1 (kill switch) / 2 / 4 / 8 on the data axis plus 1D and 2D
# spatial layouts, per the ISSUE 13 acceptance grid; ISSUE 19 adds the
# pipeline (stage-parallel) kind — the identity engines declare the
# stage protocol, so the whole traffic grid covers it too. The
# sharded (slab) blend replay is the DEFAULT, so every row below
# exercises it; the replicated flip is pinned separately.
MESHES = ["1", "data=2", "data=4", "data=8", "y=2", "y=4", "y=8",
          "y=2,x=2", "y=4,x=2", "y=2,x=4", "pipeline=4", "pipeline=8"]


@pytest.fixture(scope="module")
def conv_engine():
    """A real conv engine (not identity): bitwise parity must hold for
    arbitrary float math, not just the identity oracle."""
    return engines.create_flax_engine(
        "", None, PIN, num_input_channels=1, num_output_channels=3,
    )


@pytest.fixture(scope="module")
def id_engine():
    """The identity engine drives the wide matrix: its programs compile
    in milliseconds on the virtual CPU mesh, so 10 mesh shapes x 4
    traffic classes stay inside the tier-1 wall-clock budget; the
    conv-engine spot checks below pin the arbitrary-float-math case."""
    return engines.create_identity_engine(
        input_patch_size=PIN, output_patch_size=PIN,
        num_input_channels=1, num_output_channels=3,
    )


def make_inferencer(engine, **kw):
    kw.setdefault("crop_output_margin", False)
    return Inferencer(
        input_patch_size=PIN,
        output_patch_overlap=OVERLAP,
        num_output_channels=3,
        framework="prebuilt",
        batch_size=2,
        engine=engine,
        **kw,
    )


# one single-device reference inferencer and one mesh inferencer per
# (mesh, crop) config, shared across the whole matrix — a fresh
# Inferencer per case would recompile every program 40 times. The crop
# config uses a central-crop identity engine (pout < pin) so the margin
# crop is a REAL (1, 4, 4) crop, not a zero-width no-op.
@pytest.fixture(scope="module")
def shared(id_engine):
    crop_engine = engines.create_identity_engine(
        input_patch_size=PIN, output_patch_size=(2, 8, 8),
        num_input_channels=1, num_output_channels=3,
    )
    cache: dict = {}

    def get(mesh=None, crop=False):
        key = (mesh, crop)
        if key not in cache:
            if crop:
                cache[key] = Inferencer(
                    input_patch_size=PIN,
                    output_patch_size=(2, 8, 8),
                    output_patch_overlap=(1, 4, 4),
                    num_output_channels=3,
                    framework="prebuilt",
                    batch_size=2,
                    engine=crop_engine,
                    mesh=mesh,
                    crop_output_margin=True,
                )
            else:
                cache[key] = make_inferencer(id_engine, mesh=mesh)
        return cache[key]

    return get


# ---------------------------------------------------------------------------
# spec grammar
# ---------------------------------------------------------------------------
def test_spec_grammar():
    assert parse_mesh_spec(None).kind == "single"
    assert parse_mesh_spec("1").kind == "single"
    assert parse_mesh_spec("off").kind == "single"
    assert parse_mesh_spec("auto", 8) == MeshSpec("data", (8,))
    assert parse_mesh_spec("auto", 1).kind == "single"
    assert parse_mesh_spec("8") == MeshSpec("data", (8,))
    assert parse_mesh_spec("data=4") == MeshSpec("data", (4,))
    assert parse_mesh_spec("y=4") == MeshSpec("spatial", (4, 1))
    assert parse_mesh_spec("x=4") == MeshSpec("spatial", (1, 4))
    assert parse_mesh_spec("y=4,x=2") == MeshSpec("spatial", (4, 2))
    assert parse_mesh_spec("y=1,x=1").kind == "single"
    assert parse_mesh_spec("data=8").describe() == "data=8"
    assert parse_mesh_spec("y=4,x=2").describe() == "y=4,x=2"
    assert parse_mesh_spec("pipeline=4") == MeshSpec("pipeline", (4,))
    assert parse_mesh_spec("pipeline=4").describe() == "pipeline=4"
    assert parse_mesh_spec("pipeline=1").kind == "single"
    with pytest.raises(ValueError, match="bad mesh spec"):
        parse_mesh_spec("z=4")
    with pytest.raises(ValueError, match="does not compose"):
        parse_mesh_spec("data=4,y=2")
    with pytest.raises(ValueError, match="does not compose"):
        parse_mesh_spec("pipeline=2,y=2")
    with pytest.raises(ValueError, match="devices"):
        parse_mesh_spec("pipeline=16", 8)
    with pytest.raises(ValueError, match="duplicate"):
        parse_mesh_spec("y=2,y=4")
    with pytest.raises(ValueError, match="devices"):
        parse_mesh_spec("data=16", 8)


# ---------------------------------------------------------------------------
# the parity matrix
# ---------------------------------------------------------------------------
def _traffic_chunk(traffic: str, seed: int):
    rng = np.random.default_rng(seed)
    if traffic == "ragged":
        # non-divisible extents: edge snapping + uneven slab buckets
        return Chunk(rng.random((6, 37, 45)).astype(np.float32))
    if traffic == "uint8":
        # narrow-input device normalization path
        return Chunk(rng.integers(0, 256, (8, 40, 48), dtype=np.uint8))
    return Chunk(rng.random((8, 40, 48)).astype(np.float32))


@pytest.mark.parametrize("mesh", [m for m in MESHES if m != "1"])
@pytest.mark.parametrize(
    "traffic", ["plain", "ragged", "uint8", "crop_margin"]
)
def test_mesh_bitwise_parity_matrix(shared, mesh, traffic):
    """Every mesh shape x every traffic class == the single-device
    program, bitwise ('crop_margin' additionally exercises the
    post-blend margin crop). Identity engine: its programs compile in
    milliseconds, which is what lets a 36-case matrix live in tier-1;
    the conv spot checks below cover arbitrary float forward math."""
    crop = traffic == "crop_margin"
    chunk = _traffic_chunk(traffic, seed=abs(hash(traffic)) % 2**31)
    ref = np.asarray(shared(crop=crop)(chunk).array)
    out = np.asarray(shared(mesh=mesh, crop=crop)(chunk).array)
    assert out.dtype == ref.dtype
    assert out.shape == ref.shape
    assert np.array_equal(out, ref), (
        f"mesh {mesh} diverged from the single-device reference "
        f"(max abs diff "
        f"{np.abs(out.astype(np.float64) - ref.astype(np.float64)).max():.3e})"
    )


def test_kill_switch_spec_is_single(shared):
    """Mesh '1' (the kill-switch row of the matrix) resolves to NO
    engine at all — covered in depth by test_env_spec_and_kill_switch."""
    assert shared(mesh="1").shard_engine() is None


@pytest.mark.parametrize("mesh", ["data=8", "y=4,x=2"])
def test_conv_engine_bitwise_spot_checks(conv_engine, mesh):
    """The bit-identity contract on REAL conv forward math (per-row
    independence of batched convs is the property the replay design
    rests on) — two representative mesh kinds."""
    rng = np.random.default_rng(11)
    chunk = Chunk(rng.random((6, 37, 45)).astype(np.float32))
    ref = np.asarray(make_inferencer(conv_engine)(chunk).array)
    out = np.asarray(
        make_inferencer(conv_engine, mesh=mesh)(chunk).array
    )
    assert np.array_equal(out, ref)


def test_identity_oracle_through_mesh():
    """The identity oracle (blended overlap-add of identity patches
    reproduces the input) holds through the sharded path — the same
    oracle the reference's single-GPU tests pin."""
    rng = np.random.default_rng(0)
    chunk = rng.random((8, 32, 48)).astype(np.float32)
    engine = engines.create_identity_engine(
        input_patch_size=PIN, output_patch_size=PIN,
        num_input_channels=1, num_output_channels=3,
    )
    for spec in ("data=8", "y=4,x=2"):
        out = np.asarray(sharded_inference(
            chunk, engine, PIN, None, OVERLAP, batch_size=1,
            spec=parse_mesh_spec(spec, 8),
        ))
        np.testing.assert_allclose(
            out, np.broadcast_to(chunk, out.shape), atol=1e-5
        )


def test_uint8_output_dtype_through_mesh(id_engine):
    """The on-device quantized output path survives sharding bitwise."""
    rng = np.random.default_rng(3)
    chunk = Chunk(rng.random((8, 40, 48)).astype(np.float32))
    ref = np.asarray(
        make_inferencer(id_engine, output_dtype="uint8")(chunk).array
    )
    out = np.asarray(
        make_inferencer(id_engine, output_dtype="uint8",
                        mesh="y=2,x=2")(chunk).array
    )
    assert out.dtype == np.uint8
    assert np.array_equal(out, ref)


# ---------------------------------------------------------------------------
# kill switch + env resolution
# ---------------------------------------------------------------------------
def test_env_spec_and_kill_switch(id_engine, monkeypatch):
    """CHUNKFLOW_MESH is re-read per chunk: flipping the kill switch on
    a live inferencer restores the single-device program (the engine
    resolves to None and the ('scatter',) family runs), bit-identically."""
    rng = np.random.default_rng(1)
    chunk = Chunk(rng.random((8, 40, 48)).astype(np.float32))
    ref = np.asarray(make_inferencer(id_engine)(chunk).array)

    inf = make_inferencer(id_engine)
    monkeypatch.setenv("CHUNKFLOW_MESH", "data=4")
    assert inf.shard_engine() is not None
    out = np.asarray(inf(chunk).array)
    assert np.array_equal(out, ref)
    assert any(k[0] == "shard" for k, _ in inf._programs.items())

    monkeypatch.setenv("CHUNKFLOW_MESH", "1")
    assert inf.shard_engine() is None
    out = np.asarray(inf(chunk).array)
    assert np.array_equal(out, ref)
    assert inf._programs.peek(("scatter",)) is not None


def test_explicit_mesh_overrides_env(id_engine, monkeypatch):
    monkeypatch.setenv("CHUNKFLOW_MESH", "data=8")
    inf = make_inferencer(id_engine, mesh="y=2")
    assert inf.shard_engine().spec == MeshSpec("spatial", (2, 1))
    monkeypatch.setenv("CHUNKFLOW_MESH", "1")
    # explicit argument still wins — the env kill switch governs only
    # env-resolved meshes
    assert inf.shard_engine() is not None


def test_mesh_and_legacy_sharding_conflict(id_engine):
    with pytest.raises(ValueError, match="does not compose"):
        make_inferencer(id_engine, mesh="data=4", sharding="patch")


@pytest.mark.parametrize("legacy,kind,shape", [
    ("patch", "data", (8,)),
    ("spatial", "spatial", (8, 1)),
    ("spatial2d", "spatial", (2, 4)),
])
def test_legacy_sharding_aliases(id_engine, legacy, kind, shape):
    """The legacy sharding names map onto the unified engine layouts."""
    inf = make_inferencer(id_engine, sharding=legacy)
    spec = inf.shard_engine().spec
    assert spec.kind == kind
    assert spec.shape == shape


# ---------------------------------------------------------------------------
# legacy wrapper delegation (the subsumed modules)
# ---------------------------------------------------------------------------
def test_legacy_wrappers_delegate_bitwise(id_engine):
    from chunkflow_tpu.parallel.distributed import sharded_inference as d
    from chunkflow_tpu.parallel.spatial import spatial_sharded_inference
    from chunkflow_tpu.parallel.spatial2d import (
        spatial2d_sharded_inference,
    )

    rng = np.random.default_rng(2)
    chunk = rng.random((8, 40, 48)).astype(np.float32)
    ref = np.asarray(
        make_inferencer(id_engine)(Chunk(chunk.copy())).array
    )
    for fn in (d, spatial_sharded_inference, spatial2d_sharded_inference):
        out = np.asarray(fn(
            chunk, id_engine, PIN, PIN, OVERLAP, batch_size=2,
        ))
        assert np.array_equal(out, ref), fn.__name__


# ---------------------------------------------------------------------------
# seams: scheduler stream, serving packer, telemetry/roofline
# ---------------------------------------------------------------------------
def test_scheduled_stream_bitwise_through_mesh(id_engine, monkeypatch):
    """The adaptive scheduler seam: Inferencer.stream over a mesh-active
    inferencer is bit-identical to the serial single-device loop, and
    the stream announces its mesh (scheduler/mesh event)."""
    from chunkflow_tpu.core import telemetry

    rng = np.random.default_rng(4)
    chunks = [
        Chunk(rng.random((8, 40, 48)).astype(np.float32),
              voxel_offset=(8 * i, 0, 0))
        for i in range(4)
    ]
    refs = [
        np.asarray(make_inferencer(id_engine)(c).array) for c in chunks
    ]
    monkeypatch.setenv("CHUNKFLOW_MESH", "y=2,x=2")
    events = []
    monkeypatch.setattr(
        telemetry, "event",
        lambda kind, name, **attrs: events.append((kind, name, attrs)),
    )
    inf = make_inferencer(id_engine)
    outs = [np.asarray(c.array) for c in inf.stream(iter(chunks))]
    for ref, out in zip(refs, outs):
        assert np.array_equal(out, ref)
    assert any(
        k == "scheduler" and n == "mesh" and a.get("mesh") == "y=2,x=2"
        for k, n, a in events
    ), events


def test_packed_serving_shards_across_chips(id_engine, monkeypatch):
    """The serving seam: packed batches span the slice (B * n_chips
    slots), stay bit-identical to the per-chunk path, and feed the
    occupancy gauge per chip."""
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.serve.packer import PatchPacker

    rng = np.random.default_rng(5)
    inf = Inferencer(
        input_patch_size=PIN,
        output_patch_overlap=(0, 0, 0),
        num_output_channels=3,
        framework="prebuilt",
        batch_size=2,
        engine=id_engine,
        crop_output_margin=False,
    )
    chunks = [
        Chunk(rng.random((4, 16, 48)).astype(np.float32),
              voxel_offset=(4 * i, 0, 0))
        for i in range(8)
    ]
    monkeypatch.setenv("CHUNKFLOW_MESH", "1")
    refs = [np.asarray(inf(c).array) for c in chunks]

    monkeypatch.setenv("CHUNKFLOW_MESH", "data=4")
    telemetry.reset()
    packer = PatchPacker(inf, max_wait_ms=25.0)
    try:
        handles = [packer.submit(c) for c in chunks]
        outs = [np.asarray(h.result(timeout=120).array) for h in handles]
    finally:
        packer.close()
    for ref, out in zip(refs, outs):
        assert np.array_equal(out, ref)
    snap = telemetry.snapshot()
    assert snap["gauges"].get("serving/chips") == 4.0
    # 8 requests x 3 patches over 8-slot (2 x 4 chips) dispatches: the
    # packer must have packed across requests, not one per dispatch
    batches = snap["counters"]["serving/batches"]
    assert batches <= 4, snap["counters"]
    telemetry.reset()


def test_shard_telemetry_and_roofline_ledger(id_engine, tmp_path,
                                             monkeypatch):
    """Sharded programs ride the ProgramCache, so they land in the PR 8
    roofline ledger (programs.json) with shard/* gauges alongside."""
    import json

    from chunkflow_tpu.core import telemetry

    monkeypatch.setenv("CHUNKFLOW_MESH", "data=4")
    telemetry.reset()
    telemetry.configure(str(tmp_path))
    try:
        inf = make_inferencer(id_engine)
        rng = np.random.default_rng(6)
        np.asarray(inf(Chunk(rng.random((8, 40, 48)).astype(
            np.float32))).array)
        snap = telemetry.snapshot()
        assert snap["gauges"].get("shard/mesh_devices") == 4.0
        assert snap["gauges"].get("shard/per_chip_voxels") == float(
            8 * 40 * 48)
        assert snap["counters"].get("shard/chunks") == 1
        telemetry.flush()
    finally:
        telemetry.configure(None)
        telemetry.reset()
    catalog = json.loads((tmp_path / "programs.json").read_text())
    entries = catalog["programs"]
    shard_entries = [
        e for e in entries
        if e.get("family") == "shard" or "shard" in str(e.get("key"))
    ]
    assert shard_entries, entries
    # the ledger carries real cost accounting for the sharded program
    assert shard_entries[0].get("compile_s") is not None


def test_per_chip_attribution_gauges(id_engine, monkeypatch):
    """ISSUE 18: every sharded dispatch attributes its work per chip —
    shard/chip/<i>/voxels load gauges, a sampled readiness probe
    (shard/chip/<i>/ready_s + shard/chip_skew_s), and analytic
    collective byte counters with the compute-vs-collective split."""
    from chunkflow_tpu.core import telemetry

    monkeypatch.setenv("CHUNKFLOW_MESH", "data=8")
    monkeypatch.setenv("CHUNKFLOW_CHIP_PROBE_EVERY", "1")
    telemetry.reset()
    try:
        inf = make_inferencer(id_engine)
        rng = np.random.default_rng(11)
        np.asarray(inf(Chunk(rng.random((8, 40, 48)).astype(
            np.float32))).array)
        gauges = telemetry.snapshot()["gauges"]
        counters = telemetry.snapshot()["counters"]
    finally:
        telemetry.reset()
    chip_vox = {int(m.group("chip")): v for name, v in gauges.items()
                for m in [telemetry.CHIP_METRIC_RE.match(name)]
                if m and m.group("plane") == "shard"
                and m.group("metric") == "voxels"}
    assert sorted(chip_vox) == list(range(8))
    # attribution is real, not degenerate: whole patches only, covering
    # at least the chunk (overlap re-visits voxels), unevenly spread
    # because the padded grid does not divide 8 ways
    pvox = float(np.prod(PIN))
    total = sum(chip_vox.values())
    assert total % pvox == 0 and total >= 8 * 40 * 48
    assert len(set(chip_vox.values())) > 1
    # the readiness probe stamped every chip, cumulative hence monotone
    readies = [gauges[f"shard/chip/{i}/ready_s"] for i in range(8)]
    assert readies == sorted(readies)
    assert gauges["shard/chip_skew_s"] == pytest.approx(
        readies[-1] - readies[0])
    # analytic collective plane: the data axis all-gathers the output
    # rows. Bytes only: no seconds are reckoned from them (a device
    # trace times the ops under the `collective` scope)
    assert counters["shard/gather_bytes"] > 0
    assert gauges["shard/gather_bytes_per_chunk"] == pytest.approx(
        counters["shard/gather_bytes"])
    assert not [name for name in gauges if name.endswith("_est")]


def test_spatial_mesh_stamps_halo_bytes(id_engine, monkeypatch):
    """A 2D spatial mesh exchanges halos on both axes: the analytic
    halo counter is non-zero and separate from the replay planes. The
    sharded replay default ships fringe windows (replay_strip_bytes)
    instead of the full-stack all_gather; the replicated flip restores
    the gather plane (ISSUE 19)."""
    from chunkflow_tpu.core import telemetry

    monkeypatch.setenv("CHUNKFLOW_MESH", "y=2,x=2")
    telemetry.reset()
    try:
        inf = make_inferencer(id_engine)
        rng = np.random.default_rng(12)
        np.asarray(inf(Chunk(rng.random((8, 40, 48)).astype(
            np.float32))).array)
        snap = telemetry.snapshot()
    finally:
        telemetry.reset()
    assert snap["counters"]["shard/halo_bytes"] > 0
    assert snap["counters"]["shard/replay_strip_bytes"] > 0
    assert "shard/gather_bytes" not in snap["counters"]
    # the analytic slab+margin blend-buffer plane, per chip too
    assert snap["gauges"]["shard/replay_buffer_bytes"] > 0
    assert all(snap["gauges"].get(f"shard/chip/{i}/replay_buffer_bytes")
               for i in range(4))
    chip_vox = [snap["gauges"].get(f"shard/chip/{i}/voxels")
                for i in range(4)]
    assert all(v is not None for v in chip_vox)

    monkeypatch.setenv("CHUNKFLOW_SHARD_REPLAY", "replicated")
    telemetry.reset()
    try:
        inf = make_inferencer(id_engine)
        rng = np.random.default_rng(12)
        np.asarray(inf(Chunk(rng.random((8, 40, 48)).astype(
            np.float32))).array)
        snap = telemetry.snapshot()
    finally:
        telemetry.reset()
    assert snap["counters"]["shard/gather_bytes"] > 0
    assert "shard/replay_strip_bytes" not in snap["counters"]


def test_telemetry_off_means_no_chip_probes(id_engine, monkeypatch):
    """CHUNKFLOW_TELEMETRY=0 acceptance: the sharded path emits no
    per-chip gauges and never runs the readiness probe (no extra
    block_until_ready on the dispatch path) — and stays bitwise
    identical to the telemetry-on run."""
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.parallel import engine as engine_mod

    monkeypatch.setenv("CHUNKFLOW_MESH", "data=8")
    monkeypatch.setenv("CHUNKFLOW_CHIP_PROBE_EVERY", "1")
    rng = np.random.default_rng(13)
    chunk = rng.random((8, 40, 48)).astype(np.float32)
    telemetry.reset()
    inf_on = make_inferencer(id_engine)
    out_on = np.asarray(inf_on(Chunk(chunk.copy())).array)
    telemetry.reset()

    monkeypatch.setenv("CHUNKFLOW_TELEMETRY", "0")
    inf_off = make_inferencer(id_engine)
    out_off = np.asarray(inf_off(Chunk(chunk.copy())).array)
    snap = telemetry.snapshot()
    telemetry.reset()
    assert not any(telemetry.CHIP_METRIC_RE.match(name)
                   for name in snap["gauges"])
    assert "shard/gather_bytes" not in snap["counters"]
    np.testing.assert_array_equal(out_on, out_off)

    # and the probe itself is a free return: with telemetry off it must
    # never touch the result (no block_until_ready on the dispatch path)
    class Untouchable:
        @property
        def addressable_shards(self):
            raise AssertionError("probe touched the result while off")

    shard_engine = inf_off.shard_engine()
    assert isinstance(shard_engine, engine_mod.ShardedEngine)
    for _ in range(3):
        shard_engine._probe_chip_readiness(Untouchable())


def _bare_sharded_engine(spec):
    from chunkflow_tpu.parallel.engine import ShardedEngine

    return ShardedEngine(
        forward=lambda x: x, num_input_channels=1, num_output_channels=3,
        input_patch_size=PIN, output_patch_size=PIN, batch_size=2,
        spec=spec,
    )


def test_probe_cadence_is_sampled(monkeypatch):
    """The readiness probe fires on dispatch 0 and then every
    CHUNKFLOW_CHIP_PROBE_EVERY dispatches, not per chunk."""
    from chunkflow_tpu.core import telemetry

    monkeypatch.setenv("CHUNKFLOW_CHIP_PROBE_EVERY", "4")
    engine = _bare_sharded_engine(MeshSpec("data", (8,)))
    probed = []

    class FakeShard:
        def __init__(self):
            self.device = type("D", (), {"id": 0})()
            self.data = type("A", (), {
                "block_until_ready": lambda self: None})()

    class FakeResult:
        @property
        def addressable_shards(self):
            probed.append(True)
            return [FakeShard()]

    telemetry.reset()
    try:
        for _ in range(9):
            engine._probe_chip_readiness(FakeResult())
        assert len(probed) == 3  # dispatches 0, 4, 8
        assert "shard/chip_skew_s" in telemetry.snapshot()["gauges"]
    finally:
        telemetry.reset()


def test_program_reuse_across_same_shape_chunks(id_engine, monkeypatch):
    """Two same-shape chunks share ONE sharded program build (the
    compile-cache invariant every other family holds)."""
    monkeypatch.setenv("CHUNKFLOW_MESH", "y=4")
    inf = make_inferencer(id_engine)
    rng = np.random.default_rng(7)
    for _ in range(3):
        np.asarray(inf(Chunk(rng.random((8, 40, 48)).astype(
            np.float32))).array)
    shard_builds = [k for k, _ in inf._programs.items()
                    if k[0] == "shard"]
    assert len(shard_builds) == 1, shard_builds
    assert inf._programs.hits >= 2


def test_engine_is_graftlint_clean():
    """ISSUE 13 acceptance: GL001-GL014 clean over parallel/engine.py
    and the modules it reworked, asserted in-suite (the whole-repo gate
    in tests/tools/test_graftlint_gate.py covers them too; this pins
    the specific modules so a future baseline regeneration cannot
    quietly grandfather a finding here)."""
    from pathlib import Path

    from tools.graftlint.config import load_config
    from tools.graftlint.engine import lint_paths

    repo_root = Path(__file__).resolve().parents[2]
    config = load_config(repo_root / "pyproject.toml")
    findings, _ = lint_paths(
        [
            "chunkflow_tpu/parallel/engine.py",
            "chunkflow_tpu/parallel/pipeline.py",
            "chunkflow_tpu/parallel/distributed.py",
            "chunkflow_tpu/parallel/spatial.py",
            "chunkflow_tpu/parallel/spatial2d.py",
            "chunkflow_tpu/parallel/multihost.py",
            "chunkflow_tpu/serve/packer.py",
            "chunkflow_tpu/inference/precision.py",
            "chunkflow_tpu/ops/blend.py",
        ],
        config, repo_root=repo_root,
    )
    assert not findings, [
        f"{f.path}:{f.line}: {f.code} {f.message}" for f in findings
    ]


# ---------------------------------------------------------------------------
# ISSUE 19: sharded blend replay + the pipeline kind
# ---------------------------------------------------------------------------
def test_replay_mode_flip_bitwise_and_distinct_keys(id_engine,
                                                    monkeypatch):
    """CHUNKFLOW_SHARD_REPLAY is re-read per chunk: flipping a live
    inferencer between the sharded default and the replicated replay
    rebuilds the program (distinct cache keys — the 'replay-replicated'
    tag) and stays bit-identical."""
    rng = np.random.default_rng(21)
    chunk = rng.random((6, 37, 45)).astype(np.float32)
    ref = np.asarray(make_inferencer(id_engine)(Chunk(chunk.copy()))
                     .array)
    monkeypatch.setenv("CHUNKFLOW_MESH", "y=2,x=2")
    inf = make_inferencer(id_engine)
    out_sharded = np.asarray(inf(Chunk(chunk.copy())).array)
    monkeypatch.setenv("CHUNKFLOW_SHARD_REPLAY", "replicated")
    out_replicated = np.asarray(inf(Chunk(chunk.copy())).array)
    assert np.array_equal(out_sharded, ref)
    assert np.array_equal(out_replicated, ref)
    shard_keys = [k for k, _ in inf._programs.items() if k[0] == "shard"]
    assert len(shard_keys) == 2, shard_keys
    assert sum("replay-replicated" in k for k in shard_keys) == 1, \
        shard_keys


def test_pipeline_mesh_needs_staged_engine(conv_engine):
    """A pipeline mesh over an engine that never declared the stage
    protocol fails loudly (no silent fallback to an unpipelined
    program): the flax conv engine is opaque."""
    rng = np.random.default_rng(22)
    chunk = Chunk(rng.random((6, 37, 45)).astype(np.float32))
    inf = make_inferencer(conv_engine, mesh="pipeline=4")
    with pytest.raises(ValueError, match="stage protocol"):
        inf(chunk)


def test_stage_groups_contiguous_and_balanced():
    """parallel/pipeline.stage_groups: contiguous balanced groups,
    later stages absorb the remainder, composition order preserved."""
    from chunkflow_tpu.parallel.pipeline import (
        require_stages,
        stage_groups,
    )

    trace = []

    def body(tag):
        def run(params, x):
            trace.append(tag)
            return x + 1

        return run

    groups = stage_groups(tuple(body(i) for i in range(5)), 3)
    assert len(groups) == 3
    x = 0
    for g in groups:
        x = g(None, x)
    assert x == 5
    # contiguous order, remainder on the LATER stages: 1 + 2 + 2
    assert trace == [0, 1, 2, 3, 4]
    trace.clear()
    groups[0](None, 0)
    assert trace == [0]
    trace.clear()
    groups[2](None, 0)
    assert trace == [3, 4]
    # more stages than bodies: the extra stages are the identity
    groups = stage_groups((body("only"),), 4)
    assert len(groups) == 4 and groups[0](None, 7) == 7
    with pytest.raises(ValueError, match="stage protocol"):
        require_stages(None, None, "test context")


def test_pipeline_packed_serving_bitwise(id_engine, monkeypatch):
    """The serving seam over a pipeline mesh: packed batches stream
    through the staged ring and stay bit-identical to the per-chunk
    path (the serving acceptance row of ISSUE 19)."""
    from chunkflow_tpu.serve.packer import PatchPacker

    rng = np.random.default_rng(23)
    inf = Inferencer(
        input_patch_size=PIN,
        output_patch_overlap=(0, 0, 0),
        num_output_channels=3,
        framework="prebuilt",
        batch_size=2,
        engine=id_engine,
        crop_output_margin=False,
    )
    chunks = [
        Chunk(rng.random((4, 16, 48)).astype(np.float32),
              voxel_offset=(4 * i, 0, 0))
        for i in range(6)
    ]
    monkeypatch.setenv("CHUNKFLOW_MESH", "1")
    refs = [np.asarray(inf(c).array) for c in chunks]

    monkeypatch.setenv("CHUNKFLOW_MESH", "pipeline=4")
    packer = PatchPacker(inf, max_wait_ms=25.0)
    try:
        handles = [packer.submit(c) for c in chunks]
        outs = [np.asarray(h.result(timeout=120).array)
                for h in handles]
    finally:
        packer.close()
    for ref, out in zip(refs, outs):
        assert np.array_equal(out, ref)
    serve_keys = [k for k, _ in inf._programs.items()
                  if k[0] == "serve_forward"]
    assert any("pipeline" in k for k in serve_keys), serve_keys


def test_sharded_replay_under_pallas_interpret(id_engine, monkeypatch):
    """The kernelcheck/interpret leg covers the sharded replay path:
    with CHUNKFLOW_PALLAS=interpret the slab+margin replay runs the
    fused Pallas accumulation kernel (interpreted) and still matches
    the interpreted single-device program bitwise."""
    monkeypatch.setenv("CHUNKFLOW_PALLAS", "interpret")
    rng = np.random.default_rng(24)
    chunk = rng.random((6, 37, 45)).astype(np.float32)
    ref = np.asarray(make_inferencer(id_engine)(Chunk(chunk.copy()))
                     .array)
    for mesh in ("y=2,x=2", "pipeline=4"):
        out = np.asarray(
            make_inferencer(id_engine, mesh=mesh)(Chunk(chunk.copy()))
            .array
        )
        assert np.array_equal(out, ref), mesh


def test_replay_buffer_hbm_shrinks_to_slab_plus_halo(id_engine,
                                                     monkeypatch):
    """The HBM acceptance criterion: the sharded replay's per-chip
    blend buffer is slab+margin, not full-chunk. The analytic plane
    (shard/replay_buffer_bytes + the per-chip mirror) must match the
    slab+margin formula exactly and undercut the full-chunk figure;
    when the backend's memory_stats watermark plane reports (PR 18),
    the measured per-chip peak must also stay under the replicated
    run's peak-plus-full-buffer bound — guarded, since CPU backends
    may not report."""
    from chunkflow_tpu.core import telemetry
    from chunkflow_tpu.parallel.engine import axis_geometry

    monkeypatch.setenv("CHUNKFLOW_MESH", "y=4,x=2")
    # big enough that slab+margin genuinely undercuts the full chunk
    # (the margins are a fixed two output patches per sharded axis)
    z, y, x = 8, 120, 96
    telemetry.reset()
    try:
        inf = make_inferencer(id_engine)
        rng = np.random.default_rng(25)
        np.asarray(inf(Chunk(rng.random((z, y, x)).astype(
            np.float32))).array)
        gauges = telemetry.snapshot()["gauges"]
    finally:
        telemetry.reset()
    co = 3
    yslab = axis_geometry(y, 4, PIN[1], PIN[1])[0]
    xslab = axis_geometry(x, 2, PIN[2], PIN[2])[0]
    # margins are one output patch on each boundary-facing side
    expected = (co + 1) * z * (yslab + 2 * PIN[1]) \
        * (xslab + 2 * PIN[2]) * 4
    full_chunk = (co + 1) * z * y * x * 4
    assert gauges["shard/replay_buffer_bytes"] == float(expected)
    assert expected < full_chunk
    for i in range(8):
        assert gauges[f"shard/chip/{i}/replay_buffer_bytes"] == float(
            expected)
    # guarded watermark cross-check: when the backend reports
    # memory_stats, the per-chip measured peak exists alongside
    try:
        import jax as _jax

        stats = _jax.local_devices()[0].memory_stats()
    except Exception:
        stats = None
    if stats and stats.get("peak_bytes_in_use"):
        from chunkflow_tpu.flow import scheduler

        telemetry.reset()
        try:
            scheduler.sample_device_memory()
            g = telemetry.snapshot()["gauges"]
            assert g.get("device/chip/0/peak_bytes", 0) > 0
        finally:
            telemetry.reset()
