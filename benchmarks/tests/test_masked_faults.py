"""The masked cell's own two faults come out not correct (the pattern of
``test_faults.py``, whose two altered answers every cell is held to, this
one among them): a run whose output mask is not applied, and one whose
blank task writes no blocks. The whole of ``run.py`` in this process with
``--rehearse``."""
import json
import sys

import pytest

from conftest import bench

from cfbench import catalog

CELL = next(
    w["name"] for w in bench()["workloads"]
    if catalog.load_json("traffic", w["traffic"] + ".json")["kind"]
    == "worker_masked")


def run_main(monkeypatch, capsys):
    import run

    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", "2147483659", "--seconds",
        "2", "--trace", "0", "--rehearse"])
    assert run.main() == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


def over_limit(line):
    return {name for name, c in line["checks"].items()
            if c["value"] > c["limit"]}


def test_the_run_unbroken_is_correct(monkeypatch, capsys):
    line, err = run_main(monkeypatch, capsys)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["checks"]) == {"masked_max_abs", "blank_max_abs",
                                   "max_abs_diff", "mean_abs_diff"}
    assert not over_limit(line)
    assert "steady tasks:" in err


def test_an_output_mask_left_out_is_not_correct(monkeypatch, capsys):
    """The mask behind ``crop-margin`` passes float chunks through: the
    edge task's block is a sigmoid's where it has to be zero."""
    from chunkflow_tpu.ops import mask as mask_ops

    maskout = mask_ops.maskout

    def only_the_image_mask(chunk, mask, inverse=False):
        if chunk.array.dtype.kind == "f":
            return chunk
        return maskout(chunk, mask, inverse=inverse)

    monkeypatch.setattr(mask_ops, "maskout", only_the_image_mask)
    line, err = run_main(monkeypatch, capsys)
    assert line["correct"] is False
    assert line["failed"] == 0           # every task committed all blocks
    assert over_limit(line) == {"masked_max_abs"}
    assert 0.1 < line["checks"]["masked_max_abs"]["value"] < 1.0
    assert "not correct: failed 'masked_max_abs within the bound'" in err


def test_a_blank_task_that_writes_no_blocks_is_not_correct(monkeypatch,
                                                           capsys, tmp_path):
    """The store opened with tensorstore's defaults, as before ISSUE 37:
    a block of zeros is not kept, so the blank task (and the masked half
    of an edge task) commits with blocks missing. The driver's probe
    would end such a run at once; it is told to look away."""
    import tensorstore as ts

    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    def default_store(self, mip):
        if mip not in self._stores:
            self._stores[mip] = ts.open({
                "driver": "neuroglancer_precomputed",
                "kvstore": self.kvstore, "scale_index": mip}).result()
        return self._stores[mip]

    driver = catalog.load_module("drivers", "worker_masked")
    assert driver.stores_zero_blocks(str(tmp_path / "kept"))
    monkeypatch.setattr(PrecomputedVolume, "_store", default_store)
    assert not driver.stores_zero_blocks(str(tmp_path / "lost"))
    monkeypatch.setattr(driver, "stores_zero_blocks", lambda work: True)
    line, err = run_main(monkeypatch, capsys)
    assert line["correct"] is False
    assert line["failed"] > 0
    assert "committed with blocks missing" in err


def test_a_program_that_keeps_no_zero_block_gets_no_result(monkeypatch,
                                                           capsys):
    """What the parent of ISSUE 37 does with this cell: it ends at once,
    with another exit code than 0 and no result line."""
    driver = catalog.load_module("drivers", "worker_masked")
    monkeypatch.setattr(driver, "stores_zero_blocks", lambda work: False)
    import run

    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", "7", "--seconds", "2",
        "--trace", "0", "--rehearse"])
    with pytest.raises(SystemExit) as exit_:
        run.main()
    assert exit_.value.code not in (0, None)
    assert "correct" not in capsys.readouterr().out
