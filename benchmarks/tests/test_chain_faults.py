"""The chain cell's own faults come out not correct (the pattern of
``test_masked_faults.py``): a run whose thumbnail misses a level's
blocks, one whose result is of the raw image, one whose thumbnail is of
another channel; and a program without the chain's operators gets no
result. The whole of ``run.py`` in this process with ``--rehearse``."""
import json
import sys

import pytest

from conftest import bench

from cfbench import catalog

CELL = next(
    w["name"] for w in bench()["workloads"]
    if catalog.load_json("traffic", w["traffic"] + ".json")["kind"]
    == "worker_chain")


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
    assert set(line["checks"]) == {"thumbnail_max_abs", "max_abs_diff",
                                   "mean_abs_diff"}
    assert not over_limit(line)
    assert "input volume and levels" in err


def test_a_thumbnail_level_left_out_is_not_correct(monkeypatch, capsys):
    """The writes of one level never reach the layer: every task commits
    (its log is there) with thumbnail blocks missing."""
    from chunkflow_tpu.volume.precomputed import PrecomputedVolume

    save = PrecomputedVolume.save

    def without_level_two(self, chunk, mip=0, wait=True, **kwargs):
        if self.path.endswith("/thumbnail") and mip == 2:
            return None
        return save(self, chunk, mip=mip, wait=wait, **kwargs)

    monkeypatch.setattr(PrecomputedVolume, "save", without_level_two)
    line, err = run_main(monkeypatch, capsys)
    assert line["correct"] is False
    assert line["failed"] > 0
    assert line["checks"]["thumbnail_max_abs"]["value"] == 255.0
    assert "committed with blocks missing" in err
    assert "not correct: failed 'every fetched task committed'" in err


def test_a_result_of_the_raw_image_is_not_correct(monkeypatch, capsys):
    """``normalize-contrast`` hands the chunk on as it came: the result
    is the forward's of the raw image, which the bounds tell from the
    reference's of the normalized one."""
    from chunkflow_tpu.ops import contrast

    monkeypatch.setattr(contrast, "normalize_contrast_by_levels",
                        lambda chunk, *args, **kwargs: chunk)
    line, err = run_main(monkeypatch, capsys)
    assert line["correct"] is False
    assert line["failed"] == 0
    assert "mean_abs_diff" in over_limit(line)
    assert "thumbnail_max_abs" not in over_limit(line)


def test_a_thumbnail_of_another_channel_is_not_correct(monkeypatch, capsys):
    """The grey of the z channel where the configuration says xy: the
    result is right, the thumbnail tens of grey levels off."""
    from chunkflow_tpu.chunk import AffinityMap

    quantize = AffinityMap.quantize
    monkeypatch.setattr(AffinityMap, "quantize",
                        lambda self, mode="xy": quantize(self, mode="z"))
    line, err = run_main(monkeypatch, capsys)
    assert line["correct"] is False
    assert line["failed"] == 0
    assert over_limit(line) == {"thumbnail_max_abs"}
    assert line["checks"]["thumbnail_max_abs"]["value"] > 2
    assert "not correct: failed 'thumbnail_max_abs within the bound'" in err


def test_a_program_without_the_operators_gets_no_result(monkeypatch, capsys):
    """What the parent of ISSUE 42 does with this cell: it ends at once,
    with another exit code than 0 and no result line."""
    driver = catalog.load_module("drivers", "worker_chain")
    assert driver.runs_the_chain()
    monkeypatch.setattr(driver, "runs_the_chain", lambda: False)
    import run

    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", CELL, "--seed", "7", "--seconds", "2",
        "--trace", "0", "--rehearse"])
    with pytest.raises(SystemExit) as exit_:
        run.main()
    assert exit_.value.code not in (0, None)
    assert "correct" not in capsys.readouterr().out
