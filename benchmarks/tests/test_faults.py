"""A run whose timed path is broken underneath comes out not correct.

The whole of ``run.py`` in this process with ``--rehearse`` (which skips
the look for a chip and nothing else), while the program's ``Inferencer``
alters each answer where it is produced (``_infer``: what the worker's
pipeline and a plain call both go through). The faults a cell of this system
can have are of one kind, an answer altered: there is no state to leave
unchanged, no batch mean and, on one chip, no exchange to leave out."""
import json
import sys

import pytest

from conftest import bench

CELLS = [w["name"] for w in bench()["workloads"]]


def shifted(array):
    """Every voxel one place along x: a patch put down beside its place."""
    import jax.numpy as jnp

    return jnp.roll(array, 1, axis=-1)


def scaled(array):
    """One percent off everywhere: under the bound on the largest
    difference, caught by the bound on the mean."""
    return array * 0.99


def run_main(monkeypatch, capsys, cell, alter=None):
    import run
    from chunkflow_tpu.inference import Inferencer

    if alter is not None:
        infer = Inferencer._infer

        def broken(self, chunk, *args, **kwargs):
            out = infer(self, chunk, *args, **kwargs)
            out.array = alter(out.array).astype(out.array.dtype)
            return out

        monkeypatch.setattr(Inferencer, "_infer", broken)
    monkeypatch.setattr(sys, "argv", [
        "run.py", "--workload", cell, "--seed", "2147483659", "--seconds",
        "2", "--trace", "0", "--rehearse"])
    assert run.main() == 0
    out, err = capsys.readouterr()
    return json.loads(out.strip().splitlines()[-1]), err


@pytest.mark.parametrize("alter, fails", [
    (shifted, {"max_abs_diff", "mean_abs_diff"}),
    (scaled, {"mean_abs_diff"}),
])
@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(monkeypatch, capsys, cell, alter,
                                          fails):
    line, err = run_main(monkeypatch, capsys, cell, alter)
    assert line["correct"] is False
    assert line["failed"] == 0          # every task was committed: the
    over = {name for name, c in line["checks"].items()   # bounds said no
            if c["value"] > c["limit"]}
    assert over == fails
    # the numbers compared, beside their limits, are the run's last words
    last = err.strip().splitlines()[-len(line["checks"]):]
    assert all(text.startswith("check: ") and " limit " in text
               for text in last)


def test_the_same_run_unbroken_is_correct(monkeypatch, capsys):
    line, _ = run_main(monkeypatch, capsys, CELLS[0])
    assert line["correct"] is True and list(line)[-1] == "checks"
