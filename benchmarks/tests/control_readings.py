#!/usr/bin/env python3
"""The control behind each configuration's tolerance, read on the chip at
the cell's own size (by hand, as ``record_trace.py``; no test runs it):

    python3 benchmarks/tests/control_readings.py [--rehearse] <cell> \
        <seed> ... [--program <seed> ...]

For each seed: the comparison's block of task 0 (``check_box``: where
2x2x2 patches overlap) as the configuration's plain reference blends it,
against the plain forward in float32 ``highest``, with every
convolution's operands rounded to bfloat16 (what the chip's convolutions
read in all three configurations), with the activations in bfloat16 as
well (the precision below a float32 network's) and with float8_e4m3
operands (the precision below a bfloat16 network's); and the program
itself on the whole task through one ``Inferencer`` call, as the cell
runs it and with its
own narrower path switched on (``precision="int8"``: what
``CHUNKFLOW_PRECISION=int8`` selects). A seed after ``--program`` reads
the program as the cell runs it alone. Each side goes through ``check.judge`` as a run's
block does: max- and mean-abs-diff against the reference beside the
configuration's bounds, and whether it came out correct. The timed
path's own readings are in every run's ``checks``.
"""
import json
import os
import sys

import numpy as np

TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(TESTS))
sys.path.insert(0, os.path.dirname(os.path.dirname(TESTS)))

import run  # noqa: E402  (benchmarks/run.py: Context's helpers)
from cfbench import catalog, check, crop_blend, volume  # noqa: E402


class Ctx:
    resolve_args = staticmethod(run.Context.resolve_args)
    engine_params = run.Context.engine_params

    def __init__(self, config):
        self.config = config


def main() -> int:
    import jax
    import jax.numpy as jnp

    from cfbench.run_record import RunRecord

    argv = [a for a in sys.argv[1:] if a != "--rehearse"]
    cell_name, seeds = argv[0], argv[1:]
    program_only = set()
    if "--program" in seeds:
        at = seeds.index("--program")
        program_only = {int(s) for s in seeds[at + 1:]}
        seeds = seeds[:at] + seeds[at + 1:]
    bench = catalog.benchmark()
    cell = catalog.cell(bench, cell_name)
    config = catalog.config_of(bench, cell)
    traffic = catalog.load_json("traffic", cell["traffic"] + ".json")
    if "--rehearse" in sys.argv:   # tiny shapes: the script's own check
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    driver = catalog.load_module("drivers", traffic["kind"])
    g = driver._geometry(config, traffic)
    ctx = Ctx(config)
    reference_output = (crop_blend if "output_patch" in config
                        else check).reference_output
    crop = catalog.load_module("reference", "rsunet_crop")
    whole = {**config, "output_patch": config.get("output_patch")
             or config["patch"]}
    forwards = {
        "reference, float32 highest": crop.make_forward(whole),
        "reference, bfloat16 operands":
            crop.make_rounded_forward(config, jnp.bfloat16),
        "reference, bfloat16 activations":
            crop.make_rounded_forward(config, jnp.bfloat16, activations=True),
        "reference, float8_e4m3fn operands":
            crop.make_rounded_forward(config, jnp.float8_e4m3fn),
    }
    from chunkflow_tpu.chunk.base import Chunk
    from chunkflow_tpu.inference import Inferencer

    inferencers = {}
    engine = config["engine"]
    cropped = {"output_patch_size": tuple(config["output_patch"])} \
        if "output_patch" in config else {}
    # the program as the cell runs it, and with its own narrower path
    # switched on (inference/precision.py: CHUNKFLOW_PRECISION=int8)
    for precision in ("float32", "int8"):
        inferencers[f"program, --dtype {engine['dtype']}, "
                    f"precision {precision}"] = Inferencer(
            input_patch_size=g.patch,
            output_patch_overlap=tuple(config["overlap"]),
            batch_size=config["batch"],
            num_output_channels=config["model"]["out_channels"],
            framework="flax",
            model_variant=engine.get("model_variant", "parity"),
            model_path=ctx.resolve_args(
                [engine.get("model_path") or ""])[0],
            dtype=engine["dtype"], precision=precision, **cropped)
    box = g.check_box()
    # the program's output begins where the patches' crop margin ends
    crop = getattr(g, "crop", (0, 0, 0))
    window = tuple(slice(a - c, b - c) for a, b, c in zip(*box, crop))
    print(json.dumps({"cell": cell_name, "device": str(jax.devices()[0]),
                      "box": box, "reference": config["reference"],
                      "tolerance": {
                          k: v for k, v in config["tolerance"].items()
                          if k.endswith("_diff")}}), flush=True)
    for seed in map(int, seeds):
        image = volume.seeded_task_input(seed, g, 0)
        want, _ = reference_output(ctx, image, box)
        sides = {}
        if seed not in program_only:
            for name, forward in forwards.items():
                sides[name] = reference_output(ctx, image, box,
                                               forward=forward)[0]
        for name, inferencer in inferencers.items():
            if seed in program_only and "precision float32" not in name:
                continue
            out = np.asarray(inferencer(Chunk(image)).array, np.float64)
            sides[name] = out[(slice(None), *window)]
        for name, got in sides.items():
            record = RunRecord(cell=cell, config=config, traffic=traffic,
                               device={})
            check.judge(record, got, want, name, {})
            line = {"seed": seed, "side": name, "correct": record.correct,
                    **{k: c["value"] for k, c in record.checks.items()}}
            if name.startswith("program") and seed not in program_only:
                # how far the program lies from each precision's reference
                line["mean_abs_diff_to"] = {
                    other: float(np.abs(got - sides[other]).mean())
                    for other in forwards}
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
