"""The comparison that decides ``correct``: the program's output against
the configuration's plain reference, blended in numpy float64, under the
bound the configuration file gives with its reason."""
import numpy as np

from cfbench import blend, catalog


def reference_output(ctx, image_u8, box, forward=None):
    """([C, *box] float64, patches used): what the plain side says the
    blended output of the chunk ``image_u8`` is inside ``box``.
    ``forward``: another forward in the reference's place, for a control
    (``tests/control_readings.py``)."""
    config = ctx.config
    reference = catalog.load_module("reference", config["reference"])
    params = ctx.engine_params()
    forward = forward or reference.make_forward(config)

    def one_patch(window):
        out = forward(params, window[None, ..., None])
        return np.moveaxis(np.asarray(out[0]), -1, 0)

    return blend.blend_box(image_u8, tuple(config["patch"]),
                           tuple(config["overlap"]), box, one_patch)


def judge(record, got, want, what: str, also: dict) -> None:
    """Set ``record.correct`` and say why: ``got`` within the
    configuration's bounds of ``want``, finite, not constant, and every
    condition of ``also`` (name -> held) as well.

    ``tolerance.max_abs_diff`` bounds the largest difference and guards
    placement, blending and structure. ``tolerance.mean_abs_diff``, where
    the configuration has it, bounds the mean: the largest difference
    sits where the sigmoid is steepest and reads alike at every
    precision; the mean tells a precision below the configuration's
    apart. Every number compared goes to ``record.checks`` beside its
    limit: the result line's last key and the run's last lines."""
    tolerance = record.config["tolerance"]
    same_shape = got.shape == want.shape
    gap = np.abs(got - want) if same_shape else np.array([np.inf])
    measured = {"max_abs_diff": float(gap.max()),
                "mean_abs_diff": float(gap.mean())}
    for name, value in measured.items():
        if name in tolerance:
            record.checks[name] = {"value": value,
                                   "limit": float(tolerance[name])}
    conditions = {
        "shape": same_shape,
        **{f"{name} within the bound": c["value"] <= c["limit"]
           for name, c in record.checks.items()},
        "finite": bool(np.isfinite(got).all()),
        "not constant": float(got.std()) > 1e-3,
        **also,
    }
    record.correct = all(conditions.values())
    for name, held in conditions.items():
        if not held:
            record.notes.append(f"not correct: failed '{name}'")
    record.notes.append(f"check: {what}: " + ", ".join(
        f"{name} {value:.3e} (bound {tolerance[name]:g})"
        if name in tolerance else f"{name} {value:.3e} (no bound)"
        for name, value in measured.items()))
