"""The comparison that decides ``correct``: the program's output against
the configuration's plain reference, blended in numpy float64, under the
bound the configuration file gives with its reason."""
import numpy as np

from cfbench import blend, catalog


def reference_output(ctx, image_u8, box):
    """([C, *box] float64, patches used): what the plain side says the
    blended output of the chunk ``image_u8`` is inside ``box``."""
    config = ctx.config
    reference = catalog.load_module("reference", config["reference"])
    params = ctx.engine_params()
    forward = reference.make_forward(config)

    def one_patch(window):
        out = forward(params, window[None, ..., None])
        return np.moveaxis(np.asarray(out[0]), -1, 0)

    return blend.blend_box(image_u8, tuple(config["patch"]),
                           tuple(config["overlap"]), box, one_patch)


def judge(record, got, want, what: str, also: dict) -> None:
    """Set ``record.correct`` and say why: ``got`` within the
    configuration's bound of ``want``, finite, not constant, and every
    condition of ``also`` (name -> held) as well."""
    bound = float(record.config["tolerance"]["max_abs_diff"])
    same_shape = got.shape == want.shape
    gap = np.abs(got - want) if same_shape else np.array([np.inf])
    diff = float(gap.max())
    conditions = {
        "shape": same_shape,
        "within the bound": diff <= bound,
        "finite": bool(np.isfinite(got).all()),
        "not constant": float(got.std()) > 1e-3,
        **also,
    }
    record.correct = all(conditions.values())
    record.client["check_max_abs_diff"] = diff
    for name, held in conditions.items():
        if not held:
            record.notes.append(f"not correct: failed '{name}'")
    record.notes.append(
        f"check: {what}: max-abs-diff {diff:.3e} (bound {bound:g}), "
        f"mean-abs-diff {float(gap.mean()):.3e} (no bound yet)")
