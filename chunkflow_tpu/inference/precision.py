"""Low-precision forward variants behind the ``CHUNKFLOW_PRECISION`` spec.

"Improving Diffusion Model Efficiency Through Patching" (PAPERS.md)
motivates the patch-size/precision trade-off for exactly this patch-wise
workload: the convnet forward is the FLOPs side of the roofline, and
narrowing its compute dtype buys MXU throughput and HBM bandwidth at a
bounded output-error cost. This module is the single seam where that
trade is made:

- ``float32`` (default): the wrapper returns the engine's apply
  UNTOUCHED — the same callable object — so the default path stays
  bitwise identical to the pre-precision code (the measured-winner rule:
  no unmeasured variant ships as default).
- ``bfloat16``: the patch batch and every floating-point parameter leaf
  are rounded to bfloat16 at the engine boundary; engines built with a
  bfloat16 compute dtype (``Inferencer(dtype="bfloat16")``) then run
  their matmuls/convs natively narrow, and float32-dtype engines still
  see bfloat16-rounded values (the quantization-error model the test
  suite bounds). The result is cast back to float32.
- ``int8``: W8A8 in two legs behind ``CHUNKFLOW_INT8`` (ISSUE 17).
  ``fake`` (the default — the reference/kill-switch leg): symmetric fake
  quantization (round-to-nearest-even onto a 255-level [-127, 127]
  grid) of the patch batch and every floating-point parameter leaf at
  the engine boundary, computed in float32 — the standard W8A8
  simulation running f32 matmuls. ``real``: the engine's jaxpr is
  re-evaluated with every ``dot_general``/``conv_general_dilated``
  replaced by a REAL integer MXU op — int8 operands,
  ``preferred_element_type=jnp.int32`` accumulation — with weights
  quantized per-tensor and activations per-row at each matmul, then
  dequantized ``prod_f32 * (s_act * s_w)``. ``fakeint`` is the real
  leg's f32 twin (same interpreter, same integer-grid operands, f32
  arithmetic): where the integer dot's accumulator sums stay below
  2^24 the f32 products are exact, so ``real`` and ``fakeint`` agree
  BITWISE — the agreement oracle tests/inference/test_precision.py
  pins on the identity and small-conv engines. In every leg parameters
  quantize per-tensor and activations PER-ROW (one scale per
  leading-axis/batch entry), which keeps quantization independent of
  batch composition — the property the packed-serve and mesh bitwise
  parity contracts rest on.

What precision does NOT touch: the blend. Accumulation and weight
buffers stay float32 (``ops/blend.py``), ``normalize_blend``'s uint8
quantization contract is unchanged, and the packed-serve/mesh parity
contracts survive — the wrapper replaces the forward uniformly at the
``Inferencer._forward`` seam, which the serving packer and the sharded
engine both inherit, so packed-vs-per-chunk and mesh-vs-single stay
bitwise identical AT EVERY PRECISION (same wrapped forward, same
replayed accumulation).

Selection: explicit ``Inferencer(precision=...)`` wins (strict —
unknown values raise); otherwise the ``CHUNKFLOW_PRECISION`` env var,
resolved once at Inferencer construction (a per-chunk re-read would
retrace every program on a flip). Unrecognized env values warn ONCE on
stderr and fall back to float32 — a typo must not silently select a
quantized path, mirroring the ``CHUNKFLOW_PALLAS`` convention.

Gates: the quantization-error suite (tests/inference/test_precision.py)
bounds bf16/int8 output error against the float32 reference on the
identity AND conv engines, including ragged and crop-margin traffic.
"""
from __future__ import annotations

from typing import Callable, Optional

from chunkflow_tpu.core import envmode

__all__ = ["PRECISIONS", "resolve_precision", "wrap_apply", "int8_mode",
           "wrap_stages", "precision_tag"]

PRECISIONS = ("float32", "bfloat16", "int8")

_ALIASES = {"f32": "float32", "fp32": "float32", "bf16": "bfloat16",
            "i8": "int8"}

_MODE_CHOICES = {
    "float32": ("", "float32"),
    "bfloat16": ("bfloat16",),
    "int8": ("int8",),
}

_WARNED_VALUES: set = set()


def resolve_precision(value: Optional[str] = None) -> str:
    """The effective forward precision. An explicit ``value`` is strict
    (unknown -> ``ValueError``); the ``CHUNKFLOW_PRECISION`` env var is
    lenient (unknown -> one-time stderr warning, float32 — the shared
    warn-once contract in core/envmode.py)."""
    if value is not None:
        v = str(value).lower()
        v = _ALIASES.get(v, v)
        if v not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS} (got {value!r})"
            )
        return v
    return envmode.resolve(
        "CHUNKFLOW_PRECISION", _MODE_CHOICES, default="float32",
        note="running the float32 default — a typo must not silently "
             "select a quantized forward",
        warned=_WARNED_VALUES,
        normalize=lambda env: _ALIASES.get(env, env),
    )


def _cast_float_leaves(tree, dtype):
    import jax
    import jax.numpy as jnp

    def cast(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            return jnp.asarray(leaf, dtype)
        return leaf

    return jax.tree_util.tree_map(cast, tree)


def _fake_quant_int8(x, per_row: bool = False):
    """Symmetric int8 fake quantization in float32: round-to-nearest-even
    onto the [-127, 127] grid at scale absmax/127 — per-tensor for
    parameters, PER-ROW (``per_row=True``, one scale per leading-axis
    entry) for activation batches. Per-row matters for more than
    accuracy: a per-tensor activation scale would depend on which rows
    share a batch, breaking the row-independence property the serving
    packer's and the sharded engine's bitwise parity contracts rest on;
    with one scale per patch, quantization commutes with batch
    composition. An all-zero tensor (or row — the packer's filler slots)
    maps to exact zeros (the eps floor keeps the divide defined)."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    if per_row and x.ndim > 1:
        axes = tuple(range(1, x.ndim))
        amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    else:
        amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, jnp.float32(1e-12)) / jnp.float32(127.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    return q * scale


def _quant_float_leaves(tree):
    import jax
    import jax.numpy as jnp

    def quant(leaf):
        if hasattr(leaf, "dtype") and jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            return _fake_quant_int8(leaf)
        return leaf

    return jax.tree_util.tree_map(quant, tree)


_INT8_CHOICES = {
    "fake": ("", "fake", "0", "off"),
    "real": ("real", "1", "on"),
    "fakeint": ("fakeint",),
}
_INT8_WARNED: set = set()


def int8_mode() -> str:
    """'fake' | 'real' | 'fakeint' — the ``CHUNKFLOW_INT8`` leg of the
    int8 precision (resolved at :func:`wrap_apply` time, i.e. once per
    Inferencer, like ``CHUNKFLOW_PRECISION`` itself — a per-chunk
    re-read would retrace every program on a flip). ``fake`` is the
    measured default (boundary fake-quant, f32 matmuls — the
    reference/kill-switch leg); ``real`` runs integer-accumulating MXU
    matmuls (``preferred_element_type=jnp.int32``); ``fakeint`` is the
    real leg's exact-f32 twin for the bitwise agreement oracle."""
    return envmode.resolve(
        "CHUNKFLOW_INT8", _INT8_CHOICES, default="fake",
        note="running the fake-quant reference leg — a typo must not "
             "silently select the real integer matmul path",
        warned=_INT8_WARNED,
    )


def _quant_rows_axis(x, axis: int):
    """Integer grid + scale for a tainted (activation) operand: one
    scale per index along ``axis``, reduced over every other axis —
    the same 255-level grid expression as :func:`_fake_quant_int8`
    (identical rounding, identical eps floor), factored so the real
    and fake legs quantize onto IDENTICAL integer values. Returns
    ``(q, scale)`` with ``q`` float32-valued integers in [-127, 127]
    and ``scale`` keeping ``keepdims`` shape."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    axes = tuple(i for i in range(x.ndim) if i != axis)
    if axes:
        amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    else:
        amax = jnp.abs(x)
    scale = jnp.maximum(amax, jnp.float32(1e-12)) / jnp.float32(127.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    return q, scale


def _quant_tensor(x):
    """Per-tensor integer grid + scalar scale (the weight-side rule)."""
    import jax.numpy as jnp

    x = jnp.asarray(x, jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.maximum(amax, jnp.float32(1e-12)) / jnp.float32(127.0)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    return q, scale


def _scale_to_out(scale, out_ndim: int, out_axis: int):
    """Reshape a per-row scale (keepdims shape) to broadcast along the
    output's ``out_axis``; scalars pass through."""
    import jax.numpy as jnp

    s = jnp.asarray(scale)
    if s.size == 1:
        return s.reshape(())
    shape = [1] * out_ndim
    shape[out_axis] = s.size
    return s.reshape(shape)


def _int8_dot(params, lhs, rhs, lhs_tainted, rhs_tainted, integer):
    """One ``dot_general`` at W8A8: tainted (activation) operands
    quantize per-row over their leading axis when it is a free
    (non-contracting, non-batch) dim — the batch-composition-safe rule
    — otherwise per-tensor; untainted (weight) operands per-tensor.
    ``integer=True`` runs int8 operands with int32 accumulation (the
    real MXU op); ``integer=False`` is the exact-f32 twin on the same
    integer grid. Dequant is ``prod_f32 * (s_lhs * s_rhs)`` — one
    expression, one order, so the two legs agree bitwise wherever the
    integer sums stay below 2^24 (exact in f32)."""
    import jax.numpy as jnp
    from jax import lax

    dn = params["dimension_numbers"]
    (lc, rc), (lb, rb) = dn
    free_l = sorted(set(range(jnp.ndim(lhs))) - set(lc) - set(lb))
    free_r = sorted(set(range(jnp.ndim(rhs))) - set(rc) - set(rb))

    def quant(x, tainted, free):
        if tainted and jnp.ndim(x) > 1 and 0 in free:
            return _quant_rows_axis(x, 0)
        return _quant_tensor(x)

    ql, sl = quant(lhs, lhs_tainted, free_l)
    qr, sr = quant(rhs, rhs_tainted, free_r)
    if integer:
        prod = lax.dot_general(
            ql.astype(jnp.int8), qr.astype(jnp.int8),
            dimension_numbers=dn,
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
    else:
        prod = lax.dot_general(
            ql, qr, dimension_numbers=dn,
            preferred_element_type=jnp.float32,
        )
    # output layout: batch dims, then lhs free dims, then rhs free dims
    sl_b = _scale_to_out(sl, prod.ndim,
                         len(lb) + (free_l.index(0) if 0 in free_l else 0))
    sr_b = _scale_to_out(
        sr, prod.ndim,
        len(lb) + len(free_l) + (free_r.index(0) if 0 in free_r else 0))
    return prod * (sl_b * sr_b)


def _int8_conv(params, lhs, rhs, lhs_tainted, integer):
    """One ``conv_general_dilated`` at W8A8: the image (lhs) quantizes
    per-row over its batch axis (``dimension_numbers.lhs_spec[0]``)
    when tainted, the kernel (rhs) per-tensor; same integer/f32-twin
    and dequant contract as :func:`_int8_dot`."""
    import jax.numpy as jnp
    from jax import lax

    dn = params["dimension_numbers"]
    if lhs_tainted:
        ql, sl = _quant_rows_axis(lhs, dn.lhs_spec[0])
    else:
        ql, sl = _quant_tensor(lhs)
    qr, sr = _quant_tensor(rhs)
    kwargs = dict(
        window_strides=params["window_strides"],
        padding=params["padding"],
        lhs_dilation=params["lhs_dilation"],
        rhs_dilation=params["rhs_dilation"],
        dimension_numbers=dn,
        feature_group_count=params["feature_group_count"],
        batch_group_count=params.get("batch_group_count", 1),
    )
    if integer:
        prod = lax.conv_general_dilated(
            ql.astype(jnp.int8), qr.astype(jnp.int8),
            preferred_element_type=jnp.int32, **kwargs,
        ).astype(jnp.float32)
    else:
        prod = lax.conv_general_dilated(
            ql, qr, preferred_element_type=jnp.float32, **kwargs,
        )
    sl_b = _scale_to_out(sl, prod.ndim, dn.out_spec[0])
    return prod * (sl_b * sr)


def _eval_int8_jaxpr(jaxpr, consts, in_pairs, integer, Literal):
    """Evaluate a jaxpr with every matmul/conv touched by activation
    data replaced by its W8A8 form. ``in_pairs`` is ``[(value, taint)]``
    per invar; taint marks values derived from the patch batch (the
    activations) — untainted values are parameters and their derived
    tensors (the weights). Every other primitive binds unchanged (f32
    math on the dequantized values, exactly like the fake leg's body).
    ``pjit`` and ``custom_jvp/vjp`` bodies are evaluated recursively so
    matmuls inside jitted/custom-gradient engine blocks are still
    intercepted; other higher-order primitives (scan, while) bind
    as-is — none of the in-repo engines put matmuls inside them."""
    env = {}

    def read(v):
        if isinstance(v, Literal):
            return v.val, False
        return env[v]

    for var, val in zip(jaxpr.constvars, consts):
        env[var] = (val, False)
    for var, pair in zip(jaxpr.invars, in_pairs):
        env[var] = pair

    for eqn in jaxpr.eqns:
        pairs = [read(v) for v in eqn.invars]
        vals = [p[0] for p in pairs]
        taints = [p[1] for p in pairs]
        out_taint = any(taints)
        name = eqn.primitive.name
        if name == "dot_general" and out_taint:
            outs = [_int8_dot(eqn.params, vals[0], vals[1],
                              taints[0], taints[1], integer)]
        elif name == "conv_general_dilated" and out_taint:
            outs = [_int8_conv(eqn.params, vals[0], vals[1],
                               taints[0], integer)]
        elif name == "pjit" and out_taint:
            inner = eqn.params["jaxpr"]
            results = _eval_int8_jaxpr(inner.jaxpr, inner.consts,
                                       pairs, integer, Literal)
            outs = [val for val, _ in results]
        elif (name in ("custom_jvp_call", "custom_vjp_call")
              and out_taint
              and "call_jaxpr" in eqn.params
              and len(eqn.params["call_jaxpr"].jaxpr.invars)
              == len(pairs)):
            inner = eqn.params["call_jaxpr"]
            results = _eval_int8_jaxpr(inner.jaxpr, inner.consts,
                                       pairs, integer, Literal)
            outs = [val for val, _ in results]
        else:
            subfuns, bind_params = eqn.primitive.get_bind_params(
                eqn.params)
            result = eqn.primitive.bind(*subfuns, *vals, **bind_params)
            outs = (list(result) if eqn.primitive.multiple_results
                    else [result])
        for var, out in zip(eqn.outvars, outs):
            env[var] = (out, out_taint)

    return [read(v) for v in jaxpr.outvars]


def _int8_graph_apply(apply: Callable, params, batch, integer: bool):
    """The real-int8 forward: trace ``apply`` to a jaxpr, then replay
    it with activation-touched matmuls in W8A8 (``integer=True`` for
    int32-accumulating int8 ops, ``False`` for the exact-f32 twin).
    Runs under the caller's jit — the integer ops land in the outer
    program's jaxpr, where the test suite probes
    ``preferred_element_type=int32``."""
    import jax

    from jax.extend.core import Literal

    closed, out_shape = jax.make_jaxpr(apply, return_shape=True)(
        params, batch)
    n_params = len(jax.tree_util.tree_leaves(params))
    flat = jax.tree_util.tree_leaves((params, batch))
    pairs = [(v, i >= n_params) for i, v in enumerate(flat)]
    out_pairs = _eval_int8_jaxpr(closed.jaxpr, closed.consts, pairs,
                                 integer, Literal)
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(out_shape),
        [val for val, _ in out_pairs])


def wrap_apply(apply: Callable, precision: str) -> Callable:
    """Wrap an engine ``apply(params, batch)`` for the given precision.
    ``float32`` returns ``apply`` ITSELF (same object — the bitwise
    guarantee of the default path); the narrow variants quantize the
    batch and the float parameter leaves at the boundary and return
    float32 results for the float32 blend accumulation."""
    if precision == "float32":
        return apply
    if precision == "bfloat16":
        def bf16_apply(params, batch):
            import jax.numpy as jnp

            p = _cast_float_leaves(params, jnp.bfloat16)
            out = apply(p, jnp.asarray(batch, jnp.bfloat16))
            return jnp.asarray(out, jnp.float32)

        return bf16_apply
    if precision == "int8":
        mode = int8_mode()  # resolved once, at wrap time
        if mode == "fake":
            def int8_apply(params, batch):
                import jax.numpy as jnp

                p = _quant_float_leaves(params)
                out = apply(p, _fake_quant_int8(batch, per_row=True))
                return jnp.asarray(out, jnp.float32)

            return int8_apply

        integer = mode == "real"

        def int8_real_apply(params, batch):
            import jax.numpy as jnp

            out = _int8_graph_apply(apply, params, batch, integer)
            return jnp.asarray(out, jnp.float32)

        return int8_real_apply
    raise ValueError(f"unknown precision {precision!r}")


def precision_tag(precision: str) -> str:
    """The resolved forward precision as a ProgramCache key component:
    ``""`` for the float32 default (the no-suffix-for-the-default
    convention every knob shares), ``"prec-bfloat16"``, or
    ``"prec-int8-<fake|real|fakeint>"`` with the ``CHUNKFLOW_INT8`` leg
    folded in (the leg changes the traced program, so it is program
    identity). Joined into the sharded-engine program keys (ISSUE 19:
    precision tags compose with the pipeline/gather/kernel tags in
    shard cache keys)."""
    if precision == "float32":
        return ""
    if precision == "int8":
        return f"prec-int8-{int8_mode()}"
    return f"prec-{precision}"


def wrap_stages(stage_bodies, stage_tail, precision: str):
    """Precision-wrap a staged engine (the stage protocol,
    parallel/pipeline.py) so that the composition of the wrapped pieces
    is BITWISE :func:`wrap_apply` of the unwrapped composition — the
    identity the pipeline mesh's parity contract rests on. Returns
    ``(entry, bodies, tail)``:

    - ``entry(x)`` — the one-time activation boundary cast, applied to
      the gathered patch batch BEFORE it enters stage 0 (so the ring
      activation dtype is uniform: the ``where(stage==0, ...)`` merge
      of fresh patches and ``ppermute``-received activations sees one
      dtype);
    - ``bodies`` — per-stage wrapped bodies (parameter leaves cast at
      each stage, activations untouched — they already carry the entry
      cast);
    - ``tail`` — the wrapped tail (parameter cast + the float32 result
      cast the blend accumulation requires).

    float32 returns everything UNTOUCHED (same objects — the bitwise
    default-path rule). The int8 ``real``/``fakeint`` legs re-evaluate
    the whole forward's jaxpr (:func:`_int8_graph_apply`) and cannot be
    split at stage seams; they return ``(None, None, None)`` and a
    pipeline mesh fails loudly naming the constraint."""
    if stage_bodies is None or stage_tail is None:
        return None, None, None
    if precision == "float32":
        return (lambda x: x), tuple(stage_bodies), stage_tail
    if precision == "bfloat16":
        import jax.numpy as jnp

        def entry(x):
            return jnp.asarray(x, jnp.bfloat16)

        bodies = tuple(
            (lambda params, x, _b=body:
             _b(_cast_float_leaves(params, jnp.bfloat16), x))
            for body in stage_bodies
        )

        def tail(params, x):
            out = stage_tail(_cast_float_leaves(params, jnp.bfloat16), x)
            return jnp.asarray(out, jnp.float32)

        return entry, bodies, tail
    if precision == "int8":
        if int8_mode() != "fake":
            # real/fakeint rewrite the whole jaxpr — not stage-splittable
            return None, None, None
        import jax.numpy as jnp

        def entry(x):
            return _fake_quant_int8(x, per_row=True)

        bodies = tuple(
            (lambda params, x, _b=body: _b(_quant_float_leaves(params), x))
            for body in stage_bodies
        )

        def tail(params, x):
            out = stage_tail(_quant_float_leaves(params), x)
            return jnp.asarray(out, jnp.float32)

        return entry, bodies, tail
    raise ValueError(f"unknown precision {precision!r}")
