"""Rank lowerings of a configuration's forward without a chip.

Compiles the RSUNet of a benchmark configuration for a *described* TPU
v5e (``jax.experimental.topologies``; needs libtpu, no device) and prints
XLA's own ``estimated_cycles`` for every op of the entry computation with
its part of the model (``core/profiling.py:op_parts``: the flax module or
model scope on its ``op_name`` path, a fusion's by its widest convolution
and not by its root), the convolutions inside it and its shape and layout,
the largest first, and the total.

This is the compiler's cost model and not a measurement. It ranks two
lowerings of the same forward against each other and says which ops a
lowering leaves expensive; it is never written under the name of a speed.
Calibration, model over the chip's device time a program (my chip runs,
PR 24; docs/performance.md "Sizing a lowering offline"): 1.38 and 1.42
(rsunet-superhuman, rsunet-deepem) before the x-fold, 1.49 and 1.43 after
it; and 1.09 for a build whose pool it mis-sized 13x. Convolutions and
the rest are mis-sized differently, which is why ``--by-module`` keeps
them apart.

    JAX_PLATFORMS=cpu python tools/aot_cost.py rsunet-superhuman [--top 25]
    JAX_PLATFORMS=cpu python tools/aot_cost.py rsunet-superhuman --by-module
"""
import argparse
import json
import os
import re
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if CHECKOUT not in sys.path:  # run as a script: tools/ is on the path
    sys.path.insert(0, CHECKOUT)

from chunkflow_tpu.core import profiling  # noqa: E402

CLOCK_HZ = 1.5e9  # the rate the totals are turned into ms with; a scale

_ENTRY = re.compile(r"^ENTRY ")
# the shape is one token, or for an op with several results a tuple of them
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?([\w.\-]+) = (\((?:[^()]|\([^()]*\))*\)|\S+) "
    r"([\w\-]+)\(")
_CYCLES = re.compile(r'"estimated_cycles":"(\d+)"')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_KIND = re.compile(r", kind=(k\w+),")
_NOT_CONV_ROOT = re.compile(r"reduce|scatter|dynamic-update-slice|sort", re.I)


def entry_ops(hlo_text: str) -> list:
    """``[(cycles, op, opcode, shape with layout, op_name, fusion kind)]``
    of the entry computation of one compiled module's text, in program
    order."""
    ops, inside = [], False
    for line in hlo_text.splitlines():
        if _ENTRY.match(line):
            inside = True
            continue
        if inside and line.startswith("}"):
            break
        if not inside:
            continue
        match = _INSTRUCTION.match(line)
        cycles = _CYCLES.search(line)
        if match is None or cycles is None:
            continue
        op_name = _OP_NAME.search(line)
        kind = _KIND.search(line)
        ops.append((int(cycles.group(1)), match.group(1), match.group(3),
                    match.group(2), op_name.group(1) if op_name else "",
                    kind.group(1) if kind else ""))
    return ops


def module_of(op_name: str) -> str:
    """``jit(forward)/forward/RSUNet/enc0/conv2/conv_general_dilated`` ->
    ``enc0/conv2/conv_general_dilated``: the path below the model."""
    parts = op_name.split("/")
    return "/".join(parts[3:]) if len(parts) > 3 else op_name


def part_of_ops(hlo_text: str):
    """``({op: part}, {op: [[module path, window], ...]})`` by the
    program's own rule (``core/profiling.py:op_parts``: a fusion by its
    widest convolution, not by its root; XLA's unnamed copies by their
    readers), and the convolutions each op holds."""
    parts, convolutions = profiling.op_parts(hlo_text)
    return {op: part for by_part in parts.values()
            for part, ops in by_part.items() for op in ops}, convolutions


def by_module(hlo_text: str) -> dict:
    """``{part: [cycles of its convolutions, cycles of the rest]}`` of the
    entry computation's ops. The part is the first name under the model
    (:func:`part_of_ops`: a flax module, ``enc1``, ``up0``, or a scope of
    the model's own ``__call__``, ``pool0``, ``skip1``; ``-`` for what has
    neither). A convolution is what the chip's trace reduction takes for
    one (``benchmarks/cfbench/trace.py`` ``parse_op``): a ``kOutput``
    fusion, the convolution with its epilogue, unless its name says it is
    rooted elsewhere (a reduce-window: the pool), or a bare
    ``convolution``."""
    part_of, _ = part_of_ops(hlo_text)
    table: dict = {}
    for cycles, op, opcode, _, _, kind in entry_ops(hlo_text):
        row = table.setdefault(part_of.get(op) or "-", [0, 0])
        conv = opcode == "convolution" or (
            kind == "kOutput" and not _NOT_CONV_ROOT.search(op))
        row[0 if conv else 1] += cycles
    return table


def load_config(config: str) -> dict:
    """A benchmark configuration by its name under ``benchmarks/configs/``
    or by the path of such a file."""
    path = config if os.path.exists(config) else os.path.join(
        CHECKOUT, "benchmarks", "configs", config + ".json")
    with open(path) as f:
        return json.load(f)


def compile_forward(config: dict, batch: int):
    """The configuration's forward (``RSUNet.apply`` on one batch of
    patches, returning the configuration's output patch) compiled for one
    chip of a described v5e: the program a chip runs, the model told what
    it is lowered for (``RSUNet.platform``; the process's own backend is
    the CPU), so the blocks that take the convolution kernel there take
    it here (``rsunet.kernel_takes``)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chunkflow_tpu.models import rsunet

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    spec = config["model"]
    model = rsunet.RSUNet(
        in_channels=spec["in_channels"], out_channels=spec["out_channels"],
        width=tuple(spec["width"]),
        down_factors=tuple(map(tuple, spec["pooling"])),
        dtype=jnp.dtype(spec["compute_dtype"]),
        final_activation=spec["final_activation"],
        platform=topo.devices[0].platform)
    shape = (batch, *config["patch"], spec["in_channels"])
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros(shape)))
    params, x = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        (params, jax.ShapeDtypeStruct(shape, jnp.float32)))

    def forward(params, x):
        # under the scope the engine's programs give the model: the paths
        # are then the ones ``op_parts`` reads in a run's programs.json
        with jax.named_scope("forward"):
            return model.apply(params, x,
                               output_patch_size=config.get("output_patch"))

    return jax.jit(forward).lower(params, x).compile()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("config", help="a name under benchmarks/configs/ "
                        "or a path to such a file")
    parser.add_argument("--batch", type=int, default=None,
                        help="patches a program (default: the config's)")
    parser.add_argument("--top", type=int, default=25)
    parser.add_argument("--by-module", action="store_true",
                        help="cycles summed by part of the model, convolutions "
                        "apart from the rest, in place of the op list")
    parser.add_argument("--hlo", help="also write the optimized HLO here")
    args = parser.parse_args(argv)
    config = load_config(args.config)
    compiled = compile_forward(config, args.batch or config["batch"])
    text = compiled.as_text()
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    ops = entry_ops(text)
    total = sum(op[0] for op in ops)
    by_opcode: dict = {}
    for cycles, _, opcode, *_ in ops:
        by_opcode[opcode] = by_opcode.get(opcode, 0) + cycles
    print("XLA's cost model for a described v5e: a ranking of lowerings, "
          "NOT a measurement")
    print(f"{config['name']}: {total / 1e6:.1f} M estimated cycles a "
          f"program ({1e3 * total / CLOCK_HZ:.0f} ms at "
          f"{CLOCK_HZ / 1e9:g} GHz), {len(ops)} entry ops")
    for opcode, cycles in sorted(by_opcode.items(), key=lambda kv: -kv[1]):
        print(f"  {opcode:<24} {cycles / 1e6:8.1f} M "
              f"{100.0 * cycles / total:5.1f}%")
    part_of, convolutions = part_of_ops(text)
    kernels = [op for op in convolutions if op not in {o[1] for o in ops}]
    if kernels:
        print(f"  {len(kernels)} kernels carry no estimated cycles and are "
              f"in no total (time them on the chip):")
        for op in kernels:
            print(f"    {op} {part_of.get(op) or '-'} / " + " + ".join(
                f"{path} {window}" for path, window in convolutions[op]))
    if args.by_module:
        print(f"{'module':<10} {'conv M':>8} {'rest M':>8} {'%':>5}")
        for module, (conv, rest) in sorted(
                by_module(text).items(), key=lambda kv: -sum(kv[1])):
            print(f"{module:<10} {conv / 1e6:8.1f} {rest / 1e6:8.1f} "
                  f"{100.0 * (conv + rest) / total:5.1f}")
        return 0
    # a fusion is named, shaped and annotated after its root: beside the
    # root's path, the part the op counts under and the convolutions inside
    print(f"{'Mcycles':>8} {'%':>5}  op / shape{{layout}} / part / "
          f"convolutions inside, or the op's own path")
    for cycles, op, _, shape, op_name, _ in sorted(
            ops, reverse=True)[:args.top]:
        inside = " + ".join(f"{path} {window}"
                            for path, window in convolutions.get(op, []))
        print(f"{cycles / 1e6:8.2f} {100.0 * cycles / total:5.1f}  {op} "
              f"{shape} {part_of.get(op) or '-'} / "
              f"{inside or module_of(op_name) or '-'}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
