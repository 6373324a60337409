"""tools/aot_cost.py reads XLA's cost model out of a compiled module's
text: the entry computation's ops with their cycles, shapes and flax
parts; ``--by-module`` sums them by part (``core/profiling.py:op_parts``:
a fusion by its widest convolution, not by its root) with the convolutions
apart from the rest."""
import re

import pytest

from tools import aot_cost

HLO = '''HloModule jit_apply

%fused_computation.1 (p: bf16[8,128]) -> bf16[8,128] {
  %inside.1 = bf16[8,128]{1,0} negate(%p), backend_config={"window_config":{"estimated_cycles":"999"}}
}

%fused_computation.2 (q: bf16[8,128], k: bf16[3,3,3,128,128], h: bf16[1,1,1,128,12]) -> bf16[8,12] {
  %convolution.7 = bf16[8,128]{1,0} convolution(%q, %k), window={size=3x3x3 pad=1_1x1_1x0_0}, dim_labels=01b2f_012io->01b2f, metadata={op_name="jit(forward)/forward/RSUNet/dec0/conv3/conv_general_dilated"}
  ROOT %convolution.8 = bf16[8,12]{1,0} convolution(%convolution.7, %h), window={size=1x1x1}, dim_labels=01b2f_012io->01b2f, metadata={op_name="jit(forward)/forward/RSUNet/out/conv_general_dilated"}
}

ENTRY %main.5 (x: bf16[4,20,256,64,112]) -> bf16[4,20,256,64,112] {
  %x = bf16[4,20,256,64,112]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.7 = bf16[4,20,256,64,112]{3,4,2,1,0:T(8,128)(2,1)} copy(%x), metadata={op_name="jit(forward)/forward/RSUNet/enc0/jit(relu)/max"}, backend_config={"window_config":{"estimated_cycles":"4000000"}}
  %fusion.8 = bf16[20,256,32,9,112]{4,2,3,1,0:T(8,128)(2,1)} fusion(%copy.7), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(forward)/forward/RSUNet/enc0/conv2/conv_general_dilated"}, backend_config={"window_config":{"estimated_cycles":"500000"}}
  %reduce-window.2 = bf16[20,128,32,9,112]{4,2,3,1,0:T(8,128)(2,1)} fusion(%fusion.8), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(forward)/forward/RSUNet/pool0/reduce_max"}, backend_config={"window_config":{"estimated_cycles":"300000"}}
  %convolution.3 = bf16[20,128,32,9,72]{4,2,3,1,0:T(8,128)(2,1)} convolution(%reduce-window.2, %x), metadata={op_name="jit(forward)/forward/RSUNet/up1/conv_general_dilated"}, backend_config={"window_config":{"estimated_cycles":"200000"}}
  %copy.4 = bf16[20,128,32,9,72]{1,4,3,2,0:T(8,128)(2,1)} copy(%convolution.3), backend_config={"window_config":{"estimated_cycles":"100000"}}
  %fusion.383 = bf16[20,256,32,9,12]{4,2,3,1,0:T(8,128)(2,1)} fusion(%copy.4), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(forward)/forward/RSUNet/out/conv_general_dilated"}, backend_config={"window_config":{"estimated_cycles":"7000000"}}
  %copy.5 = bf16[20,256,32,9,12]{1,4,3,2,0:T(8,128)(2,1)} copy(%fusion.383), backend_config={"window_config":{"estimated_cycles":"60000"}}
  ROOT %fusion.9 = bf16[20,256,32,9,112]{4,2,3,1,0:T(8,128)(2,1)} fusion(%copy.7), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(forward)/forward/RSUNet/enc0/conv2/conv_general_dilated"}, backend_config={"window_config":{"estimated_cycles":"10000000"}}
}
'''


def test_entry_ops_reads_cycles_shapes_and_modules():
    ops = aot_cost.entry_ops(HLO)
    assert [(op[0], op[1], op[2], op[5]) for op in ops] == [
        (4000000, "copy.7", "copy", ""),
        (500000, "fusion.8", "fusion", "kLoop"),
        (300000, "reduce-window.2", "fusion", "kOutput"),
        (200000, "convolution.3", "convolution", ""),
        (100000, "copy.4", "copy", ""),
        (7000000, "fusion.383", "fusion", "kOutput"),
        (60000, "copy.5", "copy", ""),
        (10000000, "fusion.9", "fusion", "kOutput")]
    assert ops[-1][3].startswith("bf16[20,256,32,9,112]{4,2,3,1,0:T(8,128)")
    assert aot_cost.module_of(ops[-1][4]) == \
        "enc0/conv2/conv_general_dilated"
    assert aot_cost.module_of(ops[0][4]) == "enc0/jit(relu)/max"
    assert aot_cost.module_of("") == ""


@pytest.mark.parametrize("module,conv,rest", [
    # an output fusion is the convolution with its epilogue; the copy and
    # the loop fusion under the same module's name are not
    ("enc0", 10000000, 4500000),
    # a bare convolution counts as one
    ("up1", 200000, 0),
    # what the model's own `__call__` emits lies under the scope it opens
    # (the pool, here rooted in a reduce-window, so no convolution for all
    # its kOutput)
    ("pool0", 0, 300000),
    # a fusion XLA names after its root, the 1x1x1 head, counts under the
    # module of the 27-tap convolution inside it (PERF.md, PR 38), and so
    # does XLA's unnamed copy that only it reads
    ("dec0", 7000000, 100000),
    # what nothing reads has no part
    ("-", 0, 60000),
])
def test_by_module_keeps_convolutions_apart_from_the_rest(module, conv, rest):
    table = aot_cost.by_module(HLO)
    assert sorted(table) == ["-", "dec0", "enc0", "pool0", "up1"]
    assert table[module] == [conv, rest]
    assert sum(map(sum, table.values())) == sum(
        op[0] for op in aot_cost.entry_ops(HLO))


def test_an_op_with_several_results_is_counted():
    """A multi-output fusion's shape is a tuple, ``(bf16[..]{..}, bf16[..]
    {..})``, with blanks and tiles' parentheses inside: its cycles count
    like any op's (until ISSUE 43 the tool dropped such ops, 7.6 M of
    ``rsunet-superhuman``'s 127.9 M cycles, the pool's lane slices among
    them)."""
    line = (
        '  %slice_maximum_fusion.1 = (bf16[20,128,32,9,28]{4,2,3,1,0:'
        'T(8,128)(2,1)}, bf16[20,128,32,9,28]{4,2,3,1,0:T(8,128)(2,1)}) '
        'fusion(%fusion.218), kind=kLoop, calls=%fused_computation.9, '
        'metadata={op_name="jit(forward)/forward/RSUNet/pool0/slice"}, '
        'backend_config={"window_config":{"estimated_cycles":"3399444"}}')
    text = HLO.replace("  %convolution.3 = ", line + "\n  %convolution.3 = ")
    (op,) = [op for op in aot_cost.entry_ops(text)
             if op[1] == "slice_maximum_fusion.1"]
    assert op[0] == 3399444 and op[2] == "fusion" and op[5] == "kLoop"
    assert op[3].startswith("(bf16[20,128,32,9,28]") and op[3].endswith(")")
    assert len(aot_cost.entry_ops(text)) == len(aot_cost.entry_ops(HLO)) + 1


# ---------------------------------------------------------------------------
# the compiled text of the way down (ISSUE 43): the chip's compiler for a
# described v5e, no chip; about a quarter of a minute at the cut patch
# ---------------------------------------------------------------------------
CUT_PATCH = (20, 64, 256)   # the configuration's z and x tiles, a quarter of y


def _forward_text(kernel: bool) -> str:
    """The optimized HLO of ``rsunet-superhuman``'s forward, one cut patch
    a program, compiled for one chip of a described v5e: the program a
    chip runs (``kernel``), or the one it runs where the rule declines
    every block, XLA's convolutions throughout."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    config = dict(aot_cost.load_config("rsunet-superhuman"),
                  patch=list(CUT_PATCH))
    # an executable for a described chip can be written to the persistent
    # cache and not read back: keep it out, or the next run warns
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    from chunkflow_tpu.models import rsunet
    rule = rsunet.kernel_takes
    if not kernel:
        rsunet.kernel_takes = lambda *args: False
    try:
        return aot_cost.compile_forward(config, batch=1).as_text()
    finally:
        rsunet.kernel_takes = rule
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def superhuman_text():
    return _forward_text(kernel=False)


@pytest.fixture(scope="module")
def superhuman_kernel_text():
    return _forward_text(kernel=True)


def test_the_tool_sizes_the_program_a_chip_runs(superhuman_kernel_text):
    """The tool's process runs on the CPU and lowers for a described
    chip: the model is told so, and the folded levels' blocks, the
    embedding and the head are the kernel's calls there as on the chip,
    each listed as its module's convolution, none with a cycle count."""
    parts, convolutions = aot_cost.part_of_ops(superhuman_kernel_text)
    kernels = {op: held for op, held in convolutions.items()
               if op.startswith("folded_conv")}
    assert sorted(held[0][0] for held in kernels.values()) == sorted(
        ["embed"] + [f"{block}/conv{i}" for block in
                     ("enc0", "enc1", "dec1", "dec0") for i in (1, 2, 3)])
    assert [["dec0/conv3", "3x3x3"], ["out", "1x1x1"]] in kernels.values()
    assert {parts[op] for op in kernels} == {
        "embed", "enc0", "enc1", "dec1", "dec0"}
    assert not set(kernels) & {op[1] for op in aot_cost.entry_ops(
        superhuman_kernel_text)}
    # thirteen calls (the head rides in dec0/conv3's)
    assert len(kernels) == 13


@pytest.mark.parametrize("text", ["superhuman_text",
                                  "superhuman_kernel_text"])
def test_the_pools_window_reads_the_convolution_where_it_wrote(
        text, request):
    """``pool0``'s z and y maximum is one fusion whose operand is
    ``enc0/conv3``'s own fusion, or the kernel's call: no copy, transpose
    or reduce between an encoder block's last convolution and its pool
    (seven full-size passes before ISSUE 43, y transposed into the lanes
    and back)."""
    superhuman_text = request.getfixturevalue(text)
    entry = superhuman_text[superhuman_text.index("\nENTRY "):]
    windows = [line for line in entry.splitlines()
               if "/pool0/reduce_window_max" in line and " fusion(" in line]
    assert len(windows) == 1, windows
    operands = re.search(r" fusion\(([^)]*)\)", windows[0]).group(1)
    _, convolutions = aot_cost.part_of_ops(superhuman_text)
    held = [convolutions.get(operand.strip().lstrip("%"))
            for operand in operands.split(",")]
    assert [["enc0/conv3", "3x3x3"]] in held, (operands, held)
    assert "/pool0/reduce_max" not in entry


def test_the_encoders_way_out_costs_what_the_decoders_does(superhuman_text):
    """By module, ``enc0``'s ops that are no convolution cost under 1.5
    times ``dec0``'s, the same three convolutions on arrays of the same
    size (2.4 times with the reshape-and-``max`` pool, 4.2 at the whole
    patch), and ``pool0`` less than either block's."""
    table = aot_cost.by_module(superhuman_text)
    assert table["enc0"][1] < 1.5 * table["dec0"][1], table
    assert sum(table["pool0"]) < table["dec0"][1], table


# ---------------------------------------------------------------------------
# the convolution kernel (ISSUE 47) through the chip's compiler, Mosaic
# included, for a described v5e: what the interpreter cannot refuse (a
# rotate of 16-bit rows, a slice off the tiling, too much VMEM)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("dtype, fold, channels, blocks, window", [
    # level 0 of rsunet-superhuman: a quarter of y, the configuration's x
    ("bfloat16", 4, 28, 64, (3, 3, 3)),
    ("bfloat16", 4, 28, 64, (1, 3, 3)),
    # level 0 of rsunet-deepem: float32 activations, 128 lanes
    ("float32", 8, 16, 32, (3, 3, 3)),
])
def test_the_convolution_kernel_compiles_for_the_chip(
        one_chip, dtype, fold, channels, blocks, window):
    """One ``XFoldConv`` with its epilogue and residual through the
    kernel, under the scopes the forward gives it: Mosaic takes it, and
    the program lists the custom call as the module's convolution."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from chunkflow_tpu.core import profiling
    from chunkflow_tpu.models import rsunet

    conv = rsunet.XFoldConv(channels, window, dtype=jnp.dtype(dtype),
                            fold=fold, name="conv2")
    shape = (1, 3, 64, blocks, fold * channels)
    x = jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=one_chip)
    term = jax.ShapeDtypeStruct((fold * channels,), jnp.dtype(dtype),
                                sharding=one_chip)
    params = jax.eval_shape(
        lambda: conv.init(jax.random.PRNGKey(0),
                          jnp.zeros(shape, jnp.dtype(dtype))))
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)

    def forward(params, x, scale, shift):
        with jax.named_scope("forward"), jax.named_scope("RSUNet"), \
                jax.named_scope("enc0"):
            return conv.apply(params, x, rsunet.Epilogue(scale, shift, x))

    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(forward).lower(params, x, term, term) \
            .compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    parts, convolutions = profiling.op_parts(text)
    window_text = "x".join(map(str, window))
    (name,) = [op for op in convolutions if op.startswith("folded_conv")]
    assert convolutions[name] == [["enc0/conv2", window_text]]
    assert name in parts["forward"]["enc0"]
