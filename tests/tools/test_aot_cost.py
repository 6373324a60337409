"""tools/aot_cost.py reads XLA's cost model out of a compiled module's
text: the entry computation's ops with their cycles, shapes and flax
modules, and nothing of the fused computations."""
from tools import aot_cost

HLO = '''HloModule jit_apply

%fused_computation.1 (p: bf16[8,128]) -> bf16[8,128] {
  %inside.1 = bf16[8,128]{1,0} negate(%p), backend_config={"window_config":{"estimated_cycles":"999"}}
}

ENTRY %main.5 (x: bf16[4,20,256,64,112]) -> bf16[4,20,256,64,112] {
  %x = bf16[4,20,256,64,112]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %copy.7 = bf16[4,20,256,64,112]{3,4,2,1,0:T(8,128)(2,1)} copy(%x), metadata={op_name="jit(apply)/RSUNet/enc0/jit(relu)/max"}, backend_config={"window_config":{"estimated_cycles":"4000000"}}
  ROOT %fusion.9 = bf16[20,256,32,9,112]{4,2,3,1,0:T(8,128)(2,1)} fusion(%copy.7), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(apply)/RSUNet/enc0/conv2/conv_general_dilated"}, backend_config={"window_config":{"estimated_cycles":"10000000"}}
}
'''


def test_entry_ops_reads_cycles_shapes_and_modules():
    ops = aot_cost.entry_ops(HLO)
    assert [(op[0], op[1], op[2]) for op in ops] == [
        (4000000, "copy.7", "copy"), (10000000, "fusion.9", "fusion")]
    assert ops[1][3].startswith("bf16[20,256,32,9,112]{4,2,3,1,0:T(8,128)")
    assert aot_cost.module_of(ops[1][4]) == "enc0/conv2/conv_general_dilated"
    assert aot_cost.module_of(ops[0][4]) == "enc0/jit(relu)/max"
    assert aot_cost.module_of("") == ""
