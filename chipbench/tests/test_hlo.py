"""Kernels and collectives read from compiled HLO text."""

from chipbench.lib import hlo

# Shaped after a v5e compile of a planned transform: operands are named,
# not shaped, on the kernel's line.
TEXT = """
ENTRY %main.1 (x.1: c64[4,8]) -> c64[4,8] {
  %constant.1 = f32[8,8]{1,0:T(8,128)} constant({...})
  %x.1 = c64[4,8]{1,0:T(8,128)} parameter(0), metadata={op_name="x"}
  %custom-call.1 = f32[4,8]{1,0:T(8,128)} custom-call(%x.1), custom_call_target="X64SplitLow"
  %custom-call = f32[4,8]{1,0:T(8,128)} custom-call(%x.1), custom_call_target="X64SplitHigh"
  %_unknown_.2 = (f32[4,8]{1,0:T(8,128)}, f32[4,8]{1,0:T(8,128)}) custom-call(%custom-call.1, %custom-call, %constant.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4,8]{1,0}, f32[4,8]{1,0}, f32[8,8]{1,0}}
  %all_to_all.3 = f32[4,8]{1,0} all-to-all(%custom-call), channel_id=1, replica_groups={{0,1}}, dimensions={1}
  %a2a-start = ((f32[4,8]), f32[4,8]) all-to-all-start(%custom-call), channel_id=2
  %gte = f32[4,8]{1,0:T(8,128)} get-tuple-element(%_unknown_.2), index=0
  ROOT %custom-call.2 = c64[4,8]{1,0:T(8,128)} custom-call(%gte, %gte), custom_call_target="X64Combine"
}
"""


def test_kernel_bytes_are_its_operands_and_results():
    ops = hlo.classify(TEXT)
    # results 2 x 4·8·4 = 256; operands 128 + 128 + 8·8·4 = 512 → 768
    assert ops["kernel"] == {"_unknown_.2": 768}
    assert sorted(ops["collective"]) == ["a2a-start", "all_to_all.3"]


def test_counts():
    assert hlo.count_kernels(TEXT) == 1
    assert hlo.count_all_to_all(TEXT) == 2


def test_shape_bytes():
    assert hlo.shape_bytes("(f32[2,3]{1,0}, bf16[4], c64[], pred[8])") == 24 + 8 + 8 + 8
