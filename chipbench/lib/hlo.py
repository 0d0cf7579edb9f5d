"""What the compiled program holds, read from its HLO text.

The trace names each device operation after its HLO instruction.  From the
compiled module's text this module finds which instructions are Pallas
kernels (``custom_call_target="tpu_custom_call"``), with the bytes of
their operands and results, and which are collectives.
"""

from __future__ import annotations

import re

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}
_SHAPE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\((.*)$")
_OPERAND = re.compile(r"%([\w.\-]+)")
_COLLECTIVE = ("all-to-all", "all-gather", "all-reduce", "collective-permute", "reduce-scatter")


def shape_bytes(text: str) -> int:
    """Bytes of every array shape written in ``text``."""
    total = 0
    for dtype, dims in _SHAPE.findall(text):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def classify(hlo_text: str) -> dict:
    """``{"kernel": {name: operand + result bytes}, "collective": [name, ...]}``
    for the instructions of ``hlo_text``.  An operand's bytes are those of
    the instruction that makes it (instruction names are unique within a
    module)."""
    made: dict = {}
    kernel_lines: list = []
    collectives: list = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name, result, opcode, rest = m.groups()
        made[name] = shape_bytes(result)
        if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in rest:
            kernel_lines.append((name, rest.split("custom_call_target=")[0]))
        elif opcode.startswith(_COLLECTIVE) or (
            opcode.startswith("async-") and any(c in rest for c in _COLLECTIVE)
        ):
            collectives.append(name)
    kernels = {
        name: made[name] + sum(made.get(op, 0) for op in _OPERAND.findall(args))
        for name, args in kernel_lines
    }
    return {"kernel": kernels, "collective": collectives}


def count_kernels(hlo_text: str) -> int:
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def count_all_to_all(hlo_text: str) -> int:
    return hlo_text.count("all-to-all-start(") + hlo_text.count("all-to-all(")
