"""The control: the reference in the program's place, its GEMMs in three
bfloat16 passes (``high``) where the configurations state float32 at
``highest``.  At a size a test run holds, the program reads under each
cell's limit and the control above it, on three seeds (the chip readings
at the cells' own sizes are in PERF.md)."""

import jax
import pytest

import _faults
from chipbench.lib import harness

SEEDS = [11, 2**31 + 5, 987654321]


@pytest.mark.parametrize("cell", ["sar_fft2", "sar_range_fft", "conv_os_4097"])
def test_control_fails_the_limit_the_program_meets(cell):
    limit = harness.load_cell(cell).limits["max_err_rel"]
    for program, control in _faults.readings(jax, cell, SEEDS):
        assert program <= limit < control, (program, limit, control)

