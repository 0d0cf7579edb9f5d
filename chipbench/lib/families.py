"""Pallas kernels by family, read from their instruction names.

The program names every ``pallas_call`` after its kernel family
(``repro.core.plan.KERNEL_NAMES``), and the compiled HLO names the
kernel's instruction after it: ``fft4step.3`` is a fused four-step
kernel.  A program's row passes (its ``p{i}_rows`` scopes) run the row
families, its strided-column passes (``p{i}_cols``) the column families;
the Hermitian ``recomb`` epilogue runs neither.  A program whose kernels
carry no family name (``_unknown_.3``) has no kernel of either.
"""

from __future__ import annotations

import re

ROW_KERNELS = (
    "dft_direct", "fft4step", "pencil_rows_natural",
    "bluestein_fwd", "bluestein_inv", "bluestein_elem",
)
COL_KERNELS = ("pencil_cols", "pencil_cols_natural")


def family(instruction: str) -> str:
    """The family of an instruction name: the name less XLA's ``.<n>``."""
    return re.sub(r"\.\d+$", "", instruction)


def hbm_frac(tr: dict, families) -> float | None:
    """The achieved HBM bandwidth of the kernels of ``families`` over the
    chip's peak (percent), or None where none ran in the window.

    Bytes are each kernel's operands and results as the compiled HLO shapes
    them, once per time it runs; time is those kernels' device time."""
    kernel_bytes = tr["ops"]["kernel"]
    moved = busy_ns = 0
    for ev in tr["devices"]:
        for name, _start, dur in ev:
            if name in kernel_bytes and family(name) in families:
                moved += kernel_bytes[name]
                busy_ns += dur
    if busy_ns == 0:
        return None
    return 100.0 * moved / (busy_ns * 1e-9 * tr["peaks"]["hbm_bytes_per_s"])
