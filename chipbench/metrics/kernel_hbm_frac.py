"""``kernel_hbm_frac``: the Pallas kernels' achieved HBM bandwidth over the
chip's peak (percent).

Bytes are each kernel's operands and results as the compiled HLO shapes
them, once per time the kernel runs; time is the kernels' device time.
Both come from what the kernels are, not from the plan that made them."""


def reduce(tr: dict):
    kernel_bytes = tr["ops"]["kernel"]
    moved = 0
    busy_ns = 0
    for ev in tr["devices"]:
        for name, _start, dur in ev:
            if name in kernel_bytes:
                moved += kernel_bytes[name]
                busy_ns += dur
    if busy_ns == 0:
        return None
    return 100.0 * moved / (busy_ns * 1e-9 * tr["peaks"]["hbm_bytes_per_s"])
