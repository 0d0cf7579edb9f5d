"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds, TPU v5e constants:

    compute    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory     = HLO_bytes_per_chip / HBM_bw
    collective = collective_bytes_per_chip / link_bw

``cost_analysis()`` is per-device after SPMD partitioning (verified
empirically), so the per-chip forms above equal the prompt's
``global / (chips × rate)`` forms.  collective_bytes is parsed from the
post-optimisation HLO: result-shape bytes of every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute op (all-reduce payload ==
result; all-gather wire traffic ≈ result·(D−1)/D ≤ result — we take the
conservative result size), times any enclosing while-loop trip count when
derivable (scan-over-layers bodies).
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Optional

__all__ = [
    "HW",
    "V5E",
    "COLLECTIVE_LAUNCH_S",
    "collective_bytes",
    "roofline_terms",
    "model_flops",
    "summarize_cell",
    "fft_pass_report",
    "fft2_fallback_report",
    "HIGHEST_PASSES",
    "KARATSUBA_GEMMS",
    "leaf_contraction",
    "pass_mxu_s",
    "pass_s",
    "program_s",
    "conv_report",
    "pencil_report",
    "prune_candidates",
    "bluestein_report",
]

#: Fixed per-collective launch/dispatch charge (seconds).  Wire bytes are
#: identical whether the split-complex pair rides one stacked all-to-all or
#: two, so without a launch term the model could never prefer packing; 10 µs
#: is the right order for a TPU ICI collective dispatch and is deliberately
#: hardware-vague — it separates "fewer collectives" from "same bytes", not
#: v5e from v5p.
COLLECTIVE_LAUNCH_S = 10e-6


@dataclasses.dataclass(frozen=True)
class HW:
    name: str
    peak_flops_bf16: float
    peak_flops_f32: float
    hbm_bw: float
    link_bw: float
    hbm_bytes: float
    #: The MXU's contraction width: a GEMM's contracted dimension is padded
    #: up to a multiple of it.
    mxu_k: int = 128


V5E = HW(
    name="tpu-v5e",
    peak_flops_bf16=197e12,
    peak_flops_f32=49.3e12,
    hbm_bw=819e9,
    link_bw=50e9,
    hbm_bytes=16e9,
)

#: bfloat16 MXU passes of one float32 GEMM at ``Precision.HIGHEST``, the
#: precision of every pass kernel's GEMMs.
HIGHEST_PASSES = 6

#: Real GEMMs per complex GEMM: the kernels' Karatsuba split
#: (``kernels/fft4step.cgemm_tile``, ``kernels/dft_matmul.dft_tile``).
KARATSUBA_GEMMS = 3

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4,
    "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"=\s*((?:\w+\[[^\]]*\][^ ]*|\()[^=]*?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")

_WHILE_TRIP_RE = re.compile(r"trip_count=\"?(\d+)\"?")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device collective payload bytes by op type, from optimized HLO.

    Ops inside while-loop computations (scan-over-layers) are multiplied by
    the loop trip count when XLA recorded one (known_trip_count backend
    config); otherwise counted once (conservative lower bound, flagged).
    """
    # Map computation name → trip count for while bodies.
    trip_counts: dict[str, int] = {}
    for m in re.finditer(
        r"while\([^)]*\).*?condition=%?([\w.\-]+).*?body=%?([\w.\-]+).*?(?:trip_count=\"?(\d+)\"?)?",
        hlo_text,
    ):
        body = m.group(2)
        tc = m.group(3)
        if tc:
            trip_counts[body] = int(tc)
    # Fallback: backend_config known_trip_count appears on the while line.
    for line in hlo_text.splitlines():
        if " while(" in line:
            bm = re.search(r"body=%?([\w.\-]+)", line)
            tm = re.search(r'known_trip_count[^0-9]*(\d+)', line)
            if bm and tm:
                trip_counts[bm.group(1)] = int(tm.group(1))

    by_type: dict[str, float] = {}
    count = 0
    unrolled_unknown = 0
    current_comp = None
    comp_re = re.compile(r"^(?:%?([\w.\-]+))\s*(?:\([^)]*\))?\s*->.*{\s*$")
    for line in hlo_text.splitlines():
        mhead = re.match(r"^%?([\w.\-]+)\s+\(.*\)\s+->", line.strip())
        if line and not line.startswith(" ") and "{" in line and ("->" in line or line.startswith("ENTRY")):
            mm = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", line.strip())
            current_comp = mm.group(1) if mm else None
        m = _COLL_RE.search(line)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        nbytes = _shape_bytes(result_type)
        mult = trip_counts.get(current_comp, 1)
        if current_comp and current_comp not in trip_counts and ".body" in (current_comp or ""):
            unrolled_unknown += 1
        by_type[op] = by_type.get(op, 0.0) + nbytes * mult
        count += 1
    total = sum(by_type.values())
    return {
        "per_device_bytes": total,
        "by_type": by_type,
        "num_ops": count,
        "unknown_trip_loops": unrolled_unknown,
    }


def model_flops(n_params_active: int, tokens: int) -> float:
    """6·N·D — the useful-FLOPs yardstick (N = active params)."""
    return 6.0 * n_params_active * tokens


def leaf_contraction(p, hw: HW = V5E) -> int:
    """The summed contraction lengths of a pass's GEMM stages, each padded
    up to the MXU width: ``⌈n⌉`` for a direct DFT leaf (one stage),
    ``⌈n1⌉ + ⌈n2⌉`` for a fused four-step leaf (a lane group, ``n1 <
    128``, fills its first stage with ``128 / n1`` signals).  0 for a pass
    that runs no GEMM of its own (reorder, Bluestein chirp stages)."""

    def up(m: int) -> int:
        return -(-m // hw.mxu_k) * hw.mxu_k

    if p.kind == "direct":
        return up(p.n)
    if p.kind == "fused4":
        return up(p.n1) + up(p.n2)
    return 0


def pass_mxu_s(p, batch: int = 1, hw: HW = V5E) -> float:
    """Modeled MXU time of one program pass over ``batch`` rows: per point
    ``KARATSUBA_GEMMS`` real GEMMs of 2 flops per contracted element, over
    :func:`leaf_contraction`, each in ``HIGHEST_PASSES`` bfloat16 passes,
    at the chip's bfloat16 peak."""
    pencils, _stride, f = p.view_in if p.view_in else (1, 1, p.n)
    flops = batch * pencils * f * KARATSUBA_GEMMS * 2 * leaf_contraction(p, hw)
    return flops * HIGHEST_PASSES / hw.peak_flops_bf16


def pass_s(p, batch: int = 1, hw: HW = V5E) -> float:
    """Modeled device time of one program pass: the larger of its HBM time
    (:func:`~repro.core.plan.pass_hbm_bytes` over the HBM bandwidth) and
    its MXU time (:func:`pass_mxu_s`).  No efficiency factor: it would be
    the same for every candidate a tuner ranks."""
    from repro.core import plan as plan_lib  # local: analysis stays lazy

    return max(plan_lib.pass_hbm_bytes(p, batch) / hw.hbm_bw, pass_mxu_s(p, batch, hw))


def program_s(passes, batch: int = 1, hw: HW = V5E) -> float:
    """Modeled device time of a 1-D pass program: its passes run in turn."""
    return sum(pass_s(p, batch, hw) for p in passes)


def fft_pass_report(
    n: int, batch: int = 1, hw: HW = V5E, n2: Optional[int] = None
) -> dict:
    """Modeled HBM traffic of an FFT's linearized pass program.

    One entry per pass (the plan's HBM round trips, literally), plus the
    total and its roofline memory term — so the paper's kernel-call count is
    not just asserted by tests but observable in every dry-run artifact and
    benchmark row.  With ``n2`` the report covers the joint multi-axis 2-D
    program of an ``(..., n2, n)`` image: each pass entry carries its
    transform ``axis`` and every pass is charged the whole image it streams.
    """
    from repro.core import plan as plan_lib  # local: analysis stays lazy

    plan = plan_lib.plan_fft2(n, n2) if n2 is not None else plan_lib.plan_fft(n)
    shape2d = (n2, n) if n2 is not None else None
    passes = []
    for i, p in enumerate(plan.passes):
        nbytes = plan_lib.pass_hbm_bytes(p, batch, plan_lib.pass_other(p, plan))
        pencils, stride, f = p.view_in if p.view_in else (1, 1, p.n)
        passes.append(
            {
                "pass": i,
                "kind": p.kind,
                "axis": p.axis,
                "n": p.n,
                "view": [pencils, stride, f],
                "twiddle": list(p.twiddle_after) if p.twiddle_after else None,
                "order": p.order,
                "hbm_bytes": nbytes,
            }
        )
    total = plan_lib.program_hbm_bytes(plan.passes, batch, shape2d)
    report = {
        "n": n,
        "batch": batch,
        "hbm_round_trips": plan.hbm_round_trips,
        "passes": passes,
        "modeled_hbm_bytes": total,
        "memory_s": total / hw.hbm_bw,
    }
    if n2 is not None:
        report["n2"] = n2
    return report


def bluestein_report(
    n: int, batch: int = 1, pad: Optional[int] = None, hw: HW = V5E
) -> dict:
    """Modeled cost of the Bluestein chirp-conv program for a non-pow2 ``n``
    against a *hypothetical* native mixed-radix transform of the same length.

    The chirp-conv route runs two transforms of the pow2 pad length
    ``M = bluestein_pad(n)`` (the B̂ spectrum is interned at plan time, so
    only the forward pad-FFT and pad-IFFT cost runtime arithmetic) plus the
    O(n + M) chirp multiplies.  Against a native 5·n·log₂n yardstick that is
    a ~2·(M/n)·(log M / log n) arithmetic overhead — the classic "up to 3×
    pad, ~6× flops" Bluestein tax, reported here per size so the choice is
    observable in every dry-run artifact rather than folklore.
    """
    from repro.core import limits, plan as plan_lib  # local: analysis stays lazy

    if n > 1 and not (n & (n - 1)):
        raise ValueError(
            f"n={n} is a power of two — it runs the native schedules; the "
            f"Bluestein report covers the non-pow2 route"
        )
    m_pad = limits.bluestein_pad(n) if pad is None else pad
    prog = plan_lib.compile_bluestein(n, pad)
    passes = []
    total = 0
    for i, p in enumerate(prog):
        nbytes = plan_lib.pass_hbm_bytes(p, batch)
        passes.append(
            {
                "pass": i,
                "kind": p.kind,
                "stage": p.stage,
                "n": p.n,
                "hbm_bytes": nbytes,
            }
        )
        total += nbytes
    f32 = 4
    log2 = math.log2
    flops = batch * (2 * 5.0 * m_pad * log2(m_pad) + 8.0 * (2 * n + m_pad))
    mixed_flops = batch * 5.0 * n * max(log2(n), 1.0)
    mixed_bytes = 2 * batch * n * 2 * f32  # one signal round trip
    return {
        "n": n,
        "pad": m_pad,
        "batch": batch,
        "pad_ratio": m_pad / n,
        "hbm_round_trips": len(prog),
        "passes": passes,
        "modeled_hbm_bytes": total,
        "memory_s": total / hw.hbm_bw,
        "modeled_flops": flops,
        "mixed_radix_flops": mixed_flops,
        "mixed_radix_hbm_bytes": mixed_bytes,
        "flops_overhead": flops / mixed_flops,
        "hbm_overhead": total / mixed_bytes,
    }


def prune_candidates(candidates: list, tol: float = 0.2, vmem_budget: Optional[int] = None) -> list:
    """Roofline pruning of a tuning space — the model half of the autotuner.

    ``candidates``: ordered ``(config, modeled_cost, vmem_bytes)`` triples,
    the fixed heuristic FIRST.  The cost is one scalar in one unit across a
    space: HBM bytes for plan configs, modeled seconds for overlap-save
    blocks and pencil schedules.  Keeps candidates whose working set fits
    the VMEM budget and whose modeled cost is within ``tol`` of the
    feasible minimum — the only ones a measurement pass could ever crown —
    returned sorted by modeled cost (stable, so the heuristic wins modeled
    ties; where the model is strictly cheaper, the modeled pick deviates
    from the heuristic by design).
    """
    from repro.core.limits import VMEM_BUDGET  # local: analysis stays lazy

    budget = VMEM_BUDGET if vmem_budget is None else vmem_budget
    feasible = [c for c in candidates if c[2] <= budget]
    if not feasible:
        feasible = candidates  # degenerate: nothing fits, measure anyway
    floor = min(c[1] for c in feasible)
    kept = [c for c in feasible if c[1] <= floor * (1.0 + tol)]
    return sorted(kept, key=lambda c: c[1])


def fft2_fallback_report(n: int, n2: int, batch: int = 1, hw: HW = V5E) -> dict:
    """Joint strip-mined 2-D program vs the per-axis composition it replaced.

    For ``n2 > FUSED_MAX`` images the pre-tuner code composed a row plan
    with an ``axis=-2`` column plan; a multi-pass column plan executes
    through a transpose sandwich — two extra whole-image HBM round trips
    the joint program's width-broadcast strided passes do not pay.  Both
    schedules' modeled bytes, so the acceptance criterion (joint strictly
    below fallback) is observable, not just asserted.
    """
    from repro.core import plan as plan_lib  # local: analysis stays lazy

    f32 = 4
    joint_plan = plan_lib.plan_fft2(n, n2)
    joint = plan_lib.program_hbm_bytes(joint_plan.passes, batch, (n2, n))
    row = plan_lib.program_hbm_bytes(plan_lib.plan_fft(n).passes, batch * n2)
    col_passes = plan_lib.plan_fft(n2).passes
    col = plan_lib.program_hbm_bytes(col_passes, batch * n)
    img = batch * n2 * n * 2 * f32  # split-complex image
    transposes = 2 * 2 * img if len(col_passes) > 1 else 0  # swapaxes sandwich
    fallback = row + col + transposes
    return {
        "n": n,
        "n2": n2,
        "batch": batch,
        "joint_hbm_bytes": joint,
        "joint_passes": len(joint_plan.passes),
        "fallback_hbm_bytes": fallback,
        "fallback_transpose_bytes": transposes,
        "bytes_ratio": fallback / joint if joint else float("inf"),
        "joint_memory_s": joint / hw.hbm_bw,
        "fallback_memory_s": fallback / hw.hbm_bw,
    }


def _rfft_conv(n: int, batch: int, plan_lib, hw: HW) -> tuple:
    """Modeled HBM traffic and device time of one rfft → ⊙H → irfft pair
    at length ``n``: ``(bytes, seconds)``.

    The packed complex programs (length n/2) at signal batch, the filter's
    forward transform once, the Hermitian recombination epilogues (read m /
    write m+1 planes per direction) and the spectrum multiply (two reads,
    one write).  Split-complex float32 — the same conventions as
    :func:`~repro.core.plan.pass_hbm_bytes`.  The programs take
    :func:`program_s`, the rest its bytes at HBM bandwidth.
    """
    f32 = 4
    m = n // 2
    prog = plan_lib.plan_fft(max(m, 1)).passes
    progs = 2 * plan_lib.program_hbm_bytes(prog, batch) + plan_lib.program_hbm_bytes(prog, 1)
    # Recombination read+write per transform: 2·batch signal passes + the
    # filter's one; spectrum multiply reads batch X planes + the broadcast
    # H once and writes batch Y planes.
    recomb = (2 * batch + 1) * (2 * m + 1) * 2 * f32
    cmul_b = (2 * batch + 1) * (m + 1) * 2 * f32
    seconds = 2 * program_s(prog, batch, hw) + program_s(prog, 1, hw)
    return progs + recomb + cmul_b, seconds + (recomb + cmul_b) / hw.hbm_bw


def _paired_conv(block: int, pairs: int, batch: int, L: int, plan_lib, hw: HW) -> tuple:
    """Modeled HBM traffic and device time of the paired overlap-save path
    (:func:`repro.core.overlap.overlap_save` where ``pairs_apply`` holds):
    ``(bytes, seconds)``.

    ``pairs`` complex frames of ``block`` a signal through one fft and one
    ifft program of length ``block``, the product against the filter's
    full spectrum (two reads, one write), the filter's one rfft, the
    framing into pairs (``os_pair``: each transformed frame's window read
    for its peak and for its scaled copy, then written) and the write-back
    (``os_unpair``: the output's zero fill, then each of its ``L`` samples
    read and written once).
    """
    f32 = 4
    rows = batch * pairs
    prog = plan_lib.plan_fft(block).passes
    filt = plan_lib.plan_fft(block // 2).passes
    progs = 2 * plan_lib.program_hbm_bytes(prog, rows) + plan_lib.program_hbm_bytes(filt, 1)
    filt_recomb = (block + 1) * 2 * f32
    product = (2 * rows + 1) * block * 2 * f32
    framing = batch * (3 * 2 * pairs * block + 3 * L) * f32
    glue = filt_recomb + product + framing
    seconds = 2 * program_s(prog, rows, hw) + program_s(filt, 1, hw)
    return progs + glue, seconds + glue / hw.hbm_bw


def conv_report(
    L: int, Lh: int, batch: int = 1, hw: HW = V5E, block=None, stream: bool = False
) -> dict:
    """One-shot vs overlap-save modeled HBM traffic and device time for an
    FFT convolution.

    The one-shot path pads to ``next_pow2(L + Lh - 1)`` — beyond the fused
    regime that is a split-regime pass program per transform.  Overlap-save
    frames the signal into ``num_blocks`` blocks of ``block`` samples
    (fused regime by construction) and is priced as the schedule that runs
    (:func:`repro.core.overlap.pairs_apply`, from the shapes): two frames
    of a signal per complex transform, fft/ifft programs of length
    ``block`` over the pairs; or, with one frame a signal, the rfft/irfft
    pair (packed programs of ``block / 2``) over every frame, with the
    framing gather and tail scatter.  The ``block/(block - Lh + 1)``
    redundancy is in the frame count, so the report shows where the
    crossover actually is rather than asserting it.

    ``hbm_bytes`` is the modeled traffic; ``modeled_s`` the modeled device
    time, each pass program at the larger of its HBM and MXU time
    (:func:`program_s`) and the rest at HBM bandwidth.  The fused leaves
    wait on the MXU, whose work per point grows with the block, so the
    tuner ranks blocks by ``modeled_s``.  ``stream``: ``L`` is one
    :class:`~repro.core.overlap.StreamingConv` call (carried tail and
    chunk), which runs as one frame whenever it fits one block.
    """
    from repro.core import overlap as ov  # local: analysis stays lazy
    from repro.core import plan as plan_lib
    from repro.core.conv import next_pow2

    f32 = 4
    n_one = next_pow2(L + Lh - 1)
    one_bytes, one_s = _rfft_conv(n_one, batch, plan_lib, hw)
    one = {
        "n": n_one,
        "hbm_round_trips": 2 * plan_lib.plan_fft(n_one // 2).hbm_round_trips,
        "hbm_bytes": one_bytes,
        "memory_s": one_bytes / hw.hbm_bw,
        "modeled_s": one_s,
    }

    B = ov.pick_block(Lh, block)
    step = B - (Lh - 1)
    nb = 1 if stream and L <= B else -(-L // step)
    paired = ov.pairs_apply(nb, B)
    if paired:
        os_bytes, os_s = _paired_conv(B, -(-nb // 2), batch, L, plan_lib, hw)
    else:
        os_bytes, os_s = _rfft_conv(B, batch * nb, plan_lib, hw)
        # Framing gather (read L, write nb·B) + tail scatter (read nb·step,
        # write L), real float32.
        framing = batch * (L + nb * B + nb * step + L) * f32
        os_bytes += framing
        os_s += framing / hw.hbm_bw
    osd = {
        "block": B,
        "num_blocks": nb,
        "valid_per_block": step,
        "max_plan_n": B,
        "paired": paired,
        "leaf_n": B if paired else B // 2,
        "hbm_bytes": os_bytes,
        "memory_s": os_bytes / hw.hbm_bw,
        "modeled_s": os_s,
    }
    return {
        "L": L,
        "Lh": Lh,
        "batch": batch,
        "one_shot": one,
        "overlap_save": osd,
        "bytes_ratio": one_bytes / os_bytes if os_bytes else float("inf"),
    }


def pencil_report(
    n: int,
    d: int,
    batch: int = 1,
    *,
    n1: Optional[int] = None,
    n2: Optional[int] = None,
    pack: bool = True,
    chunks: int = 1,
    natural_order: bool = True,
    hw: HW = V5E,
) -> dict:
    """Modeled cost decomposition of the distributed pencil FFT.

    The paper's argument one level up: across a pod the slow tier is the
    interconnect, and the pencil schedule's cost is its all-to-all
    transposes against the local column/row FFT passes.  This report
    charges both sides explicitly so the distributed tuner
    (:meth:`repro.core.tuning.TuningSpace.for_pencil`) can trade them:

    * per-step **comm bytes**: every transpose moves the device's whole
      slab, ``(d-1)/d`` of it over the wire;
    * **local HBM bytes**: the n1 column program (at batch·q pencils), the
      twiddle multiply (slab read+write + the per-device table), the n2 row
      program (at batch·p pencils), and the natural-order reorder;
    * a fixed :data:`COLLECTIVE_LAUNCH_S` per collective *call* — what
      packing the split-complex pair into one stacked all-to-all halves,
      and what strip-mining into ``chunks`` pieces pays more of;
    * the pipelined middle: with ``chunks=K`` the two inner transposes
      overlap the column FFT + twiddle chunk-by-chunk, so the modeled
      middle is ``cc + fc + (K-1)·max(cc, fc)`` (per-chunk comm ``cc``,
      per-chunk compute ``fc``) instead of their sum.

    ``modeled_s`` is the config's total; ``serial_s`` is the unpacked
    ``K=1`` baseline of the same factorization, so ``overlap_win`` is
    directly the speedup the tuner is claiming.
    """
    from repro.core import plan as plan_lib  # local: analysis stays lazy

    if n1 is None or n2 is None:
        from repro.core import distributed as dist  # lazy: avoids cycle

        n1, n2 = dist.pencil_factors(n, d)
    if n1 * n2 != n:
        raise ValueError(f"pencil factors {n1}x{n2} != n={n}")
    p, q = n1 // max(d, 1), n2 // max(d, 1)
    f32, planes = 4, 2
    slab = batch * (n // max(d, 1))  # elements per plane per device
    slab_bytes = slab * planes * f32
    wire_step = slab_bytes * (d - 1) / max(d, 1)  # one transpose, per device
    a2a_steps = (3 if natural_order else 2) if d > 1 else 0
    K = max(1, chunks) if (pack and d > 1) else 1
    # Collective call count: the two inner transposes are K calls each, the
    # natural-order reorder is always one packed call; unpacked pays two
    # calls (xr, xi) per step, serially.
    if d <= 1:
        a2a_calls = 0
    elif pack:
        a2a_calls = 2 * K + (1 if natural_order else 0)
    else:
        a2a_calls = 2 * a2a_steps

    fft1_bytes = plan_lib.program_hbm_bytes(
        plan_lib.plan_fft(n1).passes, batch * q
    )
    fft2_bytes = plan_lib.program_hbm_bytes(
        plan_lib.plan_fft(n2).passes, batch * p
    )
    twiddle_bytes = 2 * slab_bytes + n1 * q * planes * f32  # slab r/w + table
    reorder_bytes = 2 * slab_bytes if (natural_order and d > 1) else 0
    local_bytes = fft1_bytes + twiddle_bytes + fft2_bytes + reorder_bytes

    t_step = wire_step / hw.link_bw
    t_mid_compute = (fft1_bytes + twiddle_bytes) / hw.hbm_bw
    if d > 1:
        cc, fc = 2 * t_step / K, t_mid_compute / K
        t_middle = cc + fc + (K - 1) * max(cc, fc)
    else:
        t_middle = t_mid_compute
    t_tail = fft2_bytes / hw.hbm_bw + reorder_bytes / hw.hbm_bw
    if natural_order and d > 1:
        t_tail += t_step
    modeled = t_middle + t_tail + a2a_calls * COLLECTIVE_LAUNCH_S
    serial = (
        a2a_steps * t_step
        + local_bytes / hw.hbm_bw
        + (2 * a2a_steps) * COLLECTIVE_LAUNCH_S
    )
    return {
        "n": n,
        "d": d,
        "batch": batch,
        "n1": n1,
        "n2": n2,
        "pack": pack,
        "chunks": K,
        "natural_order": natural_order,
        "a2a_steps": a2a_steps,
        "a2a_calls": a2a_calls,
        "comm_bytes_per_step": wire_step,
        "comm_bytes_total": wire_step * a2a_steps,
        "fft1_bytes": fft1_bytes,
        "fft2_bytes": fft2_bytes,
        "twiddle_bytes": twiddle_bytes,
        "local_hbm_bytes": local_bytes,
        "comm_s": a2a_steps * t_step,
        "memory_s": local_bytes / hw.hbm_bw,
        "modeled_s": modeled,
        "serial_s": serial,
        "overlap_win": serial / modeled if modeled else float("inf"),
    }


def roofline_terms(
    flops_per_chip: float,
    bytes_per_chip: float,
    coll_bytes_per_chip: float,
    hw: HW = V5E,
    dtype: str = "bf16",
) -> dict:
    peak = hw.peak_flops_bf16 if dtype == "bf16" else hw.peak_flops_f32
    t_c = flops_per_chip / peak
    t_m = bytes_per_chip / hw.hbm_bw
    t_x = coll_bytes_per_chip / hw.link_bw
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x), key=lambda kv: kv[1])
    return {
        "compute_s": t_c,
        "memory_s": t_m,
        "collective_s": t_x,
        "bound": dom[0],
        "step_lower_bound_s": dom[1],
        # fraction of roofline the *dominant* resource achieves if the other
        # two overlap perfectly; the perf loop drives the dominant term down.
        "balance": {
            "compute": t_c / dom[1] if dom[1] else 0.0,
            "memory": t_m / dom[1] if dom[1] else 0.0,
            "collective": t_x / dom[1] if dom[1] else 0.0,
        },
    }


def summarize_cell(record: dict, hw: HW = V5E) -> str:
    r = record
    t = r["roofline"]
    return (
        f"{r['arch']:18s} {r['shape']:12s} {r['mesh']:9s} "
        f"C={t['compute_s']*1e3:9.2f}ms M={t['memory_s']*1e3:9.2f}ms "
        f"X={t['collective_s']*1e3:9.2f}ms bound={t['bound']:10s} "
        f"useful={r.get('useful_flops_frac', 0):5.1%}"
    )
