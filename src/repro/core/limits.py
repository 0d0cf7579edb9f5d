"""Memory-hierarchy regime limits — the single source every layer consumes.

The paper's schedule is parameterized by where each transform regime ends
(§2.3.2: one kernel call while the working set fits the fast tier, two
beyond, ...).  These thresholds used to be scattered as per-module constants
(`plan.FUSED_MAX`, `overlap.OS_FACTOR`, ad-hoc VMEM budgets); they live here
so the planner, the overlap-save engine, the conv router and the autotuner
all agree on one regime map — and so the tuner (:mod:`repro.core.tuning`)
has one place to read the *fixed heuristics* it replaces with searched
decisions.

``tests/test_limits.py`` grep-asserts this file is the only assignment site
of each constant.
"""

from __future__ import annotations

__all__ = [
    "DIRECT_MAX",
    "FUSED_MAX",
    "OS_FACTOR",
    "VMEM_BUDGET",
    "VMEM_LIMIT",
    "SUBLANES",
    "LANES",
    "row_group",
    "BLUESTEIN_MIN",
    "next_pow2",
    "next_fast_len",
    "bluestein_pad",
]

#: Largest N executed as a single direct DFT matmul (one (B,N)x(N,N) GEMM).
DIRECT_MAX = 1024

#: Largest N executed by the fused four-step kernel in one HBM round trip.
#: 65536 = 256·256: an 8-row tile of it compiles for v5e within
#: :data:`VMEM_LIMIT` — see :func:`repro.core.plan.vmem_bytes`.
FUSED_MAX = 65536

#: Default overlap-save block multiplier: B = next_pow2(Lh) · OS_FACTOR.
#: 8 keeps the valid fraction per block at (B − Lh + 1)/B ≥ 7/8 — under 15%
#: redundant transform work — while staying inside the fused regime for the
#: 4k-tap filters of the Hyena/SAR workloads (8192 · 8 = 65536 = FUSED_MAX).
#: This is the fixed heuristic ``tune="measure"`` searches past.
OS_FACTOR = 8

#: Scoped-VMEM limit every TPU kernel is compiled with
#: (``pltpu.CompilerParams(vmem_limit_bytes=...)``).  The compiler's default
#: scoped limit is 16 MiB; a v5e core has 128 MiB of VMEM, and the rest is
#: left to Mosaic's internal scratch.
VMEM_LIMIT = 96 * 1024 * 1024

#: Per-grid-step VMEM working-set budget for the modeled working set of
#: :func:`repro.core.plan.vmem_bytes` / ``_pass_chunk_bytes``, which are
#: calibrated against the scoped-VMEM sizes the v5e Mosaic compiler reports
#: for each kernel.  Binds the batch-tile and pass-chunk picks (and the
#: tuner's candidate feasibility check); a third of :data:`VMEM_LIMIT`, so
#: a model error of 3x still compiles, and tiles stay small enough that
#: Mosaic compiles each kernel in seconds (its code grows with the tile).
VMEM_BUDGET = VMEM_LIMIT // 3

#: The TPU's (sublane, lane) vector tile.  A Pallas block's last two dims
#: must be multiples of these or the array's own dims, so batch tiles are
#: at least ``SUBLANES`` rows and column-pass chunks at least ``LANES``
#: columns, and a fused four-step leaf keeps its contiguous factor ``n2``
#: at ``LANES`` or more (its in-kernel ``(bt, n1, n2)`` view splits lanes).
SUBLANES = 8
LANES = 128


def row_group(n1: int, n2: int = LANES) -> int:
    """Signals per step of the four-step's row-group loop
    (:func:`repro.kernels.fft4step.four_step_rows`, and the VMEM model of
    :func:`repro.core.plan.vmem_bytes` calibrated against it): one once
    ``n1`` fills a 128-row MXU operand, else a lane group of 16 signals
    (whole groups of ``n2 // n1`` beyond that).  16 measured 3–20% faster
    than 8 on a v5e at n = 8192 … 2048, and within 1–10% of 32 at half
    its compile time."""
    return 1 if n1 >= LANES else max(2 * SUBLANES, n2 // n1)


#: Smallest non-power-of-two length the Bluestein chirp-conv leaf accepts.
#: n = 1 is the identity transform and n = 2^k routes to the native pow2
#: programs, so the chirp path only ever sees n ≥ 2 composites/primes.
BLUESTEIN_MIN = 2


def next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length()


def next_fast_len(n: int) -> int:
    """Smallest length ≥ ``n`` this engine transforms natively (pow2 —
    every leaf kernel, LUT builder and roofline account is pow2-shaped;
    arbitrary ``n`` itself routes through the Bluestein chirp leaf)."""
    return next_pow2(max(n, 1))


def bluestein_pad(n: int) -> int:
    """The chirp convolution length for a length-``n`` Bluestein transform:
    the circular conv must hold the 2n−1 support of a[j]·b[k−j], padded to
    the next power of two so the inner FFT pair stays on the native pow2
    engines.  This is the *floor* — the tuner may pick a larger pow2 pad."""
    return next_pow2(max(2 * n - 1, 1))
