"""Fused four-step FFT Pallas kernel — one HBM round trip for N ≤ 65536.

The paper's central optimisation (§2.3.2): rather than one kernel per
butterfly level (log₂N global round trips), divide the signal so that *all*
levels execute in on-chip memory, touching the slow tier once.  Fermi shared
memory → TPU VMEM; butterfly warps → MXU matmuls:

    view x as (n1, n2) row-major
    A = W1 · X                   column DFTs      (MXU GEMM 1)
    B = A ⊙ T                    twiddle          (VPU, fused)
    C = B · W2                   row DFTs         (MXU GEMM 2)
    Y = Cᵀ flattened             natural order    (VMEM-internal relayout)

The signal tile, both DFT matrices, the twiddle grid, the intermediate and
the output tile are co-resident in VMEM; the LUT operands are pinned to block
(0, 0) for every grid step so Mosaic hoists their copy out of the batch loop
(texture-memory analogue).  The batch grid dimension is ``parallel``.

The whole VMEM dataflow lives in :func:`four_step_tile` so the pass-program
kernels (``repro.kernels.pencil``) embed the same four-step engine inside
their strided-column and transposed-write passes — the tile function is the
unit of fusion.  :func:`four_step_rows` sweeps it over a tile held in refs
a row group at a time, which keeps what Mosaic unrolls (and its compile
time) at one group, however large the batch tile.  On top of the selectable output layout (``natural_order``),
``fft4step_call`` accepts a post-GEMM per-bin twiddle (``twiddle_after``)
applied in the epilogue before the write, so a multiplicative phase stage
(modulation, delay, inter-level twiddle of a follow-on factor) costs zero
extra HBM passes.

Both GEMMs are plain 2-D contractions with 128-aligned operand shapes for
n1, n2 ≥ 128 (N ≥ 16384); below that the split keeps n2 = 128 lanes and
n1 ≥ 8 sublanes.  The GEMMs run at ``Precision.HIGHEST`` (float32 on the
MXU) — the FFT's 1e-3 accuracy contract needs more than one bf16 pass.
Inverse transforms use conjugated LUTs with 1/N folded into W2 — the
scaled table *is* the LUT, no extra pass (paper §2.3.1 spirit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fft_xla import cmul
from repro.core.limits import VMEM_LIMIT, row_group

__all__ = ["fft4step_call", "four_step_rows", "four_step_tile", "cgemm_tile"]


def cgemm_tile(ar, ai, br, bi):
    """Karatsuba complex GEMM on split planes: 3 real MXU GEMMs."""
    dot = functools.partial(
        jnp.dot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    k1 = dot(ar + ai, br)
    k2 = dot(ar, bi - br)
    k3 = dot(ai, br + bi)
    return k1 - k3, k1 + k2


def four_step_tile(
    xr, xi, w1r, w1i, tr, ti, w2r, w2i, n1: int, n2: int, natural_order: bool = True
):
    """The four-step dataflow on a VMEM-resident (bt, n1·n2) tile.

    Pure jnp on arrays already in VMEM — callable from any Pallas kernel
    body (through :func:`four_step_rows`) or traced directly for reference.
    Returns (yr, yi) of shape (bt, n1·n2), in natural or pencil (k1-major)
    order.  Every relayout splits or merges whole 128-lane rows (``n2`` is
    at least ``LANES``, see :func:`repro.core.plan.four_step_split`), the
    only lane reshapes Mosaic lowers.
    """
    bt = xr.shape[0]
    n = n1 * n2
    if bt == 1:
        # One signal: the (n1, n2) view is a plain 2-D matrix.
        ar, ai = cgemm_tile(w1r, w1i, xr.reshape(n1, n2), xi.reshape(n1, n2))
        br = ar * tr - ai * ti
        bi = ar * ti + ai * tr
        cr, ci = cgemm_tile(br, bi, w2r, w2i)
        if natural_order:
            return cr.T.reshape(1, n), ci.T.reshape(1, n)
        return cr.reshape(1, n), ci.reshape(1, n)
    # (bt, n) → (n1, bt·n2): put the contracted factor on rows.
    xr = xr.reshape(bt, n1, n2).transpose(1, 0, 2).reshape(n1, bt * n2)
    xi = xi.reshape(bt, n1, n2).transpose(1, 0, 2).reshape(n1, bt * n2)
    # GEMM-1: column DFTs.  A = W1 @ X  ((n1,n1) @ (n1, bt·n2)).
    ar, ai = cgemm_tile(w1r, w1i, xr, xi)
    # Twiddle: A viewed (n1, bt, n2) ⊙ T[n1, 1, n2].
    ar = ar.reshape(n1, bt, n2)
    ai = ai.reshape(n1, bt, n2)
    trb = tr[:, None, :]
    tib = ti[:, None, :]
    br = ar * trb - ai * tib
    bi = ar * tib + ai * trb
    # GEMM-2: row DFTs.  C = B @ W2  ((n1·bt, n2) @ (n2, n2)).
    cr, ci = cgemm_tile(
        br.reshape(n1 * bt, n2), bi.reshape(n1 * bt, n2), w2r, w2i
    )
    cr = cr.reshape(n1, bt, n2)
    ci = ci.reshape(n1, bt, n2)
    if natural_order:
        # Y[b, k2·n1 + k1] = C[k1, b, k2] — VMEM-internal relayout.
        return cr.transpose(1, 2, 0).reshape(bt, n), ci.transpose(1, 2, 0).reshape(bt, n)
    # Pencil (k1-major) layout: caller composes/undoes ordering.
    return cr.transpose(1, 0, 2).reshape(bt, n), ci.transpose(1, 0, 2).reshape(bt, n)


def four_step_rows(x_r, x_i, luts, n1: int, n2: int, write, natural_order=True):
    """Run :func:`four_step_tile` over every row of the (bt, n) refs
    ``x_r``/``x_i``, a row group per ``fori_loop`` step, and hand each
    group's result to ``write(rows, yr, yi)``.

    The loop keeps the unrolled kernel body at one group's size, whatever
    the tile: a group is one signal once ``n1`` fills a 128-row MXU
    operand (the 2-D path), else ``SUBLANES`` signals batched through the
    3-D relayouts (a one-row (n2, n1) → (1, n) merge below 128 lanes is not
    lowered).  A tile of fewer rows than a group is zero-padded to one; a
    ragged tail is computed as the tile's last full group, of which only
    the tail rows are written — so ``write`` may target the input refs
    themselves (an in-place sweep): rows are independent, and no row is
    read after it was written.  ``luts`` = (w1r, w1i, tr, ti, w2r, w2i),
    loaded values."""
    bt, n = x_r.shape
    g = row_group(n1)
    if bt < g:
        pad = jnp.zeros((g - bt, n), jnp.float32)
        yr, yi = four_step_tile(
            jnp.concatenate([x_r[...], pad]), jnp.concatenate([x_i[...], pad]),
            *luts, n1, n2, natural_order,
        )
        write(slice(None), yr[:bt], yi[:bt])
        return

    def group(start):
        rows = pl.ds(start, g)
        return four_step_tile(
            x_r[rows, :], x_i[rows, :], *luts, n1, n2, natural_order
        )

    def body(s, carry):
        start = pl.multiple_of(s * g, g)
        write(pl.ds(start, g), *group(start))
        return carry

    jax.lax.fori_loop(0, bt // g, body, 0)
    tail = bt % g
    if tail:
        yr, yi = group(bt - g)
        write(pl.ds(bt - tail, tail), yr[g - tail:], yi[g - tail:])


def _make_kernel(n1: int, n2: int, natural_order: bool, has_epilogue: bool):
    def kernel(x_r, x_i, w1_r, w1_i, t_r, t_i, w2_r, w2_i, *rest):
        if has_epilogue:
            e_r, e_i, o_r, o_i = rest
        else:
            o_r, o_i = rest
        luts = [r[...] for r in (w1_r, w1_i, t_r, t_i, w2_r, w2_i)]

        def write(rows, yr, yi):
            if has_epilogue:
                # Post-GEMM per-position twiddle: y[b, j] *= e[j].
                yr, yi = cmul(yr, yi, e_r[...], e_i[...])
            o_r[rows, :] = yr
            o_i[rows, :] = yi

        four_step_rows(x_r, x_i, luts, n1, n2, write, natural_order)

    return kernel


def fft4step_call(
    xr: jax.Array,
    xi: jax.Array,
    w1r: jax.Array,
    w1i: jax.Array,
    twr: jax.Array,
    twi: jax.Array,
    w2r: jax.Array,
    w2i: jax.Array,
    *,
    batch_tile: int,
    natural_order: bool = True,
    twiddle_after: tuple[jax.Array, jax.Array] | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused four-step FFT: x (B, n1·n2) split-complex; B % batch_tile == 0.

    ``twiddle_after`` — optional (real, imag) per-output-position phasors of
    shape (n,): multiplied into the result in the VMEM epilogue (after the
    ``natural_order`` relayout), so phase post-processing rides the same
    HBM round trip.  The pass program's *inter-factor* twiddle goes through
    ``kernels.pencil``'s column kernel instead (it is per-pencil-phase, not
    per-position); this call-level hook is the public surface for per-bin
    phase stages — modulation, delay, fftshift-by-phase-ramp.
    """
    b, n = xr.shape
    n1 = w1r.shape[0]
    n2 = w2r.shape[0]
    assert n == n1 * n2, (n, n1, n2)
    assert b % batch_tile == 0, (b, batch_tile)
    grid = (b // batch_tile,)
    sig = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    lut1 = pl.BlockSpec((n1, n1), lambda i: (0, 0))
    lutt = pl.BlockSpec((n1, n2), lambda i: (0, 0))
    lut2 = pl.BlockSpec((n2, n2), lambda i: (0, 0))
    in_specs = [sig, sig, lut1, lut1, lutt, lutt, lut2, lut2]
    operands = [xr, xi, w1r, w1i, twr, twi, w2r, w2i]
    if twiddle_after is not None:
        er, ei = twiddle_after
        er = jnp.asarray(er, jnp.float32).reshape(1, n)
        ei = jnp.asarray(ei, jnp.float32).reshape(1, n)
        lute = pl.BlockSpec((1, n), lambda i: (0, 0))
        in_specs += [lute, lute]
        operands += [er, ei]
    out_shape = [
        jax.ShapeDtypeStruct((b, n), jnp.float32),
        jax.ShapeDtypeStruct((b, n), jnp.float32),
    ]
    fn = pl.pallas_call(
        _make_kernel(n1, n2, natural_order, twiddle_after is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[sig, sig],
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
    )
    return tuple(fn(*operands))
