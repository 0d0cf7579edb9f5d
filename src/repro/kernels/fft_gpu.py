"""Pallas-on-Triton GPU variants of the FFT row leaves — the paper's
native hardware, landed leaf-by-leaf.

The source paper's speedup is a *shared-memory* budget argument: tile the
transform so the working set lives in the SM's fast tier and the signal
touches global memory once per pass.  The TPU kernels already encode that
schedule; what changes on CUDA-class devices is only the launch surface:

* BlockSpecs stay (they are the tiling), but the index maps must be
  Triton-friendly — no ``dimension_semantics`` or other Mosaic-only
  compiler params (``plt.CompilerParams`` supplies ``num_warps``/
  ``num_stages`` instead, and interpret mode takes none);
* batch tiles are picked against the per-SM shared-memory budget
  (:func:`repro.core.plan.pick_batch_tile_gpu` /
  :func:`repro.core.limits.memory_budget`) rather than ``VMEM_BUDGET`` —
  the LUT operands software-pipeline through the ``dot`` K loop instead of
  residing whole, so the model charges stripes, not matrices;
* the in-kernel math is *identical*: :func:`~repro.kernels.dft_matmul.dft_tile`
  and :func:`~repro.kernels.fft4step.four_step_tile` are pure-jnp tile
  engines and compile unchanged under either lowering.

Claim surface (:func:`gpu_claims`): row transforms over the contiguous
last axis — whole-signal passes (the ≤ ``FUSED_MAX`` one-call regimes),
contiguous pencil-order row passes, and the natural-order fused-write row
pass.  Strided-column passes, digit-reversal reorders, ``axis=-2`` image
columns and the Hermitian recombination epilogues are **not claimed yet**:
:func:`execute_program_gpu` runs those through a traced-XLA per-pass
fallback (same LUT tables, same scaling convention) so a mixed program
stays correct while the backend grows leaf-by-leaf.

Everything runs under ``REPRO_PALLAS_INTERPRET=1`` (or automatically on a
CPU host) through the Pallas interpreter, so CI proves numerics and jaxpr
purity without a GPU; a real GPU picks up the Triton lowering with zero
code changes.
"""

from __future__ import annotations

import functools
from typing import Callable, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plt

from repro.core import faults
from repro.core import plan as plan_lib
from repro.core.fft_xla import cmul
from repro.kernels import ops, pencil
from repro.kernels.dft_matmul import dft_tile
from repro.kernels.fft4step import four_step_tile

Planes = Tuple[jax.Array, jax.Array]

__all__ = [
    "dft_matmul_gpu_call",
    "fft4step_gpu_call",
    "rows_natural_gpu_call",
    "execute_program_gpu",
    "execute_plan_gpu",
    "gpu_claims",
]


def gpu_claims(p: plan_lib.Pass) -> bool:
    """Does the GPU backend execute this program pass natively?

    Claimed: ``axis=-1`` direct/fused4 row leaves — whole-signal passes
    and contiguous-row passes (``stride == 1``), including the
    natural-order fused transposed write — and every Bluestein stage
    (chirp pre/post multiplies, the B̂ product, and the fused pad-conv
    passes: :mod:`repro.kernels.bluestein` lowers on both backends).
    Unclaimed (→ xla fallback): strided-column passes, reorders,
    ``axis=-2`` column transforms, and epilogue pass kinds (rfft/irfft
    recombination).
    """
    if p.axis != -1 or p.kind not in ("direct", "fused4", "bluestein"):
        return False
    if p.kind == "bluestein":
        return True
    pencils, stride, _f = p.view_in if p.view_in else (1, 1, p.n)
    return pencils == 1 or stride == 1


def _call_kwargs(interpret: bool) -> dict:
    """Triton compiler params for real lowering; nothing under interpret
    (the interpreter has no backend to hand them to)."""
    if interpret:
        return {}
    return {"compiler_params": plt.CompilerParams(num_warps=4, num_stages=2)}


def dft_matmul_gpu_call(
    xr: jax.Array,
    xi: jax.Array,
    wr: jax.Array,
    wi: jax.Array,
    *,
    batch_tile: int,
    interpret: bool = False,
) -> Planes:
    """Triton-shaped direct DFT GEMM: y = x @ W, x (B, N) split-complex.

    Same BlockSpec tiling as :func:`~repro.kernels.dft_matmul.dft_matmul_call`
    — signal blocked over the batch grid, LUT pinned to block (0, 0) — with
    GPU compiler params instead of Mosaic ``dimension_semantics``.
    """
    b, n = xr.shape
    assert b % batch_tile == 0, (b, batch_tile)

    def kernel(x_r, x_i, w_r, w_i, o_r, o_i):
        yr, yi = dft_tile(x_r[...], x_i[...], w_r[...], w_i[...])
        o_r[...] = yr
        o_i[...] = yi

    sig = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    lut = pl.BlockSpec((n, n), lambda i: (0, 0))
    fn = pl.pallas_call(
        kernel,
        name=plan_lib.kernel_name("dft_direct", gpu=True),
        grid=(b // batch_tile,),
        in_specs=[sig, sig, lut, lut],
        out_specs=[sig, sig],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n), jnp.float32),
        ],
        interpret=interpret,
        **_call_kwargs(interpret),
    )
    return tuple(fn(xr, xi, wr, wi))


def fft4step_gpu_call(
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
    interpret: bool = False,
) -> Planes:
    """Triton-shaped fused four-step FFT, x (B, n1·n2) split-complex."""
    b, n = xr.shape
    n2 = twr.shape[1]
    n1 = n // n2
    assert n == n1 * n2, (n, n1, n2)
    assert b % batch_tile == 0, (b, batch_tile)
    luts = [w1r, w1i, twr, twi, w2r, w2i]

    def kernel(x_r, x_i, w1_r, w1_i, t_r, t_i, w2_r, w2_i, o_r, o_i):
        yr, yi = four_step_tile(
            x_r[...], x_i[...],
            w1_r[...], w1_i[...], t_r[...], t_i[...], w2_r[...], w2_i[...],
            n1, n2, natural_order,
        )
        o_r[...] = yr
        o_i[...] = yi

    sig = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    fn = pl.pallas_call(
        kernel,
        name=plan_lib.kernel_name("fft4step", gpu=True),
        grid=(b // batch_tile,),
        in_specs=[sig, sig] + pencil._lut_specs(luts, lambda i: (0, 0)),
        out_specs=[sig, sig],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n), jnp.float32),
        ],
        interpret=interpret,
        **_call_kwargs(interpret),
    )
    return tuple(fn(xr, xi, *luts))


def rows_natural_gpu_call(
    xr: jax.Array,
    xi: jax.Array,
    luts,
    *,
    kind: str,
    n1: int = 0,
    n2: int = 0,
    chunk: int,
    interpret: bool = False,
) -> Planes:
    """Contiguous-row pass with the natural-order transpose fused into its
    strided write, Triton-shaped: x (B, p, f) → y (B, f, p)."""
    b, p, f = xr.shape
    assert p % chunk == 0, (p, chunk)
    in_sig = pl.BlockSpec((1, chunk, f), lambda i, j: (i, j, 0))
    out_sig = pl.BlockSpec((1, f, chunk), lambda i, j: (i, 0, j))
    in_specs = [in_sig, in_sig] + pencil._lut_specs(luts, lambda i, j: (0, 0))
    fn = pl.pallas_call(
        pencil._make_rows_kernel(kind, n1, n2, len(luts), scratch=False),
        name=plan_lib.kernel_name("pencil_rows_natural", gpu=True),
        grid=(b, p // chunk),
        in_specs=in_specs,
        out_specs=[out_sig, out_sig],
        out_shape=[
            jax.ShapeDtypeStruct((b, f, p), jnp.float32),
            jax.ShapeDtypeStruct((b, f, p), jnp.float32),
        ],
        interpret=interpret,
        **_call_kwargs(interpret),
    )
    return tuple(fn(xr, xi, *pencil._as_ops(luts)))


def _tile_for_gpu(p: plan_lib.Pass, batch_tiles: Mapping[int, int] | None) -> int:
    if batch_tiles is not None and p.n in batch_tiles:
        return batch_tiles[p.n]
    return plan_lib.pick_batch_tile_gpu(p)


def _leaf_kernel_gpu(
    xr, xi, p: plan_lib.Pass, inverse, interpret, batch_tiles, natural_order=True
) -> Planes:
    """Single-pallas_call GPU transform of the last axis (2-D input)."""
    if p.n == 1:
        return xr, xi
    bt = _tile_for_gpu(p, batch_tiles)
    xr, xi, b, pad = ops._pad_batch(xr, xi, bt)
    if p.kind == "direct":
        wr, wi = ops._direct_luts(p.n, inverse)
        yr, yi = dft_matmul_gpu_call(
            xr, xi, jnp.asarray(wr), jnp.asarray(wi),
            batch_tile=bt, interpret=interpret,
        )
    else:
        w1r, w1i, tr, ti, w2r, w2i = ops._fused_luts(p.n1, p.n2, inverse, natural_order)
        yr, yi = fft4step_gpu_call(
            xr, xi,
            jnp.asarray(w1r), jnp.asarray(w1i),
            jnp.asarray(tr), jnp.asarray(ti),
            jnp.asarray(w2r), jnp.asarray(w2i),
            batch_tile=bt, natural_order=natural_order, interpret=interpret,
        )
    return (yr, yi) if pad == 0 else (yr[:b], yi[:b])


def _row_transform_xla(xr2, xi2, p: plan_lib.Pass, luts, natural: bool = True):
    """Traced last-axis transform of (R, f) planes — the fallback's engine
    (the same pure-jnp tiles the kernels embed, just not inside a
    pallas_call)."""
    if p.kind == "direct":
        return dft_tile(xr2, xi2, jnp.asarray(luts[0]), jnp.asarray(luts[1]))
    w1r, w1i, tr, ti, w2r, w2i = (jnp.asarray(a) for a in luts)
    return four_step_tile(xr2, xi2, w1r, w1i, tr, ti, w2r, w2i, p.n1, p.n2, natural)


def _bluestein_xla_pass(xr, xi, p: plan_lib.Pass, inverse) -> Planes:
    """One Bluestein program stage, traced through XLA.

    Same interned chirp/B̂ tables as the kernel path; the pad-length
    transform runs through :func:`repro.core.fft_xla.four_step_fft`
    (forward for ``fwd``, true inverse — 1/M folded — for ``inv``).
    """
    from repro.core import fft_xla
    from repro.core import twiddle as tw

    n, m_pad = p.n, p.n1
    if p.stage in ("pre", "fwd"):
        ar, ai = tw.bluestein_chirp(n, inverse)
        xr, xi = cmul(xr, xi, jnp.asarray(ar)[None], jnp.asarray(ai)[None])
        xr = jnp.pad(xr, ((0, 0), (0, m_pad - n)))
        xi = jnp.pad(xi, ((0, 0), (0, m_pad - n)))
        if p.stage == "pre":
            return xr, xi
        xr, xi = fft_xla.four_step_fft(xr, xi)
    if p.stage in ("mul", "fwd"):
        br, bi = tw.bluestein_spectrum(n, m_pad, inverse)
        return cmul(xr, xi, jnp.asarray(br)[None], jnp.asarray(bi)[None])
    if p.stage == "inv":
        xr, xi = fft_xla.four_step_fft(xr, xi, inverse=True)
    elif p.stage != "post":
        raise ValueError(f"unknown bluestein stage {p.stage!r}")
    pr, pi = tw.bluestein_postchirp(n, inverse)
    return cmul(
        xr[:, :n], xi[:, :n], jnp.asarray(pr)[None], jnp.asarray(pi)[None]
    )


def _xla_pass(xr, xi, p: plan_lib.Pass, fs, inverse) -> Planes:
    """One unclaimed program pass over (B, n) planes, traced through XLA.

    Mirrors :func:`repro.kernels.ops._apply_pass` semantics — same host-cached
    LUT tables, same per-pass 1/f inverse folding, same twiddle-after
    convention — but materializes its transposes as plain XLA ops.  This is
    the per-leaf fallback the capability negotiation promises: a plan whose
    program mixes claimed and unclaimed passes still executes end to end.
    """
    b, n = xr.shape
    if p.kind == "reorder":
        perm = (0,) + tuple(range(len(fs), 0, -1))
        xr = xr.reshape(b, *fs).transpose(perm).reshape(b, n)
        xi = xi.reshape(b, *fs).transpose(perm).reshape(b, n)
        return xr, xi
    if p.kind == "bluestein":
        return _bluestein_xla_pass(xr, xi, p, inverse)
    pencils, stride, f = p.view_in if p.view_in else (1, 1, p.n)
    if pencils == 1:
        natural = p.order == "natural"
        luts = ops._transform_luts(p, inverse, natural)
        return _row_transform_xla(xr, xi, p, luts, natural=natural)
    luts = ops._transform_luts(p, inverse)
    if stride == 1:
        rr = xr.reshape(b * pencils, f)
        ri = xi.reshape(b * pencils, f)
        rr, ri = _row_transform_xla(rr, ri, p, luts)
        if p.view_out != p.view_in:
            # Natural-order write: (b, p, f) → (b, f, p), materialized.
            rr = rr.reshape(b, pencils, f).swapaxes(-1, -2)
            ri = ri.reshape(b, pencils, f).swapaxes(-1, -2)
        return rr.reshape(b, n), ri.reshape(b, n)
    # Strided-column pass: transform length f down axis -2 of the
    # (b·groups, f, stride) view, then the inter-factor twiddle.
    groups = pencils // stride
    xr3 = xr.reshape(b * groups, f, stride).swapaxes(-1, -2)
    xi3 = xi.reshape(b * groups, f, stride).swapaxes(-1, -2)
    rr, ri = _row_transform_xla(xr3.reshape(-1, f), xi3.reshape(-1, f), p, luts)
    yr3 = rr.reshape(b * groups, stride, f).swapaxes(-1, -2)
    yi3 = ri.reshape(b * groups, stride, f).swapaxes(-1, -2)
    if p.twiddle_after is not None:
        tr, ti = ops._pass_twiddle_luts(*p.twiddle_after, inverse)
        yr3, yi3 = cmul(yr3, yi3, jnp.asarray(tr)[None], jnp.asarray(ti)[None])
    return yr3.reshape(b, n), yi3.reshape(b, n)


def _gpu_pass(xr, xi, p: plan_lib.Pass, inverse, interpret, batch_tiles) -> Planes:
    """One claimed row-leaf pass through the Triton-shaped kernels."""
    b, n = xr.shape
    if p.kind == "bluestein":
        return ops._bluestein_pass(
            xr, xi, p, inverse, interpret, _tile_for_gpu(p, batch_tiles), gpu=True
        )
    pencils, stride, f = p.view_in if p.view_in else (1, 1, p.n)
    if pencils == 1:
        return _leaf_kernel_gpu(
            xr, xi, p, inverse, interpret, batch_tiles,
            natural_order=p.order == "natural",
        )
    luts = ops._transform_luts(p, inverse)
    if p.view_out != p.view_in:
        chunk = plan_lib.pick_pass_chunk(p, budget=plan_lib.memory_budget())
        xr3 = xr.reshape(b, pencils, f)
        xi3 = xi.reshape(b, pencils, f)
        yr3, yi3 = rows_natural_gpu_call(
            xr3, xi3, luts, kind=p.kind, n1=p.n1, n2=p.n2,
            chunk=chunk, interpret=interpret,
        )
        return yr3.reshape(b, n), yi3.reshape(b, n)
    rr = xr.reshape(b * pencils, f)
    ri = xi.reshape(b * pencils, f)
    rr, ri = _leaf_kernel_gpu(rr, ri, p, inverse, interpret, batch_tiles)
    return rr.reshape(b, n), ri.reshape(b, n)


def execute_program_gpu(
    xr: jax.Array,
    xi: jax.Array,
    passes: Sequence[plan_lib.Pass],
    *,
    inverse: bool = False,
    interpret: bool | None = None,
    batch_tiles: Mapping[int, int] | None = None,
    claims: Callable[[plan_lib.Pass], bool] = gpu_claims,
    degradations: list | None = None,
) -> Planes:
    """Walk a linearized pass program over (B, n) split planes, executing
    claimed passes through the Triton-shaped kernels and the rest through
    the traced-XLA fallback — per-leaf negotiation, one buffer.

    Claimed leaves run under :func:`repro.core.faults.run_leaf`: a leaf
    that fails to trace/compile is retried once, then (pallas_gpu, kind)
    is quarantined and the leaf demotes to the same traced-XLA fallback
    unclaimed passes use, recorded on ``degradations``."""
    if interpret is None:
        interpret = ops.should_interpret()
    fs = [q.n for q in passes if q.kind != "reorder"]
    for i, p in enumerate(passes):
        # Passes may pin their own direction (the Bluestein inner conv).
        eff = p.inverse if p.inverse is not None else inverse
        if claims(p):
            xr, xi = faults.run_leaf(
                "pallas_gpu",
                p.kind,
                lambda xr=xr, xi=xi, p=p, eff=eff: _gpu_pass(
                    xr, xi, p, eff, interpret, batch_tiles
                ),
                lambda xr=xr, xi=xi, p=p, eff=eff: _xla_pass(xr, xi, p, fs, eff),
                degradations=degradations,
                index=i,
            )
        else:
            xr, xi = _xla_pass(xr, xi, p, fs, eff)
    return xr, xi


def execute_plan_gpu(
    xr: jax.Array,
    xi: jax.Array,
    fft_plan: plan_lib.FFTPlan,
    *,
    inverse: bool = False,
    interpret: bool | None = None,
    batch_tiles: Mapping[int, int] | None = None,
    order: str = "natural",
    degradations: list | None = None,
) -> Planes:
    """Execute a 1-D :class:`~repro.core.plan.FFTPlan` over the last axis
    with the GPU claim surface (any leading batch dims)."""
    n = xr.shape[-1]
    if n != fft_plan.n:
        raise faults.PlanError(f"plan is for n={fft_plan.n}, input has n={n}")
    passes = (
        fft_plan.passes
        if order == "natural"
        else plan_lib.compile_passes(fft_plan.n, order=order)
    )
    lead = xr.shape[:-1]
    b = int(np.prod(lead)) if lead else 1
    yr, yi = execute_program_gpu(
        xr.reshape(b, n), xi.reshape(b, n), passes,
        inverse=inverse, interpret=interpret, batch_tiles=batch_tiles,
        degradations=degradations,
    )
    return yr.reshape(*lead, n), yi.reshape(*lead, n)
