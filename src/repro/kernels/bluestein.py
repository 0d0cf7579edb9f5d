"""Bluestein chirp-conv Pallas kernels — arbitrary-length FFT leaves.

Bluestein's identity jk = (j² + k² − (k−j)²)/2 turns a length-``n`` DFT of
ANY ``n`` into one circular convolution at a pow2 pad ``M ≥ 2n−1`` between
the chirp-modulated signal and the conjugate chirp — a transform this
engine already knows how to run in one HBM round trip.  These kernels keep
the §2.3.2 call-count discipline for the new leaf kind: in the fused
regime (``M ≤ FUSED_MAX``) the whole pipeline is exactly TWO
``pallas_call``s —

* ``bluestein_fwd_call`` — chirp pre-multiply, the zero-pad to ``M``
  (VMEM-internal ``concatenate``, never an HBM pad pass), the forward
  pad-length transform through the same :func:`~repro.kernels.dft_matmul.
  dft_tile` / :func:`~repro.kernels.fft4step.four_step_rows` engines every
  other leaf uses, and the ⊙B̂ chirp-spectrum multiply — one kernel;
* ``bluestein_inv_call`` — the inverse pad-length transform (1/M folded in
  its LUTs), the slice back to ``n`` (VMEM-internal) and the chirp
  post-multiply (1/n folded for outer-inverse transforms) — the second.

Past the fused regime the pad length's own split program runs the conv and
``bluestein_elem_call`` supplies the elementwise chirp stages (``pre`` /
``mul`` / ``post``) as single-call passes bracketing it.

The chirp planes and the B̂ spectrum are host-cached float64 tables
(:mod:`repro.core.twiddle`), pinned to block (0, 0) like every other LUT —
computed once per interned plan, served at VMEM bandwidth.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fft_xla import cmul
from repro.core.limits import VMEM_LIMIT
from repro.core.plan import kernel_name
from repro.kernels.dft_matmul import dft_tile
from repro.kernels.fft4step import four_step_rows

Planes = tuple[jax.Array, jax.Array]

__all__ = [
    "bluestein_fwd_call",
    "bluestein_inv_call",
    "bluestein_elem_call",
]


def _params() -> pltpu.CompilerParams:
    """Mosaic batch-parallel semantics and the VMEM ceiling."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
    )


def _inner_specs(inner_luts) -> list:
    """BlockSpecs of the pad-length transform's LUT operands."""
    return [pl.BlockSpec(np.shape(a), lambda i: (0, 0)) for a in inner_luts]


def _inner_transform(x_r, x_i, inner, inner_kind: str, in1: int, in2: int, write):
    """The pad-length transform of the (bt, M) refs ``x_r``/``x_i`` — the
    same engines every pow2 leaf runs, called from inside the chirp kernel
    — handing each row group's result to ``write(rows, yr, yi)``."""
    if inner_kind == "direct":
        yr, yi = dft_tile(x_r[...], x_i[...], inner[0][...], inner[1][...])
        write(slice(None), yr, yi)
        return
    four_step_rows(x_r, x_i, [w[...] for w in inner], in1, in2, write)


def bluestein_fwd_call(
    xr: jax.Array,
    xi: jax.Array,
    luts,
    *,
    n: int,
    m_pad: int,
    inner_kind: str,
    in1: int = 0,
    in2: int = 0,
    batch_tile: int,
    interpret: bool = False,
) -> Planes:
    """Fused Bluestein forward half: x (B, n) → FFT_M(chirp·x ‖ 0) ⊙ B̂ (B, M).

    ``luts`` = (chirp_r, chirp_i, *inner_fwd_luts, spec_r, spec_i): the
    (1, n) pre-chirp planes, the forward pad-length transform's LUTs
    (direct W or fused W1/T/W2), and the (1, M) B̂ spectrum planes.
    """
    b, _n = xr.shape
    assert _n == n and b % batch_tile == 0, (xr.shape, n, batch_tile)

    def kernel(x_r, x_i, a_r, a_i, *rest):
        inner = rest[: -4]
        b_r, b_i, o_r, o_i = rest[-4:]
        yr, yi = cmul(x_r[...], x_i[...], a_r[...], a_i[...])
        zeros = jnp.zeros((yr.shape[0], m_pad - n), jnp.float32)
        # The padded chirp-signal is staged in the output block, which the
        # pad-length transform then overwrites in place.
        o_r[...] = jnp.concatenate([yr, zeros], axis=-1)
        o_i[...] = jnp.concatenate([yi, zeros], axis=-1)

        def write(rows, fr, fi):
            fr, fi = cmul(fr, fi, b_r[...], b_i[...])
            o_r[rows, :] = fr
            o_i[rows, :] = fi

        _inner_transform(o_r, o_i, inner, inner_kind, in1, in2, write)

    sig_in = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    sig_out = pl.BlockSpec((batch_tile, m_pad), lambda i: (i, 0))
    chirp = pl.BlockSpec((1, n), lambda i: (0, 0))
    spec = pl.BlockSpec((1, m_pad), lambda i: (0, 0))
    in_specs = [sig_in, sig_in, chirp, chirp]
    in_specs += _inner_specs(luts[2:-2])
    in_specs += [spec, spec]
    fn = pl.pallas_call(
        kernel,
        name=kernel_name("bluestein_fwd"),
        grid=(b // batch_tile,),
        in_specs=in_specs,
        out_specs=[sig_out, sig_out],
        out_shape=[
            jax.ShapeDtypeStruct((b, m_pad), jnp.float32),
            jax.ShapeDtypeStruct((b, m_pad), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )
    return tuple(fn(xr, xi, *(jnp.asarray(a) for a in luts)))


def bluestein_inv_call(
    xr: jax.Array,
    xi: jax.Array,
    luts,
    *,
    n: int,
    m_pad: int,
    inner_kind: str,
    in1: int = 0,
    in2: int = 0,
    batch_tile: int,
    interpret: bool = False,
) -> Planes:
    """Fused Bluestein inverse half: x (B, M) → chirp·IFFT_M(x)[:n] (B, n).

    ``luts`` = (*inner_inv_luts, post_r, post_i): the inverse pad-length
    transform's LUTs (1/M folded in) and the (1, n) post-chirp planes (1/n
    folded when the outer transform is an inverse DFT).
    """
    b, _m = xr.shape
    assert _m == m_pad and b % batch_tile == 0, (xr.shape, m_pad, batch_tile)

    def kernel(x_r, x_i, *rest):
        inner = rest[: -4]
        p_r, p_i, o_r, o_i = rest[-4:]

        def write(rows, gr, gi):
            gr, gi = cmul(gr[:, :n], gi[:, :n], p_r[...], p_i[...])
            o_r[rows, :] = gr
            o_i[rows, :] = gi

        _inner_transform(x_r, x_i, inner, inner_kind, in1, in2, write)

    sig_in = pl.BlockSpec((batch_tile, m_pad), lambda i: (i, 0))
    sig_out = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    chirp = pl.BlockSpec((1, n), lambda i: (0, 0))
    in_specs = [sig_in, sig_in]
    in_specs += _inner_specs(luts[:-2])
    in_specs += [chirp, chirp]
    fn = pl.pallas_call(
        kernel,
        name=kernel_name("bluestein_inv"),
        grid=(b // batch_tile,),
        in_specs=in_specs,
        out_specs=[sig_out, sig_out],
        out_shape=[
            jax.ShapeDtypeStruct((b, n), jnp.float32),
            jax.ShapeDtypeStruct((b, n), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )
    return tuple(fn(xr, xi, *(jnp.asarray(a) for a in luts)))


def bluestein_elem_call(
    xr: jax.Array,
    xi: jax.Array,
    planes,
    *,
    stage: str,
    n: int,
    m_pad: int,
    batch_tile: int,
    interpret: bool = False,
) -> Planes:
    """One elementwise chirp stage of the split-regime Bluestein program.

    ``pre``  — (B, n) → chirp·x zero-padded to (B, M);
    ``mul``  — (B, M) → x ⊙ B̂ in place;
    ``post`` — (B, M) → chirp·x[:, :n] (B, n).
    ``planes`` is the stage's (1, width) LUT pair.  One ``pallas_call``
    each — the split-regime conv pays 3 chirp trips on top of the pad
    program's own, all still kernels (no traced glue).
    """
    b = xr.shape[0]
    assert b % batch_tile == 0, (b, batch_tile)
    w_in = n if stage == "pre" else m_pad
    w_out = m_pad if stage in ("pre", "mul") else n
    w_lut = n if stage in ("pre", "post") else m_pad
    assert xr.shape[1] == w_in, (xr.shape, stage, w_in)

    def kernel(x_r, x_i, a_r, a_i, o_r, o_i):
        yr, yi = x_r[...], x_i[...]
        if stage == "post":
            yr, yi = yr[:, :n], yi[:, :n]
        yr, yi = cmul(yr, yi, a_r[...], a_i[...])
        if stage == "pre":
            zeros = jnp.zeros((yr.shape[0], m_pad - n), jnp.float32)
            yr = jnp.concatenate([yr, zeros], axis=-1)
            yi = jnp.concatenate([yi, zeros], axis=-1)
        o_r[...] = yr
        o_i[...] = yi

    sig_in = pl.BlockSpec((batch_tile, w_in), lambda i: (i, 0))
    sig_out = pl.BlockSpec((batch_tile, w_out), lambda i: (i, 0))
    lut = pl.BlockSpec((1, w_lut), lambda i: (0, 0))
    fn = pl.pallas_call(
        kernel,
        name=kernel_name("bluestein_elem"),
        grid=(b // batch_tile,),
        in_specs=[sig_in, sig_in, lut, lut],
        out_specs=[sig_out, sig_out],
        out_shape=[
            jax.ShapeDtypeStruct((b, w_out), jnp.float32),
            jax.ShapeDtypeStruct((b, w_out), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=_params(),
    )
    return tuple(fn(xr, xi, *(jnp.asarray(a) for a in planes)))
