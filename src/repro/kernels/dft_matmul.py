"""Direct DFT-by-matmul Pallas kernel — the N ≤ 1024 one-call regime.

Paper §2.3.2: "When the data quantity is less than 1024, we don't need to
divide" — the whole transform runs in shared memory from one kernel launch.
TPU translation: the whole batch tile, the DFT matrix and the result are
co-resident in VMEM, and the transform is a single (bt, N) × (N, N) MXU
matmul per plane combination:

    Y = X @ W,   W[n, k] = exp(∓2πi·n·k/N)

The DFT matrix enters through a BlockSpec whose index map pins every grid
step to the same block — Mosaic keeps it in VMEM across the whole batch grid,
which is exactly the texture-LUT behaviour of §2.3.1 (computed once, served
from the fast tier).  Complex arithmetic uses the 3-GEMM Karatsuba split.
Inverse scaling (1/N) is folded into the W operand by the wrapper: zero extra
arithmetic, the LUT *is* the scaled table.

:func:`dft_tile` is the reusable VMEM tile transform the pass-program
kernels (``repro.kernels.pencil``) embed for their strided-column and
transposed-write passes, and ``dft_matmul_call`` grows a post-GEMM per-bin
twiddle epilogue (``twiddle``) so a multiplicative phase stage rides the
same HBM round trip.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fft_xla import cmul
from repro.core.limits import VMEM_LIMIT

__all__ = ["dft_matmul_call", "dft_tile"]


def dft_tile(xr, xi, wr, wi):
    """Y = X @ W on a VMEM-resident (bt, n) tile — Karatsuba, 3 real GEMMs.

    Pure jnp on arrays already in VMEM; callable from any Pallas kernel body.
    """
    dot = functools.partial(
        jnp.dot, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    k1 = dot(xr + xi, wr)
    k2 = dot(xr, wi - wr)
    k3 = dot(xi, wr + wi)
    return k1 - k3, k1 + k2


def _make_kernel(has_epilogue: bool):
    def kernel(x_r, x_i, w_r, w_i, *rest):
        if has_epilogue:
            e_r, e_i, o_r, o_i = rest
        else:
            o_r, o_i = rest
        yr, yi = dft_tile(x_r[...], x_i[...], w_r[...], w_i[...])
        if has_epilogue:
            # Post-GEMM per-bin twiddle: y[b, k] *= e[k] (split complex).
            yr, yi = cmul(yr, yi, e_r[...], e_i[...])
        o_r[...] = yr
        o_i[...] = yi

    return kernel


def dft_matmul_call(
    xr: jax.Array,
    xi: jax.Array,
    wr: jax.Array,
    wi: jax.Array,
    *,
    batch_tile: int,
    twiddle: tuple[jax.Array, jax.Array] | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """y = x @ W for split-complex x:(B, N), W:(N, N); B % batch_tile == 0.

    ``twiddle`` — optional (real, imag) per-bin phasors of shape (N,),
    multiplied into the result in the VMEM epilogue.
    """
    b, n = xr.shape
    assert b % batch_tile == 0, (b, batch_tile)
    grid = (b // batch_tile,)
    sig_spec = pl.BlockSpec((batch_tile, n), lambda i: (i, 0))
    lut_spec = pl.BlockSpec((n, n), lambda i: (0, 0))  # VMEM-resident LUT
    in_specs = [sig_spec, sig_spec, lut_spec, lut_spec]
    operands = [xr, xi, wr, wi]
    if twiddle is not None:
        er, ei = twiddle
        er = jnp.asarray(er, jnp.float32).reshape(1, n)
        ei = jnp.asarray(ei, jnp.float32).reshape(1, n)
        tw_spec = pl.BlockSpec((1, n), lambda i: (0, 0))
        in_specs += [tw_spec, tw_spec]
        operands += [er, ei]
    out_shape = [
        jax.ShapeDtypeStruct((b, n), jnp.float32),
        jax.ShapeDtypeStruct((b, n), jnp.float32),
    ]
    fn = pl.pallas_call(
        _make_kernel(twiddle is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[sig_spec, sig_spec],
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=VMEM_LIMIT
        ),
    )
    return tuple(fn(*operands))
