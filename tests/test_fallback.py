"""The pallas path's traced-XLA fallback (``ops._xla_pass`` /
``ops._cols_image_xla``): the pass a quarantined leaf demotes to.

Each branch of the fallback must compute what the kernel computes for the
same pass, and a program whose leaves are partly demoted must still be one
correct transform over one buffer.  The kernels run in Pallas interpret
mode on the CPU.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import faults
from repro.core import fft as F
from repro.core import plan as P
from repro.kernels import ops

# Small ``fused_max`` values reach the split-regime branches at small n, as
# the tuner's plan configs do.
_SPLIT = P.compile_passes(4096, 64, "natural")
_SPLIT_PENCIL = P.compile_passes(4096, 64, "pencil")
_BLUESTEIN_FUSED = P.compile_bluestein(97)
_BLUESTEIN_SPLIT = {p.stage: p for p in P.compile_bluestein(97, None, 128) if p.stage}
_FUSED = P.plan_fft(2048).passes[0]

# case → (passes, input shape): row passes take (B, n), column passes a
# (B, rows, width) image.
ROW_CASES = {
    "direct": ([P.plan_fft(256).passes[0]], (8, 256)),
    "fused_natural": ([_FUSED], (16, 2048)),
    "fused_pencil": ([dataclasses.replace(_FUSED, order="pencil")], (8, 2048)),
    "rows_pencil": ([_SPLIT_PENCIL[1]], (2, 4096)),
    "rows_natural": ([_SPLIT[1]], (2, 4096)),
    "cols_twiddle": ([_SPLIT[0]], (2, 4096)),
    "bluestein_fwd": ([_BLUESTEIN_FUSED[0]], (8, 97)),
    "bluestein_inv": ([_BLUESTEIN_FUSED[1]], (8, 256)),
    "bluestein_pre": ([_BLUESTEIN_SPLIT["pre"]], (8, 97)),
    "bluestein_mul": ([_BLUESTEIN_SPLIT["mul"]], (8, 256)),
    "bluestein_post": ([_BLUESTEIN_SPLIT["post"]], (8, 256)),
}
COL_CASES = {
    "image_cols_whole": (
        [p for p in P.plan_fft2(128, 64).passes if p.axis == -2],
        (2, 64, 128),
    ),
    "image_cols_strip": (
        [p for p in P.plan_fft2(128, 4096, 64).passes if p.axis == -2],
        (1, 4096, 128),
    ),
}


def _planes(shape, seed):
    rng = np.random.default_rng(seed)
    return (
        jnp.asarray(rng.standard_normal(shape), jnp.float32),
        jnp.asarray(rng.standard_normal(shape), jnp.float32),
    )


@pytest.mark.parametrize("case", sorted(ROW_CASES) + sorted(COL_CASES))
def test_fallback_pass_equals_kernel_pass(case):
    cols = case in COL_CASES
    passes, shape = (COL_CASES if cols else ROW_CASES)[case]
    assert passes, case
    for inverse in (False, True):
        for i, p in enumerate(passes):
            xr, xi = _planes(shape, seed=i)
            if cols:
                want = ops._cols_image_kernel(xr, xi, p, inverse, True)
                got = ops._cols_image_xla(xr, xi, p, inverse)
            else:
                want = ops._pass_kernel(xr, xi, p, inverse, True, None, None)
                got = ops._xla_pass(xr, xi, p, inverse)
            want = np.asarray(want[0]) + 1j * np.asarray(want[1])
            got = np.asarray(got[0]) + 1j * np.asarray(got[1])
            assert got.shape == want.shape, (case, i, got.shape, want.shape)
            np.testing.assert_allclose(
                got, want, atol=1e-5 * np.abs(want).max(), err_msg=f"{case} pass {i}"
            )


@pytest.fixture
def quarantine():
    """``faults.quarantine``, with the process-global quarantine, the
    degradation ledger and the plan cache cleared after the case."""
    F._plan_cached.cache_clear()
    yield faults.quarantine
    faults.clear_quarantine()
    faults.clear_degradations()
    F._plan_cached.cache_clear()


# case → (spec, input shape, the pass kind quarantined).  The inner pad
# program of n = 40000 (pad 2^17 = 512 · 256) runs direct leaves.
MIXED = {
    "bluestein_40000_bluestein": (F.FFTSpec(n=40000), (1, 40000), "bluestein"),
    "bluestein_40000_direct": (F.FFTSpec(n=40000), (1, 40000), "direct"),
    "fft2_64x4096_direct": (F.FFTSpec(n=4096, kind="fft2", n2=64), (1, 64, 4096), "direct"),
    "fft2_64x4096_fused4": (F.FFTSpec(n=4096, kind="fft2", n2=64), (1, 64, 4096), "fused4"),
}


def _pallas_calls(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            n += 1
            continue
        for v in e.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                n += _pallas_calls(getattr(inner, "jaxpr", inner))
    return n


@pytest.mark.parametrize("case", sorted(MIXED))
def test_mixed_program_runs_kernels_and_fallback_in_one_buffer(case, quarantine):
    spec, shape, kind = MIXED[case]
    quarantine("pallas", kind, "test")
    planned = F.plan(spec, backend="pallas", tune="off")
    demoted = [i for i, p in enumerate(planned.passes) if p.kind == kind]
    assert demoted and len(demoted) < len(planned.passes), case
    xr, xi = _planes(shape, seed=0)
    jaxpr = jax.make_jaxpr(planned.apply_planes)(xr, xi).jaxpr
    assert _pallas_calls(jaxpr) == len(planned.passes) - len(demoted), case
    assert sorted(d["pass"] for d in planned.degradations) == demoted
    yr, yi = planned.apply_planes(xr, xi)
    x = np.asarray(xr) + 1j * np.asarray(xi)
    ref = np.fft.fft2(x) if spec.n2 is not None else np.fft.fft(x)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    assert np.abs(got - ref).max() <= 1e-3 * np.abs(ref).max(), case
