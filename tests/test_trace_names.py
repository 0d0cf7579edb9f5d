"""Names inside the pass program: kernel families and named scopes.

Every ``pallas_call`` is named from ``plan.KERNEL_NAMES`` (the compiled HLO
names each kernel instruction after it), and the work is scoped by
transform kind, complex boundary (``to_planes`` / ``from_planes``), pass
(``p{i}_rows`` / ``p{i}_cols``), Hermitian epilogue (``recomb``) and
overlap-save stage (``os_*``).  A profiler trace carries both.  These
tests read them on the CPU, from the jaxpr and from the lowered text's
debug locations; ``test_tpu_compile.py`` reads them from programs compiled
for a v5e.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.core import fft as F
from repro.core import overlap
from repro.core import plan as P


def _pallas_names(jaxpr, acc):
    """The ``name`` of every ``pallas_call`` in ``jaxpr`` and its sub-jaxprs."""
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            acc.append(e.params["name"])
            continue
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", None)
                if inner is not None:
                    _pallas_names(getattr(inner, "jaxpr", inner), acc)
    return acc


def _complex_call(spec, backend, shape):
    planned = F.plan(spec, backend=backend, tune="off")
    return planned, (jnp.zeros(shape, jnp.complex64),)


def _real_call(spec, backend, shape):
    planned = F.plan(spec, backend=backend, tune="off")
    return planned, (jnp.zeros(shape, jnp.float32),)


def _irfft_call(spec, backend, shape):
    planned = F.plan(spec, backend=backend, tune="off")
    z = jnp.zeros(shape, jnp.float32)
    return planned, ((z, z),)


def _conv_call(backend):
    fn = lambda x, h: overlap.fft_conv_os(x, h, block=512, backend=backend)  # noqa: E731
    return fn, (jnp.zeros((2, 3000), jnp.float32), jnp.zeros((33,), jnp.float32))


# case → (maker of (callable, args), the kernel names its jaxpr holds)
CASES = {
    "fft_direct": (lambda: _complex_call(F.FFTSpec(n=256), "pallas", (4, 256)), {"dft_direct"}),
    "fft_fused4": (lambda: _complex_call(F.FFTSpec(n=4096), "pallas", (4, 4096)), {"fft4step"}),
    "fft_split": (
        lambda: _complex_call(F.FFTSpec(n=2**17), "pallas", (1, 2**17)),
        {"pencil_cols", "pencil_rows_natural"},
    ),
    "fft2": (
        lambda: _complex_call(F.FFTSpec(n=256, kind="fft2", n2=64), "pallas", (2, 64, 256)),
        {"dft_direct", "pencil_cols"},
    ),
    "fft2_strip_cols": (
        lambda: _complex_call(F.FFTSpec(n=128, kind="fft2", n2=2**17), "pallas", (1, 2**17, 128)),
        {"dft_direct", "pencil_cols", "pencil_cols_natural"},
    ),
    "rfft": (
        lambda: _real_call(F.FFTSpec(n=4096, kind="rfft"), "pallas", (4, 4096)),
        {"fft4step", "recomb_fwd"},
    ),
    "irfft": (
        lambda: _irfft_call(F.FFTSpec(n=4096, kind="irfft"), "pallas", (4, 2049)),
        {"fft4step", "recomb_inv"},
    ),
    "bluestein_fused": (
        lambda: _complex_call(F.FFTSpec(n=2029), "pallas", (4, 2029)),
        {"bluestein_fwd", "bluestein_inv"},
    ),
    "bluestein_split": (
        lambda: _complex_call(F.FFTSpec(n=40000), "pallas", (1, 40000)),
        {"bluestein_elem", "pencil_cols", "pencil_rows_natural"},
    ),
    "conv_os": (lambda: _conv_call("pallas"), {"dft_direct", "recomb_fwd", "recomb_inv"}),
    "gpu_direct": (lambda: _complex_call(F.FFTSpec(n=256), "pallas_gpu", (4, 256)), {"dft_direct_gpu"}),
    "gpu_fused4": (lambda: _complex_call(F.FFTSpec(n=4096), "pallas_gpu", (4, 4096)), {"fft4step_gpu"}),
    "gpu_split": (
        lambda: _complex_call(F.FFTSpec(n=2**17), "pallas_gpu", (1, 2**17)),
        {"pencil_rows_natural_gpu"},
    ),
    "gpu_bluestein": (
        lambda: _complex_call(F.FFTSpec(n=2029), "pallas_gpu", (4, 2029)),
        {"bluestein_fwd_gpu", "bluestein_inv_gpu"},
    ),
    "gpu_bluestein_split": (
        lambda: _complex_call(F.FFTSpec(n=40000), "pallas_gpu", (1, 40000)),
        {"bluestein_elem_gpu", "pencil_rows_natural_gpu"},
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_every_pallas_call_is_named_from_the_vocabulary(case):
    build, want = CASES[case]
    fn, args = build()
    names = _pallas_names(jax.make_jaxpr(fn)(*args).jaxpr, [])
    assert names, case
    assert set(names) <= set(P.KERNEL_NAMES), (case, names)
    assert set(names) == want, (case, names)


def test_the_cases_cover_every_kernel_name():
    covered = set().union(*(want for _, want in CASES.values()))
    assert covered == set(P.KERNEL_NAMES)


def test_kernel_name_refuses_names_outside_the_vocabulary():
    assert P.kernel_name("fft4step") == "fft4step"
    assert P.kernel_name("fft4step", gpu=True) == "fft4step_gpu"
    with pytest.raises(ValueError):
        P.kernel_name("pencil_cols", gpu=True)
    with pytest.raises(ValueError):
        P.kernel_name("fused4")


def test_pass_scope_names_rows_cols_and_reorder():
    one_d = P.plan_fft(2**17).passes
    assert [P.pass_scope(i, p) for i, p in enumerate(one_d)] == ["p0_cols", "p1_rows"]
    two_d = P.plan_fft2(256, 64).passes
    assert [P.pass_scope(i, p) for i, p in enumerate(two_d)] == ["p0_rows", "p1_cols"]
    assert P.pass_scope(2, P.Pass(kind="reorder", n=8)) == "p2_reorder"


def _locations(fn, *args) -> str:
    return jax.jit(fn).lower(*args).as_text(debug_info=True)


def test_scopes_name_the_planned_fft2():
    planned, args = _complex_call(F.FFTSpec(n=256, kind="fft2", n2=64), "pallas", (2, 64, 256))
    text = _locations(planned, *args)
    for path in (
        "fft2/to_planes/",
        "fft2/p0_rows/dft_direct/pallas_call",
        "fft2/p1_cols/pencil_cols/pallas_call",
        "fft2/from_planes/",
    ):
        assert path in text, path


def test_scopes_name_the_overlap_save_stages():
    fn, args = _conv_call("pallas")
    text = _locations(fn, *args)
    for path in (
        "/os_frame/",
        "/os_filter/rfft/p0_rows/dft_direct/pallas_call",
        "/os_filter/rfft/recomb/recomb_fwd/pallas_call",
        "/rfft/p0_rows/dft_direct/pallas_call",
        "/os_product/",
        "/irfft/recomb/recomb_inv/pallas_call",
        "/irfft/p0_rows/dft_direct/pallas_call",
        "/os_discard/",
        "/os_tail/",
    ):
        assert path in text, path


def test_apply_planes_is_scoped_by_kind():
    planned = F.plan(F.FFTSpec(n=256, kind="ifft"), backend="pallas", tune="off")
    z = jnp.zeros((4, 256), jnp.float32)
    text = _locations(planned.apply_planes, z, z)
    assert "ifft/p0_rows/dft_direct/pallas_call" in text
