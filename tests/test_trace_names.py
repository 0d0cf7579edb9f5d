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

import json
import os
import pathlib
import subprocess
import sys

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


def _conv_call(backend, length=3000):
    """7 frames a signal (paired complex frames) at the default length; one
    frame (the rfft/irfft pair) at ``length`` ≤ 480."""
    fn = lambda x, h: overlap.fft_conv_os(x, h, block=512, backend=backend)  # noqa: E731
    return fn, (jnp.zeros((2, length), jnp.float32), jnp.zeros((33,), jnp.float32))


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
    # paired frames; the one recomb_fwd is the filter's own spectrum
    "conv_os": (lambda: _conv_call("pallas"), {"dft_direct", "recomb_fwd"}),
    "conv_os_one_frame": (lambda: _conv_call("pallas", 400), {"dft_direct", "recomb_fwd", "recomb_inv"}),
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


OS_PATHS = {
    # the paired complex frames: no rfft packing of frames; the filter's
    # half spectrum is the rfft's, recombined once
    3000: (
        "/os_pair/",
        "/os_filter/rfft/p0_rows/dft_direct/pallas_call",
        "/os_filter/rfft/recomb/recomb_fwd/pallas_call",
        "/fft/p0_rows/dft_direct/pallas_call",
        "/os_product/",
        "/ifft/p0_rows/dft_direct/pallas_call",
        "/os_unpair/",
    ),
    # one frame a signal: the rfft/irfft pair
    400: (
        "/os_frame/",
        "/os_filter/rfft/p0_rows/dft_direct/pallas_call",
        "/os_filter/rfft/recomb/recomb_fwd/pallas_call",
        "/rfft/p0_rows/dft_direct/pallas_call",
        "/os_product/",
        "/irfft/recomb/recomb_inv/pallas_call",
        "/irfft/p0_rows/dft_direct/pallas_call",
        "/os_discard/",
        "/os_tail/",
    ),
}


@pytest.mark.parametrize("length", sorted(OS_PATHS))
def test_scopes_name_the_overlap_save_stages(length):
    fn, args = _conv_call("pallas", length)
    text = _locations(fn, *args)
    for path in OS_PATHS[length]:
        assert path in text, path
    paired = length > 480
    gone = {"/os_frame/", "/os_discard/", "/os_tail/", "/irfft/", "recomb_inv"} if paired else {"/os_pair/", "/os_unpair/"}
    assert not any(g in text for g in gone), length
    if paired:  # the only recombination is the filter's
        assert all("/os_filter/" in line for line in text.splitlines() if "/recomb/" in line)


_OS_BARE_BODY = r"""
import contextlib, importlib, json
import jax, jax.numpy as jnp
from repro.core import overlap as O


class _Bare(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def program(mod):
    fn = lambda x, h: mod.fft_conv_os(x, h, block=512, backend="pallas")
    args = (jnp.zeros((2, 3000), jnp.float32), jnp.zeros((33,), jnp.float32))
    lowered = jax.jit(fn).lower(*args)
    return {"jaxpr": str(jax.make_jaxpr(fn)(*args)), "text": lowered.as_text(),
            "located": lowered.as_text(debug_info=True)}


scoped = program(O)
jax.named_scope = lambda name: _Bare()  # the same code with no scopes
print(json.dumps({"scoped": scoped, "bare": program(importlib.reload(O))}))
"""


def test_pair_scopes_change_no_equation():
    """``os_pair`` and ``os_unpair`` are metadata: with every scope taken
    away the paired program has the same equations and the same text."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", _OS_BARE_BODY], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.splitlines()[-1])
    scoped, bare = got["scoped"], got["bare"]
    assert "/os_pair/" in scoped["located"] and "/os_unpair/" in scoped["located"]
    assert "os_pair" not in bare["located"] and "os_unpair" not in bare["located"]
    assert scoped["jaxpr"] == bare["jaxpr"]
    assert scoped["text"] == bare["text"]


def test_apply_planes_is_scoped_by_kind():
    planned = F.plan(F.FFTSpec(n=256, kind="ifft"), backend="pallas", tune="off")
    z = jnp.zeros((4, 256), jnp.float32)
    text = _locations(planned.apply_planes, z, z)
    assert "ifft/p0_rows/dft_direct/pallas_call" in text


# -- the pencil path: pencil/<step> scopes, metadata only -------------------

_PENCIL_BODY = r"""
import contextlib, importlib, json
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import distributed as D
from repro.core import fft as F

mesh = jax.make_mesh((4,), ('x',))
n = 4096
sig = jnp.zeros((2, n), jnp.float32)
img = jnp.zeros((2, 128, 256), jnp.float32)
row_spec = P(None, 'x', None)


def programs(mod):
    cases = {
        'pfft': (lambda a, b: mod.pfft_sharded(a, b, mesh, 'x', chunks=4), sig),
        'pifft': (lambda a, b: mod.pifft_sharded(a, b, mesh, 'x', chunks=4), sig),
        'pfft2d': (jax.shard_map(
            lambda a, b: mod.pfft2d(a, b, n1=128, n2=256, axis_name='x', num_shards=4),
            mesh=mesh, in_specs=(row_spec, row_spec), out_specs=(row_spec, row_spec),
            check_vma=False), img),
    }
    out = {}
    with F.use_backend('pallas'):
        for name, (fn, x) in cases.items():
            jaxpr = str(jax.make_jaxpr(fn)(x, x))
            lowered = jax.jit(fn).lower(x, x)
            out[name] = {
                'jaxpr': jaxpr,
                'a2a': jaxpr.count('all_to_all'),
                'text': lowered.as_text(),
                'located': lowered.as_text(debug_info=True),
            }
    return out


class _Bare(contextlib.ContextDecorator):
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


scoped = programs(D)
real = jax.named_scope
jax.named_scope = lambda name: _Bare()  # the same code with no scopes
bare = programs(importlib.reload(D))
jax.named_scope = real
print(json.dumps({'scoped': scoped, 'bare': bare}))
"""

PENCIL_PATHS = {
    "pfft": (
        "pencil/pack/", "pencil/a2a0/c0/all_to_all", "pencil/a2a0/c3/all_to_all",
        "pencil/unpack/", "pencil/n1_cols/fft/p0_cols/", "pencil/twiddle/",
        "pencil/a2a1/c0/all_to_all", "pencil/a2a1/c3/all_to_all", "pencil/a2a1/merge/",
        "pencil/n2_rows/fft/p0_rows/", "pencil/a2a2/all_to_all", "pencil/reorder/",
    ),
    "pifft": (
        "pencil/pack/", "pencil/a2a0/all_to_all", "pencil/reorder/",
        "pencil/n2_rows/ifft/p0_rows/", "pencil/a2a1/c0/all_to_all", "pencil/twiddle/",
        "pencil/n1_cols/ifft/p0_cols/", "pencil/a2a2/c3/all_to_all", "pencil/a2a2/merge/",
        "pencil/unpack/",
    ),
    "pfft2d": (
        "pencil/n2_rows/p0_rows/", "pencil/pack/", "pencil/a2a0/all_to_all",
        "pencil/unpack/", "pencil/n1_cols/p0_cols/", "pencil/a2a1/all_to_all",
    ),
}
#: Collectives of each program: 2K + 1 natural-order transposes at K = 4;
#: the 2-D transform's two.
PENCIL_A2A = {"pfft": 9, "pifft": 9, "pfft2d": 2}


@pytest.fixture(scope="module")
def pencil_programs():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run(
        [sys.executable, "-c", _PENCIL_BODY], env=env, capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.splitlines()[-1])


@pytest.mark.parametrize("case", sorted(PENCIL_PATHS))
def test_scopes_name_the_pencil_steps_and_change_no_equation(case, pencil_programs):
    scoped, bare = pencil_programs["scoped"][case], pencil_programs["bare"][case]
    for path in PENCIL_PATHS[case]:
        assert path in scoped["located"], (case, path)
    assert "pencil/" not in bare["located"], case
    # metadata only: the same equations and the same program, scopes aside
    assert scoped["jaxpr"] == bare["jaxpr"], case
    assert scoped["text"] == bare["text"], case
    assert scoped["a2a"] == bare["a2a"] == PENCIL_A2A[case], case
