"""Compile rehearsal: every main-path kernel compiles for a described v5e.

Interpret mode (what the rest of the suite runs on the CPU) never checks
block tiling or VMEM; the TPU compiler does, and it is installed here.  Each
case compiles one kernel family at a real width, with ``interpret=False``,
for a v5e that is described rather than attached, and asserts that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs, so
these cases say nothing about results or time.  The benchmark cells'
programs, compiled the same way at test size, must name every kernel
instruction by its family and put it under its pass's scope, which is what
a profiler trace of the chip shows.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and the suite runs in several).
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fft as F
from repro.core import overlap
from repro.core import plan as P
from repro.core import twiddle as tw
from repro.kernels import ops, pencil


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _leaf(n, batch):
    p = P._leaf_pass(n)
    return (lambda a, b: ops._leaf_kernel(a, b, p, False, False, None)), (batch, n)


def _row_pass(n, index, batch):
    plan = P.plan_fft(n)
    p = plan.passes[index]
    return (lambda a, b: ops._pass_kernel(a, b, p, False, False, None, None)), (batch, n)


def _col_pass(n, n2, index, batch):
    p = P.plan_fft2(n, n2).passes[index]
    assert p.axis == -2
    return (lambda a, b: ops._cols_image_kernel(a, b, p, False, False)), (batch, n2, n)


def _recomb(inverse, n, batch):
    m = n // 2
    wr, wi = tw.rfft_recomb_twiddle(n, inverse=inverse)
    call = pencil.irfft_recomb_call if inverse else pencil.rfft_recomb_call
    width = m + 1 if inverse else m
    return (lambda a, b: call(a, b, wr, wi, interpret=False)), (batch, width)


def _bluestein(n, stage, batch):
    (p,) = [q for q in P.compile_bluestein(n) if q.stage == stage]
    bt = P.pick_batch_tile(p)
    width = p.view_in[2]
    return (lambda a, b: ops._bluestein_pass(a, b, p, False, False, bt)), (batch, width)


# name → builder of (jitted kernel call, input shape)
CASES = {
    "dft_matmul_1024": lambda: _leaf(1024, 512),
    "fused4_4096": lambda: _leaf(4096, 256),
    "fused4_4096_ragged_13_rows": lambda: _leaf(4096, 13),
    "fused4_2048_one_row": lambda: _leaf(2048, 1),
    "fused4_8192": lambda: _leaf(8192, 64),
    "fused4_65536": lambda: _leaf(65536, 16),
    "cols_pass_2^20": lambda: _row_pass(2**20, 0, 2),
    "rows_natural_2^20": lambda: _row_pass(2**20, 1, 2),
    "image_cols_4096x8192": lambda: _col_pass(8192, 4096, -1, 1),
    "strip_cols_twiddle_2^17": lambda: _col_pass(128, 2**17, -2, 1),
    "cols_natural_2^17": lambda: _col_pass(128, 2**17, -1, 1),
    "rfft_recomb_2^16": lambda: _recomb(False, 2**16, 64),
    "irfft_recomb_2^16": lambda: _recomb(True, 2**16, 64),
    "rfft_recomb_12288_ragged": lambda: _recomb(False, 12288, 3),
    "bluestein_fwd_2029": lambda: _bluestein(2029, "fwd", 64),
    "bluestein_inv_2029": lambda: _bluestein(2029, "inv", 64),
    "bluestein_pre_40000": lambda: _bluestein(40000, "pre", 16),
    "bluestein_mul_40000": lambda: _bluestein(40000, "mul", 16),
    "bluestein_post_40000": lambda: _bluestein(40000, "post", 16),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, one_chip, no_persistent_cache):
    fn, shape = CASES[case]()
    arg = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    compiled = jax.jit(fn).lower(arg, arg).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text, case
    # The compiled program is the kernel plus layout glue: no XLA FFT or
    # dot stands in for it.
    assert " dot(" not in text and " fft(" not in text, case



# -- the benchmark cells' programs: kernels and passes named ---------------

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=", re.M)
_KERNEL = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target="tpu_custom_call"'
    r'.*?metadata=\{op_name="([^"]*)"',
    re.M,
)
_OP_NAME = re.compile(r'metadata=\{op_name="([^"]*)"')
_WHERE = re.compile(r"p\d+_(rows|cols)|recomb")
OS_SCOPES = {"os_frame", "os_filter", "os_product", "os_discard", "os_tail", "os_pair", "os_unpair"}
#: The convolution's stages at the cell's shape: 6 frames a signal, paired.
CONV_SCOPES = {"os_pair", "os_filter", "os_product", "os_unpair"}


def _planned(spec, shape, dtype, sharding):
    fn = jax.jit(F.plan(spec, backend="pallas"))
    return fn, (jax.ShapeDtypeStruct(shape, dtype, sharding=sharding),)


def _conv(sharding):
    fn = jax.jit(lambda x, h: overlap.fft_conv_os(x, h, block=4096, backend="pallas"))
    return fn, (
        jax.ShapeDtypeStruct((8, 20000), jnp.float32, sharding=sharding),
        jax.ShapeDtypeStruct((257,), jnp.float32, sharding=sharding),
    )


# cell → (maker of (jitted program, argument shapes) at test size, the
# pass scopes its kernels run under)
CELLS = {
    "sar_fft2": (
        lambda sh: _planned(F.FFTSpec(n=2048, kind="fft2", n2=1024), (2, 1024, 2048), jnp.complex64, sh),
        {"p0_rows", "p1_cols"},
    ),
    "sar_range_fft": (
        lambda sh: _planned(F.FFTSpec(n=8192), (64, 8192), jnp.complex64, sh),
        {"p0_rows"},
    ),
    "conv_os_4097": (_conv, {"p0_rows", "recomb"}),
}


@pytest.fixture
def real_kernels(monkeypatch):
    """Lower the Pallas kernels for the chip, not for the interpreter."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_cell_program_names_kernels_and_passes(cell, one_chip, no_persistent_cache, real_kernels):
    build, want = CELLS[cell]
    fn, args = build(one_chip)
    text = fn.lower(*args).compile().as_text()
    unnamed = [n for n in _INSTR.findall(text) if n.startswith(("_unknown_", "_lambda_"))]
    assert not unnamed, unnamed
    kernels = _KERNEL.findall(text)
    assert kernels and len(kernels) == text.count('custom_call_target="tpu_custom_call"'), cell
    seen = set()
    for name, path in kernels:
        family = re.sub(r"\.\d+$", "", name)
        assert family in P.KERNEL_NAMES, name
        scopes = path.split("/")[:-1]  # the last part is the primitive
        assert scopes[-1] == family, path
        where = [s for s in scopes if _WHERE.fullmatch(s)]
        assert where, path
        seen.add(where[-1])
        # a row pass runs a row kernel, a column pass a strided-column
        # kernel, the Hermitian epilogue a recomb kernel
        if where[-1] == "recomb":
            assert family.startswith("recomb_"), path
        elif where[-1].endswith("_cols"):
            assert family.startswith("pencil_cols"), path
        else:
            assert not family.startswith(("pencil_cols", "recomb_")), path
    assert seen == want, (cell, seen)
    named = {s for found in _OP_NAME.findall(text) for p in found.split(";") for s in p.split("/")}
    if cell == "conv_os_4097":
        # paired complex frames: four-step leaves only; the one
        # recombination is the filter's half spectrum, under os_filter
        assert {re.sub(r"\.\d+$", "", name) for name, _ in kernels} == {"fft4step", "recomb_fwd"}
        assert [p for n, p in kernels if n.startswith("recomb")] and all(
            "/os_filter/" in p for n, p in kernels if n.startswith("recomb")
        )
        assert named & OS_SCOPES == CONV_SCOPES
    else:
        assert not OS_SCOPES & named



# -- the four-chip cell's program: the pencil steps named ------------------

_PENCIL_STEP = re.compile(r"pencil/(a2a\d|n1_cols|n2_rows)(/|$)")


def test_pencil_cell_program_names_its_steps(topo, no_persistent_cache, real_kernels):
    """``pfft_sharded`` over a described v5e 2x2 at test size, K = 4: nine
    collectives, each under its ``pencil/a2a{step}`` scope; the column leaf
    runs strided-column kernels under ``pencil/n1_cols``, the row leaf row
    kernels under ``pencil/n2_rows``."""
    import numpy as np
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.core import distributed as D

    mesh = Mesh(np.array(topo.devices), ("x",), axis_types=(AxisType.Auto,))
    arg = jax.ShapeDtypeStruct(
        (8, 2**16), jnp.float32, sharding=NamedSharding(mesh, PartitionSpec(None, "x"))
    )
    with F.use_backend("pallas"):
        fn = jax.jit(lambda a, b: D.pfft_sharded(a, b, mesh, "x", chunks=4))
        text = fn.lower(arg, arg).compile().as_text()
    steps = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        where = _OP_NAME.search(line)
        if m is None or where is None:
            continue
        if " all-to-all(" in line or 'custom_call_target="tpu_custom_call"' in line:
            step = _PENCIL_STEP.search(where.group(1))
            assert step, line
            steps.setdefault(step.group(1), []).append(re.sub(r"\.\d+$", "", m.group(1)))
    a2a = {s: len(v) for s, v in steps.items() if s.startswith("a2a")}
    assert a2a == {"a2a0": 4, "a2a1": 4, "a2a2": 1}, a2a
    assert steps["n1_cols"] and all(f.startswith("pencil_cols") for f in steps["n1_cols"])
    assert steps["n2_rows"] and not any(f.startswith("pencil_cols") for f in steps["n2_rows"])
