"""Compile rehearsal: every main-path kernel compiles for a described v5e.

Interpret mode (what the rest of the suite runs on the CPU) never checks
block tiling or VMEM; the TPU compiler does, and it is installed here.  Each
case compiles one kernel family at a real width, with ``interpret=False``,
for a v5e that is described rather than attached, and asserts that the
compiled program holds the kernel (``tpu_custom_call``).  Nothing runs, so
these cases say nothing about results or time.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and the suite runs in several).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

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

