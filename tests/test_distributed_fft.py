"""Distributed pencil FFT — runs in a subprocess with 8 fake devices so the
rest of the suite keeps the default single-device environment."""

import pytest

from conftest import run_in_subprocess

_BODY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D

mesh = jax.make_mesh((8,), ('x',))
np.random.seed(0)

# ---- 1-D forward, natural order ------------------------------------------
for n in (1024, 8192):
    x = (np.random.randn(2, n) + 1j*np.random.randn(2, n)).astype(np.complex64)
    xr, xi = jnp.asarray(x.real), jnp.asarray(x.imag)
    ref = np.fft.fft(x)
    yr, yi = D.pfft_sharded(xr, xi, mesh, 'x')
    rel = np.abs((np.asarray(yr)+1j*np.asarray(yi)) - ref).max() / np.abs(ref).max()
    assert rel < 5e-5, ('natural', n, rel)

    # ---- pencil layout + inverse-from-pencil (the 4-a2a conv path) -------
    pr, pi = D.pfft_sharded(xr, xi, mesh, 'x', natural_order=False)
    zr, zi = D.pifft_sharded(pr, pi, mesh, 'x', from_pencil=True)
    err = np.abs((np.asarray(zr)+1j*np.asarray(zi)) - x).max()
    assert err < 5e-5, ('pencil roundtrip', n, err)

    # pencil layout semantics: [k1, k2] holds X[k1 + n1*k2]
    n1, n2 = D.pencil_factors(n, 8)
    pen = (np.asarray(pr)+1j*np.asarray(pi)).reshape(2, n1, n2)
    perm = ref.reshape(2, n2, n1).transpose(0, 2, 1)
    rel = np.abs(pen - perm).max() / np.abs(ref).max()
    assert rel < 5e-5, ('pencil layout', n, rel)

    # ---- natural-order inverse -------------------------------------------
    zr, zi = D.pifft_sharded(yr, yi, mesh, 'x')
    err = np.abs((np.asarray(zr)+1j*np.asarray(zi)) - x).max()
    assert err < 5e-5, ('natural roundtrip', n, err)

# ---- inverse via pfft(inverse=True) ---------------------------------------
x = (np.random.randn(1, 2048) + 1j*np.random.randn(1, 2048)).astype(np.complex64)
ref = np.fft.ifft(x)
yr, yi = D.pfft_sharded(jnp.asarray(x.real), jnp.asarray(x.imag), mesh, 'x', inverse=True)
rel = np.abs((np.asarray(yr)+1j*np.asarray(yi)) - ref).max() / (np.abs(ref).max())
assert rel < 5e-5, ('pfft inverse', rel)

# ---- 2-D (SAR layout): rows sharded --------------------------------------
from jax.sharding import NamedSharding, PartitionSpec as P
n1, n2 = 128, 256
img = (np.random.randn(2, n1, n2) + 1j*np.random.randn(2, n1, n2)).astype(np.complex64)
spec = P(None, 'x', None)
fn = jax.shard_map(
    lambda xr, xi: D.pfft2d(xr, xi, n1=n1, n2=n2, axis_name='x', num_shards=8),
    mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec), check_vma=False)
yr, yi = fn(jnp.asarray(img.real), jnp.asarray(img.imag))
ref2 = np.fft.fft2(img)
rel = np.abs((np.asarray(yr)+1j*np.asarray(yi)) - ref2).max() / np.abs(ref2).max()
assert rel < 5e-5, ('fft2d', rel)

print('DISTRIBUTED_FFT_OK')
"""


@pytest.mark.slow
def test_distributed_fft_8dev():
    out = run_in_subprocess(_BODY, devices=8)
    assert "DISTRIBUTED_FFT_OK" in out


_GRAD_BODY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D

mesh = jax.make_mesh((8,), ('x',))
n = 1024
np.random.seed(1)
x = np.random.randn(2, n).astype(np.float32)

def loss(xr):
    yr, yi = D.pfft_sharded(xr, jnp.zeros_like(xr), mesh, 'x')
    return jnp.sum(yr**2 + yi**2)

g = jax.grad(loss)(jnp.asarray(x))
# Parseval: d/dx sum|FFT(x)|^2 = 2*n*x
np.testing.assert_allclose(np.asarray(g), 2*n*x, rtol=1e-3)
print('DIST_GRAD_OK')
"""


@pytest.mark.slow
def test_distributed_fft_differentiable():
    out = run_in_subprocess(_GRAD_BODY, devices=8)
    assert "DIST_GRAD_OK" in out


_CONV_OS_BODY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D
from repro.core.conv import fft_conv

mesh = jax.make_mesh((8,), ('x',))
np.random.seed(3)
x = np.random.randn(2, 50000).astype(np.float32)
h = np.random.randn(257,).astype(np.float32)

y = np.asarray(D.pconv_os_sharded(jnp.asarray(x), jnp.asarray(h), mesh, 'x',
                                  block=1024))
ref = np.asarray(fft_conv(jnp.asarray(x), jnp.asarray(h), overlap_save=False))
rel = np.abs(y - ref).max() / np.abs(ref).max()
assert rel < 1e-4, ('pconv_os', rel)

# blocks are embarrassingly parallel: ZERO collectives in the program
jx = str(jax.make_jaxpr(
    lambda a, b: D.pconv_os_sharded(a, b, mesh, 'x', block=1024)
)(jnp.asarray(x), jnp.asarray(h)))
for coll in ('all_to_all', 'all_gather', 'psum', 'ppermute'):
    assert coll not in jx, coll
print('PCONV_OS_OK')
"""


@pytest.mark.slow
def test_distributed_overlap_save_conv_8dev():
    out = run_in_subprocess(_CONV_OS_BODY, devices=8)
    assert "PCONV_OS_OK" in out


_PACKED_BODY = r"""
import os, tempfile
# Fresh cache path: proves the pencil decisions themselves never write a
# cache, independent of what other suites left in the session-wide file.
os.environ['REPRO_TUNING_CACHE'] = os.path.join(
    tempfile.mkdtemp(), 'tuning.json')
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import distributed as D
from repro.core import tuning

mesh = jax.make_mesh((8,), ('x',))
n = 8192
x = jnp.zeros((n,), jnp.float32)

def n_a2a(fn):
    sm = jax.shard_map(fn, mesh=mesh, in_specs=(P('x'), P('x')),
                            out_specs=(P('x'), P('x')),
                            check_vma=False)
    return str(jax.make_jaxpr(sm)(x, x)).count('all_to_all')

def fwd(natural, **kw):
    return lambda xr, xi: D.pfft(xr, xi, n=n, axis_name='x', num_shards=8,
                                 natural_order=natural, **kw)

def inv(from_pencil, **kw):
    return lambda xr, xi: D.pifft(xr, xi, n=n, axis_name='x', num_shards=8,
                                  from_pencil=from_pencil, **kw)

# Packed split-complex: ONE stacked a2a per transpose.  The default path's
# count follows the tuned chunk count K: 2K + 1 natural, 2K pencil.
K_nat = D.plan_pencil(n, 8).a2a_chunks
K_pen = D.plan_pencil(n, 8, natural_order=False).a2a_chunks
assert n_a2a(fwd(True)) == 2 * K_nat + 1, (n_a2a(fwd(True)), K_nat)
assert n_a2a(fwd(False)) == 2 * K_pen, (n_a2a(fwd(False)), K_pen)
assert n_a2a(inv(False)) == 2 * K_nat + 1
assert n_a2a(inv(True)) == 2 * K_pen

# Forcing K pins the count exactly: K=1 is the flat packed pipeline (3
# collectives, was 6 per-plane calls), K=2 double-buffers the middle (5).
assert n_a2a(fwd(True, chunks=1)) == 3
assert n_a2a(fwd(True, chunks=2)) == 5
assert n_a2a(fwd(False, chunks=1)) == 2
assert n_a2a(inv(False, chunks=2)) == 5

# Legacy per-plane baseline kept for A/B: two a2a per step.
assert n_a2a(fwd(True, pack=False)) == 6
assert n_a2a(fwd(False, pack=False)) == 4
assert n_a2a(inv(False, pack=False)) == 6

# pfft2d: one packed a2a per transpose (2), per-plane legacy 4.
img = jnp.zeros((8, 128, 256), jnp.float32)
def n_a2a_2d(pack):
    sm = jax.shard_map(
        lambda xr, xi: D.pfft2d(xr, xi, n1=128, n2=256, axis_name='x',
                                num_shards=8, pack=pack),
        mesh=mesh, in_specs=(P(None, 'x'), P(None, 'x')),
        out_specs=(P(None, 'x'), P(None, 'x')),
        check_vma=False)
    return str(jax.make_jaxpr(sm)(img, img)).count('all_to_all')
assert n_a2a_2d(True) == 2, n_a2a_2d(True)
assert n_a2a_2d(False) == 4, n_a2a_2d(False)

# Chunked overlap stays correct, not just countable.
np.random.seed(5)
xv = (np.random.randn(2, n) + 1j*np.random.randn(2, n)).astype(np.complex64)
ref = np.fft.fft(xv)
yr, yi = D.pfft_sharded(jnp.asarray(xv.real), jnp.asarray(xv.imag), mesh, 'x',
                        chunks=2)
rel = np.abs((np.asarray(yr)+1j*np.asarray(yi)) - ref).max() / np.abs(ref).max()
assert rel < 5e-5, ('chunked numerics', rel)

# All tuned decisions above were modeled, never measured: no timings, and
# nothing leaked into the persistent cache.
assert tuning.measure_log() == (), tuning.measure_log()
assert not os.path.exists(tuning.cache_path()), tuning.cache_path()
print('PACKED_A2A_OK')
"""


@pytest.mark.slow
def test_packed_collective_counts_8dev():
    out = run_in_subprocess(_PACKED_BODY, devices=8)
    assert "PACKED_A2A_OK" in out


_TUNE_DET_BODY = r"""
import json, os, tempfile
os.environ['REPRO_TUNING_CACHE'] = os.path.join(
    tempfile.mkdtemp(), 'tuning.json')
from repro.core import tuning

picks = {}
for n in (4096, 8192, 65536):
    for d in (8, 16):
        for nat in (True, False):
            cfg = tuning.pencil_config(n, d, natural_order=nat)
            picks[f'{n}/{d}/{nat}'] = cfg
            # tune="measure" must clamp to the same modeled pick: an SPMD
            # host is never allowed to time its way to a private config.
            assert tuning.pencil_config(n, d, tune='measure',
                                        natural_order=nat) == cfg
assert tuning.measure_log() == ()
assert not os.path.exists(tuning.cache_path())
print('PICKS=' + json.dumps(picks, sort_keys=True))
"""


@pytest.mark.slow
def test_pencil_tuning_deterministic_across_processes():
    """Two fresh processes must derive the identical modeled pencil config
    with no cache file mediating — the SPMD-safety contract."""
    outs = [run_in_subprocess(_TUNE_DET_BODY, devices=8) for _ in range(2)]
    lines = [
        next(ln for ln in o.splitlines() if ln.startswith("PICKS="))
        for o in outs
    ]
    assert lines[0] == lines[1]


_NONSQUARE_BODY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as D

d = {devices}
mesh = jax.make_mesh((d,), ('x',))
np.random.seed(7)
for n in (2048, 32768):
    n1, n2 = D.pencil_factors(n, d)
    assert n1 != n2 and n1 % d == 0 and n2 % d == 0, (n, n1, n2)
    x = (np.random.randn(2, n) + 1j*np.random.randn(2, n)).astype(np.complex64)
    ref = np.fft.fft(x)
    yr, yi = D.pfft_sharded(jnp.asarray(x.real), jnp.asarray(x.imag), mesh, 'x')
    rel = np.abs((np.asarray(yr)+1j*np.asarray(yi)) - ref).max() / np.abs(ref).max()
    assert rel < 5e-5, ('nonsquare', n, d, rel)
    zr, zi = D.pifft_sharded(yr, yi, mesh, 'x')
    err = np.abs((np.asarray(zr)+1j*np.asarray(zi)) - x).max()
    assert err < 5e-5, ('nonsquare roundtrip', n, d, err)

# explicit factors override flows through the plan layer
n = 8192
x = (np.random.randn(1, n) + 1j*np.random.randn(1, n)).astype(np.complex64)
ref = np.fft.fft(x)
yr, yi = D.pfft_sharded(jnp.asarray(x.real), jnp.asarray(x.imag), mesh, 'x',
                        factors=(512, 16))
rel = np.abs((np.asarray(yr)+1j*np.asarray(yi)) - ref).max() / np.abs(ref).max()
assert rel < 5e-5, ('factors override', rel)
print('NONSQUARE_OK')
"""


@pytest.mark.slow
@pytest.mark.parametrize("devices", [8, 16])
def test_nonsquare_factors(devices):
    out = run_in_subprocess(_NONSQUARE_BODY.format(devices=devices),
                            devices=devices)
    assert "NONSQUARE_OK" in out
