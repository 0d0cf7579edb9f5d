"""Entry ``pfft_sharded``: the distributed pencil FFT of a batch of long
complex signals, each split over every chip of the cell.

One call is ``repro.core.distributed.pfft_sharded(xr, xi, mesh, axis)`` on
``(batch, n)`` float32 planes whose last axis is sharded contiguously over
a mesh of the cell's chips, with the tuner's own schedule: three packed
all-to-all transposes (the two inner ones strip-mined into K chunks)
around the n1 column leaf and the n2 row leaf, output in natural order.
``compare`` whole signals of the last call, drawn from the seed, one from
each equal part of the batch, are compared with ``numpy.fft.fft`` in
float64.
"""

from __future__ import annotations

import functools
import math

import numpy as np

from chipbench.lib import reference as R


def samples(cfg, traffic) -> int:
    """Complex input points of one call."""
    return cfg["batch"] * cfg["n"]


def essential(cfg, traffic) -> dict:
    """One read of the input and one write of the output (two float32
    planes), 5·n·log2 n flops per signal, and ``a2a_bytes``: what each
    device sends off-chip per call, from the shapes alone.  Each of the
    three transposes of a natural-order transform moves the device's slab
    of both planes, ``8 · batch · n/d`` bytes, of which ``(d − 1)/d`` goes
    to the other devices."""
    b, n, d = cfg["batch"], cfg["n"], cfg["chips"]
    return {
        "bytes": 2 * 8 * b * n,
        "flops": 5.0 * b * n * math.log2(n),
        "a2a_bytes": 3 * 8 * b * (n // d) * (d - 1) // d,
    }


def _planes(jax, shape, seed, sharding):
    """Standard normal float32 planes ``(xr, xi)`` of ``shape``, made on the
    devices already sharded, from the seed (the key is an argument, so
    every seed runs the same compiled program)."""

    def make(key):
        kr, ki = jax.random.split(key)
        return jax.random.normal(kr, shape), jax.random.normal(ki, shape)

    return jax.jit(make, out_shardings=(sharding, sharding))(jax.random.key(R.seed32(seed)))


@functools.lru_cache(maxsize=None)
def _program(jax, mesh, axis):
    """The jitted call, one per mesh, so that every seed a process reads
    runs the program it compiled once."""
    from repro.core import distributed as D

    return jax.jit(lambda a, b: D.pfft_sharded(a, b, mesh, axis))


def build(jax, cfg, traffic, seed, devices) -> dict:
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as D

    axis = cfg["mesh_axis"]
    mesh = Mesh(np.array(devices), (axis,), axis_types=(AxisType.Auto,))
    shape = (cfg["batch"], cfg["n"])
    xr, xi = _planes(jax, shape, seed, NamedSharding(mesh, P(None, axis)))
    # The handle pfft_sharded resolves for itself (plan_pencil is interned).
    pencil = D.plan_pencil(cfg["n"], len(devices))
    return {
        "fn": _program(jax, mesh, axis),
        "args": (xr, xi),
        "plans": [pencil.plan_n1, pencil.plan_n2],
        "note": {"pencil": pencil.describe().splitlines()[0]},
    }


def picks(cfg, traffic, seed) -> np.ndarray:
    """``compare`` whole signals drawn from the seed, one from each equal
    part of the batch."""
    return R.stratified(cfg["batch"], traffic["compare"], seed)


def _host(xr, xi, sel) -> np.ndarray:
    return np.asarray(xr[sel]).astype(np.float64) + 1j * np.asarray(xi[sel])


def answers(out, args, sel) -> tuple:
    """The produced signals and the inputs the reference needs, on the host
    as complex128."""
    return _host(*out, sel), _host(*args, sel)


def reference(host_in, cfg, traffic) -> np.ndarray:
    return np.stack(R.threaded(np.fft.fft, host_in))


def control(jax, args, sel, cfg, traffic) -> np.ndarray:
    """The transform in three bfloat16 passes, in the program's place, on
    the compared signals, on one chip."""
    dev = args[0].sharding.mesh.devices.flat[0]
    tabs = jax.device_put(R.tables(cfg["n"]), dev)
    xr, xi = (jax.device_put(a[sel], dev) for a in args)
    yr, yi = jax.jit(R.fft_high)(xr, xi, tabs)
    return np.asarray(yr).astype(np.float64) + 1j * np.asarray(yi)
