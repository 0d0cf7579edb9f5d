"""Chip smoke test: the pass-program FFT's main path on a TPU, one phase per
kernel family, through the public API at the sizes of
``repro.configs.fftbench.FFT_SHAPES``.

    python chip_smoke.py               # one chip: every single-chip phase
    python chip_smoke.py --four-chips  # the pencil pfft / pfft2d path only

Each phase plans with backend negotiation (no ``backend=`` override) and
asserts it picked ``pallas``, runs the transform eagerly as users call it
and once under ``jax.jit``, compares both with ``np.fft`` in float64 on
sampled rows at 1e-3·max|ref|, counts the ``tpu_custom_call`` kernels in
the compiled HLO (at least one per planned pass), and checks that no leaf
was demoted (``faults.degradation_log()`` stays empty).  It prints one JSON
line per phase; the wall time in it is for information only.  The last
line of a passing run is ``{"ok": true, "device": {...}}``.

The script refuses to run — before any phase, printing no result — when
JAX finds no TPU, when the Pallas kernels would run in interpret mode, or
when ``REPRO_FAULTS`` / ``REPRO_PALLAS_INTERPRET`` is set: a smoke test that
could fall back to the CPU or to XLA would not show the chip.  It starts no
child process, so the chip stays with this one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

TOL = 1e-3  # × max|ref|, float32 planes against a float64 reference
SEED = 0


def _fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(1)


def _preflight(chips: int):
    for var in ("REPRO_FAULTS", "REPRO_PALLAS_INTERPRET"):
        if os.environ.get(var) is not None:
            _fail(f"{var} is set; the smoke test runs only the real kernels")
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as err:
        _fail(f"the repro package is not beside this script ({err})")
    import jax

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        _fail(f"{chips} chips needed, {len(devices)} found")
    from repro.kernels import ops

    if ops.should_interpret():
        _fail("Pallas would run in interpret mode")
    return jax, devices


def _hlo_kernels(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _max_err(got, ref) -> tuple[float, float]:
    return float(np.abs(got - ref).max()), TOL * float(np.abs(ref).max())


def _run_phase(jax, name, fn, x, ref_fn, rows, planned, note=""):
    """Run ``fn(x)`` eagerly and jitted and check both: ``ref_fn(rows)`` is
    the float64 reference of output rows ``rows``; ``planned()`` lists, once
    the eager call has planned them, the PlannedFFT handles whose passes
    the compiled program must hold."""
    from repro.core import faults

    t0 = time.perf_counter()
    eager = jax.block_until_ready(fn(x))
    t_eager = time.perf_counter() - t0
    planned = planned()
    for p in planned:
        assert p.backend.name == "pallas", (name, p.backend.name)
    passes = sum(len(p.passes) for p in planned)
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(x).compile()
    t_compile = time.perf_counter() - t0
    kernels = _hlo_kernels(compiled)
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(x))
    t_jit = time.perf_counter() - t0
    ref = ref_fn(rows)
    err_e, tol = _max_err(np.asarray(eager[rows], np.complex128), ref)
    err_j, _ = _max_err(np.asarray(out[rows], np.complex128), ref)
    del eager, out
    degraded = len(faults.degradation_log())
    rec = {
        "phase": name,
        "shape": list(x.shape),
        "bytes_in": int(x.nbytes),
        "backend": "pallas",
        "passes": passes,
        "kernels": kernels,
        "max_err": max(err_e, err_j),
        "tol": tol,
        "compile_s": round(t_compile, 3),
        "degradations": degraded,
        "wall_s_info": {"eager_incl_compile": round(t_eager, 3), "jit": round(t_jit, 4)},
    }
    if note:
        rec["note"] = note
    print(json.dumps(rec), flush=True)
    if not (kernels >= passes and max(err_e, err_j) <= tol and degraded == 0):
        raise AssertionError(f"{name} failed its checks: {rec}")


def _complex_input(jax, shape, seed=SEED):
    import jax.numpy as jnp

    kr, ki = jax.random.split(jax.random.key(seed))
    return jax.jit(
        lambda: (jax.random.normal(kr, shape) + 1j * jax.random.normal(ki, shape)).astype(
            jnp.complex64
        )
    )()


def _real_input(jax, shape, seed=SEED):
    return jax.jit(lambda: jax.random.normal(jax.random.key(seed), shape))()


def _sample_rows(b: int) -> np.ndarray:
    return np.unique(np.array([0, b // 3, (2 * b) // 3, b - 1]))


def phase_fft(jax, name, n, batch):
    from repro.core import fft as F

    planned = F.plan(F.FFTSpec(n=n))
    x = _complex_input(jax, (batch, n))
    rows = _sample_rows(batch)
    ref = lambda r: np.fft.fft(np.asarray(x[r], np.complex128), axis=-1)  # noqa: E731
    _run_phase(jax, name, planned, x, ref, rows, lambda: [planned])


def phase_sar(jax):
    from repro.core import conv, fft as F

    n2, n, batch = 4096, 8192, 4
    planned = F.plan(F.FFTSpec(n=n, kind="fft2", n2=n2))
    x = _complex_input(jax, (batch, n2, n))
    ref = lambda r: np.fft.fft2(np.asarray(x[r], np.complex128))  # noqa: E731
    note = (
        "batch cut from 32 to 4 scenes (~1 GiB in): 32 scenes are 8 GiB in "
        "and 8 GiB out, more than the chip's 16 GB"
    )
    _run_phase(
        jax, "sar_4kx8k", planned, x, ref, np.array([0]), lambda: [planned], note
    )
    del x

    # Range compression: one scene through fft_conv2d's rfft2/irfft2 pair
    # (the Hermitian recomb epilogues), a per-row chirp matched filter.
    taps = 257
    t = np.arange(taps) / taps
    h = np.cos(np.pi * 40.0 * t * t).astype(np.float32)[None]
    scene = _real_input(jax, (n2, n), seed=SEED + 1)
    N = 1 << (n + taps - 2).bit_length()
    planned = lambda: [  # noqa: E731
        F.plan(F.FFTSpec(n=N, kind="rfft2", n2=n2)),
        F.plan(F.FFTSpec(n=N, kind="irfft2", n2=n2)),
    ]
    rows = _sample_rows(n2)

    def ref(r):
        xs = np.asarray(scene[r], np.float64)
        H = np.fft.rfft(h[0].astype(np.float64), N)
        return np.fft.irfft(np.fft.rfft(xs, N, axis=-1) * H, N, axis=-1)[:, :n]

    _run_phase(
        jax, "sar_range_compression", lambda a: conv.fft_conv2d(a, h), scene,
        ref, rows, planned,
    )


def phase_conv(jax):
    from repro.core import fft as F
    from repro.core import overlap

    L, batch, taps = 2**19, 32, 4097
    h = (np.hanning(taps) * np.cos(0.05 * np.arange(taps))).astype(np.float32)
    x = _real_input(jax, (batch, L), seed=SEED + 2)
    F.clear_plan_log()
    fn = lambda a: overlap.fft_conv_os(a, h)  # noqa: E731
    # The rfft/irfft pair the (tuned) block size planned.
    planned = lambda: [  # noqa: E731
        F.plan(s) for s in {s for s, _ in F.plan_log() if s.kind in ("rfft", "irfft")}
    ]
    N = 1 << (L + taps - 2).bit_length()

    def ref(r):
        xs = np.asarray(x[r], np.float64)
        H = np.fft.rfft(h.astype(np.float64), N)
        return np.fft.irfft(np.fft.rfft(xs, N, axis=-1) * H, N, axis=-1)[:, :L]

    _run_phase(jax, "conv_512k", fn, x, ref, _sample_rows(batch), planned)


def single_chip(jax):
    phase_fft(jax, "table1_4096", 4096, 4096)
    phase_fft(jax, "pod_1m", 2**20, 64)
    phase_sar(jax)
    phase_conv(jax)
    phase_fft(jax, "radar_bluestein_12288", 12288, 1024)


def four_chips(jax, devices):
    """pfft_sharded on pod_16m and pfft2d on sar_4kx8k over a 4-chip mesh."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

    from repro.core import distributed as D
    from repro.core import faults

    mesh = Mesh(np.array(devices[:4]), ("x",), axis_types=(AxisType.Auto,))

    def sharded_input(shape, spec, seed):
        kr, ki = jax.random.split(jax.random.key(seed))
        make = jax.jit(
            lambda: (jax.random.normal(kr, shape), jax.random.normal(ki, shape)),
            out_shardings=(NamedSharding(mesh, spec),) * 2,
        )
        return make()

    def check(name, fn, args, ref_fn, rows, note=""):
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        t_compile = time.perf_counter() - t0
        hlo = compiled.as_text()
        t0 = time.perf_counter()
        yr, yi = jax.block_until_ready(compiled(*args))
        t_run = time.perf_counter() - t0
        on = sorted(d.id for d in yr.sharding.device_set)
        got = np.asarray(yr[rows], np.float64) + 1j * np.asarray(yi[rows], np.float64)
        ref = ref_fn(rows)
        err, tol = _max_err(got, ref)
        rec = {
            "phase": name,
            "shape": list(args[0].shape),
            "bytes_in": int(2 * args[0].nbytes),
            "backend": "pallas",
            "devices": on,
            "all_to_all": hlo.count("all-to-all-start(") + hlo.count("all-to-all("),
            "kernels": _hlo_kernels(compiled),
            "max_err": err,
            "tol": tol,
            "compile_s": round(t_compile, 3),
            "degradations": len(faults.degradation_log()),
            "wall_s_info": round(t_run, 4),
        }
        if note:
            rec["note"] = note
        print(json.dumps(rec), flush=True)
        ok = (
            len(on) == 4 and rec["all_to_all"] > 0 and rec["kernels"] > 0
            and err <= tol and rec["degradations"] == 0
        )
        if not ok:
            raise AssertionError(f"{name} failed its checks: {rec}")

    n, batch = 2**24, 32
    xr, xi = sharded_input((batch, n), P(None, "x"), SEED)

    def ref16(r):
        return np.fft.fft(
            np.asarray(xr[r], np.float64) + 1j * np.asarray(xi[r], np.float64), axis=-1
        )

    check(
        "pod_16m_pfft_sharded",
        lambda a, b: D.pfft_sharded(a, b, mesh, "x"),
        (xr, xi), ref16, np.array([0, batch - 1]),
    )
    del xr, xi

    n1, n2, batch = 4096, 8192, 8
    spec = P(None, "x", None)
    xr, xi = sharded_input((batch, n1, n2), spec, SEED + 1)
    body = lambda a, b: D.pfft2d(a, b, n1=n1, n2=n2, axis_name="x", num_shards=4)  # noqa: E731
    fn = jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec), check_vma=False
    )

    def ref2d(r):
        return np.fft.fft2(np.asarray(xr[r], np.float64) + 1j * np.asarray(xi[r], np.float64))

    check(
        "sar_4kx8k_pfft2d", fn, (xr, xi), ref2d, np.array([0]),
        note="batch 8 scenes (2 GiB in over 4 chips)",
    )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--four-chips", action="store_true",
        help="run only the pencil pfft/pfft2d path on a 4-chip mesh",
    )
    args = ap.parse_args(argv)
    chips = 4 if args.four_chips else 1
    jax, devices = _preflight(chips)
    try:
        if args.four_chips:
            four_chips(jax, devices)
        else:
            single_chip(jax)
    except Exception as err:  # report the phase that failed, print no result
        print(f"chip_smoke: FAILED: {type(err).__name__}: {err}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps(
        {"ok": True, "device": {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}}
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
