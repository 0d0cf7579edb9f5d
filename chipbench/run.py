"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for.  Set-up makes the inputs on the device from the seed, plans
through the public API (negotiation must pick ``pallas``), compiles the
cell's jitted entry and warms it up.  The window then drives one call at a
time, each timed from dispatch to ready, for ``--seconds``.  Afterwards
the last call's output is compared with a float64 ``numpy.fft`` reference.
The last line of standard output is one JSON object: with ``--trace 0``
the end-to-end metrics, with ``--trace 1`` the per-layer metrics read from
the profiler's trace of the window.

It refuses to run, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, when the Pallas kernels would run in interpret
mode, when ``REPRO_FAULTS`` or ``REPRO_PALLAS_INTERPRET`` is set, or when
the chip's kind is not in ``chipbench/peaks.json``.  It starts no child
process.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _fail(msg: str) -> None:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _preflight(chips: int):
    for var in ("REPRO_FAULTS", "REPRO_PALLAS_INTERPRET"):
        if os.environ.get(var) is not None:
            _fail(f"{var} is set; the benchmark runs only the real kernels")
    try:
        from repro.runtime.compile_cache import enable_compile_cache
    except ImportError as err:
        _fail(f"the repro package is not in this checkout's src/ ({err})")
    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        _fail(f"no TPU: JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        _fail(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    from repro.kernels import ops

    if ops.should_interpret():
        _fail("Pallas would run in interpret mode")
    from chipbench.lib import peaks

    try:
        peaks.lookup(devices[0].device_kind)
    except KeyError as err:
        _fail(str(err))
    return jax, devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-trace", default=None, help="write the compact trace here")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.lib import harness

    try:
        cell = harness.load_cell(args.workload)
    except (KeyError, FileNotFoundError) as err:
        _fail(f"cannot load workload {args.workload!r}: {err}")
    jax, devices = _preflight(cell.chips)
    try:
        result = harness.run(
            jax, cell, args.seed, args.seconds, bool(args.trace), devices, T_START,
            save_trace=args.save_trace,
        )
    except Exception as err:  # report the failure, print no result
        import traceback

        traceback.print_exc()
        _fail(f"FAILED: {type(err).__name__}: {err}")
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
