"""Benchmark harness entrypoint — one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run table1 sar # subset
  PYTHONPATH=src python -m benchmarks.run --smoke    # CI: fast sanity pass

``--smoke`` runs a tiny-size, low-rep subset so CI catches import breakage
and API drift in every bench module without paying full benchmark time.
Emits ``name,...`` CSV rows (paper-table stand-ins documented per module).
"""

import sys

from repro.runtime.compile_cache import enable_compile_cache

from benchmarks import (
    bench_bluestein,
    bench_fftconv,
    bench_pfft,
    bench_roofline,
    bench_sar,
    bench_serve,
    bench_table1,
    bench_tuning,
)

SUITES = {
    "table1": bench_table1.main,     # paper Table 1 / Figs 7-10
    "sar": bench_sar.main,           # paper §3 SAR motivation
    "fftconv": bench_fftconv.main,   # LM integration (spectral layers)
    "tuning": bench_tuning.main,     # autotuned vs fixed-heuristic blocks
    "roofline": bench_roofline.main, # dry-run roofline summary
    "serve": bench_serve.main,       # prefill/insert/generate phase timings
    "pfft": bench_pfft.main,         # distributed pencil scaling (fake devices)
    "bluestein": bench_bluestein.main,  # non-pow2 vs padded-pow2 vs jnp.fft
}

#: Suites with a fast-path smoke mode; the rest are import-checked only.
SMOKE_SUITES = {
    "table1": lambda: bench_table1.main(smoke=True),
    "sar": lambda: bench_sar.main(smoke=True),
    # cross-checks overlap-save against one-shot, so CI exercises the engine
    "fftconv": lambda: bench_fftconv.main(smoke=True),
    # runs the tuner (model + measure) and asserts cache determinism
    "tuning": lambda: bench_tuning.main(smoke=True),
    # asserts streamed == one-shot numerics + zero-new-plan discipline
    # before timing a small serving sweep
    "serve": lambda: bench_serve.main(smoke=True),
    # one 16-fake-device point: numerics + packed collective counts
    "pfft": lambda: bench_pfft.main(smoke=True),
    # gates chirp-conv leaves on numerics vs numpy before timing
    "bluestein": lambda: bench_bluestein.main(smoke=True),
}


def main() -> None:
    enable_compile_cache()
    args = sys.argv[1:]
    smoke = "--smoke" in args
    picks = [a for a in args if a in SUITES] or list(SUITES)
    for name in picks:
        if smoke:
            runner = SMOKE_SUITES.get(name)
            if runner is None:
                print(f"# ---- {name}: import ok, no smoke mode ----", flush=True)
                continue
            print(f"# ---- {name} (smoke) ----", flush=True)
            runner()
            continue
        print(f"# ---- {name} ----", flush=True)
        SUITES[name]()


if __name__ == "__main__":
    main()
