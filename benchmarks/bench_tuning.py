"""Autotuned vs fixed-heuristic overlap-save — the tuner's ledger.

Sweeps the ``BENCH_conv.json`` / ``BENCH_fft.json`` long-conv shapes
(L ∈ 2¹⁸..2²⁰, Lh ∈ {1025, 4097}) through two block policies:

* ``fixed`` — the historical ``OS_FACTOR=8`` heuristic block
  (:func:`repro.core.overlap.pick_block`);
* ``tuned`` — ``tune="measure"``: the roofline model prunes the block
  candidates to the few within ~20% of the modeled-minimum device time, the
  measurement pass times them (fixed heuristic always included, so tuned
  can never lose), and the winner lands in the persistent tuning cache.

Each row records both blocks, both wall-clocks, the measured speedup and
the modeled HBM bytes of both schedules; full runs append a
``BENCH_tuning.json`` trajectory entry.  ``--smoke`` runs a tiny shape,
cross-checks tuned == fixed numerics, and asserts the tune="model" cache
round-trips deterministically (same spec → same config, cache hit on the
second plan, zero measurements) — the CI contract.

  PYTHONPATH=src python -m benchmarks.bench_tuning [--smoke]
"""

from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._trajectory import append_trajectory
from repro.analysis import roofline as rl
from repro.core import fft as fft_lib
from repro.core import tuning
from repro.core.overlap import fft_conv_os, pick_block

# The acceptance sweep: the bench_fftconv shapes the tuner must never lose
# on, spanning the auto-routed overlap-save regime.
SWEEP = [
    (2**18, 1025), (2**18, 4097),
    (2**19, 1025), (2**19, 4097),
    (2**20, 1025), (2**20, 4097),
]
SMOKE_SWEEP = [(2**13, 129)]

TRAJECTORY = os.path.join(os.path.dirname(__file__), "..", "BENCH_tuning.json")


def _time(fn, *args, reps=3, warmup=1) -> float:
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _time_pair(fa, fb, *args, reps=3, warmup=1) -> tuple:
    """Interleaved A/B min-of-reps so machine drift (frequency scaling,
    background load) hits both policies alike instead of whichever ran
    second."""
    for _ in range(warmup):
        jax.block_until_ready(fa(*args))
        jax.block_until_ready(fb(*args))
    ta = tb = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fa(*args))
        ta = min(ta, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fb(*args))
        tb = min(tb, time.perf_counter() - t0)
    return ta, tb


def run(sweep, reps=3, batch=4):
    rows = []
    for L, Lh in sweep:
        x = jnp.asarray(np.random.randn(batch, L).astype(np.float32))
        h = jnp.asarray(np.random.randn(Lh).astype(np.float32))
        fixed = pick_block(Lh)
        # the measured winner (persistent: a warm cache skips the search)
        tuned = tuning.tuned_block(L, Lh, batch, "xla", "measure")
        f_fixed = jax.jit(
            lambda a, b, blk=fixed: fft_conv_os(a, b, block=blk, backend="xla")
        )
        f_tuned = jax.jit(
            lambda a, b, blk=tuned: fft_conv_os(a, b, block=blk, backend="xla")
        )
        if tuned == fixed:
            # Identical schedule — timing it twice only manufactures noise.
            fixed_s = tuned_s = _time(f_fixed, x, h, reps=reps)
        else:
            fixed_s, tuned_s = _time_pair(f_fixed, f_tuned, x, h, reps=reps)
        rows.append(
            {
                "L": L,
                "Lh": Lh,
                "batch": batch,
                "fixed_block": fixed,
                "tuned_block": tuned,
                "fixed_us": fixed_s * 1e6,
                "tuned_us": tuned_s * 1e6,
                "speedup": fixed_s / tuned_s if tuned_s else float("inf"),
                "modeled_fixed_gb": rl.conv_report(L, Lh, batch=batch, block=fixed)[
                    "overlap_save"
                ]["hbm_bytes"]
                / 1e9,
                "modeled_tuned_gb": rl.conv_report(L, Lh, batch=batch, block=tuned)[
                    "overlap_save"
                ]["hbm_bytes"]
                / 1e9,
            }
        )
    return rows


def _assert_model_cache_round_trip():
    """The CI contract: tune="model" is deterministic and cache-backed —
    same spec → same config, cache hit on the second plan, and the model
    path never touches the device timer."""
    tuning.clear_measure_log()
    spec = fft_lib.FFTSpec(n=2**17, kind="fft")
    cfg1 = fft_lib.plan(spec, backend="pallas", tune="model").tuned
    assert cfg1 is not None, "model mode must produce a tuned config"
    # a fresh interning cache forces plan() back through the tuner, which
    # must now hit the persisted entry and return the identical config
    fft_lib._plan_cached.cache_clear()
    cfg2 = fft_lib.plan(spec, backend="pallas", tune="model").tuned
    assert cfg1 == cfg2, (cfg1, cfg2)
    b1 = tuning.tuned_block(2**18, 1025, 2, "xla", "model")
    b2 = tuning.tuned_block(2**18, 1025, 2, "xla", "model")
    assert b1 == b2
    assert tuning.measure_log() == (), "model mode measured something"
    print(f"tuning.model_cache_round_trip,ok,block={b1}")


def main(emit=print, smoke: bool = False):
    sweep = SMOKE_SWEEP if smoke else SWEEP
    emit(
        "tuning.name,seq_len,filter_len,fixed_block,tuned_block,"
        "fixed_ms,tuned_ms,speedup,modeled_fixed_gb,modeled_tuned_gb"
    )
    rows = run(sweep, reps=2 if smoke else 3, batch=2 if smoke else 4)
    for r in rows:
        emit(
            f"tuning,{r['L']},{r['Lh']},{r['fixed_block']},{r['tuned_block']},"
            f"{r['fixed_us']/1e3:.2f},{r['tuned_us']/1e3:.2f},"
            f"{r['speedup']:.3f},{r['modeled_fixed_gb']:.4f},"
            f"{r['modeled_tuned_gb']:.4f}"
        )
    if smoke:
        # numerics: the tuned block changes the schedule, never the math
        L, Lh = SMOKE_SWEEP[0]
        x = jnp.asarray(np.random.randn(2, L).astype(np.float32))
        h = jnp.asarray(np.random.randn(Lh).astype(np.float32))
        y_f = fft_conv_os(x, h, block=pick_block(Lh), backend="xla")
        y_t = fft_conv_os(x, h, backend="xla", tune="measure")
        err = float(jnp.abs(y_f - y_t).max() / jnp.abs(y_f).max())
        assert err < 1e-4, f"tuned overlap-save diverged: {err}"
        _assert_model_cache_round_trip()
        return
    append_trajectory(TRAJECTORY, tuning=rows)


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
