"""Faults planted under the timed path, and small configurations the tests
run whole on the CPU.

Each fault takes the entry's jitted callable and returns a broken one:

* ``unchanged``: the call returns its input, as a step that leaves its
  state as it was;
* ``half_batch``: the second half of the batch is left out (zeros);
* ``answer_altered``: one output bin of every answer is wrong where the
  kernel writes it.

Every cell runs on one chip, so no cell has an exchange between chips to
leave out.
"""

from __future__ import annotations

import time

SMALL = {
    "sar_fft2": {"batch": 2, "azimuth_lines": 16, "range_samples": 256},
    "sar_range_fft": {"batch": 2, "azimuth_lines": 8, "range_samples": 512},
    "conv_os_4097": {"channels": 4, "signal_len": 8192, "taps": 257},
}


def unchanged(jax, fn, built):
    return jax.jit(lambda *a: a[0])


def half_batch(jax, fn, built):
    return jax.jit(lambda *a: (o := fn(*a)).at[o.shape[0] // 2 :].set(0))


def answer_altered(jax, fn, built):
    return jax.jit(lambda *a: fn(*a).at[..., 1].set(0))


FAULTS = {"unchanged": unchanged, "half_batch": half_batch, "answer_altered": answer_altered}


def small_cell(name):
    from chipbench.lib import harness

    cell = harness.load_cell(name)
    cell.config.update(SMALL[name])
    return cell


def run_small(jax, name, fault=None, seed=2**31 + 7):
    """One whole run of the small cell on the CPU's Pallas interpreter,
    with ``fault`` planted under the timed path."""
    from chipbench.lib import harness
    from repro.core import fft as F

    wrap = None if fault is None else (lambda fn, built: fault(jax, fn, built))
    with F.use_backend("pallas"):
        return harness.run(
            jax, small_cell(name), seed, 0.2, False, jax.devices(), time.perf_counter(), wrap=wrap
        )


def readings(jax, name, seeds):
    """The program's and the control's widest gaps over ``max|ref|``, per
    seed, at the small size."""
    from chipbench.readings import reading
    from repro.core import fft as F

    cell = small_cell(name)
    with F.use_backend("pallas"):
        return [reading(jax, cell, s, jax.devices()) for s in seeds]
