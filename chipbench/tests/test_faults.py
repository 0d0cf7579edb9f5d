"""Whole runs of the harness on the CPU, at small sizes, with the timed path
broken underneath: ``correct`` has to come out false for every fault a
cell can have, and true with none."""

import jax
import pytest

import _faults

ONE_CHIP = ("sar_fft2", "sar_range_fft", "conv_os_4097")


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_sound_run_is_correct(cell):
    r = _faults.run_small(jax, cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {"msamples_per_s", "setup_s"}


@pytest.mark.parametrize("fault", sorted(_faults.FAULTS))
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_fault_fails_the_run(cell, fault):
    r = _faults.run_small(jax, cell, _faults.FAULTS[fault])
    assert not r["correct"], r["checks"]
    assert r["checks"]["max_err_rel"]["value"] > r["checks"]["max_err_rel"]["limit"]

