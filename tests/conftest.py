"""Shared fixtures.  NOTE: no XLA_FLAGS here — tests see 1 CPU device by
design (the 512-device mesh belongs to the dry-run only).  Multi-device
tests spawn subprocesses with their own flags."""

import os
import subprocess
import sys
import tempfile

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(REPO, "src")

# Keep the autotuner's persistent cache out of the developer's real cache —
# unconditionally, so an exported REPRO_TUNING_CACHE in the developer's
# shell is never read from or written to by the suite.  Subprocesses the
# tests spawn inherit the throwaway path via the environment.
os.environ["REPRO_TUNING_CACHE"] = os.path.join(
    tempfile.mkdtemp(prefix="repro-tuning-"), "tuning.json"
)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


#: A pseudo-backend for ``parametrize("backend", [..., DEMOTED], indirect=True)``:
#: the case plans with ``pallas`` while every kernel launch fails, so each
#: leaf is quarantined and runs ``repro.kernels.ops``' traced-XLA fallback.
DEMOTED = "demoted"


@pytest.fixture
def backend(request):
    """The registered backend a case plans with (indirect parametrization).

    For :data:`DEMOTED` it yields ``"pallas"`` with ``kernel.launch``
    armed for every attempt.  Quarantine is process-global, so the
    quarantine, the armed faults, the degradation ledger and the plan
    cache are cleared after the case.
    """
    from repro.core import faults
    from repro.core import fft as F

    if request.param != DEMOTED:
        yield request.param
        return
    F._plan_cached.cache_clear()
    try:
        with faults.inject_fault("kernel.launch", times=10**9):
            yield "pallas"
    finally:
        faults.clear_quarantine()
        faults.clear_faults()
        faults.clear_degradations()
        F._plan_cached.cache_clear()


def run_in_subprocess(code: str, devices: int = 8, timeout: int = 600) -> str:
    """Run python ``code`` with N fake CPU devices; returns stdout."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code],
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"subprocess failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout
