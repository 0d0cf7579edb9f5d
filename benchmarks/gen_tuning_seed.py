"""Regenerate the shipped seed tuning cache (repro/data/tuning_seed.json).

Measures the plan-level tuning spaces for a small roster of common specs on
the current device and dumps the winners — ``mode: "measure"`` entries, so
``tune="measure"`` plans of a seeded spec hit the seed and perform ZERO
first-request measurements (the package-data layer sits beneath the user
cache; see :func:`repro.core.tuning.seed_cache`).

Run on each device_kind whose entries should ship; the JSON accumulates
across runs (existing keys for other devices are preserved).  The roster
deliberately avoids the specs the tuning test-suite measures
(n=2**17 batch=0, n=4096 batch=2, fft2 64×2**17) — those tests assert that
a fresh cache DOES measure, which a seed hit would silence.

  PYTHONPATH=src python -m benchmarks.gen_tuning_seed
"""

from __future__ import annotations

import json
import os
import tempfile

SEED_PATH = os.path.join(
    os.path.dirname(__file__), "..", "src", "repro", "data", "tuning_seed.json"
)

#: (n, batch_hint) roster — the serving/bench hot sizes.
ROSTER = [(8192, 2), (65536, 2)]


def main() -> None:
    # Measure into a scratch user cache so this run neither reads the
    # developer's warm cache nor pollutes it with roster entries.
    scratch = tempfile.mkdtemp(prefix="seed_gen_")
    os.environ["REPRO_TUNING_CACHE"] = os.path.join(scratch, "cache.json")

    from repro.core import fft as fft_lib
    from repro.core import tuning

    entries: dict = {}
    if os.path.exists(SEED_PATH):
        with open(SEED_PATH) as f:
            entries = json.load(f)

    for n, batch in ROSTER:
        spec = fft_lib.FFTSpec(n=n, kind="fft", batch_hint=batch)
        space = tuning.TuningSpace.for_plan(spec, "pallas")
        entries[f"{tuning.device_key()}|{space.key}"] = {
            "config": space.decide("measure"),
            "mode": "measure",
        }

    with open(SEED_PATH, "w") as f:
        json.dump(entries, f, indent=1, sort_keys=True)
    print(f"wrote {len(entries)} entries to {SEED_PATH}")


if __name__ == "__main__":
    main()
