"""The benchmark's own tests: ``python -m pytest chipbench/tests``.

They run on the CPU (``JAX_PLATFORMS=cpu``): the metric readers on traces
recorded on the chip, the essential-work counts against hand counts, the
peaks table, the control, and whole runs of the harness with the timed
path broken underneath.
"""

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

