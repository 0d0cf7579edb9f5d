"""Shared benchmark-trajectory append: one JSON history file per suite.

Each full benchmark run appends one timestamped entry to its
``BENCH_*.json`` so later PRs can diff numbers against this PR's baseline
on the same host.  One implementation for all suites, so format/robustness
changes (e.g. the corrupt-history fallback) happen in one place.
"""

from __future__ import annotations

import json
import os
import time

import jax


def device_provenance() -> dict:
    """``{backend, device_kind}`` of the device this process would run on —
    recorded in every trajectory row so a number diffed across PRs is only
    ever compared against the same silicon (a TPU row and a CPU row of
    the same suite are different baselines, not a regression)."""
    try:
        kind = jax.devices()[0].device_kind
    except Exception:  # pragma: no cover - no devices visible
        kind = "unknown"
    return {"backend": jax.default_backend(), "device_kind": kind}


def load_trajectory(path: str) -> list:
    """The JSON history list at ``path``, tolerantly: unreadable/corrupt
    history starts fresh, and rows written before device provenance existed
    (pre-PR-8 ``BENCH_*.json``) are backfilled with
    ``device_kind``/``backend`` of ``"unknown"`` instead of KeyError-ing
    whichever bench script re-appends to the old trajectory."""
    history = []
    if os.path.exists(path):
        try:
            with open(path) as f:
                history = json.load(f)
        except (json.JSONDecodeError, OSError):
            history = []
    if not isinstance(history, list):
        history = []
    for row in history:
        if isinstance(row, dict):
            row.setdefault("device_kind", "unknown")
            row.setdefault("backend", "unknown")
    return [row for row in history if isinstance(row, dict)]


def append_trajectory(path: str, **payload) -> None:
    """Append ``{timestamp, backend, device_kind, **payload}`` to the JSON
    list at ``path`` (created if missing; unreadable history starts
    fresh)."""
    entry = {
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        **device_provenance(),
        **payload,
    }
    path = os.path.abspath(path)
    history = load_trajectory(path)
    history.append(entry)
    with open(path, "w") as f:
        json.dump(history, f, indent=1)
