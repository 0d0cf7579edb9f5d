"""Distributed pencil-FFT scaling sweep — packed/overlapped vs serial.

A CPU tool: it measures nothing on a chip.  Each point runs in a child
process forced to ``JAX_PLATFORMS=cpu`` with fake host devices, so its
times are XLA-on-CPU times, and a chip held by this process would never
reach the children.  The on-chip pencil path is ``chip_smoke.py
--four-chips``.

Runs the pencil path at 8/16/32/48 fake devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, the
test-suite's subprocess idiom, one fresh process per point so the device
count can vary) and times two schedules on the same shape:

* ``serial`` — the historical per-plane schedule (``pack=False``): two
  all-to-alls per transpose step, every collective serialized against the
  local FFT work;
* ``tuned`` — :func:`repro.core.distributed.plan_pencil`'s modeled pick:
  split-complex pair packed into ONE stacked a2a per transpose, the two
  inner transposes strip-mined into K chunks and double-buffered against
  the column FFT/twiddle.

48 devices is not a power of two, so that point runs a 3×16 data×model
mesh (batch sharded 3-way, the transform pencil-split over 16) — the
realistic pod shape where the FFT axis is a power-of-two sub-mesh.

Each row records both wall-clocks, the tuned schedule (n1×n2, K), the
jaxpr-verified collective counts, and the roofline comm model
(``comm_mb_step`` per-transpose wire bytes, ``local_hbm_mb``,
``modeled_s``/``serial_modeled_s`` — :func:`repro.analysis.roofline.
pencil_report`).  Full runs append a ``BENCH_pfft.json`` trajectory
entry.  ``--smoke`` runs one 16-device point with small N, asserts
numerics + collective counts, and skips the trajectory — the CI contract.

  PYTHONPATH=src python -m benchmarks.bench_pfft [--smoke]
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks._trajectory import append_trajectory

TRAJECTORY = os.path.join(os.path.dirname(__file__), "..", "BENCH_pfft.json")
SRC = os.path.join(os.path.dirname(__file__), "..", "src")

#: (devices, data_parallel, fft_shards, n, batch) — the scaling sweep.
SWEEP = [
    (8, 1, 8, 1 << 18, 4),
    (16, 1, 16, 1 << 18, 4),
    (32, 1, 32, 1 << 18, 4),
    (48, 3, 16, 1 << 18, 6),
]
SMOKE_SWEEP = [(16, 1, 16, 1 << 14, 2)]

_CHILD = r"""
import json, sys, time
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.core import distributed as D

dp, d, n, batch, reps = (int(a) for a in sys.argv[1:6])
axes = ("b", "x")
mesh = jax.make_mesh((dp, d), axes)
pl = D.plan_pencil(n, d)

spec = P("b", "x")


def make(**kw):
    fn = jax.shard_map(
        lambda xr, xi: D.pfft(
            xr, xi, n=n, axis_name="x", num_shards=d, **kw
        ),
        mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec), check_vma=False)
    return jax.jit(fn)


serial = make(pack=False)
tuned = make()  # the modeled pick: packed, K chunks

rng = np.random.default_rng(0)
x = rng.standard_normal((batch, n)).astype(np.float32)
sh = jax.sharding.NamedSharding(mesh, spec)
xr = jax.device_put(x, sh)
xi = jax.device_put(np.zeros_like(x), sh)

# correctness first: both schedules against numpy
ref = np.fft.fft(x)
for name, fn in (("serial", serial), ("tuned", tuned)):
    yr, yi = fn(xr, xi)
    rel = (np.abs((np.asarray(yr) + 1j * np.asarray(yi)) - ref).max()
           / np.abs(ref).max())
    assert rel < 5e-5, (name, rel)

# jaxpr-verified collective counts (what the packing/overlap bought)
a2a_serial = str(jax.make_jaxpr(serial)(xr, xi)).count("all_to_all")
a2a_tuned = str(jax.make_jaxpr(tuned)(xr, xi)).count("all_to_all")
assert a2a_serial == 6, a2a_serial
assert a2a_tuned == 2 * pl.a2a_chunks + 1, (a2a_tuned, pl.a2a_chunks)


def time_pair(fa, fb):
    for _ in range(2):
        jax.block_until_ready(fa(xr, xi))
        jax.block_until_ready(fb(xr, xi))
    ta = tb = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fa(xr, xi))
        ta = min(ta, time.perf_counter() - t0)
        t0 = time.perf_counter()
        jax.block_until_ready(fb(xr, xi))
        tb = min(tb, time.perf_counter() - t0)
    return ta, tb


t_serial, t_tuned = time_pair(serial, tuned)
rep = pl.report
print("ROW=" + json.dumps({
    "devices": dp * d, "mesh": f"{dp}x{d}", "fft_shards": d,
    "n": n, "batch": batch,
    "n1": pl.n1, "n2": pl.n2, "K": pl.a2a_chunks,
    "a2a_serial": a2a_serial, "a2a_tuned": a2a_tuned,
    "t_serial_s": t_serial, "t_tuned_s": t_tuned,
    "speedup": t_serial / t_tuned,
    "comm_mb_step": rep["comm_bytes_per_step"] / 2**20,
    "local_hbm_mb": rep["local_hbm_bytes"] / 2**20,
    "modeled_s": rep["modeled_s"], "serial_modeled_s": rep["serial_s"],
}))
"""


def _run_point(devices, dp, d, n, batch, reps) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(SRC)
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(dp), str(d), str(n), str(batch),
         str(reps)],
        capture_output=True, text=True, env=env, timeout=1200,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"bench_pfft child ({devices} devices) failed:\n{out.stderr}"
        )
    line = next(
        ln for ln in out.stdout.splitlines() if ln.startswith("ROW=")
    )
    return json.loads(line[len("ROW="):])


def run(sweep, reps=5) -> list:
    rows = []
    print(
        "pfft,devices,mesh,n,batch,n1,n2,K,a2a_serial,a2a_tuned,"
        "t_serial_s,t_tuned_s,speedup,comm_mb_step,modeled_s"
    )
    for devices, dp, d, n, batch in sweep:
        row = _run_point(devices, dp, d, n, batch, reps)
        rows.append(row)
        print(
            f"pfft,{row['devices']},{row['mesh']},{row['n']},{row['batch']},"
            f"{row['n1']},{row['n2']},{row['K']},{row['a2a_serial']},"
            f"{row['a2a_tuned']},{row['t_serial_s']:.4f},"
            f"{row['t_tuned_s']:.4f},{row['speedup']:.2f},"
            f"{row['comm_mb_step']:.3f},{row['modeled_s']:.2e}",
            flush=True,
        )
    return rows


def main(smoke: bool = False) -> None:
    if smoke:
        rows = run(SMOKE_SWEEP, reps=3)
        for row in rows:
            assert row["a2a_tuned"] < row["a2a_serial"], row
        print("bench_pfft smoke ok")
        return
    rows = run(SWEEP)
    slow = [r for r in rows if r["t_tuned_s"] > r["t_serial_s"]]
    if slow:
        print(f"# WARNING: tuned slower at {[r['devices'] for r in slow]}")
    append_trajectory(TRAJECTORY, rows=rows)


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv[1:])
