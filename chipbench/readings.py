"""The readings a cell's limit is set from: the program's and the control's
``max_err_rel`` on many seeds, at the cell's own size, in one process.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3,...

For each seed it builds the cell's inputs and jitted entry as a run does,
calls it once, and compares the output with the float64 reference; then it
puts the control (the same transform with every GEMM in three bfloat16
passes) in the program's place on the same inputs and compares that.  One
JSON line per seed, then a summary with the lower reading (the program's
largest) and the upper reading (the control's smallest).  The benchmark's
own runs never run the control.  Same refusals as ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def reading(jax, cell, seed: int, devices) -> tuple[float, float]:
    """``(program, control)``: the ``max_err_rel`` of one call of the cell's
    jitted entry, and of the control on the same inputs, for ``seed``."""
    from chipbench.lib import reference

    e, cfg, traffic = cell.entry, cell.config, cell.traffic
    built = e.build(jax, cfg, traffic, seed, devices[: cell.chips])
    sel = e.picks(cfg, traffic, seed)
    out = jax.block_until_ready(built["fn"](*built["args"]))
    got, host_in = e.answers(out, built["args"], sel)
    del out
    ref = e.reference(host_in, cfg, traffic)
    ctl = e.control(jax, built["args"], sel, cfg, traffic)
    return reference.max_err_rel(got, ref), reference.max_err_rel(ctl, ref)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench import run
    from chipbench.lib import harness

    cell = harness.load_cell(args.workload)
    jax, devices = run._preflight(cell.chips)
    program, control = [], []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        p, c = reading(jax, cell, seed, devices)
        program.append(p)
        control.append(c)
        print(json.dumps({
            "cell": cell.name, "seed": seed, "program": p, "control": c,
            "seconds": time.perf_counter() - t0,
        }), flush=True)
    print(json.dumps({
        "cell": cell.name, "seeds": len(program), "lower": max(program), "upper": min(control),
        "limit": cell.limits["max_err_rel"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
