"""Serving launcher: phase-timed batched decode sweeps.

Shares its measurement path (:func:`repro.serving.spectral_serve.sweep_once`)
with ``benchmarks/bench_serve.py``, so the CLI's numbers and the benchmark's
numbers are the same numbers.

  PYTHONPATH=src python -m repro.launch.serve --arch xlstm-125m --reduced \
      --batch 4 --prompt-len 32 --max-new 16
  PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
      --reduced --batch 8 --prompt-len 32,128,512 --phase-times
"""

from __future__ import annotations

import argparse

import jax

from repro.configs.base import get_config
from repro.core import faults
from repro.configs.reduce import make_reduced
from repro.models import model as model_lib
from repro.runtime.compile_cache import enable_compile_cache
from repro.serving.engine import Engine, ServeConfig
from repro.serving.spectral_serve import sweep_once


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument(
        "--prompt-len",
        default="32",
        help="prompt length, or a comma-separated sweep (e.g. 32,128,512)",
    )
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument(
        "--phase-times",
        action="store_true",
        help="print per-phase seconds (prefill / insert / generate) per row",
    )
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)
    params, _ = model_lib.init_unzipped(jax.random.PRNGKey(0), cfg)
    engine = Engine(
        cfg,
        params,
        ServeConfig(
            max_new=args.max_new,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            seed=args.seed,
        ),
    )

    rows = []
    for plen in (int(p) for p in str(args.prompt_len).split(",")):
        r = sweep_once(
            engine,
            batch=args.batch,
            prompt_len=plen,
            max_new=args.max_new,
            warmup=args.warmup,
            seed=args.seed,
        )
        rows.append(r)
        line = (
            f"batch={r['batch']} prompt={r['prompt_len']} max_new={r['max_new']} "
            f"decode={r['decode_tok_per_s']} tok/s e2e={r['e2e_tok_per_s']} tok/s"
        )
        if args.phase_times:
            line += (
                f"  [prefill {r['prefill_s']:.4f}s ({r['prefill_s_per_req']:.4f}/req)"
                f" insert {r['insert_s']:.4f}s generate {r['generate_s']:.4f}s]"
            )
        print(line)
    fired = faults.fault_counters()
    if fired or faults.quarantined():
        # Chaos-drill visibility: injected sites that fired (REPRO_FAULTS)
        # and kernels demoted to their XLA fallback this process.
        print(
            "faults: "
            + (
                " ".join(f"{site}x{n}" for site, n in sorted(fired.items()))
                or "none"
            )
            + f"; quarantined={list(faults.quarantined())}"
            + f"; degradations={len(faults.degradation_log())}"
        )
    return rows


if __name__ == "__main__":
    main()
