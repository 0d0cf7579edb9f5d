import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this driver builds the production mesh, the sharded train /
prefill / decode function, lowers it against ShapeDtypeStruct inputs (zero
allocation), compiles, and records:

  * memory_analysis()       → per-chip bytes (proves it fits 16 GB HBM)
  * cost_analysis()         → per-chip FLOPs / bytes (roofline C and M terms)
  * HLO collective parse    → per-chip collective bytes (roofline X term)

Artifacts land in artifacts/dryrun/<arch>__<shape>__<mesh>.json and feed
EXPERIMENTS.md §Dry-run / §Roofline.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only]
"""

import argparse
import dataclasses
import functools
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline as rl
from repro.configs import specs as specs_lib
from repro.configs.base import (
    LM_SHAPES,
    TrainConfig,
    get_config,
    list_archs,
    shapes_for,
)
from repro.launch import shardings as sh_lib
from repro.launch.mesh import make_production_mesh, parallel_config_for
from repro.models import model as model_lib
from repro.sharding.logical import mesh_context
from repro.train.train_loop import make_train_step

ART_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "artifacts", "dryrun")

FSDP_THRESHOLD = 2_000_000_000  # params; above this shard params over data too


def train_cfg_for(arch: str) -> TrainConfig:
    # adafactor for the 480B MoE (Adam moments would not fit); adamw elsewhere.
    # microbatches=8: global batch 256 → 2 sequences per chip per microbatch;
    # bounds live activations (measured: 31.7 GB → 9.0 GB on h2o train_4k)
    # and is what enables the DP-overlap of reduce-scatter with compute.
    opt = "adafactor" if arch == "arctic-480b" else "adamw"
    mb = 16 if arch in ("arctic-480b", "qwen2-vl-72b") else 8
    return TrainConfig(optimizer=opt, microbatches=mb)


def _mesh_and_par(cfg, multi_pod: bool):
    mesh = make_production_mesh(multi_pod=multi_pod)
    fsdp = sh_lib.param_count(cfg) > FSDP_THRESHOLD
    par = parallel_config_for(mesh, fsdp=fsdp, sequence_parallel=True)
    return mesh, par


def _lower_train(cfg, shape, mesh, par, arch):
    tc = train_cfg_for(arch)
    state_sds = sh_lib.abstract_train_state(cfg, tc)
    state_sh = sh_lib.train_state_shardings(cfg, tc, mesh, par)
    batch_sds = specs_lib.input_specs(cfg, shape)
    batch_sh = sh_lib.batch_shardings(cfg, shape, mesh, par, batch_sds)
    metrics_sh = {
        k: NamedSharding(mesh, P())
        for k in ("loss", "ce", "aux", "tokens", "grad_norm", "lr")
    }
    step = make_train_step(cfg, tc)

    def wrapped(state, batch):
        with mesh_context(mesh, par):
            return step(state, batch)

    fn = jax.jit(
        wrapped,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metrics_sh),
        donate_argnums=(0,),
    )
    return fn.lower(state_sds, batch_sds)


def _lower_prefill(cfg, shape, mesh, par):
    batch_sds = specs_lib.input_specs(cfg, shape)
    batch_sh = sh_lib.batch_shardings(cfg, shape, mesh, par, batch_sds)
    params_sds, axes = sh_lib.abstract_params(cfg)
    from repro.sharding.partition import param_shardings

    params_sh = param_shardings(axes, params_sds, mesh, par)

    def wrapped(params, batch):
        with mesh_context(mesh, par):
            return model_lib.prefill(params, batch, cfg)

    # Explicit output shardings: without them XLA may replicate the (large)
    # prefill caches across the mesh.
    out_sds = jax.eval_shape(wrapped, params_sds, batch_sds)
    logits_sh = sh_lib.batch_shardings(cfg, shape, mesh, par, out_sds[0])
    caches_sh = sh_lib.cache_shardings(cfg, mesh, par, out_sds[1])
    fn = jax.jit(
        wrapped, in_shardings=(params_sh, batch_sh), out_shardings=(logits_sh, caches_sh)
    )
    return fn.lower(params_sds, batch_sds)


DECODE_CACHE_MODE = {
    # measured per arch (§Perf hillclimb 2): 'carry' aliases the cache in
    # place but reshards per layer when its sharding conflicts with use;
    # 'ys' double-buffers but never reshards.
    "yi-6b": "ys",
    "phi4-mini-3.8b": "ys",
    "gemma3-12b": "ys",
    "h2o-danube-1.8b": "ys",
}


def _lower_decode(cfg, shape, mesh, par):
    cfg = dataclasses.replace(
        cfg, decode_cache_mode=DECODE_CACHE_MODE.get(cfg.name, "carry")
    )
    params_sds, axes = sh_lib.abstract_params(cfg)
    from repro.sharding.partition import param_shardings

    # Weight-stationary decode for FSDP models (§Perf hillclimb 2): weights
    # keep their 2-D (data × model) sharding; the one-token activations are
    # replicated over data so no weight all-gathers are emitted.  The KV
    # cache keeps the regular batch/SP sharding (computed with `par`).
    par_act = dataclasses.replace(par, decode_weight_stationary=par.fsdp)
    params_sh = param_shardings(axes, params_sds, mesh, par_act)
    tok_sds, cache_sds, t_sds = specs_lib.decode_state_specs(cfg, shape)
    cache_sh = sh_lib.cache_shardings(cfg, mesh, par, cache_sds)
    tok_sh = sh_lib.batch_shardings(cfg, shape, mesh, par_act, tok_sds)

    def wrapped(params, tokens, caches, t):
        with mesh_context(mesh, par_act):
            return model_lib.decode_step(params, tokens, caches, t, cfg)

    out_sds = jax.eval_shape(wrapped, params_sds, tok_sds, cache_sds, t_sds)
    logits_sh = sh_lib.batch_shardings(cfg, shape, mesh, par_act, out_sds[0])
    fn = jax.jit(
        wrapped,
        in_shardings=(params_sh, tok_sh, cache_sh, NamedSharding(mesh, P())),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(2,),
    )
    return fn.lower(params_sds, tok_sds, cache_sds, t_sds)


# ---------------------------------------------------------------------------
# fftbench cells: distributed FFT lowerings (the paper's own workload)
# ---------------------------------------------------------------------------


def _fft_plan_info(fft_shape, model_n: int) -> dict:
    """Plan metadata recorded alongside the lowering, with modeled HBM bytes
    per pass so the round-trip count is observable in every artifact, not
    just asserted by tests.  1-D pencil cells record the per-leaf pass
    programs (one plan per pencil factor); 2-D cells record the ONE joint
    rows+columns program ``pfft2d`` now splits around its all-to-alls.
    Each leaf also carries the GPU-shaped account (``gpu_reports``): per-pass
    shared-memory bytes against the device budget and global-memory round
    trips under the ``pallas_gpu`` claim set, so the pallas↔xla crossover is
    auditable from the artifact alone."""
    from repro.core import distributed as dist
    from repro.core import plan as plan_lib
    from repro.kernels.fft_gpu import gpu_claims

    def _gpu_report(m: int, batch: int) -> dict:
        rep = rl.gpu_program_report(
            plan_lib.plan_fft(m).passes, gpu_claims, batch=batch
        )
        return {
            k: rep[k]
            for k in (
                "global_round_trips",
                "smem_bytes_max",
                "smem_budget",
                "modeled_global_bytes",
                "claims",
            )
        }

    def _bluestein_reports(lengths, batch: int) -> list:
        # Non-pow2 leaves route through the Bluestein chirp-conv program;
        # record its pad/flops overhead vs the hypothetical mixed-radix
        # transform so the tax is observable in the artifact.
        return [
            rl.bluestein_report(m, batch=batch) for m in lengths if m & (m - 1)
        ]

    if fft_shape.kind == "fft2d":
        # (batch, n1, n2) images: last axis n2 rows-first, columns n1.
        n_row, n_col = fft_shape.n2, fft_shape.n
        info = {
            "leaf_lengths": [n_col, n_row],
            "joint_schedule": plan_lib.describe(n_row, n2=n_col),
            "hbm_round_trips": plan_lib.plan_fft2(n_row, n_col).hbm_round_trips,
            "pass_programs": [
                rl.fft_pass_report(n_row, batch=fft_shape.batch, n2=n_col)
            ],
            "gpu_reports": [_gpu_report(n_row, fft_shape.batch * n_col)],
        }
        blu = _bluestein_reports([n_row], fft_shape.batch * n_col)
        if blu:
            info["bluestein_reports"] = blu
        return info
    # The tuned pencil schedule the driver will actually run: modeled-only
    # (`tuning.pencil_config`), so the dry-run host derives the same factors
    # / packing / chunk count as every SPMD host of the real mesh.
    ppl = dist.plan_pencil(fft_shape.n, model_n)
    leaf_ns = [ppl.n1, ppl.n2]
    total = fft_shape.n
    # Schedule facts only — backend negotiation on the dry-run host (CPU)
    # would misstate what the production TPU pencil driver picks.
    info = {
        "leaf_lengths": leaf_ns,
        "leaf_schedules": [plan_lib.describe(m) for m in leaf_ns],
        "pencil_schedule": ppl.describe(),
        "a2a_count": ppl.a2a_count(fft_shape.kind != "fftconv"),
        "comm_report": {
            k: ppl.report[k]
            for k in ("comm_bytes_per_step", "local_hbm_bytes", "modeled_s")
        },
        "hbm_round_trips": max(
            plan_lib.plan_fft(m).hbm_round_trips for m in leaf_ns
        ),
        # A length-m leaf runs over batch × (total/m) pencils — charge the
        # full global pencil count or the modeled bytes understate the real
        # traffic by total/m (the figure bench_table1 reports would disagree).
        "pass_programs": [
            rl.fft_pass_report(m, batch=fft_shape.batch * (total // m))
            for m in leaf_ns
        ],
        "gpu_reports": [
            _gpu_report(m, fft_shape.batch * (total // m)) for m in leaf_ns
        ],
    }
    if fft_shape.kind == "fftconv":
        # One-shot vs overlap-save modeled bytes at a canonical 4k-tap
        # filter, so every conv artifact shows the schedule the single-chip
        # path would pick and what the blocked alternative costs.
        info["conv_report"] = rl.conv_report(
            fft_shape.n, 4097, batch=fft_shape.batch
        )
    blu = _bluestein_reports(
        leaf_ns, fft_shape.batch * (total // max(leaf_ns))
    )
    if blu:
        info["bluestein_reports"] = blu
    return info


def _lower_fft(fft_shape, mesh, par):
    from repro.core import distributed as dist

    batch_axes = ("pod", "data") if par.pod_axis else ("data",)
    model_n = mesh.shape["model"]

    if fft_shape.kind == "fft1d":
        n = fft_shape.n
        spec = P(batch_axes, "model")
        x_sds = jax.ShapeDtypeStruct((fft_shape.batch, n), jnp.float32)

        def body(xr, xi):
            return dist.pfft(
                xr, xi, n=n, axis_name="model", num_shards=model_n
            )

        fn = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
            check_vma=False,
        )
        jfn = jax.jit(fn, in_shardings=(NamedSharding(mesh, spec),) * 2)
        return jfn.lower(x_sds, x_sds)

    if fft_shape.kind == "fft2d":
        n1, n2 = fft_shape.n, fft_shape.n2
        spec = P(batch_axes, "model", None)
        x_sds = jax.ShapeDtypeStruct((fft_shape.batch, n1, n2), jnp.float32)

        def body2(xr, xi):
            return dist.pfft2d(
                xr, xi, n1=n1, n2=n2, axis_name="model", num_shards=model_n
            )

        fn = jax.shard_map(
            body2, mesh=mesh, in_specs=(spec, spec), out_specs=(spec, spec),
            check_vma=False,
        )
        jfn = jax.jit(fn, in_shardings=(NamedSharding(mesh, spec),) * 2)
        return jfn.lower(x_sds, x_sds)

    if fft_shape.kind == "fftconv":
        n = fft_shape.n
        spec = P(batch_axes, "model")
        hspec = P("model")
        x_sds = jax.ShapeDtypeStruct((fft_shape.batch, n), jnp.float32)
        h_sds = jax.ShapeDtypeStruct((n,), jnp.float32)

        def bodyc(xr, xi, hr, hi):
            # forward in pencil layout, multiply, inverse from pencil:
            # 4 all-to-alls total instead of 6 (beyond-paper optimisation).
            yr, yi = dist.pfft(
                xr, xi, n=n, axis_name="model", num_shards=model_n,
                natural_order=False,
            )
            pr = yr * hr - yi * hi
            pi = yr * hi + yi * hr
            return dist.pifft(
                pr, pi, n=n, axis_name="model", num_shards=model_n,
                from_pencil=True,
            )

        fn = jax.shard_map(
            bodyc, mesh=mesh, in_specs=(spec, spec, hspec, hspec),
            out_specs=(spec, spec),
            check_vma=False,
        )
        jfn = jax.jit(
            fn,
            in_shardings=(NamedSharding(mesh, spec),) * 2
            + (NamedSharding(mesh, hspec),) * 2,
        )
        return jfn.lower(x_sds, x_sds, h_sds, h_sds)

    raise ValueError(fft_shape.kind)


# ---------------------------------------------------------------------------


def active_params(cfg) -> int:
    """Active params per token (MoE: top-k + shared experts only)."""
    total = sh_lib.param_count(cfg)
    if cfg.num_experts and cfg.top_k:
        values, _ = sh_lib.abstract_params(cfg)
        import jax as _jax

        expert = 0
        flat = _jax.tree_util.tree_flatten_with_path(values)[0]
        for path, leaf in flat:
            keys = "/".join(str(getattr(p, "key", getattr(p, "name", p))) for p in path)
            # stacked-over-layers expert weights are rank 4: (L, E, D, F)
            is_expert = cfg.num_experts in leaf.shape[:2] and leaf.ndim in (3, 4)
            if "moe" in keys and any(k in keys for k in ("wi_gate", "wi_up", "wo")) and is_expert:
                expert += int(leaf.size)
        active = total - expert + expert * cfg.top_k // cfg.num_experts
        return active
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False) -> dict:
    mesh_tag = "2x16x16" if multi_pod else "16x16"
    os.makedirs(ART_DIR, exist_ok=True)
    out_path = os.path.join(ART_DIR, f"{arch}__{shape_name}__{mesh_tag}.json")
    if os.path.exists(out_path) and not force:
        with open(out_path) as f:
            return json.load(f)

    cfg = get_config(arch)
    t0 = time.time()
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag,
        "status": "error",
    }
    try:
        if cfg.family == "fft":
            import repro.configs.fftbench as fb

            fft_shape = next(s for s in fb.FFT_SHAPES if s.name == shape_name)
            mesh = make_production_mesh(multi_pod=multi_pod)
            par = parallel_config_for(mesh)
            lowered = _lower_fft(fft_shape, mesh, par)
            record["fft_plan"] = _fft_plan_info(fft_shape, mesh.shape["model"])
            tokens = 0
            n_active = 0
            dtype = "f32"
        else:
            shape = LM_SHAPES[shape_name]
            mesh, par = _mesh_and_par(cfg, multi_pod)
            if shape.kind == "train":
                lowered = _lower_train(cfg, shape, mesh, par, arch)
                tokens = shape.global_batch * shape.seq_len
            elif shape.kind == "prefill":
                lowered = _lower_prefill(cfg, shape, mesh, par)
                tokens = shape.global_batch * shape.seq_len
            else:
                lowered = _lower_decode(cfg, shape, mesh, par)
                tokens = shape.global_batch  # one new token per sequence
                if "spectral" in cfg.pattern():
                    # streaming-conv decode plan: chunk/block grain, flush
                    # cadence and per-flush HBM traffic of the spectral state
                    from repro.models.layers import spectral as spec_lib

                    record["spectral_stream"] = spec_lib.stream_plan_info(
                        cfg, batch=shape.global_batch
                    )
            n_active = active_params(cfg)
            dtype = "bf16"

        compiled = lowered.compile()
        t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        # Loop-aware costs from our own HLO walk (XLA's cost_analysis counts
        # while bodies once — verified; see analysis/hlo.py).
        from repro.analysis.hlo import analyze as hlo_analyze

        hc = hlo_analyze(hlo)
        coll = {
            "per_device_bytes": hc.collective_bytes,
            "by_type": hc.collective_by_type,
            "num_ops": hc.collective_ops,
            "unknown_trip_loops": hc.unknown_trip_loops,
        }
        flops = float(hc.flops)
        bytes_acc = float(hc.bytes)
        n_chips = mesh.devices.size

        peak_mem = (
            ma.argument_size_in_bytes
            + ma.output_size_in_bytes
            + ma.temp_size_in_bytes
            - ma.alias_size_in_bytes
        )
        terms = rl.roofline_terms(
            flops, bytes_acc, coll["per_device_bytes"], dtype=dtype
        )
        # MODEL_FLOPS: 6·N·D for a train step (fwd+bwd), 2·N·D fwd-only.
        useful = 0.0
        if cfg.family != "fft":
            per_tok = 6 if LM_SHAPES[shape_name].kind == "train" else 2
            useful = float(per_tok) * n_active * tokens
        record.update(
            status="ok",
            compile_s=round(t_compile, 1),
            chips=int(n_chips),
            per_chip=dict(
                flops=flops,
                dot_flops=float(hc.dot_flops),
                hbm_bytes=bytes_acc,
                collective_bytes=coll["per_device_bytes"],
                collective_by_type=coll["by_type"],
                collective_ops=coll["num_ops"],
                unknown_trip_loops=coll["unknown_trip_loops"],
                xla_cost_flops=float(ca.get("flops", 0.0)),
                xla_bytes_accessed=float(ca.get("bytes accessed", 0.0)),
                peak_memory_bytes=int(peak_mem),
                argument_bytes=int(ma.argument_size_in_bytes),
                temp_bytes=int(ma.temp_size_in_bytes),
                output_bytes=int(ma.output_size_in_bytes),
                aliased_bytes=int(ma.alias_size_in_bytes),
            ),
            fits_hbm=bool(peak_mem < rl.V5E.hbm_bytes),
            roofline=terms,
            useful_flops=useful,
            useful_flops_frac=(useful / n_chips) / flops if flops else 0.0,
            active_params=n_active,
            tokens_per_step=tokens,
        )
    except Exception as e:  # noqa: BLE001 — record and continue the sweep
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()[-2000:]
    with open(out_path, "w") as f:
        json.dump(record, f, indent=1)
    return record


def all_cells(include_fft=True):
    cells = []
    for arch in list_archs():
        cfg = get_config(arch)
        if cfg.family == "fft":
            if include_fft:
                import repro.configs.fftbench as fb

                cells += [(arch, s.name) for s in fb.FFT_SHAPES]
            continue
        cells += [(arch, s.name) for s in shapes_for(arch)]
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true", help="2x16x16 mesh")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-fft", action="store_true")
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    if args.all:
        cells = all_cells(include_fft=not args.no_fft)
    else:
        assert args.arch and args.shape, "--arch and --shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape_name in cells:
        for mp in meshes:
            rec = run_cell(arch, shape_name, mp, force=args.force)
            if rec["status"] == "ok":
                t = rec["roofline"]
                print(
                    f"OK   {arch:18s} {shape_name:12s} {rec['mesh']:8s} "
                    f"compile={rec['compile_s']:6.1f}s "
                    f"C={t['compute_s']*1e3:8.2f}ms M={t['memory_s']*1e3:8.2f}ms "
                    f"X={t['collective_s']*1e3:8.2f}ms bound={t['bound']:10s} "
                    f"mem={rec['per_chip']['peak_memory_bytes']/1e9:5.2f}GB "
                    f"fits={rec['fits_hbm']}",
                    flush=True,
                )
            else:
                failures += 1
                print(f"FAIL {arch:18s} {shape_name:12s} mp={mp}: {rec['error']}", flush=True)
    print(f"done; failures={failures}")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
