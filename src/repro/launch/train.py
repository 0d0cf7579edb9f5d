"""Training launcher: mesh setup, sharded state, checkpoint/restart loop.

The real-cluster entrypoint (works identically on CPU for small configs):

  PYTHONPATH=src python -m repro.launch.train --arch xlstm-125m \
      --reduced --steps 200 --mesh 1x1 --ckpt-dir /tmp/run1

Fault-tolerance behaviour exercised here:
  * auto-resume from the newest complete checkpoint (elastic: the stored
    arrays are topology-free, restore re-shards onto the current mesh);
  * async checkpointing every --ckpt-every steps, keep-N garbage collection;
  * a step watchdog that snapshots + aborts on hangs (crash-only restart);
  * straggler stats (EWMA step times) reported at the end.

XLA flags for compute/comm overlap on real TPU pods are set below (no-ops
on CPU): latency-hiding scheduler + async collectives.
"""

from __future__ import annotations

import argparse
import os
import time

# Overlap flags must be set before jax initializes XLA.
_overlap_flags = (
    " --xla_tpu_enable_latency_hiding_scheduler=true"
    " --xla_tpu_enable_async_collective_fusion=true"
)
if "--dry-overlap-flags" in os.sys.argv:  # documented, applied on TPU only
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + _overlap_flags

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import TrainConfig, get_config
from repro.configs.reduce import make_reduced
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.launch import shardings as sh_lib
from repro.launch.mesh import make_mesh, parallel_config_for
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import StepWatchdog, StragglerStats, with_retries
from repro.sharding.logical import mesh_context
from repro.train.train_loop import init_train_state, make_train_step


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", help="smoke-scale config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--stop-at", type=int, default=None,
                    help="stop (simulate a crash) after this step; schedule still spans --steps")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x2")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--watchdog-timeout", type=float, default=600.0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = make_reduced(cfg)

    dshape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = make_mesh(dshape, ("data", "model"))
    par = parallel_config_for(mesh)

    tc = TrainConfig(
        optimizer=args.optimizer,
        learning_rate=args.lr,
        total_steps=args.steps,
        warmup_steps=max(1, args.steps // 20),
        batch_size=args.batch,
        seq_len=args.seq,
        microbatches=args.microbatches,
        grad_compression=args.grad_compression,
    )
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch)

    # ---- state: init or elastic resume ---------------------------------
    state_sh = sh_lib.train_state_shardings(cfg, tc, mesh, par)
    mgr = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0
    state = None
    if mgr is not None:
        latest = mgr.latest_step()
        if latest is not None:
            like = sh_lib.abstract_train_state(cfg, tc)
            state, extra = mgr.restore(latest, like, shardings=state_sh)
            start_step = int(extra.get("data_step", latest))
            print(f"[resume] restored step {latest} onto mesh {dshape} "
                  f"({mesh.devices.size} devices)")
    if state is None:
        state = init_train_state(jax.random.PRNGKey(tc.seed), cfg, tc)
        state = jax.device_put(state, state_sh)

    data = SyntheticLM(dcfg, start_step=start_step)

    step_raw = make_train_step(cfg, tc)

    def stepper(s, b):
        with mesh_context(mesh, par):
            return step_raw(s, b)

    step_fn = jax.jit(stepper, in_shardings=(state_sh, None), out_shardings=(state_sh, None), donate_argnums=(0,))

    # ---- loop with watchdog / straggler tracking ------------------------
    def on_hang():
        print("[watchdog] step exceeded timeout — aborting for supervisor restart")
        os._exit(17)

    watchdog = StepWatchdog(args.watchdog_timeout, on_hang)
    stats = StragglerStats()
    losses = []
    stop = min(args.steps, args.stop_at) if args.stop_at else args.steps
    for i in range(start_step, stop):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(i).items()}
        watchdog.arm()
        t0 = time.time()
        state, metrics = with_retries(lambda: step_fn(state, batch))
        jax.block_until_ready(metrics["loss"])
        dt = time.time() - t0
        watchdog.disarm()
        slow = stats.record(dt)
        losses.append(float(metrics["loss"]))
        if i % args.log_every == 0 or i == args.steps - 1:
            print(
                f"step {i:5d} loss={losses[-1]:.4f} ce={float(metrics['ce']):.4f} "
                f"gnorm={float(metrics['grad_norm']):.3f} lr={float(metrics['lr']):.2e} "
                f"dt={dt*1e3:.0f}ms{' [straggler]' if slow else ''}",
                flush=True,
            )
        if mgr is not None and (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, state, extra={"data_step": i + 1}, blocking=False)
    if mgr is not None:
        mgr.save(stop, state, extra={"data_step": stop}, blocking=True)
        mgr.wait()
    watchdog.close()
    print("final:", {"loss_first": losses[0], "loss_last": losses[-1], **stats.summary()})
    return losses


if __name__ == "__main__":
    main()
