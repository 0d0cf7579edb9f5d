"""Roofline-seeded autotuner — measured plan tuning with a persistent cache.

The paper's performance claim rests on dividing the data "reasonably
according to the size of data"; until now every such division in this
reproduction was a fixed constant (``OS_FACTOR=8`` overlap-save blocks, the
VMEM-budget pass chunk, the ``FUSED_MAX`` crossover).  Adámek et al. (GPU
overlap-and-save) and Bergach et al. (model-guided FFT mapping) both show
those constants leave several-fold throughput on the table across shapes.
This module turns each of them into a searched decision:

1. a :class:`TuningSpace` enumerates the candidate configs of one decision —
   overlap-save block sizes for a ``(L, Lh)`` convolution, or whole plan
   configs (fused-vs-split crossover, per-pass chunk width, leaf batch
   tile) for an :class:`~repro.core.fft.FFTSpec`;
2. the roofline model prunes the space
   (:func:`repro.analysis.roofline.prune_candidates`): only candidates
   within ~20% of the modeled-minimum cost (HBM bytes for plan configs,
   device seconds for overlap-save blocks and pencil schedules), and whose
   per-grid-step working set fits :data:`~repro.core.limits.VMEM_BUDGET`,
   survive;
3. ``tune="measure"`` times the survivors on device (min-of-reps,
   ``block_until_ready``) and records the winner in a **persistent JSON
   cache** keyed by ``(device_kind, backend, spec)`` — so the search runs
   once per device and shape, ever; ``tune="model"`` skips measurement and
   takes the modeled pick — the zero-measurement default, which keeps the
   fixed heuristic on modeled ties but DOES deviate when the model finds a
   strictly cheaper schedule (e.g. swapping a direct leaf whose n² DFT
   matrix dominates the stream for a fused four-step engine, or a smaller
   overlap-save block whose leaves do less MXU work per point);
   ``tune="off"`` bypasses the tuner entirely and is the exact historical
   behavior.

Consumers — :func:`repro.core.fft.plan`,
:func:`repro.core.overlap.fft_conv_os` / :class:`~repro.core.overlap.
StreamingConv`, and :func:`repro.core.distributed.pconv_os_sharded` — pass
``tune=`` through; the default mode comes from the ``REPRO_FFT_TUNE``
environment variable (``model`` when unset).  The cache file lives at
``REPRO_TUNING_CACHE`` (default ``~/.cache/repro-fft/tuning.json``).

Every on-device timing is appended to :func:`measure_log`, which is how the
tests assert cache hits perform **zero** measurements.
"""

from __future__ import annotations

import json
import os
import time
import warnings
from typing import Callable, Optional

from repro.core import faults

__all__ = [
    "TUNE_MODES",
    "CACHE_SCHEMA_VERSION",
    "resolve_mode",
    "TuningSpace",
    "TuningCache",
    "cache",
    "cache_path",
    "seed_cache",
    "device_key",
    "plan_config",
    "tuned_block",
    "modeled_block",
    "pencil_config",
    "measure_log",
    "clear_measure_log",
]

TUNE_MODES = ("off", "model", "measure")

#: On-disk cache file schema.  Bump when the file layout changes; a file
#: with any other version is quarantined as foreign rather than guessed at.
CACHE_SCHEMA_VERSION = 1

#: Tolerance of the roofline pruning: candidates more than 20% above the
#: modeled-minimum cost are never worth measuring.
PRUNE_TOL = 0.2

#: Version of the overlap-save block model, part of every ``os_block``
#: cache key: a pick persisted under another model is not found, so it is
#: never served in place of this model's pick.
OS_BLOCK_MODEL = "t1"

#: Timing discipline for the measurement pass.
MEASURE_REPS = 5
MEASURE_WARMUP = 2

#: A candidate must beat the fixed heuristic by this fraction to dethrone
#: it: within the margin the measurement is noise, and keeping the default
#: preserves "tuned is never slower than fixed" across noisy re-runs.
DEFAULT_MARGIN = 0.10

#: Survivors are timed in this many interleaved rounds (min across rounds),
#: so slow machine drift lands on every candidate instead of whichever was
#: measured last.
MEASURE_ROUNDS = 2


def resolve_mode(tune: Optional[str]) -> str:
    """Resolve a ``tune=`` argument: explicit value, else ``REPRO_FFT_TUNE``,
    else ``"model"`` (the zero-measurement modeled pick)."""
    if tune is None:
        tune = os.environ.get("REPRO_FFT_TUNE") or "model"
    if tune not in TUNE_MODES:
        raise faults.PlanError(f"tune must be one of {TUNE_MODES}, got {tune!r}")
    return tune


# ---------------------------------------------------------------------------
# Persistent cache
# ---------------------------------------------------------------------------


def cache_path() -> str:
    """Resolved per-operation so tests can redirect via the environment."""
    env = os.environ.get("REPRO_TUNING_CACHE")
    if env:
        return env
    return os.path.join(
        os.path.expanduser("~"), ".cache", "repro-fft", "tuning.json"
    )


_SEED_CACHE: Optional[dict] = None


def seed_cache() -> dict:
    """The read-only seed tuning cache shipped as package data
    (``repro/data/tuning_seed.json``) — measured winners for common
    (device_kind, spec) pairs, layered *beneath* the user cache so a spec
    present in the seed plans tuned out of the box with zero first-request
    measurement.  Missing or unreadable package data degrades to empty."""
    global _SEED_CACHE
    if _SEED_CACHE is None:
        data: dict = {}
        try:
            from importlib import resources

            text = (
                resources.files("repro.data")
                .joinpath("tuning_seed.json")
                .read_text()
            )
            loaded = json.loads(text)
            if isinstance(loaded, dict):
                data = loaded
        except Exception:  # pragma: no cover - package-data-less installs
            data = {}
        _SEED_CACHE = data
    return _SEED_CACHE


def device_key() -> str:
    """First device's kind — the hardware half of every cache key (a config
    tuned on one accelerator generation must not leak onto another)."""
    import jax  # local: keep module import cheap

    try:
        return jax.devices()[0].device_kind.replace("|", "_")
    except Exception:  # pragma: no cover - backendless builds
        return jax.default_backend()


class TuningCache:
    """The persistent winner store: a versioned JSON file
    (``{"version": CACHE_SCHEMA_VERSION, "entries": {...}}``) whose entries
    map ``device|backend|decision|spec`` keys to
    ``{"config": ..., "mode": ...}``.

    Reads are lazy and memoized per path.  Writes re-read the file, merge,
    and replace it atomically (temp file + ``os.replace``), so concurrent
    processes sharing one cache append winners instead of clobbering each
    other's, and a reader can never observe a half-written file.  An
    unwritable cache directory degrades to memory-only rather than failing
    the transform.

    Robustness: a corrupted, truncated, or foreign-schema cache file is
    quarantined to a ``.corrupt`` sibling with a warning and the cache
    rebuilds from the packaged seed (:func:`seed_cache` layers beneath
    every :meth:`get`) — seeded specs keep planning with zero measurements.
    Pre-versioning flat files are still readable and upgrade to the
    versioned schema on the next write.  The ``tuning.cache_read`` /
    ``tuning.cache_write`` fault sites cover both paths."""

    def __init__(self):
        self._mem: dict = {}
        self._loaded_path: Optional[str] = None

    @staticmethod
    def _quarantine_corrupt(path: str, reason: str) -> None:
        corrupt = path + ".corrupt"
        try:
            os.replace(path, corrupt)
            moved = f"quarantined to {corrupt}"
        except OSError:
            moved = "could not quarantine the file"
        warnings.warn(
            f"tuning cache {path} is unusable ({reason}); {moved}; "
            f"rebuilding from the packaged seed",
            RuntimeWarning,
            stacklevel=3,
        )

    @staticmethod
    def _validate_schema(data, path: str) -> dict:
        """Entries of a loaded cache document, or {} after quarantining a
        foreign-schema file."""
        if (
            isinstance(data, dict)
            and data.get("version") == CACHE_SCHEMA_VERSION
            and isinstance(data.get("entries"), dict)
        ):
            return data["entries"]
        if (
            isinstance(data, dict)
            and "version" not in data
            and all(
                isinstance(v, dict) and "config" in v for v in data.values()
            )
        ):
            # Pre-versioning flat schema: readable as-is, upgraded on the
            # next put().
            return data
        TuningCache._quarantine_corrupt(
            path, f"foreign schema (version {data.get('version') if isinstance(data, dict) else type(data).__name__!r})"
        )
        return {}

    @staticmethod
    def _read_file(path: str) -> dict:
        if not os.path.exists(path):
            return {}
        try:
            faults.maybe_fail("tuning.cache_read", path=path)
            with open(path) as f:
                data = json.load(f)
        except faults.TuningCacheError:
            # Injected read fault: behave like an unreadable file — memory +
            # seed keep serving, nothing is quarantined (the file is fine).
            return {}
        except (json.JSONDecodeError, OSError) as err:
            TuningCache._quarantine_corrupt(path, f"{type(err).__name__}: {err}")
            return {}
        return TuningCache._validate_schema(data, path)

    def _load(self) -> dict:
        path = cache_path()
        if self._loaded_path != path:
            self._loaded_path = path
            self._mem = self._read_file(path)
        return self._mem

    def get(self, key: str) -> Optional[dict]:
        hit = self._load().get(key)
        if hit is not None:
            return hit
        # User-cache miss: fall through to the shipped read-only seed, so
        # common (device_kind, spec) pairs are tuned out of the box.  A
        # later put() of the same key shadows the seed (user cache wins).
        return seed_cache().get(key)

    def put(self, key: str, entry: dict) -> None:
        mem = self._load()
        mem[key] = entry
        path = cache_path()
        try:
            faults.maybe_fail("tuning.cache_write", path=path)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            # Merge-on-write: another process may have persisted winners
            # since our load; union them (our new entry wins its own key).
            merged = {**self._read_file(path), **mem}
            doc = {"version": CACHE_SCHEMA_VERSION, "entries": merged}
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=1, sort_keys=True)
            os.replace(tmp, path)
            self._mem = merged
        except (OSError, faults.TuningCacheError):
            pass  # memory-only fallback

    def clear(self) -> None:
        """Drop the in-memory view AND the persisted file (tests)."""
        self._mem = {}
        self._loaded_path = None
        path = cache_path()
        try:
            if os.path.exists(path):
                os.remove(path)
        except OSError:
            pass


#: Process-wide cache instance every decision goes through.
cache = TuningCache()


# ---------------------------------------------------------------------------
# Measurement log (how tests assert "zero measurements on a cache hit")
# ---------------------------------------------------------------------------

_MEASURE_LOG: list = []


def measure_log() -> tuple:
    """Every on-device timing taken this process: (decision, key, config)."""
    return tuple(_MEASURE_LOG)


def clear_measure_log() -> None:
    _MEASURE_LOG.clear()


def _time(fn, reps: int = MEASURE_REPS, warmup: int = MEASURE_WARMUP) -> float:
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------------------
# TuningSpace
# ---------------------------------------------------------------------------


class TuningSpace:
    """The candidate configs of ONE tunable decision.

    ``candidates`` is an ordered list of ``(config, modeled_cost,
    vmem_bytes)`` triples — the fixed heuristic's pick FIRST, so modeled
    ties resolve to the historical behavior.  ``measure_fn(config)`` runs
    one on-device trial and returns seconds.
    """

    def __init__(
        self,
        decision: str,
        key: str,
        candidates: list,
        measure_fn: Optional[Callable] = None,
        budget: Optional[int] = None,
    ):
        if not candidates:
            raise ValueError(f"empty tuning space for {decision} {key}")
        self.decision = decision
        self.key = key
        self.candidates = candidates
        self.measure_fn = measure_fn
        #: Fast-tier working-set budget the feasibility pruning binds against
        #: (None → the TPU ``VMEM_BUDGET`` default inside prune_candidates).
        self.budget = budget

    # -- construction ------------------------------------------------------

    @classmethod
    def for_os_block(
        cls,
        L: int,
        Lh: int,
        batch: int,
        backend: Optional[str],
        chunk: Optional[int] = None,
    ):
        """Overlap-save block sizes for a ``(batch, L) ⊛ (Lh,)`` convolution.

        Candidates: every power of two from the fixed heuristic's floor
        (``2·next_pow2(Lh)`` — at least half of each block valid) up to
        :data:`~repro.core.limits.FUSED_MAX`, heuristic default first.
        Each candidate's cost is the modeled device seconds of
        :func:`repro.analysis.roofline.conv_report` (``modeled_s``) for the
        schedule that block runs, paired complex frames or one rfft frame a
        signal: each leaf at the larger of its HBM and MXU time, the glue at
        HBM bandwidth.  That is exactly the trade the block size moves:
        small blocks re-transform more overlap, large blocks pay more MXU
        work per point (the fused leaves' GEMMs contract over ``√block``).
        The working set checked against the VMEM budget is that of the leaf
        the schedule runs (``block`` paired, ``block / 2`` unpaired).

        ``chunk`` keys the decision to a *streaming call grain* (serving
        decode, strip ingest): the modeled signal becomes one chunked call
        (``Lh − 1`` carried tail + ``chunk`` fresh samples) and measurement
        times :class:`repro.core.overlap.StreamingConv` chunk calls instead
        of one long ingest — a block sized for a million-sample ingest
        wastes its unfilled step every call when chunks are short.
        """
        from repro.analysis import roofline as rl
        from repro.core import overlap as ov
        from repro.core.limits import FUSED_MAX, next_pow2
        from repro.core import plan as plan_lib

        default = ov.pick_block(Lh)
        blocks = [default]
        b = max(2 * next_pow2(Lh), 2)
        while b <= FUSED_MAX:
            if b != default and b > Lh - 1:
                blocks.append(b)
            b *= 2
        L_call = (chunk + Lh - 1) if chunk else L  # per-call signal length
        cands = []
        for blk in blocks:
            modeled = rl.conv_report(
                L_call, Lh, batch=batch, block=blk, stream=bool(chunk)
            )["overlap_save"]
            leaf = plan_lib._leaf_pass(modeled["leaf_n"])
            vmem = plan_lib.vmem_bytes(leaf, plan_lib.pick_batch_tile(leaf))
            cands.append(({"block": blk}, modeled["modeled_s"], vmem))

        def measure(config):
            import jax
            import jax.numpy as jnp
            import numpy as np

            h = jnp.asarray(
                np.random.default_rng(1).standard_normal((Lh,)), jnp.float32
            )
            if chunk:
                sc = ov.StreamingConv(
                    h, block=config["block"], backend=backend, tune="off"
                )
                x = jnp.asarray(
                    np.random.default_rng(0).standard_normal((batch, chunk)),
                    jnp.float32,
                )
                state = sc.init_state((batch,))
                fn = jax.jit(sc.__call__)
                return _time(lambda: fn(x, state))
            x = jnp.asarray(
                np.random.default_rng(0).standard_normal((batch, L)), jnp.float32
            )
            fn = jax.jit(
                lambda a, b: ov.fft_conv_os(
                    a, b, block=config["block"], backend=backend, tune="off"
                )
            )
            return _time(lambda: fn(x, h))

        key = (
            f"{backend or 'auto'}|os_block.{OS_BLOCK_MODEL}|"
            f"L={L},Lh={Lh},batch={batch}"
        )
        if chunk:
            key += f",chunk={chunk}"
        return cls("os_block", key, cands, measure)

    @classmethod
    def for_plan(cls, spec, backend_name: str):
        """Whole plan configs for an FFTSpec: the fused-vs-split crossover
        (``fused_max``), the leaf engine boundary (``direct_max`` — direct
        DFT matmul vs fused four-step for boundary leaves), a per-pass
        chunk-width scale, and a leaf batch-tile scale — the heuristic
        config first.

        Chunk and tile scalings do not move the modeled HBM bytes (the
        bytes are the signal + LUT streams, not the grid decomposition), so
        the roofline keeps them all and only measurement separates them;
        ``fused_max`` / ``direct_max`` alternatives DO move modeled bytes
        (an extra factor is an extra image round trip; a direct leaf
        streams its n² DFT matrix) and are pruned hard — ``tune="model"``
        keeps the historical plan on ties and deviates only where the
        model's HBM-byte account is strictly cheaper.

        Candidate enumeration and feasibility bind against
        :data:`~repro.core.limits.VMEM_BUDGET`.
        """
        from repro.core import limits, plan as plan_lib
        from repro.core.limits import DIRECT_MAX, FUSED_MAX, VMEM_BUDGET

        n, n2 = spec.n, getattr(spec, "n2", None)
        budget = VMEM_BUDGET

        def build(fused_max, direct_max=DIRECT_MAX, pad=None):
            if n2 is not None:
                return plan_lib.plan_fft2(n, n2, fused_max, direct_max)
            return plan_lib.plan_fft(n, fused_max, direct_max, pad=pad)

        def config_for(
            fused_max, chunk_shift, tile_shift, direct_max=DIRECT_MAX, pad=None
        ):
            plan = build(fused_max, direct_max, pad)
            chunks = {}
            for i, p in enumerate(plan.passes):
                if p.kind == "reorder":
                    continue
                if p.axis == -2:
                    # Column passes sweep the image width (n row bins).
                    base = plan_lib.pick_pass_chunk(p, budget=budget, width=n)
                elif p.view_in and p.view_in[0] == 1:
                    continue  # whole-signal pass: batch-tiled, not chunked
                else:
                    base = plan_lib.pick_pass_chunk(p, budget=budget)
                chunks[str(i)] = max(1, base >> chunk_shift)
            tiles = {}
            for p in plan.leaf_passes:
                base = plan_lib.pick_batch_tile(p, budget)
                tiles[str(p.n)] = max(1, base >> tile_shift)
            cfg = {
                "fused_max": fused_max,
                "direct_max": direct_max,
                "chunks": chunks,
                "batch_tiles": tiles,
            }
            if pad is not None:
                cfg["bluestein_pad"] = pad
            return cfg

        def modeled(fused_max, direct_max=DIRECT_MAX, pad=None):
            plan = build(fused_max, direct_max, pad)
            shape2d = (n2, n) if n2 is not None else None
            return plan_lib.program_hbm_bytes(
                plan.passes, spec.batch_hint or 1, shape2d
            )

        def vmem_of(config):
            plan = build(
                config["fused_max"],
                config.get("direct_max", DIRECT_MAX),
                config.get("bluestein_pad"),
            )
            worst = 0
            for i, p in enumerate(plan.passes):
                if p.kind == "reorder":
                    continue
                c = config["chunks"].get(str(i))
                if c is not None:
                    worst = max(worst, plan_lib._pass_chunk_bytes(p, c))
                else:
                    t = config["batch_tiles"].get(str(p.n))
                    if t is not None:
                        worst = max(worst, plan_lib.vmem_bytes(p, t))
            return worst

        # Crossover and engine alternatives — only those that actually
        # change the compiled program are worth carrying.
        fms = [(FUSED_MAX, DIRECT_MAX)]
        for fm in (FUSED_MAX // 2, FUSED_MAX // 4):
            if fm <= DIRECT_MAX:
                continue
            # A smaller crossover can push a tall image's column program
            # past the strip-mined gate — skip such alternates outright.
            if n2 is not None and not plan_lib.joint2d_supported(n2, fm):
                continue
            if build(fm).passes != build(FUSED_MAX).passes:
                fms.append((fm, DIRECT_MAX))
        for dm in (DIRECT_MAX // 2, DIRECT_MAX // 4):
            if build(FUSED_MAX, dm).passes != build(FUSED_MAX).passes:
                fms.append((FUSED_MAX, dm))
        # Chirp pad-length alternatives for non-pow2 (Bluestein) 1-D specs:
        # the minimal next_pow2(2n-1) pad first, its doubling second (a
        # doubled pad can re-factorise the inner conv more favourably; the
        # model usually prunes it — extra signal bytes — but measurement
        # gets to disagree).
        pads = [None]
        if n2 is None and n & (n - 1):
            m0 = limits.bluestein_pad(n)
            pads = [m0, 2 * m0]
        cands, seen = [], set()
        for pad in pads:
            for fm, dm in fms:
                for chunk_shift, tile_shift in ((0, 0), (1, 0), (2, 0), (0, 1)):
                    cfg = config_for(fm, chunk_shift, tile_shift, dm, pad)
                    sig = json.dumps(cfg, sort_keys=True)
                    if sig in seen:
                        continue
                    seen.add(sig)
                    cands.append((cfg, modeled(fm, dm, pad), vmem_of(cfg)))

        def measure(config):
            import jax
            import jax.numpy as jnp
            import numpy as np

            plan = build(
                config["fused_max"],
                config.get("direct_max", DIRECT_MAX),
                config.get("bluestein_pad"),
            )
            chunks = {int(k): v for k, v in config["chunks"].items()}
            tiles = {int(k): v for k, v in config["batch_tiles"].items()}
            b = spec.batch_hint or 2
            rng = np.random.default_rng(0)
            shape = (b, n2, n) if n2 is not None else (b, n)
            xr = jnp.asarray(rng.standard_normal(shape), jnp.float32)
            from repro.kernels import ops as kernel_ops

            fn = jax.jit(
                lambda a: kernel_ops.execute_plan(
                    a, a, plan, batch_tiles=tiles, chunks=chunks
                )
            )
            return _time(lambda: fn(xr))

        size = f"n={n}" + (f",n2={n2}" if n2 is not None else "")
        key = (
            f"{backend_name}|plan|{spec.kind}|{size}|"
            f"batch={spec.batch_hint or 0}"
        )
        return cls("plan", key, cands, measure, budget=budget)

    @classmethod
    def for_pencil(
        cls,
        n: int,
        d: int,
        batch: int = 1,
        backend: Optional[str] = None,
        natural_order: bool = True,
    ):
        """The distributed pencil FFT's decisions, as ONE joint space:
        ``pencil_factors`` balance (every power-of-two n1·n2 = n with both
        factors divisible by ``d``), the all-to-all chunk count ``K`` the
        two inner transposes are strip-mined into (K | q, so every chunk
        is a whole number of columns per device), and whether the
        split-complex pair is packed into one stacked collective per
        transpose — the distributed analogue of the rfft even/odd packing,
        halving the collective count for the same wire bytes.

        Candidate costs are :func:`repro.analysis.roofline.pencil_report`
        ``modeled_s`` — *seconds*, not HBM bytes, because this decision
        trades interconnect time against local HBM time and only a common
        unit can rank them.  ``prune_candidates`` is unit-agnostic (it
        compares scalars), so the same pruning applies.

        This space deliberately has **no measure_fn**: the pencil path runs
        inside ``shard_map`` across the hosts of a multi-process mesh, and
        a per-host measurement (or cache hit) could pick different configs
        on different hosts and desynchronize the SPMD program — the
        ``pconv_os_sharded`` precedent.  :func:`pencil_config` therefore
        never measures and never touches the persistent cache.
        """
        from repro.analysis import roofline as rl
        from repro.core import distributed as dist  # lazy: avoids cycle
        from repro.core import plan as plan_lib

        base = dist.pencil_factors(n, d)
        splits = []
        n1 = 1
        while n1 <= n:
            n2 = n // n1
            if n1 * n2 == n and n1 % d == 0 and n2 % d == 0:
                splits.append((n1, n2))
            n1 *= 2
        if base in splits:  # heuristic (balanced) factorization first
            splits.remove(base)
        splits.insert(0, base)

        def vmem_of(n1, n2):
            worst = 0
            for m in (n1, n2):
                for leaf in plan_lib.plan_fft(m).leaf_passes:
                    worst = max(
                        worst,
                        plan_lib.vmem_bytes(leaf, plan_lib.pick_batch_tile(leaf)),
                    )
            return worst

        cands = []
        for n1, n2 in splits:
            q = n2 // d
            vmem = vmem_of(n1, n2)
            for pack in (True, False):
                for K in (1, 2, 4, 8):
                    if K > 1 and (not pack or K > q or q % K):
                        continue
                    rep = rl.pencil_report(
                        n, d, batch,
                        n1=n1, n2=n2, pack=pack, chunks=K,
                        natural_order=natural_order,
                    )
                    cfg = {"n1": n1, "n2": n2, "pack": pack, "a2a_chunks": K}
                    cands.append((cfg, rep["modeled_s"], vmem))
        # Heuristic-first convention: (balanced, packed, K=1) leads so
        # modeled ties keep the simplest schedule.
        cands.sort(
            key=lambda c: (
                (c[0]["n1"], c[0]["n2"]) != base,
                not c[0]["pack"],
                c[0]["a2a_chunks"],
            )
        )
        key = (
            f"{backend or 'auto'}|pencil|n={n},d={d},batch={batch},"
            f"natural={int(natural_order)}"
        )
        return cls("pencil", key, cands, measure_fn=None)

    # -- decision ----------------------------------------------------------

    def decide(self, mode: str) -> dict:
        """Run the tuner's decision procedure at ``mode``; returns a config.

        off     → the fixed heuristic (first candidate), no cache traffic.
        model   → roofline-pruned modeled minimum; cached.
        measure → cache hit returns instantly; otherwise time the pruned
                  survivors — the fixed heuristic always among them, so the
                  measured winner is never slower than the heuristic — and
                  cache the winner.  A ``model``-mode cache entry is
                  upgraded (re-measured) the first time measure runs.
        """
        from repro.analysis.roofline import prune_candidates

        if mode == "off":
            return self.candidates[0][0]
        key = f"{device_key()}|{self.key}"
        hit = cache.get(key)
        if hit is not None and (mode == "model" or hit.get("mode") == "measure"):
            return hit["config"]
        survivors = prune_candidates(
            self.candidates, tol=PRUNE_TOL, vmem_budget=self.budget
        )
        if mode == "measure" and self.measure_fn is not None:
            default = self.candidates[0]
            if all(s is not default for s in survivors):
                # The model may prune the fixed heuristic; measurement must
                # still beat it on the clock, not just on its modeled cost.
                survivors = [default] + survivors
            times = [float("inf")] * len(survivors)
            for _round in range(MEASURE_ROUNDS):
                for i, (config, _cost, _vmem) in enumerate(survivors):
                    times[i] = min(times[i], self.measure_fn(config))
                    _MEASURE_LOG.append(
                        (self.decision, key, json.dumps(config, sort_keys=True))
                    )
            best = min(range(len(survivors)), key=times.__getitem__)
            pick = survivors[best][0]
            t_default = next(
                (times[i] for i, s in enumerate(survivors) if s is default), None
            )
            if t_default is not None and t_default <= times[best] * (1 + DEFAULT_MARGIN):
                pick = default[0]  # within noise of the heuristic: keep it
        else:
            pick = survivors[0][0]
            mode = "model"
        cache.put(key, {"config": pick, "mode": mode})
        return pick


# ---------------------------------------------------------------------------
# Decision entry points (what plan() / the conv engines call)
# ---------------------------------------------------------------------------


def tuned_block(
    L: int,
    Lh: int,
    batch: int = 1,
    backend: Optional[str] = None,
    tune: Optional[str] = None,
    chunk: Optional[int] = None,
) -> int:
    """The overlap-save block size for a ``(batch, L) ⊛ (Lh,)`` convolution
    under the resolved tune mode (``off`` → the ``OS_FACTOR`` heuristic).
    ``chunk`` keys the decision (and its measurement) to a streaming call
    grain — see :meth:`TuningSpace.for_os_block`."""
    mode = resolve_mode(tune)
    space = TuningSpace.for_os_block(L, Lh, batch, backend, chunk=chunk)
    return int(space.decide(mode)["block"])


def modeled_block(
    L: int,
    Lh: int,
    batch: int = 1,
    backend: Optional[str] = None,
    chunk: Optional[int] = None,
) -> int:
    """The pure roofline block pick, bypassing cache AND measurement: a
    deterministic function of the shape alone.  SPMD callers
    (:func:`repro.core.distributed.pconv_os_sharded`) use this so every
    host of a multi-process mesh derives the identical block — a per-host
    cache hit or measurement could diverge and desynchronize the
    ``shard_map`` program's shapes.  ``chunk`` keys the decision to a
    streaming call grain exactly as :func:`tuned_block`'s does
    (:class:`~repro.core.overlap.StreamingConv.chunk_hint` under
    sharding), still cache-free."""
    from repro.analysis.roofline import prune_candidates

    space = TuningSpace.for_os_block(L, Lh, batch, backend, chunk=chunk)
    return int(prune_candidates(space.candidates, tol=PRUNE_TOL)[0][0]["block"])


def pencil_config(
    n: int,
    d: int,
    batch: int = 1,
    backend: Optional[str] = None,
    tune: Optional[str] = None,
    natural_order: bool = True,
) -> dict:
    """The distributed pencil FFT's tuned decisions — factor balance, a2a
    chunk count K, split-complex packing — for a length-``n`` transform
    over ``d`` devices.

    CACHE-FREE AND MEASUREMENT-FREE BY CONSTRUCTION: the pick is a pure
    function of ``(n, d, batch, backend, mode)`` so every host of a
    multi-process SPMD mesh derives the identical config with no cache
    file and no on-device timing (``measure_log()`` stays empty).
    ``tune="measure"`` therefore clamps to the modeled pick here — to
    deviate, pass explicit overrides (``factors=``/``chunks=``/``pack=``)
    to :func:`repro.core.distributed.plan_pencil` on every host.

    ``"off"`` is the historical schedule: balanced factors, serial
    transposes (K=1) — packed, since stacking the pair is a pure win the
    satellite made unconditional.
    """
    from repro.analysis.roofline import prune_candidates

    mode = resolve_mode(tune)
    if d <= 1:
        from repro.core import distributed as dist  # lazy: avoids cycle

        n1, n2 = dist.pencil_factors(n, max(d, 1))
        return {"n1": n1, "n2": n2, "pack": True, "a2a_chunks": 1}
    space = TuningSpace.for_pencil(n, d, batch, backend, natural_order)
    if mode == "off":
        return space.candidates[0][0]
    return dict(prune_candidates(space.candidates, tol=PRUNE_TOL)[0][0])


def plan_config(spec, backend_name: str, tune: Optional[str] = None) -> Optional[dict]:
    """The tuned plan config for ``spec`` on ``backend_name`` (None for
    ``off`` — all heuristics — and for backends that do not consume the
    pass program's grid decomposition)."""
    mode = resolve_mode(tune)
    if mode == "off":
        return None
    if backend_name != "pallas":
        # Only the pallas executors consume chunks/tiles; other backends
        # re-derive their own schedule, so there is nothing to tune yet.
        return None
    space = TuningSpace.for_plan(spec, backend_name)
    return space.decide(mode)
