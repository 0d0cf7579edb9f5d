"""Distributed pencil FFT — the paper's hierarchy lifted to the pod level.

On a single chip the paper's schedule bounds HBM↔on-chip round trips; across
a TPU pod the analogous slow tier is ICI, and the analogous schedule bounds
**all-to-all transposes**.  A length-N transform sharded over D devices is
factored N = N1 · N2 (both divisible by D) and executed as:

    a2a-transpose → local FFT(N1) → twiddle → a2a-transpose → local FFT(N2)
    [→ a2a-transpose for natural output order]

Every local FFT executes a per-leaf :class:`~repro.core.fft.PlannedFFT` (one
frozen plan per pencil factor, fused kernels on TPU), and the per-device
twiddle slab is generated with traced iota from
``lax.axis_index`` — no device ever materialises another shard's table.

The pencil path is a *planned, tuned, overlapped* pipeline:

* **Packed collectives** — the split-complex ``(xr, xi)`` pair rides ONE
  stacked ``all_to_all`` per transpose (the distributed analogue of the
  rfft even/odd packing): 3 collectives for a natural-order forward, not
  the 6 the per-plane path paid.  ``pack=False`` keeps the historical
  serial path for A/B benchmarking.
* **Chunk-overlapped transposes** — the two inner all-to-alls are
  strip-mined into ``K`` column chunks, double-buffered so chunk *i*'s
  transpose is in flight while chunk *i−1* runs its local column FFT +
  twiddle (``lax`` slicing inside the ``shard_map`` body; XLA's async
  collectives overlap the wire with the compute).  ``K`` is a tuned
  decision.
* **Plan layer** — :func:`plan_pencil` resolves the tuned decisions
  (factor balance, K, packing — :func:`repro.core.tuning.pencil_config`,
  modeled-only so every SPMD host agrees deterministically) into a cached
  :class:`PencilPlan` whose :meth:`~PencilPlan.describe` prints the pencil
  schedule (factors, collective count, modeled comm MB) exactly like
  single-device plan handles do.
* **Degenerate meshes** — with one shard the pencil path collapses to the
  local single-chip plan: zero collectives in the program (jaxpr-asserted
  in the tests), and ``natural_order=False``/``from_pencil=True`` keep
  their k1-major layout semantics via a purely local four-step.

Beyond-paper optimisation (recorded in EXPERIMENTS.md §Perf): with
``natural_order=False`` the spectrum stays in "k1-major" pencil layout and
the inverse consumes it directly, so an fft→pointwise→ifft round trip (the
long-conv pattern) costs **2** packed all-to-alls instead of the natural
path's 6.

These functions use raw ``jax.lax`` collectives and must run inside a
``shard_map`` body (or under jit with the axis bound); :func:`pfft_sharded`
is the standalone convenience wrapper.

The compiled program names each step (``jax.named_scope``: metadata a
profiler trace carries, nothing at run time), all inside ``pencil``: the
transposes ``a2a{step}`` in the order they run, each strip-mined chunk's
as ``a2a{step}/c{k}`` (its reassembly ``a2a{step}/merge``), the leaves
``n1_cols`` and ``n2_rows`` (around the leaf plans' own scopes), the
``twiddle``, the natural-order ``reorder``, and ``pack`` / ``unpack`` for
stacking and splitting the split-complex pair.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.core import faults
from repro.core import fft as fft_lib
from repro.core import plan as plan_lib
from repro.core import twiddle as tw
from repro.core.fft_xla import cmul

Planes = Tuple[jax.Array, jax.Array]

__all__ = [
    "pfft",
    "pifft",
    "pencil_factors",
    "PencilPlan",
    "plan_pencil",
    "pfft_sharded",
    "pifft_sharded",
    "pfft2d",
    "pconv_os_sharded",
]


def _leaf_plan(
    n: int, inverse: bool, backend: str | None, axis: int = -1
) -> "fft_lib.PlannedFFT":
    """Per-leaf :class:`PlannedFFT` for the local pencil transforms.

    Each pencil factor gets its own plan (cached by spec), so the local
    length-n1 and length-n2 passes reuse frozen schedules and LUTs instead of
    re-dispatching on a backend string per call.  ``axis=-2`` plans are the
    column passes of the pass program: axis-capable backends (pallas, xla)
    execute them in place over the strided view — the hand-rolled
    swapaxes sandwiches this driver used to carry are gone.
    """
    return fft_lib.plan(
        fft_lib.FFTSpec(n=n, kind="ifft" if inverse else "fft", axis=axis),
        backend=backend,
    )


def pencil_factors(n: int, d: int) -> tuple[int, int]:
    """Split n = n1 · n2 (powers of two), both divisible by d, near-square."""
    n1, n2 = plan_lib.balanced_split(n)
    while n1 % d and n2 >= d * 2:
        n1 *= 2
        n2 //= 2
    if n1 % d or n2 % d:
        raise faults.PlanError(f"cannot pencil-split n={n} over {d} devices")
    return n1, n2


def _local_twiddle(n1: int, n2: int, q: int, axis_name: str, inverse: bool):
    """Twiddle slab T[k1, n2] for this device's n2 ∈ [d·q, (d+1)·q).

    Delegates to :func:`repro.core.twiddle.traced_twiddle`'s column window:
    with x64 disabled (the default) the int64 iotas this used to build
    silently downcast to int32 and the ``(k1·m2) % n`` reduction overflowed
    for n > 2³¹ — the huge-N regime pencil FFTs exist for.
    """
    d = jax.lax.axis_index(axis_name)
    return tw.traced_twiddle(n1, n2, inverse, col_start=d * q, col_count=q)


def _a2a(x, axis_name, split_axis, concat_axis):
    faults.maybe_fail(
        "pencil.all_to_all", axis_name=axis_name, split_axis=split_axis
    )
    return jax.lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis, tiled=True
    )


# ---------------------------------------------------------------------------
# Plan layer: PencilPlan / plan_pencil
# ---------------------------------------------------------------------------


class PencilPlan:
    """The frozen schedule of one distributed pencil transform.

    The pencil analogue of :class:`~repro.core.fft.PlannedFFT`: factors,
    packing, chunk count and the per-leaf local plans are resolved ONCE
    (through :func:`repro.core.tuning.pencil_config` — modeled-only, so
    every host of an SPMD mesh derives the identical schedule) and reused
    by every ``pfft``/``pifft`` call of the same shape.  ``describe()``
    prints the schedule with modeled comm MB next to it, like the
    single-device handles.
    """

    def __init__(
        self,
        n: int,
        d: int,
        *,
        inverse: bool,
        backend: Optional[str],
        config: dict,
        natural_order: bool = True,
    ):
        from repro.analysis import roofline as rl  # lazy: analysis layer

        self.n, self.d, self.inverse = n, d, inverse
        self.backend = backend
        self.n1, self.n2 = int(config["n1"]), int(config["n2"])
        if self.n1 * self.n2 != n:
            raise faults.PlanError(f"pencil factors {self.n1}x{self.n2} != n={n}")
        if d > 1 and (self.n1 % d or self.n2 % d):
            raise faults.PlanError(
                f"pencil factors {self.n1}x{self.n2} not divisible by d={d}"
            )
        self.p = self.n1 // max(d, 1)
        self.q = self.n2 // max(d, 1)
        self.pack = bool(config.get("pack", True))
        k = int(config.get("a2a_chunks", 1))
        # K must divide the per-device column count — clamp a foreign or
        # hand-written config rather than fail the transform.
        while k > 1 and (k > self.q or self.q % k):
            k //= 2
        self.a2a_chunks = k if self.pack else 1
        self.tuned = dict(config)
        self.plan_n1 = _leaf_plan(self.n1, inverse, backend, axis=-2)
        self.plan_n2 = _leaf_plan(self.n2, inverse, backend)
        #: d == 1 natural order collapses to the single-chip program.
        self.local_plan = (
            _leaf_plan(n, inverse, backend) if d <= 1 else None
        )
        self.report = rl.pencil_report(
            n,
            d,
            n1=self.n1,
            n2=self.n2,
            pack=self.pack,
            chunks=self.a2a_chunks,
            natural_order=natural_order,
        )

    def a2a_count(self, natural_order: bool = True) -> int:
        """Collectives one transform emits (what the jaxpr tests assert)."""
        if self.d <= 1:
            return 0
        if self.pack:
            return 2 * self.a2a_chunks + (1 if natural_order else 0)
        return 2 * (3 if natural_order else 2)

    def describe(self) -> str:
        kind = "pifft" if self.inverse else "pfft"
        mb = self.report["comm_bytes_per_step"] / 2**20
        local_mb = self.report["local_hbm_bytes"] / 2**20
        head = (
            f"{kind} N={self.n} over d={self.d}: factors {self.n1}x{self.n2} "
            f"(p={self.p}, q={self.q}); "
        )
        if self.d <= 1:
            sched = "collapses to the local plan, 0 collectives"
        else:
            sched = (
                f"{'packed' if self.pack else 'split-plane'} a2a x"
                f"{self.a2a_count(True)} natural / x{self.a2a_count(False)} "
                f"pencil (K={self.a2a_chunks}); comm {mb:.2f} MB/step"
            )
        lines = [head + sched + f"; local HBM {local_mb:.2f} MB"]
        if self.local_plan is not None:
            lines.append(f"  local: {self.local_plan.describe()}")
        lines.append(f"  leaf n1: {self.plan_n1.describe()}")
        lines.append(f"  leaf n2: {self.plan_n2.describe()}")
        return "\n".join(lines)

    def __repr__(self):
        return (
            f"PencilPlan(n={self.n}, d={self.d}, {self.n1}x{self.n2}, "
            f"pack={self.pack}, K={self.a2a_chunks})"
        )


@functools.lru_cache(maxsize=256)
def _pencil_plan_cached(
    n: int,
    d: int,
    inverse: bool,
    backend: Optional[str],
    mode: str,
    factors: Optional[tuple],
    pack: Optional[bool],
    chunks: Optional[int],
    natural_order: bool,
) -> PencilPlan:
    from repro.core import tuning  # lazy: tuning imports the conv engines

    config = dict(
        tuning.pencil_config(
            n, d, backend=backend, tune=mode, natural_order=natural_order
        )
    )
    if factors is not None:
        config["n1"], config["n2"] = factors
    if pack is not None:
        config["pack"] = pack
    if chunks is not None:
        config["a2a_chunks"] = chunks
    return PencilPlan(
        n,
        d,
        inverse=inverse,
        backend=backend,
        config=config,
        natural_order=natural_order,
    )


def plan_pencil(
    n: int,
    num_shards: int,
    *,
    inverse: bool = False,
    backend: Optional[str] = None,
    tune: Optional[str] = None,
    factors: Optional[tuple] = None,
    pack: Optional[bool] = None,
    chunks: Optional[int] = None,
    natural_order: bool = True,
) -> PencilPlan:
    """Resolve a distributed pencil transform into a cached
    :class:`PencilPlan`.

    ``tune`` selects how the schedule's knobs are chosen — ``"off"`` is the
    historical balanced/serial schedule, ``"model"`` (the default) the
    roofline-modeled pick; both are cache-free pure functions of the shape
    so SPMD hosts agree (``"measure"`` clamps to the modeled pick here —
    see :func:`repro.core.tuning.pencil_config`).  ``factors``/``pack``/
    ``chunks`` override single decisions explicitly (every host must pass
    the same values).
    """
    from repro.core import tuning  # lazy: tuning imports the conv engines

    return _pencil_plan_cached(
        int(n),
        int(num_shards),
        bool(inverse),
        backend,
        tuning.resolve_mode(tune),
        tuple(factors) if factors is not None else None,
        pack,
        chunks,
        bool(natural_order),
    )


# ---------------------------------------------------------------------------
# The overlapped middle: a2a-in → column compute → a2a-out, K chunks
# ---------------------------------------------------------------------------


def _middle_pipelined(
    z: jax.Array,
    *,
    axis_name: str,
    d: int,
    q: int,
    k: int,
    la: int,
    compute: Callable,
    step: int,
) -> jax.Array:
    """The pencil schedule's middle section on the packed (2, ..., p, n2)
    stack: transpose to column slabs, run ``compute`` on each column chunk,
    transpose back — strip-mined into ``k`` chunks of ``q/k`` columns per
    device and software-pipelined so chunk *i*'s all-to-all is issued
    before chunk *i−1*'s compute is consumed (double-buffering: XLA's
    async collectives can then overlap the wire with the column FFT).

    ``compute(chunk, col_start, width)`` maps a (2, ..., n1, width) column
    chunk (``col_start`` the traced global column offset of this device's
    window) to its transformed chunk of the same shape.  The transposes
    in and out are the schedule's ``a2a{step}`` and ``a2a{step + 1}``.
    """
    lead = z.shape[:-1]  # (2, *batch, p)
    qk = q // k
    zs = z.reshape(*lead, d, q)
    didx = jax.lax.axis_index(axis_name)

    def send(c):
        # Columns {j·q + c·qk .. j·q + (c+1)·qk} for every destination j —
        # exactly the slices whose tiled all-to-all lands as this chunk's
        # contiguous (n1, qk) column slab on device j.
        with jax.named_scope(f"a2a{step}"), jax.named_scope(f"c{c}"):
            sl = jax.lax.slice_in_dim(zs, c * qk, (c + 1) * qk, axis=zs.ndim - 1)
            return _a2a(sl.reshape(*lead, d * qk), axis_name, la + 1, la)

    recv = send(0)
    outs = []
    for c in range(k):
        nxt = send(c + 1) if c + 1 < k else None  # next transfer in flight
        y = compute(recv, didx * q + c * qk, qk)
        with jax.named_scope(f"a2a{step + 1}"), jax.named_scope(f"c{c}"):
            outs.append(_a2a(y, axis_name, la, la + 1))  # back to row slabs
        recv = nxt
    with jax.named_scope(f"a2a{step + 1}"), jax.named_scope("merge"):
        outs = [o.reshape(*lead, d, qk) for o in outs]
        out = jnp.stack(outs, axis=-2)  # (..., p, d, k, qk): chunk-major columns
        return out.reshape(*lead, d * q)


@jax.named_scope("pack")
def _pack2(xr, xi):
    return jnp.stack([xr, xi])


@jax.named_scope("unpack")
def _unpack2(z):
    return z[0], z[1]


# ---------------------------------------------------------------------------
# pfft / pifft
# ---------------------------------------------------------------------------


@jax.named_scope("pencil")
def pfft(
    xr: jax.Array,
    xi: jax.Array,
    *,
    n: int,
    axis_name: str,
    num_shards: int,
    inverse: bool = False,
    natural_order: bool = True,
    backend: str | None = None,
    tune: str | None = None,
    pack: bool | None = None,
    chunks: int | None = None,
    factors: tuple | None = None,
    pplan: PencilPlan | None = None,
) -> Planes:
    """Distributed FFT over the last axis; call inside shard_map.

    ``xr/xi``: local shard (..., n // num_shards) of the globally length-``n``
    signal, contiguous (block) sharding.  Returns the local output shard.
    With ``natural_order=False`` the output is in pencil (k1-major) layout:
    global flat index k1·n2 + k2 holds X[k1 + n1·k2].

    The schedule (factor balance, split-complex packing, the a2a chunk
    count K the two inner transposes are overlapped at) comes from
    :func:`plan_pencil`; pass ``pplan`` to reuse a handle across calls, or
    ``pack``/``chunks``/``factors`` to override single decisions (SPMD:
    identical on every host).  With one shard the transform collapses to
    the local single-chip plan — zero collectives.
    """
    d = num_shards
    pl = pplan or plan_pencil(
        n,
        d,
        inverse=inverse,
        backend=backend,
        tune=tune,
        factors=factors,
        pack=pack,
        chunks=chunks,
        natural_order=natural_order,
    )
    n1, n2, p, q = pl.n1, pl.n2, pl.p, pl.q
    lead = xr.shape[:-1]
    la = len(lead)  # number of leading batch axes

    if d <= 1:
        if natural_order:
            return pl.local_plan.apply_planes(xr, xi)
        # Local four-step in pencil layout — keeps the k1-major semantics
        # callers of natural_order=False rely on, with zero collectives.
        xr = xr.reshape(*lead, n1, n2)
        xi = xi.reshape(*lead, n1, n2)
        xr, xi = pl.plan_n1.apply_planes(xr, xi)
        twr, twi = tw.traced_twiddle(n1, n2, inverse)
        xr, xi = cmul(xr, xi, twr, twi)
        xr, xi = pl.plan_n2.apply_planes(xr, xi)
        return xr.reshape(*lead, n), xi.reshape(*lead, n)

    # Local shard is rows [d·p, (d+1)·p) of the (n1, n2) matrix.
    xr = xr.reshape(*lead, p, n2)
    xi = xi.reshape(*lead, p, n2)

    if not pl.pack:
        return _pfft_serial_unpacked(
            xr, xi, pl, axis_name=axis_name, inverse=inverse,
            natural_order=natural_order, la=la, lead=lead,
        )

    z = _pack2(xr, xi)  # (2, *lead, p, n2): ONE collective per transpose
    lz = la + 1

    def col_chunk(chunk, col_start, width):
        cr, ci = _unpack2(chunk)
        with jax.named_scope("n1_cols"):
            cr, ci = pl.plan_n1.apply_planes(cr, ci)
        with jax.named_scope("twiddle"):
            twr, twi = tw.traced_twiddle(
                n1, n2, inverse, col_start=col_start, col_count=width
            )
            cr, ci = cmul(cr, ci, twr, twi)
        return _pack2(cr, ci)

    z = _middle_pipelined(
        z, axis_name=axis_name, d=d, q=q, k=pl.a2a_chunks, la=lz,
        compute=col_chunk, step=0,
    )
    # after the transposes back: (2, *lead, p, n2) with full rows.
    # FFT over n2 (last axis, local).  (For inverse=True the two leaf
    # transforms already contribute 1/n1 · 1/n2 = 1/n scaling.)
    zr, zi = _unpack2(z)
    with jax.named_scope("n2_rows"):
        zr, zi = pl.plan_n2.apply_planes(zr, zi)
    if not natural_order:
        return zr.reshape(*lead, p * n2), zi.reshape(*lead, p * n2)
    # Final a2a transpose → natural order: C (p, n2) → C^T slab (q2, n1) —
    # one packed collective even though no chunk-overlap applies here.
    q2 = n2 // d
    z = _pack2(zr, zi)
    with jax.named_scope("a2a2"):
        z = _a2a(z, axis_name, lz + 1, lz)  # (2, ..., n1, q2)
    with jax.named_scope("reorder"):
        z = jnp.swapaxes(z, -1, -2)  # (q2, n1) = C^T rows = natural order
        return (
            z[0].reshape(*lead, q2 * n1),
            z[1].reshape(*lead, q2 * n1),
        )


def _pfft_serial_unpacked(
    xr, xi, pl: PencilPlan, *, axis_name, inverse, natural_order, la, lead
) -> Planes:
    """The historical per-plane serial schedule (2 collectives per
    transpose, no chunk overlap) — kept as the A/B baseline the packed
    path is benchmarked against (``bench_pfft``)."""
    n1, n2, p, q = pl.n1, pl.n2, pl.p, pl.q
    d = pl.d
    xr = _a2a(xr, axis_name, la + 1, la)
    xi = _a2a(xi, axis_name, la + 1, la)
    xr, xi = pl.plan_n1.apply_planes(xr, xi)
    twr, twi = _local_twiddle(n1, n2, q, axis_name, inverse)
    xr, xi = cmul(xr, xi, twr, twi)
    xr = _a2a(xr, axis_name, la, la + 1)
    xi = _a2a(xi, axis_name, la, la + 1)
    xr, xi = pl.plan_n2.apply_planes(xr, xi)
    if not natural_order:
        return xr.reshape(*lead, p * n2), xi.reshape(*lead, p * n2)
    q2 = n2 // d
    xr = _a2a(xr, axis_name, la + 1, la)
    xi = _a2a(xi, axis_name, la + 1, la)
    xr = jnp.swapaxes(xr, -1, -2)
    xi = jnp.swapaxes(xi, -1, -2)
    return xr.reshape(*lead, q2 * n1), xi.reshape(*lead, q2 * n1)


@jax.named_scope("pencil")
def pifft(
    xr: jax.Array,
    xi: jax.Array,
    *,
    n: int,
    axis_name: str,
    num_shards: int,
    from_pencil: bool = False,
    backend: str | None = None,
    tune: str | None = None,
    pack: bool | None = None,
    chunks: int | None = None,
    factors: tuple | None = None,
    pplan: PencilPlan | None = None,
) -> Planes:
    """Distributed inverse FFT.

    With ``from_pencil=True`` consumes the k1-major layout produced by
    ``pfft(..., natural_order=False)`` using the mirrored schedule (no extra
    reordering collective).  Packing / chunk-overlap mirror :func:`pfft`.
    """
    d = num_shards
    pl = pplan or plan_pencil(
        n,
        d,
        inverse=True,
        backend=backend,
        tune=tune,
        factors=factors,
        pack=pack,
        chunks=chunks,
        natural_order=not from_pencil,
    )
    n1, n2, p, q = pl.n1, pl.n2, pl.p, pl.q
    lead = xr.shape[:-1]
    la = len(lead)

    if d <= 1:
        if not from_pencil:
            return pl.local_plan.apply_planes(xr, xi)
        # Mirror of the d=1 pencil-layout forward, still collective-free.
        xr = xr.reshape(*lead, n1, n2)
        xi = xi.reshape(*lead, n1, n2)
        xr, xi = pl.plan_n2.apply_planes(xr, xi)
        twr, twi = tw.traced_twiddle(n1, n2, True)
        xr, xi = cmul(xr, xi, twr, twi)
        xr, xi = pl.plan_n1.apply_planes(xr, xi)
        return xr.reshape(*lead, n), xi.reshape(*lead, n)

    if not pl.pack:
        return _pifft_serial_unpacked(
            xr, xi, pl, axis_name=axis_name, from_pencil=from_pencil,
            la=la, lead=lead,
        )

    if not from_pencil:
        # Natural order: device holds C^T rows (q, n1); transpose to pencil
        # with one packed collective.
        z = _pack2(xr.reshape(*lead, q, n1), xi.reshape(*lead, q, n1))
        with jax.named_scope("a2a0"):
            z = _a2a(z, axis_name, la + 2, la + 1)  # (2, ..., n2_slab rows, p)
        with jax.named_scope("reorder"):
            z = jnp.swapaxes(z, -1, -2)  # (2, ..., p, n2)
    else:
        z = _pack2(xr.reshape(*lead, p, n2), xi.reshape(*lead, p, n2))
    lz = la + 1
    # Mirror of pfft: inverse FFT over n2 (rows, local)...
    zr, zi = _unpack2(z)
    with jax.named_scope("n2_rows"):
        zr, zi = pl.plan_n2.apply_planes(zr, zi)
    z = _pack2(zr, zi)

    def col_chunk(chunk, col_start, width):
        with jax.named_scope("twiddle"):
            twr, twi = tw.traced_twiddle(
                n1, n2, True, col_start=col_start, col_count=width
            )
            cr, ci = _unpack2(chunk)
            cr, ci = cmul(cr, ci, twr, twi)
        with jax.named_scope("n1_cols"):
            cr, ci = pl.plan_n1.apply_planes(cr, ci)
        return _pack2(cr, ci)

    z = _middle_pipelined(
        z, axis_name=axis_name, d=d, q=q, k=pl.a2a_chunks, la=lz,
        compute=col_chunk, step=0 if from_pencil else 1,
    )
    with jax.named_scope("unpack"):
        return z[0].reshape(*lead, p * n2), z[1].reshape(*lead, p * n2)


def _pifft_serial_unpacked(
    xr, xi, pl: PencilPlan, *, axis_name, from_pencil, la, lead
) -> Planes:
    """Historical per-plane inverse schedule (A/B baseline)."""
    n1, n2, p, q = pl.n1, pl.n2, pl.p, pl.q
    if not from_pencil:
        xr = xr.reshape(*lead, q, n1)
        xi = xi.reshape(*lead, q, n1)
        xr = _a2a(xr, axis_name, la + 1, la)
        xi = _a2a(xi, axis_name, la + 1, la)
        xr = jnp.swapaxes(xr, -1, -2)
        xi = jnp.swapaxes(xi, -1, -2)
    else:
        xr = xr.reshape(*lead, p, n2)
        xi = xi.reshape(*lead, p, n2)
    xr, xi = pl.plan_n2.apply_planes(xr, xi)
    xr = _a2a(xr, axis_name, la + 1, la)
    xi = _a2a(xi, axis_name, la + 1, la)
    twr, twi = _local_twiddle(n1, n2, q, axis_name, inverse=True)
    xr, xi = cmul(xr, xi, twr, twi)
    xr, xi = pl.plan_n1.apply_planes(xr, xi)
    xr = _a2a(xr, axis_name, la, la + 1)
    xi = _a2a(xi, axis_name, la, la + 1)
    return xr.reshape(*lead, p * n2), xi.reshape(*lead, p * n2)


@jax.named_scope("pencil")
def pfft2d(
    xr: jax.Array,
    xi: jax.Array,
    *,
    n1: int,
    n2: int,
    axis_name: str,
    num_shards: int,
    inverse: bool = False,
    backend: str | None = None,
    pack: bool = True,
) -> Planes:
    """Distributed 2-D FFT (SAR range/azimuth): rows local, columns pencil.

    xr/xi: local shard (..., n1 // D, n2) of a (n1, n2) image, rows sharded
    over ``axis_name``.  Each shard consumes ONE joint 2-D plan
    (``FFTSpec(kind='fft2')`` — the same compiled rows+columns program the
    single-chip path runs) split around the collectives: the row passes run
    on the row-sharded slab, then one all-to-all transpose, the in-place
    column passes on the column slab, and the transpose back — 2 packed
    all-to-alls per direction with the split-complex pair stacked into one
    collective each (``pack=False`` keeps the historical 4-call schedule).
    """
    del num_shards  # the joint plan is shard-count-agnostic (slab widths vary)
    lead = xr.shape[:-2]
    la = len(lead)

    joint = fft_lib.plan(
        fft_lib.FFTSpec(n=n2, kind="ifft2" if inverse else "fft2", n2=n1),
        backend=backend,
    )

    # (1) row passes of the joint program over n2 — local and contiguous.
    with jax.named_scope("n2_rows"):
        xr, xi = joint.apply_rows(xr, xi)
    if pack:
        # (2) ONE packed a2a transpose: (p, n2) → (n1, q) column slabs.
        z = _pack2(xr, xi)
        with jax.named_scope("a2a0"):
            z = _a2a(z, axis_name, la + 2, la + 1)
        # (3) column passes over n1 — in place down axis -2 of the slab.
        xr, xi = _unpack2(z)
        with jax.named_scope("n1_cols"):
            xr, xi = joint.apply_cols(xr, xi)
        # (4) one packed a2a back to row slabs (p, n2).
        z = _pack2(xr, xi)
        with jax.named_scope("a2a1"):
            z = _a2a(z, axis_name, la + 1, la + 2)
        return _unpack2(z)
    xr = _a2a(xr, axis_name, la + 1, la)
    xi = _a2a(xi, axis_name, la + 1, la)
    xr, xi = joint.apply_cols(xr, xi)
    xr = _a2a(xr, axis_name, la, la + 1)
    xi = _a2a(xi, axis_name, la, la + 1)
    return xr, xi


def _auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis ``Auto``.  ``jax.make_mesh`` builds
    ``Explicit`` axes, under which the shard_map results carry mesh-typed
    shardings that eager code and autodiff outside a ``jax.set_mesh``
    context reject; the pencil drivers place their own shardings, so they
    run under ``Auto`` axes whatever mesh the caller built."""
    auto = (AxisType.Auto,) * len(mesh.axis_names)
    if tuple(mesh.axis_types) == auto:
        return mesh
    return Mesh(mesh.devices, mesh.axis_names, axis_types=auto)


def _shard_wrap(fn, mesh: Mesh, axis: str):
    mesh = _auto_mesh(mesh)

    def wrapper(xr, xi, **kw):
        nbatch = xr.ndim - 1
        pspec = P(*([None] * nbatch + [axis]))
        f = functools.partial(fn, axis_name=axis, **kw)
        return jax.shard_map(
            f, mesh=mesh, in_specs=(pspec, pspec), out_specs=(pspec, pspec),
            check_vma=False,
        )(xr, xi)

    return wrapper


def pfft_sharded(
    xr,
    xi,
    mesh: Mesh,
    axis: str,
    *,
    inverse=False,
    natural_order=True,
    backend=None,
    tune=None,
    pack=None,
    chunks=None,
    factors=None,
):
    """Standalone distributed FFT: shards the last axis over ``mesh[axis]``."""
    n = xr.shape[-1]
    d = mesh.shape[axis]
    return _shard_wrap(pfft, mesh, axis)(
        xr,
        xi,
        n=n,
        num_shards=d,
        inverse=inverse,
        natural_order=natural_order,
        backend=backend,
        tune=tune,
        pack=pack,
        chunks=chunks,
        factors=factors,
    )


def pifft_sharded(
    xr,
    xi,
    mesh: Mesh,
    axis: str,
    *,
    from_pencil=False,
    backend=None,
    tune=None,
    pack=None,
    chunks=None,
    factors=None,
):
    n = xr.shape[-1]
    d = mesh.shape[axis]
    return _shard_wrap(pifft, mesh, axis)(
        xr,
        xi,
        n=n,
        num_shards=d,
        from_pencil=from_pencil,
        backend=backend,
        tune=tune,
        pack=pack,
        chunks=chunks,
        factors=factors,
    )


def pconv_os_sharded(
    x: jax.Array,
    h: jax.Array,
    mesh: Mesh,
    axis: str,
    *,
    causal: bool = True,
    block: int | None = None,
    backend: str | None = None,
    tune: str | None = None,
    chunk_hint: int | None = None,
) -> jax.Array:
    """Distributed overlap-save convolution: blocks sharded over ``mesh[axis]``.

    The overlap-save blocks of :func:`repro.core.overlap.fft_conv_os` are
    embarrassingly parallel — every block carries its own ``Lh − 1`` history
    in the overlapping frame — so the convolution shards over the *block*
    axis with ``shard_map`` and pays **zero** all-to-alls, versus the 2 of
    the packed pencil ``pfft → ⊙H → pifft`` path (and its transforms stay in
    the fused one-round-trip regime, where the pencil leaves may not).

    ``x``: (..., L) replicated input; ``h`` broadcasts like ``fft_conv``.
    The schedule is :func:`repro.core.overlap.overlap_save`'s: where it
    pairs two frames per complex transform the pairs shard, else the
    frames, their count padded up to a multiple of the mesh axis size
    (zero frames, or windows whose outputs fall past ``L_out``).  Returns
    the (..., L) causal output (or L + Lh − 1 with ``causal=False``),
    replicated — the framing and the tail assembly run outside the
    ``shard_map`` body.

    Block tuning here is DETERMINISTIC by construction: with ``block=None``
    and ``tune`` ≠ "off" the block is the pure roofline pick
    (:func:`repro.core.tuning.modeled_block`) — never a cache hit or a
    measurement, which could differ across the hosts of a multi-process
    mesh and desynchronize the shard_map program.  ``chunk_hint`` keys the
    modeled pick to a streaming call grain (the sharded analogue of
    :class:`~repro.core.overlap.StreamingConv`'s ``chunk_hint``), still
    cache-free.  To use a measured winner, tune on one host
    (``tuning.tuned_block(..., "measure")``) and pass the result as
    ``block=`` explicitly.
    """
    from repro.core import overlap as ov  # lazy: distributed loads before overlap at package init
    from repro.core import tuning

    x = jnp.asarray(x)
    out_dtype = x.dtype
    x = x.astype(jnp.float32)
    h = jnp.asarray(h, jnp.float32)
    d = mesh.shape[axis]
    L, Lh = x.shape[-1], h.shape[-1]
    batch = int(np.prod(x.shape[:-1])) if x.ndim > 1 else 1
    if block is not None:
        B = ov.pick_block(Lh, block)
    elif tuning.resolve_mode(tune) == "off" or Lh < 2:
        B = ov.pick_block(Lh)
    else:
        B = tuning.modeled_block(L, Lh, batch, backend, chunk=chunk_hint)
    L_out = L if causal else L + Lh - 1
    Hr, Hi = ov.filter_spectrum(h, B, backend)  # computed once, replicated

    def run(fn, data, hr, hi):
        # pairs shard on their leading axis, frames on the block axis
        spec = P(axis) if isinstance(data, tuple) else P(*([None] * (data.ndim - 2)), axis, None)
        return jax.shard_map(
            fn, mesh=_auto_mesh(mesh), in_specs=(spec, P(), P()), out_specs=spec,
            check_vma=False,
        )(data, hr, hi)

    y = ov.overlap_save(
        x, Hr, Hi, block=B, overlap=Lh - 1, length=L_out, backend=backend, shards=d, run=run
    )
    return y.astype(out_dtype)
