"""Public FFT API — plan-and-execute over a backend registry.

The paper's core idea is that the transform *schedule* (kernel-call count,
memory-tier placement, LUT reuse — §2.3, §3) is decided once per size and
reused.  This module exposes that as a plan-and-execute API in the FFTW /
cuFFT mold:

    spec    = FFTSpec(n=4096, kind="fft", axis=-1)
    planned = plan(spec)             # cached: plan(spec) is plan(spec)
    y       = planned(x)             # executes the frozen schedule

:func:`plan` resolves an :class:`FFTSpec` (length, kind, axis, precision,
batch hint) into a hashable :class:`PlannedFFT` executor carrying the
:class:`repro.core.plan.FFTPlan` schedule, pre-materialized twiddle/DFT LUTs,
the chosen per-leaf batch tiles, and a backend selected from the **backend
registry**.

Backends
--------
Backends are registered entries (:func:`register_backend`), not an if/elif
chain.  Each declares capabilities (platforms, precisions, max length) and
selection is by capability negotiation against the running platform unless a
name is forced per call or scoped with the :func:`use_backend` context
manager.  Built-in entries:

``pallas``    fused Pallas TPU kernels (``repro.kernels``), one HBM round trip
              per plan level.  Runs under ``interpret=True`` on CPU.  A
              leaf quarantined by a fault runs its traced-XLA twin
              (``repro.kernels.ops``) inside the same program.
``xla``       pure-JAX four-step with the same factorisation (MXU matmuls on
              TPU, portable everywhere).  Preferred on CPU and GPU hosts.
``stockham``  radix-2 butterfly reference (the paper's original formulation).

Module functions ``fft/ifft/rfft/irfft/fft2/ifft2/rfft2/irfft2`` remain as
thin plan-cached wrappers (each call re-uses the cached :class:`PlannedFFT`);
the 1-D kinds grow an ``axis=`` argument for transforms over a non-last axis,
while the 2-D kinds always transform the last two axes.  ``fft2``/``ifft2``
compile into ONE joint multi-axis pass program (rows, then in-place strided
columns — zero transposes between the axes); ``rfft2``/``irfft2`` add the
row-wise Hermitian recombination epilogue around it.

All complex transforms accept either a complex array or a ``(real, imag)``
tuple of float32 planes, and return whichever form was supplied.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import inspect
import itertools
import os
import threading
import types
import warnings
from typing import Callable, Mapping, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults
from repro.core import fft_xla
from repro.core import plan as plan_lib
from repro.core import twiddle as tw
from repro.core.faults import NumericsError, PlanError

Planes = Tuple[jax.Array, jax.Array]
ArrayOrPlanes = Union[jax.Array, Planes]

__all__ = [
    "FFTSpec",
    "PlannedFFT",
    "plan",
    "BackendCapabilities",
    "register_backend",
    "available_backends",
    "get_backend",
    "use_backend",
    "default_backend",
    "plan_log",
    "clear_plan_log",
    "PLAN_LOG_MAX",
    "fft",
    "ifft",
    "rfft",
    "irfft",
    "fft2",
    "ifft2",
    "rfft2",
    "irfft2",
]

KINDS = ("fft", "ifft", "rfft", "irfft", "fft2", "ifft2", "rfft2", "irfft2")
_COMPLEX_KINDS = ("fft", "ifft")
_2D_KINDS = ("fft2", "ifft2", "rfft2", "irfft2")

#: Relative tolerance of the opt-in ``check="parseval"`` energy guard —
#: generous for float32 accumulation; it flags corruption, not rounding.
PARSEVAL_RTOL = 1e-2


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# FFTSpec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FFTSpec:
    """What to transform — the hashable key a :class:`PlannedFFT` is built for.

    n:          transform length along ``axis``.  Any length ≥ 1: powers of
                two run the paper's native schedules; other lengths compile
                into the planner's Bluestein chirp-conv leaf (a cached
                power-of-two circular convolution at ``bluestein_pad(n)``).
                ``rfft2``/``irfft2`` still require a power of two.  For
                ``irfft``/``irfft2`` this is the *output* signal length along
                the last axis; for the 2-D kinds it is the last-axis (row)
                length and ``n2`` the second-to-last (column) length.
    kind:       'fft' | 'ifft' | 'rfft' | 'irfft' | 'fft2' | 'ifft2' |
                'rfft2' | 'irfft2'.  The 2-D complex kinds compile into ONE
                joint pass program (rows then in-place columns); ``rfft2``
                transforms a real ``(..., n2, n)`` image into its
                ``(..., n2, n//2 + 1)`` half-spectrum (numpy ``rfft2``
                layout: real transform over the last axis, full complex
                transform over axis -2) and ``irfft2`` inverts it.
    axis:       transform axis (2-D kinds always use the last two axes).
    precision:  compute precision of the planes ('float32' for now; the field
                exists so mixed-precision plans slot in without an API break).
    batch_hint: expected batch rows, used to cap the kernel batch tile so a
                small batch is not padded up to the VMEM-optimal tile.
    n2:         second-to-last-axis length, 2-D kinds only.
    """

    n: int
    kind: str = "fft"
    axis: int = -1
    precision: str = "float32"
    batch_hint: Optional[int] = None
    n2: Optional[int] = None

    def __post_init__(self):
        if self.kind not in KINDS:
            raise PlanError(f"unknown FFT kind {self.kind!r}; one of {KINDS}")
        if self.n < 1:
            raise PlanError(f"FFT length must be >= 1, got {self.n}")
        if self.kind in ("rfft2", "irfft2") and not _is_pow2(self.n):
            raise PlanError(
                f"{self.kind} requires a power-of-two row length, got n={self.n}; "
                f"non-power-of-two lengths are supported for "
                f"{_COMPLEX_KINDS + ('rfft', 'irfft', 'fft2', 'ifft2')} via the "
                f"Bluestein chirp-conv route"
            )
        if self.kind in ("rfft", "irfft", "rfft2", "irfft2") and self.n < 2:
            raise PlanError(f"{self.kind} length must be >= 2, got {self.n}")
        if self.kind in _2D_KINDS:
            if self.n2 is None or not _is_pow2(self.n2):
                raise PlanError(
                    f"{self.kind} needs a power-of-two n2 (column length), got "
                    f"{self.n2}; only the last (row) axis takes non-power-of-two "
                    f"lengths (Bluestein route)"
                )
            if self.axis != -1:
                raise PlanError(f"{self.kind} always transforms the last two axes")
        elif self.n2 is not None:
            raise PlanError(f"n2 is only meaningful for the 2-D kinds {_2D_KINDS}")
        if self.batch_hint is not None and self.batch_hint < 1:
            raise PlanError(f"batch_hint must be >= 1, got {self.batch_hint}")


# ---------------------------------------------------------------------------
# Backend registry + capability negotiation
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a backend can run, consulted during plan-time negotiation.

    platforms:           JAX platforms the backend runs on at all.
    preferred_platforms: platforms where it should win negotiation (scored
                         above plain support).
    precisions:          plane precisions it implements.
    max_n:               largest supported transform length (None = unbounded).
    priority:            tie-break between equally-capable backends.
    native_2d:           the backend fn executes a joint multi-axis plan
                         (``fft_plan.n2`` set) in one call.  Backends without
                         it still serve 2-D specs — the handle composes the
                         cached row and ``axis=-2`` column 1-D plans of the
                         same backend.
    bluestein:           the backend executes non-power-of-two lengths (the
                         planner's Bluestein chirp-conv leaves).  Backends
                         without it (``stockham``) disclaim non-pow2 specs
                         during negotiation.
    """

    platforms: frozenset = frozenset({"cpu", "gpu", "tpu"})
    preferred_platforms: frozenset = frozenset()
    precisions: frozenset = frozenset({"float32"})
    max_n: Optional[int] = None
    priority: int = 10
    native_2d: bool = False
    bluestein: bool = False

    def supports(self, spec: FFTSpec, platform: str) -> bool:
        if platform not in self.platforms:
            return False
        if spec.precision not in self.precisions:
            return False
        if self.max_n is not None and max(spec.n, spec.n2 or 0) > self.max_n:
            return False
        if not self.bluestein and not _is_pow2(spec.n):
            return False
        return True

    def score(self, platform: str) -> int:
        return self.priority + (100 if platform in self.preferred_platforms else 0)


@dataclasses.dataclass(frozen=True)
class Backend:
    """A registered executor: transforms the last axis of split planes.

    ``takes_axis`` backends additionally accept ``axis=-2`` and transform the
    second-to-last axis in place (the pencil column pass) — detected from the
    function signature at registration.

    ``seq`` is the registration sequence number — the negotiation tie-break
    (see :func:`_negotiate`).
    """

    name: str
    fn: Callable  # (xr, xi, *, inverse: bool, planned: PlannedFFT) -> Planes
    capabilities: BackendCapabilities
    takes_axis: bool = False
    seq: int = 0


_REGISTRY: dict = {}
_REGISTRY_SEQ = itertools.count()


def _accepts_axis(fn: Callable) -> bool:
    try:
        return "axis" in inspect.signature(fn).parameters
    except (TypeError, ValueError):  # builtins / exotic callables
        return False


def register_backend(
    name: str,
    fn: Callable,
    capabilities: BackendCapabilities | None = None,
    *,
    overwrite: bool = False,
) -> Backend:
    """Register ``fn`` as FFT backend ``name``.

    ``fn(xr, xi, *, inverse, planned)`` must transform the last axis of the
    split float32 planes, following ``planned.fft_plan``'s schedule (or its
    own, for reference backends).  If it also takes an ``axis`` keyword it
    will be handed ``axis=-2`` column transforms directly (no transpose glue).
    Registering an existing name requires ``overwrite=True`` so a typo cannot
    silently shadow a built-in.
    """
    if not overwrite and name in _REGISTRY:
        raise PlanError(f"FFT backend {name!r} is already registered")
    entry = Backend(
        name,
        fn,
        capabilities or BackendCapabilities(),
        takes_axis=_accepts_axis(fn),
        seq=next(_REGISTRY_SEQ),
    )
    _REGISTRY[name] = entry
    # Existing cached plans may have negotiated without this entry (or hold a
    # stale fn under overwrite=True) — re-resolve on next plan().
    _plan_cached.cache_clear()
    return entry


def available_backends() -> tuple:
    """Registered backend names, sorted."""
    return tuple(sorted(_REGISTRY))


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise PlanError(
            f"unknown FFT backend {name!r}; registered: {available_backends()}"
        ) from None


def _negotiate(spec: FFTSpec, platform: str) -> Backend:
    """Highest capability score wins; ties break toward the *most recently
    registered* entry, so an explicitly registered platform-preferred backend
    beats a built-in default that also prefers the platform (the built-ins
    register first)."""
    best = None
    for entry in _REGISTRY.values():
        if not entry.capabilities.supports(spec, platform):
            continue
        key = (entry.capabilities.score(platform), entry.seq)
        if best is None or key > (best.capabilities.score(platform), best.seq):
            best = entry
    if best is None:
        raise PlanError(
            f"no registered FFT backend supports {spec} on platform {platform!r}"
        )
    return best


# ---------------------------------------------------------------------------
# Default-backend scoping
# ---------------------------------------------------------------------------

_GLOBAL_DEFAULT: Optional[str] = os.environ.get("REPRO_FFT_BACKEND") or None
_scope = threading.local()


def _scope_stack() -> list:
    stack = getattr(_scope, "stack", None)
    if stack is None:
        stack = _scope.stack = []
    return stack


@contextlib.contextmanager
def use_backend(name: str):
    """Scope the default FFT backend: ``with use_backend('stockham'): ...``.

    Nested scopes stack; the previous default is restored on exit even when
    the body raises.  The name is validated against the registry on entry.
    """
    get_backend(name)  # fail fast on unknown names
    stack = _scope_stack()
    stack.append(name)
    try:
        yield
    finally:
        stack.pop()


def default_backend() -> Optional[str]:
    """The backend name new plans will use absent a per-call ``backend=``.

    Innermost :func:`use_backend` scope, else the ``REPRO_FFT_BACKEND``
    environment override, else None — meaning capability negotiation picks
    per plan (xla on CPU/GPU, pallas on TPU).
    """
    stack = _scope_stack()
    if stack:
        return stack[-1]
    return _GLOBAL_DEFAULT


def set_default_backend(name: str) -> None:  # deprecated shim
    """Deprecated: use :func:`use_backend` (scoped) instead."""
    warnings.warn(
        "set_default_backend is deprecated; use the use_backend() context "
        "manager (scoped) or pass backend= to plan()",
        DeprecationWarning,
        stacklevel=2,
    )
    global _GLOBAL_DEFAULT
    get_backend(name)
    _GLOBAL_DEFAULT = name


# ---------------------------------------------------------------------------
# Planes helpers
# ---------------------------------------------------------------------------


def _split(x: ArrayOrPlanes) -> tuple[jax.Array, jax.Array, bool]:
    """Returns (real, imag, was_complex)."""
    if isinstance(x, (tuple, list)):
        xr, xi = x
        return jnp.asarray(xr, jnp.float32), jnp.asarray(xi, jnp.float32), False
    x = jnp.asarray(x)
    if jnp.iscomplexobj(x):
        return (
            jnp.real(x).astype(jnp.float32),
            jnp.imag(x).astype(jnp.float32),
            True,
        )
    return x.astype(jnp.float32), jnp.zeros_like(x, jnp.float32), True


def _join(yr, yi, was_complex: bool) -> ArrayOrPlanes:
    if was_complex:
        return jax.lax.complex(yr, yi)
    return yr, yi


def _input_shape(x: ArrayOrPlanes) -> tuple:
    if isinstance(x, (tuple, list)):
        return jnp.shape(x[0])
    return jnp.shape(x)


# ---------------------------------------------------------------------------
# PlannedFFT
# ---------------------------------------------------------------------------


def _materialize_luts(
    fft_plan: plan_lib.FFTPlan, inverse: bool, backend_name: str
) -> tuple:
    """Host-side LUTs for every program pass — the paper's texture-memory
    tables built at plan time so first execution pays no table construction.

    Warms the exact builder the backend will hit (ops' scaled transform-LUT
    and inter-factor twiddle caches for pallas, the twiddle factory
    otherwise); the returned references keep the arrays alive for the
    lifetime of the plan."""
    luts = []
    if backend_name == "pallas":
        from repro.kernels import ops as kernel_ops  # lazy: avoids cycle

        for p in fft_plan.passes:
            # Bluestein inner-conv passes pin their own direction.
            eff = p.inverse if p.inverse is not None else inverse
            if p.kind == "reorder":
                continue
            if p.kind == "bluestein":
                luts.append(kernel_ops._bluestein_luts(p, eff))
            elif p.kind == "direct":
                luts.append(kernel_ops._direct_luts(p.n, eff))
            else:
                luts.append(kernel_ops._fused_luts(p.n1, p.n2, eff))
            if p.twiddle_after is not None:
                luts.append(kernel_ops._pass_twiddle_luts(*p.twiddle_after, eff))
        return tuple(luts)
    for p in fft_plan.leaf_passes:
        if p.kind == "bluestein":
            # Chirp planes + B̂ spectrum, interned like every twiddle table.
            luts.append(tw.bluestein_chirp(p.n, inverse))
            luts.append(tw.bluestein_spectrum(p.n, p.n1, inverse))
            luts.append(tw.bluestein_postchirp(p.n, inverse))
        elif p.kind == "direct":
            luts.append(tw.dft_matrix(p.n, inverse))
        else:
            luts.append(tw.dft_matrix(p.n1, inverse))
            luts.append(tw.twiddle_grid(p.n1, p.n2, inverse))
            luts.append(tw.dft_matrix(p.n2, inverse))
    return tuple(luts)


def _pick_tiles(fft_plan: plan_lib.FFTPlan, batch_hint: Optional[int]) -> tuple:
    """((leaf_n, batch_tile), ...) — budget-picked, capped by the batch hint.

    The hint only applies to level-free plans: under a split level each
    leaf runs with batch × co-factor rows, so capping by the user batch
    alone would collapse the tile (and explode the kernel grid) on large
    sizes.
    """
    tiles = []
    for p in fft_plan.leaf_passes:
        bt = plan_lib.pick_batch_tile(p)
        if batch_hint is not None and not fft_plan.levels:
            cap = 1 << (batch_hint - 1).bit_length()  # next pow2 >= hint
            bt = max(1, min(bt, cap))
        tiles.append((p.n, bt))
    return tuple(tiles)


def _tuned_tiles(
    fft_plan: plan_lib.FFTPlan,
    batch_hint: Optional[int],
    cfg: Optional[dict],
) -> tuple:
    """The heuristic tiles of :func:`_pick_tiles`, scaled per leaf by a
    tuned plan config.

    The tuner's tile is relative to the *hint-free* heuristic (it cannot
    know per-call batch hints), so it is applied as a scale on top of the
    hint-capped default — a tuned halving halves the capped tile too, and
    the modeled (no-op) pick leaves the hint behavior untouched."""
    tiles = dict(_pick_tiles(fft_plan, batch_hint))
    if cfg:
        for leaf_n, bt in cfg.get("batch_tiles", {}).items():
            n = int(leaf_n)
            if n not in tiles:
                continue
            base = plan_lib.pick_batch_tile(fft_plan.leaf_pass(n))
            while base > int(bt) and tiles[n] > 1:
                base //= 2
                tiles[n] = max(1, tiles[n] // 2)
    return tuple(tiles.items())


class PlannedFFT:
    """A frozen, executable transform schedule (the cuFFT/FFTW plan handle).

    Carries the :class:`FFTSpec`, the resolved :class:`Backend`, the
    :class:`~repro.core.plan.FFTPlan` factorisation, pre-materialized
    twiddle/DFT LUTs and per-leaf batch tiles.  Calling it runs the
    transform; instances are hashable and interned by :func:`plan` so
    ``plan(spec) is plan(spec)``.

    The complex kinds — including fft2/ifft2, whose rows+columns compile
    into ONE joint :class:`~repro.core.plan.FFTPlan` program — execute
    directly through the backend.  The real-packing kinds (rfft/irfft/
    rfft2/irfft2) hold child PlannedFFT handles for their inner complex
    transforms plus an ``epilogue`` :class:`~repro.core.plan.Pass` — the
    Hermitian recombination executed as one more program pass (a single
    Pallas kernel on the pallas backend) rather than traced XLA glue; the
    2-D real kinds apply it row-wise between the row and column programs.
    """

    def __init__(
        self,
        spec: FFTSpec,
        backend: Backend,
        fft_plan: Optional[plan_lib.FFTPlan],
        *,
        children: tuple = (),
        luts: tuple = (),
        batch_tiles: tuple = (),
        epilogue: Optional[plan_lib.Pass] = None,
        tuned: Optional[dict] = None,
    ):
        self.spec = spec
        self.backend = backend
        self.fft_plan = fft_plan
        self.children = children
        self.luts = luts
        self.epilogue = epilogue
        self._batch_tiles = dict(batch_tiles)
        #: The tuning config this plan was built from (None = fixed
        #: heuristics) — see :mod:`repro.core.tuning`.
        self.tuned = tuned
        #: pass index → tuned grid-step chunk, consumed by the pallas
        #: executor; empty when untuned (heuristic chunks per pass).
        self.pass_chunks: Mapping[int, int] = (
            {int(k): int(v) for k, v in tuned.get("chunks", {}).items()}
            if tuned
            else {}
        )
        #: Leaf demotions recorded at execution time (kernel failed twice →
        #: quarantined → traced-XLA fallback) — see :mod:`repro.core.faults`.
        #: Empty on the happy path; appended to by the executors through the
        #: ``degradations`` thread, deduplicated per (backend, kind, pass).
        self._degradations: list = []

    # -- identity ----------------------------------------------------------

    def __hash__(self):
        return hash((self.spec, self.backend.name))

    def __eq__(self, other):
        return (
            isinstance(other, PlannedFFT)
            and self.spec == other.spec
            and self.backend.name == other.backend.name
        )

    def __repr__(self):
        return f"PlannedFFT({self.spec}, backend={self.backend.name!r})"

    # -- introspection -----------------------------------------------------

    @property
    def batch_tiles(self) -> Mapping[int, int]:
        """leaf length → chosen kernel batch tile (read-only: the handle is
        interned and shared process-wide)."""
        return types.MappingProxyType(self._batch_tiles)

    @property
    def degradations(self) -> tuple:
        """Leaf demotions this plan has taken (snapshot, execution-recorded).

        Each entry is ``{"backend", "kind", "pass", "reason"}``: a
        pallas leaf that failed twice, was quarantined, and now executes
        through the traced-XLA fallback.  Includes the children's ledgers
        for the real-packing / composed kinds.
        """
        recs = list(self._degradations)
        for c in self.children:
            recs.extend(c.degradations)
        return tuple(recs)

    @property
    def hbm_round_trips(self) -> int:
        if self.fft_plan is not None:
            return self.fft_plan.hbm_round_trips
        trips = sum(c.hbm_round_trips for c in self.children)
        return trips + (1 if self.epilogue is not None else 0)

    @property
    def passes(self) -> tuple:
        """The linearized pass program this handle executes, in execution
        order (child passes for the real-packing kinds, with the Hermitian
        recombination epilogue slotted where it actually runs)."""
        if self.fft_plan is not None:
            return self.fft_plan.passes
        ep = (self.epilogue,) if self.epilogue is not None else ()
        kind = self.spec.kind
        if kind == "irfft":
            return ep + self.children[0].passes
        if kind == "rfft2":
            inner, cols = self.children
            return inner.passes + ep + cols.passes
        if kind == "irfft2":
            inner, cols = self.children
            return cols.passes + ep + inner.passes
        return tuple(p for c in self.children for p in c.passes) + ep

    def describe(self) -> str:
        spec = self.spec
        size = f"N={spec.n2}x{spec.n}" if spec.n2 is not None else f"N={spec.n}"
        head = f"{spec.kind} {size} backend={self.backend.name}: "
        if self.fft_plan is not None:
            return (
                head
                + plan_lib.describe_program(self.fft_plan)
                + self._describe_tuned()
                + self._describe_bluestein()
                + self._describe_degraded()
            )
        parts = [plan_lib.describe_program(c.fft_plan) for c in self.children
                 if c.fft_plan is not None]
        s = head + " | ".join(parts)
        if self.epilogue is not None:
            s += f"; epilogue pass: {self.epilogue.kind} n={self.epilogue.n}"
        return (
            s
            + self._describe_bluestein()
            + self._describe_degraded()
        )

    def _describe_bluestein(self) -> str:
        """Chirp-conv pad and modeled overhead vs a hypothetical mixed-radix
        transform, appended for non-power-of-two lengths so the Bluestein tax
        is visible next to the schedule that pays it."""
        n = self.spec.n
        if n < 2 or not (n & (n - 1)):
            return ""
        from repro.analysis import roofline as rl  # lazy: analysis layer

        pad = (self.tuned or {}).get("bluestein_pad")
        rep = rl.bluestein_report(n, pad=pad)
        return (
            f"; bluestein: pad {rep['pad']} ({rep['pad_ratio']:.2f}x), "
            f"{rep['flops_overhead']:.1f}x flops vs mixed-radix, "
            f"{rep['hbm_round_trips']} hbm round trips"
        )

    def _describe_degraded(self) -> str:
        """Leaf demotions, appended so a degraded schedule is visible next
        to the plan that took it (empty on the happy path)."""
        recs = self.degradations
        if not recs:
            return ""
        parts = [
            f"pass {r['pass']} {r['kind']} ({r['backend']}→xla)" for r in recs
        ]
        return "; DEGRADED: " + ", ".join(parts)

    def _describe_tuned(self) -> str:
        """The tuned choices per pass, appended to :meth:`describe` so the
        searched decisions are visible next to the schedule they shape."""
        if not self.tuned:
            return ""
        parts = [
            f"fused_max={self.tuned['fused_max']}",
            f"direct_max={self.tuned.get('direct_max', plan_lib.DIRECT_MAX)}",
        ]
        for i, c in sorted(self.pass_chunks.items()):
            parts.append(f"pass {i} chunk={c}")
        for n, bt in sorted(self._batch_tiles.items()):
            parts.append(f"leaf {n} tile={bt}")
        return "; tuned: " + ", ".join(parts)

    # -- execution ---------------------------------------------------------

    def _complex(self, xr, xi, inverse: bool, axis: int = -1) -> Planes:
        """Backend-executed complex transform over ``axis`` (-1 or -2).

        ``axis=-2`` goes to the backend natively when it declared axis
        support (the pencil column pass); otherwise through a transpose
        sandwich so externally registered last-axis backends keep working.
        """
        if axis == -1 or self.backend.takes_axis:
            return self.backend.fn(xr, xi, inverse=inverse, planned=self, axis=axis) \
                if self.backend.takes_axis \
                else self.backend.fn(xr, xi, inverse=inverse, planned=self)
        xr, xi = jnp.swapaxes(xr, axis, -1), jnp.swapaxes(xi, axis, -1)
        yr, yi = self.backend.fn(xr, xi, inverse=inverse, planned=self)
        return jnp.swapaxes(yr, axis, -1), jnp.swapaxes(yi, axis, -1)

    def _to_last(self, a):
        return jnp.moveaxis(a, self.spec.axis, -1)

    def _from_last(self, a):
        return jnp.moveaxis(a, -1, self.spec.axis)

    def apply_planes(self, xr: jax.Array, xi: jax.Array) -> Planes:
        """Run the planned transform on split float32 planes (axis-aware).

        This is the raw entry point used by the distributed pencil driver and
        the conv layer; :meth:`__call__` adds complex-array packing on top.
        An ``axis=-2`` complex plan executes as an in-place column pass on
        axis-capable backends — no materialized transpose.  The work is
        named by the spec's kind, as in :meth:`__call__`.
        """
        with jax.named_scope(self.spec.kind):
            return self._planes(xr, xi)

    def _planes(self, xr: jax.Array, xi: jax.Array) -> Planes:
        kind = self.spec.kind
        if kind in ("fft2", "ifft2"):
            return self._fft2_planes(xr, xi)
        ax = self.spec.axis
        if ax < 0:
            ax += xr.ndim
        if kind in _COMPLEX_KINDS and ax == xr.ndim - 2 and xr.ndim >= 2:
            return self._complex(xr, xi, inverse=kind == "ifft", axis=-2)
        move = ax != xr.ndim - 1
        if move:
            xr, xi = self._to_last(xr), self._to_last(xi)
        if kind in _COMPLEX_KINDS:
            yr, yi = self._complex(xr, xi, inverse=kind == "ifft")
        else:
            raise PlanError(f"apply_planes on {kind!r} plan; use __call__")
        if move:
            yr, yi = self._from_last(yr), self._from_last(yi)
        return yr, yi

    def __call__(
        self, x: ArrayOrPlanes, check: Optional[str] = None
    ) -> ArrayOrPlanes:
        """Execute the planned transform.

        ``check`` arms an opt-in numerics guard over the result (host-side,
        eager-only): ``"nan"`` raises :class:`~repro.core.faults.NumericsError`
        on non-finite output values; ``"parseval"`` checks energy
        conservation (complex kinds) at :data:`PARSEVAL_RTOL` — a cheap
        structured detector for silent corruption on degraded or unfamiliar
        hardware paths.

        The compiled program names the transform's work by the spec's kind
        (``fft``, ``rfft2``, ...), the complex boundary ``to_planes`` /
        ``from_planes``, the Hermitian epilogue ``recomb`` and each pass
        ``p{i}_rows`` / ``p{i}_cols`` (``jax.named_scope``: metadata that a
        profiler trace carries, nothing at run time).  Child plans run
        inside their parent's scope.
        """
        kind = self.spec.kind
        with jax.named_scope(kind):
            if kind in _COMPLEX_KINDS or kind in ("fft2", "ifft2"):
                with jax.named_scope("to_planes"):
                    xr, xi, was_c = _split(x)
                yr, yi = self._planes(xr, xi)
                with jax.named_scope("from_planes"):
                    out = _join(yr, yi, was_c)
            elif kind == "rfft":
                out = self._rfft(x)
            elif kind == "irfft":
                out = self._irfft(x)
            elif kind == "rfft2":
                out = self._rfft2(x)
            else:
                out = self._irfft2(x)
        if check is not None:
            self._run_check(x, out, check)
        return out

    def _run_check(self, x, out, check: str) -> None:
        """The opt-in numerics guards behind ``__call__(x, check=...)``."""
        if check not in ("nan", "parseval"):
            raise PlanError(
                f"unknown numerics check {check!r}; expected 'nan' or 'parseval'",
                spec=self.spec,
                backend=self.backend.name,
            )
        ins = list(x) if isinstance(x, (tuple, list)) else [x]
        outs = list(out) if isinstance(out, (tuple, list)) else [out]
        if any(isinstance(a, jax.core.Tracer) for a in ins + outs):
            raise PlanError(
                "numerics checks are host-side guards; call the plan with "
                "check= outside jit",
                spec=self.spec,
                backend=self.backend.name,
            )
        if check == "nan":
            if not all(bool(jnp.all(jnp.isfinite(a))) for a in outs):
                raise NumericsError(
                    "non-finite values in planned FFT output",
                    spec=self.spec,
                    backend=self.backend.name,
                    check="nan",
                )
            return
        kind = self.spec.kind
        if kind not in ("fft", "ifft", "fft2", "ifft2"):
            raise PlanError(
                f'check="parseval" covers the complex kinds, not {kind!r}',
                spec=self.spec,
                backend=self.backend.name,
            )

        def energy(arrays) -> float:
            # Split planes sum to the same |z|² as the packed complex array.
            return float(
                sum(np.sum(np.abs(np.asarray(a, np.complex128)) ** 2) for a in arrays)
            )

        e_in, e_out = energy(ins), energy(outs)
        scale = self.spec.n * (self.spec.n2 or 1)
        expected = e_in * scale if kind in ("fft", "fft2") else e_in / scale
        if not np.isclose(e_out, expected, rtol=PARSEVAL_RTOL, atol=1e-30):
            raise NumericsError(
                f"Parseval energy mismatch: output {e_out:.6g}, expected "
                f"{expected:.6g} (rtol {PARSEVAL_RTOL})",
                spec=self.spec,
                backend=self.backend.name,
                check="parseval",
            )

    # -- 2-D execution: ONE joint program, no transposes between the axes ---

    def _check_image(self, xr):
        n, n2 = self.spec.n, self.spec.n2
        if xr.ndim < 2 or xr.shape[-2:] != (n2, n):
            raise PlanError(
                f"{self.spec.kind} planned for (..., {n2}, {n}) images, "
                f"got shape {tuple(xr.shape)}"
            )

    def _axis_child(self, axis: int, inverse: bool) -> "PlannedFFT":
        """Cached 1-D plan of the same backend over one image axis — the
        composition path for backends without native multi-axis programs."""
        n = self.spec.n if axis == -1 else self.spec.n2
        return plan(
            FFTSpec(
                n=n,
                kind="ifft" if inverse else "fft",
                axis=axis,
                precision=self.spec.precision,
            ),
            backend=self.backend.name,
        )

    def _fft2_planes(self, xr, xi) -> Planes:
        self._check_image(xr)
        inverse = self.spec.kind == "ifft2"
        if self.fft_plan is not None and self.backend.capabilities.native_2d:
            # The joint program in one backend call: row passes over the
            # last axis, then the in-place strided-column pass — zero
            # materialized transposes (jaxpr-asserted in the tests).
            return self._complex(xr, xi, inverse=inverse)
        xr, xi = self._row_col_plans()[0].apply_planes(xr, xi)
        return self._row_col_plans()[1].apply_planes(xr, xi)

    def _row_col_plans(self) -> tuple:
        """The per-axis 1-D plans of the composition path: the pre-built
        children for beyond-fused column lengths, lazily cached axis plans
        otherwise (backends without native multi-axis programs)."""
        if self.children:
            return self.children
        inverse = self.spec.kind == "ifft2"
        return self._axis_child(-1, inverse), self._axis_child(-2, inverse)

    def apply_rows(self, xr: jax.Array, xi: jax.Array) -> Planes:
        """Run only the row (last-axis) sub-program of a 2-D plan.

        The distributed pencil driver consumes the joint program in two
        halves around its all-to-all transposes: row passes on the
        row-sharded slab, column passes on the column slab."""
        if self.spec.kind not in ("fft2", "ifft2"):
            raise PlanError(f"apply_rows needs a 2-D complex plan, not {self.spec.kind!r}")
        inverse = self.spec.kind == "ifft2"
        if self.fft_plan is None or not self.backend.capabilities.native_2d:
            return self._row_col_plans()[0].apply_planes(xr, xi)
        from repro.kernels import ops as kernel_ops  # lazy: avoids cycle

        row_idx = [i for i, p in enumerate(self.fft_plan.passes) if p.axis == -1]
        row_passes = tuple(self.fft_plan.passes[i] for i in row_idx)
        lead, n = xr.shape[:-1], xr.shape[-1]
        b = int(np.prod(lead)) if lead else 1
        yr, yi = kernel_ops.execute_program(
            xr.reshape(b, n),
            xi.reshape(b, n),
            row_passes,
            inverse=inverse,
            batch_tiles=self._batch_tiles,
            chunks=self._half_chunks(row_idx),
            degradations=self._degradations,
        )
        return yr.reshape(*lead, n), yi.reshape(*lead, n)

    def _half_chunks(self, idx: list) -> Optional[dict]:
        """Re-index tuned pass chunks onto a program half (the joint
        program's pass indices renumber when rows/cols run separately)."""
        chunks = {
            j: self.pass_chunks[i]
            for j, i in enumerate(idx)
            if i in self.pass_chunks
        }
        return chunks or None

    def apply_cols(self, xr: jax.Array, xi: jax.Array) -> Planes:
        """Run only the column (axis -2) sub-program of a 2-D plan, in place
        over whatever width the slab carries (see :meth:`apply_rows`)."""
        if self.spec.kind not in ("fft2", "ifft2"):
            raise PlanError(f"apply_cols needs a 2-D complex plan, not {self.spec.kind!r}")
        inverse = self.spec.kind == "ifft2"
        if self.fft_plan is None or not self.backend.capabilities.native_2d:
            return self._row_col_plans()[1].apply_planes(xr, xi)
        from repro.kernels import ops as kernel_ops  # lazy: avoids cycle

        col_idx = [i for i, p in enumerate(self.fft_plan.passes) if p.axis == -2]
        col_passes = tuple(self.fft_plan.passes[i] for i in col_idx)
        if not col_passes:
            return xr, xi
        lead, (rows, w) = xr.shape[:-2], xr.shape[-2:]
        if rows != self.spec.n2:
            raise PlanError(f"plan is for n2={self.spec.n2} columns, got {rows}")
        b = int(np.prod(lead)) if lead else 1
        yr, yi = kernel_ops.execute_program2d(
            xr.reshape(b, rows, w),
            xi.reshape(b, rows, w),
            col_passes,
            inverse=inverse,
            batch_tiles=self._batch_tiles,
            chunks=self._half_chunks(col_idx),
            degradations=self._degradations,
        )
        return yr.reshape(*lead, rows, w), yi.reshape(*lead, rows, w)

    def _recomb_kernel(self) -> bool:
        """Whether the Hermitian recombination runs as a Pallas epilogue pass
        (pallas backend) instead of traced XLA glue."""
        return self.backend.name == "pallas" and self.epilogue is not None

    @jax.named_scope("recomb")
    def _recomb_fwd(self, Zr, Zi) -> Planes:
        """Forward Hermitian recombination over the last axis: the packed
        (..., m) spectrum → (..., m+1) real-FFT bins.  One Pallas epilogue
        pass on the pallas backend (row-wise over any leading dims — the 2-D
        kinds reuse it across the image's rows), traced jnp elsewhere."""
        wr_np, wi_np = self.luts[0]
        m = Zr.shape[-1]
        if self._recomb_kernel():

            def kernel() -> Planes:
                from repro.kernels import ops as kernel_ops
                from repro.kernels import pencil as pencil_kernels

                lead = Zr.shape[:-1]
                b = int(np.prod(lead)) if lead else 1
                Xr, Xi = pencil_kernels.rfft_recomb_call(
                    Zr.reshape(b, m), Zi.reshape(b, m), wr_np, wi_np,
                    interpret=kernel_ops.should_interpret(),
                )
                return Xr.reshape(*lead, m + 1), Xi.reshape(*lead, m + 1)

            return faults.run_leaf(
                self.backend.name,
                self.epilogue.kind,
                kernel,
                lambda: fft_xla.rfft_recomb(
                    Zr, Zi, jnp.asarray(wr_np), jnp.asarray(wi_np)
                ),
                degradations=self._degradations,
            )
        wr, wi = jnp.asarray(wr_np), jnp.asarray(wi_np)
        return fft_xla.rfft_recomb(Zr, Zi, wr, wi)

    @jax.named_scope("recomb")
    def _recomb_inv(self, Xr, Xi) -> Planes:
        """Inverse recombination over the last axis: (..., m+1) bins → the
        packed (..., m) spectrum (mirror of :meth:`_recomb_fwd`)."""
        wr_np, wi_np = self.luts[0]  # e^{+2πik/n}
        m = Xr.shape[-1] - 1
        if self._recomb_kernel():

            def kernel() -> Planes:
                from repro.kernels import ops as kernel_ops
                from repro.kernels import pencil as pencil_kernels

                lead = Xr.shape[:-1]
                b = int(np.prod(lead)) if lead else 1
                Zr, Zi = pencil_kernels.irfft_recomb_call(
                    Xr.reshape(b, m + 1), Xi.reshape(b, m + 1), wr_np, wi_np,
                    interpret=kernel_ops.should_interpret(),
                )
                return Zr.reshape(*lead, m), Zi.reshape(*lead, m)

            return faults.run_leaf(
                self.backend.name,
                self.epilogue.kind,
                kernel,
                lambda: fft_xla.irfft_recomb(
                    Xr, Xi, jnp.asarray(wr_np), jnp.asarray(wi_np)
                ),
                degradations=self._degradations,
            )
        wr, wi = jnp.asarray(wr_np), jnp.asarray(wi_np)
        return fft_xla.irfft_recomb(Xr, Xi, wr, wi)

    def _rfft(self, x: jax.Array) -> Planes:
        """Real FFT via even/odd complex packing — N/2-point complex transform.

        Beyond-paper optimisation: the paper transforms complex signals only;
        for the real signals of the SAR / long-conv workloads this halves both
        the arithmetic and — more importantly here — the HBM traffic of the
        forward transform.  Returns (real, imag) planes of n//2 + 1 bins.

        The Hermitian recombination is the plan's ``epilogue`` pass: one
        Pallas kernel round trip on the pallas backend (see
        ``kernels.pencil.rfft_recomb_call``), traced jnp on the others.
        """
        n = self.spec.n
        x = jnp.asarray(x, jnp.float32)
        move = self.spec.axis != -1
        if move:
            x = self._to_last(x)
        if x.shape[-1] != n:
            raise PlanError(f"rfft planned for n={n}, got axis length {x.shape[-1]}")
        (inner,) = self.children
        if n % 2:
            # Odd length: full complex transform (Bluestein leaf), sliced to
            # the n//2+1 Hermitian bins.
            Xr, Xi = inner._complex(x, jnp.zeros_like(x), inverse=False)
            Xr, Xi = Xr[..., : n // 2 + 1], Xi[..., : n // 2 + 1]
        else:
            zr = x[..., 0::2]  # even samples  -> real plane
            zi = x[..., 1::2]  # odd samples   -> imag plane
            Zr, Zi = inner._complex(zr, zi, inverse=False)
            Xr, Xi = self._recomb_fwd(Zr, Zi)
        if move:
            Xr, Xi = self._from_last(Xr), self._from_last(Xi)
        return Xr, Xi

    def _irfft(self, x: Planes) -> jax.Array:
        """Inverse of the rfft packing; output is the length-``n`` real signal.

        The recombination prologue mirrors :meth:`_rfft`: a single Pallas
        pass on the pallas backend, traced jnp elsewhere.
        """
        n = self.spec.n
        Xr, Xi = x
        move = self.spec.axis != -1
        if move:
            Xr, Xi = self._to_last(Xr), self._to_last(Xi)
        m = n // 2
        if Xr.shape[-1] != m + 1:
            raise PlanError(f"irfft expects n//2+1={m + 1} bins, got {Xr.shape[-1]}")
        (inner,) = self.children
        if n % 2:
            # Odd length: Hermitian-extend the bins to the full spectrum,
            # complex inverse (Bluestein leaf), real part.
            Zr = jnp.concatenate([Xr, jnp.flip(Xr[..., 1:], -1)], axis=-1)
            Zi = jnp.concatenate([Xi, -jnp.flip(Xi[..., 1:], -1)], axis=-1)
            out, _ = inner._complex(Zr, Zi, inverse=True)
        else:
            Zr, Zi = self._recomb_inv(Xr, Xi)
            zr, zi = inner._complex(Zr, Zi, inverse=True)
            out = jnp.stack([zr, zi], axis=-1).reshape(*zr.shape[:-1], n)
        if move:
            out = self._from_last(out)
        return out

    def _rfft2(self, x: jax.Array) -> Planes:
        """Real 2-D FFT: row rfft (packed complex rows + row-wise Hermitian
        recombination epilogue) followed by the full complex column pass over
        the (..., n2, n//2+1) half-spectrum — numpy ``rfft2`` layout.  On the
        pallas backend every stage is a kernel pass: the packed row program,
        the recombination epilogue, and the in-place strided-column pass."""
        n = self.spec.n
        x = jnp.asarray(x, jnp.float32)
        self._check_image(x)
        inner, cols = self.children
        zr = x[..., 0::2]  # even samples  -> real plane
        zi = x[..., 1::2]  # odd samples   -> imag plane
        Zr, Zi = inner._complex(zr, zi, inverse=False)
        Xr, Xi = self._recomb_fwd(Zr, Zi)  # (..., n2, n//2 + 1)
        return cols._complex(Xr, Xi, inverse=False, axis=-2)

    def _irfft2(self, x: Planes) -> jax.Array:
        """Inverse of :meth:`_rfft2`: column ifft over the half-spectrum,
        inverse recombination row-wise, packed row ifft, sample interleave."""
        n, n2 = self.spec.n, self.spec.n2
        Xr, Xi = x
        m = n // 2
        if Xr.ndim < 2 or Xr.shape[-2:] != (n2, m + 1):
            raise PlanError(
                f"irfft2 expects (..., {n2}, {m + 1}) bins, got {tuple(Xr.shape)}"
            )
        inner, cols = self.children
        Xr, Xi = cols._complex(Xr, Xi, inverse=True, axis=-2)
        Zr, Zi = self._recomb_inv(Xr, Xi)
        zr, zi = inner._complex(Zr, Zi, inverse=True)
        return jnp.stack([zr, zi], axis=-1).reshape(*zr.shape[:-1], n)


# ---------------------------------------------------------------------------
# plan()
# ---------------------------------------------------------------------------


def plan(
    spec: FFTSpec | int,
    *,
    backend: Optional[str] = None,
    tune: Optional[str] = None,
) -> PlannedFFT:
    """Resolve ``spec`` into an interned :class:`PlannedFFT` executor.

    ``backend=None`` uses the innermost :func:`use_backend` scope, the
    ``REPRO_FFT_BACKEND`` env var, or capability negotiation, in that order.
    Plans are cached: the same (spec, backend, platform, tune mode) returns
    the *same* object, so jit tracing of a planned call hits the
    compilation cache.

    ``tune`` selects how the plan's performance knobs (fused-vs-split
    crossover, per-pass chunk widths, leaf batch tiles) are chosen:
    ``"off"`` keeps the fixed VMEM-budget heuristics, ``"model"`` (the
    default, also via ``REPRO_FFT_TUNE``) takes the roofline model's pick
    with zero measurements, and ``"measure"`` times the roofline-pruned
    survivors once and records the winner in the persistent tuning cache —
    see :mod:`repro.core.tuning`.
    """
    from repro.core import tuning  # lazy: tuning imports the conv engines

    if isinstance(spec, int):
        spec = FFTSpec(n=spec)
    name = backend if backend is not None else default_backend()
    return _plan_cached(spec, name, jax.default_backend(), tuning.resolve_mode(tune))


#: Ring-buffer capacity of the plan log: long sessions (serving loops that
#: plan thousands of shapes) keep the most recent schedules instead of
#: growing without bound.
PLAN_LOG_MAX = 1024

#: Every (FFTSpec, backend name) materialized by :func:`_plan_cached`, in
#: creation order — a bounded deque of the last :data:`PLAN_LOG_MAX`
#: entries.  Cache hits don't re-log, so the tail of the log after a
#: snapshot is exactly the set of *new* schedules an operation forced —
#: which is how the tests assert overlap-save never plans past FUSED_MAX.
_PLAN_LOG: collections.deque = collections.deque(maxlen=PLAN_LOG_MAX)


def plan_log() -> tuple:
    """Snapshot of the most recent (spec, backend_name) pairs planned this
    process (ring buffer of :data:`PLAN_LOG_MAX`; oldest entries fall off)."""
    return tuple(_PLAN_LOG)


def clear_plan_log() -> None:
    """Empty the plan log (the creation-order record, NOT the plan cache —
    existing :class:`PlannedFFT` handles stay interned)."""
    _PLAN_LOG.clear()


@functools.lru_cache(maxsize=1024)
def _plan_cached(
    spec: FFTSpec, backend_name: Optional[str], platform: str, tune: str = "model"
) -> PlannedFFT:
    planned = _build_plan(spec, backend_name, platform, tune)
    _PLAN_LOG.append((spec, planned.backend.name))
    return planned


def _build_plan(
    spec: FFTSpec, backend_name: Optional[str], platform: str, tune: str = "model"
) -> PlannedFFT:
    from repro.core import tuning  # lazy: tuning imports the conv engines

    if backend_name is None:
        entry = _negotiate(spec, platform)
    else:
        entry = get_backend(backend_name)
        if not entry.capabilities.supports(spec, platform):
            raise PlanError(
                f"backend {entry.name!r} does not support {spec} on {platform!r}"
            )

    kind = spec.kind
    if kind in _COMPLEX_KINDS:
        cfg = tuning.plan_config(spec, entry.name, tune)
        fft_plan = plan_lib.plan_fft(
            spec.n,
            cfg["fused_max"] if cfg else plan_lib.FUSED_MAX,
            cfg.get("direct_max", plan_lib.DIRECT_MAX) if cfg else plan_lib.DIRECT_MAX,
            pad=cfg.get("bluestein_pad") if cfg else None,
        )
        if entry.name == "pallas" and platform == "tpu":
            plan_lib.check_tpu_vmem(fft_plan.passes)
        return PlannedFFT(
            spec,
            entry,
            fft_plan,
            luts=_materialize_luts(fft_plan, kind == "ifft", entry.name),
            batch_tiles=_tuned_tiles(fft_plan, spec.batch_hint, cfg),
            tuned=cfg,
        )

    if kind in ("fft2", "ifft2") and plan_lib.joint2d_supported(spec.n2):
        # ONE joint multi-axis program: row passes over the last axis,
        # then the column passes over n2 — in-place for fused-regime
        # columns, strip-mined (width-swept multi-factor strided passes)
        # beyond — no per-axis child plans and no transposes between the
        # axes (compile_passes2d).
        cfg = tuning.plan_config(spec, entry.name, tune)
        fft_plan = plan_lib.plan_fft2(
            spec.n,
            spec.n2,
            cfg["fused_max"] if cfg else plan_lib.FUSED_MAX,
            cfg.get("direct_max", plan_lib.DIRECT_MAX) if cfg else plan_lib.DIRECT_MAX,
        )
        if entry.name == "pallas" and platform == "tpu":
            plan_lib.check_tpu_vmem(fft_plan.passes)
        return PlannedFFT(
            spec,
            entry,
            fft_plan,
            luts=_materialize_luts(fft_plan, kind == "ifft2", entry.name),
            batch_tiles=_tuned_tiles(fft_plan, None, cfg),
            tuned=cfg,
        )

    def child(n: int, inverse: bool, batch_hint: Optional[int], axis: int = -1) -> PlannedFFT:
        return _plan_cached(
            FFTSpec(
                n=n,
                kind="ifft" if inverse else "fft",
                axis=axis,
                precision=spec.precision,
                batch_hint=batch_hint,
            ),
            entry.name,
            platform,
            tune,
        )

    if kind in ("fft2", "ifft2"):
        # Column length beyond even the strip-mined gate (> FUSED_MAX²):
        # the handle composes the row plan and the axis=-2 column plan.
        inverse2 = kind == "ifft2"
        rows = child(spec.n, inverse2, None)
        cols = child(spec.n2, inverse2, None, axis=-2)
        return PlannedFFT(spec, entry, None, children=(rows, cols))

    inverse = kind in ("irfft", "irfft2")
    if kind in ("rfft", "irfft") and spec.n % 2:
        # Odd length: the even/odd complex packing needs an even split, so
        # the real transform runs as a full-length complex Bluestein FFT
        # (imag plane zero) sliced to the n//2+1 Hermitian bins — no
        # recombination epilogue.
        inner = child(spec.n, inverse, spec.batch_hint)
        return PlannedFFT(spec, entry, None, children=(inner,))
    m = spec.n // 2
    bins = (1, 1, m + 1)
    epilogue = plan_lib.Pass(
        kind="irfft_recomb" if inverse else "rfft_recomb",
        n=spec.n,
        view_in=bins if inverse else (1, 1, m),
        view_out=(1, 1, m) if inverse else bins,
        order="natural",
    )
    luts = (tw.rfft_recomb_twiddle(spec.n, inverse=inverse),)
    # The packed complex row transform sees the caller's batch unchanged.
    inner = child(m, inverse, spec.batch_hint if kind in ("rfft", "irfft") else None)
    if kind in ("rfft", "irfft"):
        return PlannedFFT(
            spec, entry, None, children=(inner,), luts=luts, epilogue=epilogue
        )
    # rfft2 / irfft2: packed rows + recomb epilogue + axis=-2 column pass
    # over the half-spectrum (the column plan executes in place at whatever
    # width the slab carries, so the non-power-of-two m+1 bins are fine).
    cols = child(spec.n2, inverse, None, axis=-2)
    return PlannedFFT(
        spec, entry, None, children=(inner, cols), luts=luts, epilogue=epilogue
    )


# ---------------------------------------------------------------------------
# Built-in backends
# ---------------------------------------------------------------------------


def _swap_to_last(fn):
    """Run a last-axis transform over axis -2 via a transpose sandwich."""

    def run(xr, xi, *args, **kw):
        xr, xi = jnp.swapaxes(xr, -1, -2), jnp.swapaxes(xi, -1, -2)
        yr, yi = fn(xr, xi, *args, **kw)
        return jnp.swapaxes(yr, -1, -2), jnp.swapaxes(yi, -1, -2)

    return run


def _stockham_backend(xr, xi, *, inverse, planned, axis=-1):
    f = fft_xla.stockham_fft
    if axis == -2:
        f = _swap_to_last(f)
    return f(xr, xi, inverse=inverse)


def _xla_backend(xr, xi, *, inverse, planned, axis=-1):
    n = planned.fft_plan.n
    if n & (n - 1):
        # Non-pow2: traced Bluestein (chirp → cached pow2 conv → chirp).
        f = fft_xla.bluestein_fft
        if axis == -2:
            f = _swap_to_last(f)
        return f(xr, xi, inverse=inverse)
    if axis == -2:
        if n <= plan_lib.DIRECT_MAX and n > 1:
            # Transpose-free column DFT: contract axis -2 directly (the XLA
            # analogue of the pencil column pass); 1/n for inverse is the
            # leaf convention of four_step_fft's direct leaves.
            yr, yi = fft_xla._col_dft(xr, xi, n, inverse)
            if inverse:
                yr, yi = yr / n, yi / n
            return yr, yi
        return _swap_to_last(fft_xla.four_step_fft)(xr, xi, inverse=inverse)
    return fft_xla.four_step_fft(xr, xi, inverse=inverse)


def _pallas_backend(xr, xi, *, inverse, planned, axis=-1):
    from repro.kernels import ops as kernel_ops  # lazy: avoids import cycle

    return kernel_ops.execute_plan(
        xr,
        xi,
        planned.fft_plan,
        inverse=inverse,
        batch_tiles=planned.batch_tiles,
        axis=axis,
        chunks=planned.pass_chunks or None,
        degradations=planned._degradations,
    )


register_backend(
    "stockham",
    _stockham_backend,
    BackendCapabilities(priority=0),
)
register_backend(
    "xla",
    _xla_backend,
    BackendCapabilities(
        preferred_platforms=frozenset({"cpu", "gpu"}), bluestein=True
    ),
)
register_backend(
    "pallas",
    _pallas_backend,
    BackendCapabilities(
        platforms=frozenset({"cpu", "tpu"}),  # cpu = interpret mode
        preferred_platforms=frozenset({"tpu"}),
        native_2d=True,  # executes joint rows+cols programs in one call
        bluestein=True,
    ),
)

# ---------------------------------------------------------------------------
# Plan-cached convenience wrappers (compatibility surface)
# ---------------------------------------------------------------------------


def fft(x: ArrayOrPlanes, *, axis: int = -1, backend: Optional[str] = None) -> ArrayOrPlanes:
    """Complex FFT over ``axis`` (any length ≥ 1), via a cached plan.

    Non-power-of-two lengths route through the planner's Bluestein leaf."""
    n = int(_input_shape(x)[axis])
    return plan(FFTSpec(n=n, kind="fft", axis=axis), backend=backend)(x)


def ifft(x: ArrayOrPlanes, *, axis: int = -1, backend: Optional[str] = None) -> ArrayOrPlanes:
    n = int(_input_shape(x)[axis])
    return plan(FFTSpec(n=n, kind="ifft", axis=axis), backend=backend)(x)


def rfft(x: jax.Array, *, axis: int = -1, backend: Optional[str] = None) -> Planes:
    """Real FFT: n//2+1 bins over ``axis`` via even/odd complex packing."""
    n = int(jnp.shape(x)[axis])
    return plan(FFTSpec(n=n, kind="rfft", axis=axis), backend=backend)(x)


def irfft(x: Planes, n: int, *, axis: int = -1, backend: Optional[str] = None) -> jax.Array:
    """Inverse of :func:`rfft`; output is the length-``n`` real signal."""
    return plan(FFTSpec(n=n, kind="irfft", axis=axis), backend=backend)(x)


def fft2(x: ArrayOrPlanes, *, backend: Optional[str] = None) -> ArrayOrPlanes:
    """2-D FFT over the last two axes (row pass then column pass)."""
    shape = _input_shape(x)
    spec = FFTSpec(n=int(shape[-1]), kind="fft2", n2=int(shape[-2]))
    return plan(spec, backend=backend)(x)


def ifft2(x: ArrayOrPlanes, *, backend: Optional[str] = None) -> ArrayOrPlanes:
    shape = _input_shape(x)
    spec = FFTSpec(n=int(shape[-1]), kind="ifft2", n2=int(shape[-2]))
    return plan(spec, backend=backend)(x)


def rfft2(x: jax.Array, *, backend: Optional[str] = None) -> Planes:
    """Real 2-D FFT of an (..., n2, n) image: (..., n2, n//2 + 1) bins
    (numpy ``rfft2`` layout), via a cached rfft2 plan."""
    shape = jnp.shape(x)
    spec = FFTSpec(n=int(shape[-1]), kind="rfft2", n2=int(shape[-2]))
    return plan(spec, backend=backend)(x)


def irfft2(x: Planes, n: int, n2: int, *, backend: Optional[str] = None) -> jax.Array:
    """Inverse of :func:`rfft2`; output is the real (..., n2, n) image."""
    return plan(FFTSpec(n=n, kind="irfft2", n2=n2), backend=backend)(x)
