"""Typed failures, deterministic fault injection, and per-process quarantine.

Production FFT serving (the paper's remote-sensing pitch) cannot afford a
process death every time a kernel refuses to compile on an unknown
device_kind or a tuning-cache file is half-written.  This module is the
single home for everything the engine does *on purpose* when something
goes wrong:

``ReproError`` taxonomy
    Every user-facing error the engine raises derives from ``ReproError``
    and carries the failing context (fault ``site``, ``spec``, ``backend``,
    ``pass_kind``, plus free-form keys) as attributes, formatted into the
    message.  Subclasses multiply inherit from the builtin exception the
    pre-taxonomy code raised (``PlanError`` is a ``ValueError``,
    ``ServeError`` is both a ``ValueError`` and a ``RuntimeError``, ...)
    so ``except ValueError`` call sites keep working.

Fault-injection registry
    A fixed set of named ``SITES`` is compiled into the hot paths via
    ``maybe_fail(site, **context)`` — a no-op unless the site is armed.
    Arm sites deterministically with the ``inject_fault(site, times=...)``
    context manager (tests) or the ``REPRO_FAULTS=site[:times],site2``
    environment variable (CI chaos jobs / ops drills).  A fired site
    raises that site's typed error with ``injected=True``.

Quarantine + degradation ledger
    ``run_leaf`` wraps a claimed pallas/pallas_gpu leaf.  An injected
    fault gets one retry, then the ``(backend, pass-kind)`` pair is
    quarantined for the rest of the process and the leaf executes through
    its traced XLA fallback; each demotion is recorded on the owning plan's
    ``degradations`` list and in a process-global ledger surfaced by
    ``ServeSession.health()``.  A real kernel failure (a compiler refusal,
    say) is never demoted: it raises ``KernelError`` with the compiler's
    message, so no run silently times XLA in place of its kernels.

Everything here is host-side Python: injection fires at trace time, never
inside a jitted computation, so the no-fault jaxpr is byte-identical to a
build without this module in the loop.
"""

from __future__ import annotations

import collections
import contextlib
import os
import threading
from typing import Callable, Dict, Optional, Tuple

__all__ = [
    "ReproError",
    "PlanError",
    "KernelError",
    "TuningCacheError",
    "CollectiveError",
    "ServeError",
    "NumericsError",
    "SITES",
    "inject_fault",
    "maybe_fail",
    "arm_env_faults",
    "fault_counters",
    "clear_faults",
    "quarantine",
    "is_quarantined",
    "quarantined",
    "clear_quarantine",
    "record_degradation",
    "degradation_log",
    "clear_degradations",
    "run_leaf",
]


# ---------------------------------------------------------------------------
# error taxonomy
# ---------------------------------------------------------------------------


class ReproError(Exception):
    """Base of every typed error the engine raises on purpose.

    Context (``site`` / ``spec`` / ``backend`` / ``pass_kind`` and any
    extra keyword pairs) is kept as attributes and appended to the
    message so a bare traceback names the failing plan, not just a line.
    """

    def __init__(
        self,
        message: str = "",
        *,
        site: Optional[str] = None,
        spec=None,
        backend: Optional[str] = None,
        pass_kind: Optional[str] = None,
        injected: bool = False,
        **context,
    ):
        self.site = site
        self.spec = spec
        self.backend = backend
        self.pass_kind = pass_kind
        self.injected = injected
        self.context = dict(context)
        bits = []
        for key, val in (
            ("site", site),
            ("spec", spec),
            ("backend", backend),
            ("pass", pass_kind),
        ):
            if val is not None:
                bits.append(f"{key}={val!r}" if not isinstance(val, str) else f"{key}={val}")
        bits.extend(f"{k}={v!r}" for k, v in self.context.items())
        if injected:
            bits.append("injected")
        super().__init__(message + (f" [{', '.join(bits)}]" if bits else ""))


class PlanError(ReproError, ValueError):
    """Invalid spec, unknown backend, failed negotiation, bad plan input."""


class KernelError(ReproError, RuntimeError):
    """A claimed pallas leaf failed to trace/compile/launch."""


class TuningCacheError(ReproError, RuntimeError):
    """The persistent tuning cache could not be read or written."""


class CollectiveError(ReproError, RuntimeError):
    """A pencil collective (all-to-all) failed."""


class ServeError(ReproError, ValueError, RuntimeError):
    """A serve phase failed or a request was rejected (backpressure)."""


class NumericsError(ReproError, ArithmeticError):
    """An opt-in numerics guard (check="nan"/"parseval") tripped."""


# ---------------------------------------------------------------------------
# fault-injection registry
# ---------------------------------------------------------------------------

#: The named sites compiled into the engine.  Arming any other name is a
#: PlanError — chaos configs fail fast instead of silently never firing.
SITES: Tuple[str, ...] = (
    "kernel.launch",
    "tuning.cache_read",
    "tuning.cache_write",
    "pencil.all_to_all",
    "serve.prefill",
    "serve.insert",
    "serve.generate",
)

_SITE_EXC: Dict[str, type] = {
    "kernel.launch": KernelError,
    "tuning.cache_read": TuningCacheError,
    "tuning.cache_write": TuningCacheError,
    "pencil.all_to_all": CollectiveError,
    "serve.prefill": ServeError,
    "serve.insert": ServeError,
    "serve.generate": ServeError,
}

_LOCK = threading.Lock()
_ARMED: Dict[str, dict] = {}
_FIRED: collections.Counter = collections.Counter()
_ENV_PARSED = False


def _check_site(site: str) -> None:
    if site not in SITES:
        raise PlanError(
            f"unknown fault site {site!r}; registered sites: {', '.join(SITES)}"
        )


def arm_env_faults(force: bool = False) -> None:
    """Parse ``REPRO_FAULTS`` (comma list of ``site`` or ``site:times``).

    Runs once lazily on the first ``maybe_fail``; ``force=True`` re-reads
    the environment (tests).
    """
    global _ENV_PARSED
    if _ENV_PARSED and not force:
        return
    _ENV_PARSED = True
    raw = os.environ.get("REPRO_FAULTS", "")
    for item in (s.strip() for s in raw.split(",")):
        if not item:
            continue
        site, _, times = item.partition(":")
        _check_site(site)
        n = int(times) if times else 1
        with _LOCK:
            _ARMED[site] = {"remaining": n, "exc": _SITE_EXC[site]}


@contextlib.contextmanager
def inject_fault(site: str, *, times: int = 1, exc: Optional[type] = None):
    """Arm ``site`` to raise its typed error the next ``times`` hits.

    Deterministic: exactly the next ``times`` executions of the site fail,
    then the site reverts to whatever arming it had before the block.
    """
    _check_site(site)
    with _LOCK:
        prev = _ARMED.get(site)
        _ARMED[site] = {"remaining": times, "exc": exc or _SITE_EXC[site]}
    try:
        yield
    finally:
        with _LOCK:
            if prev is None:
                _ARMED.pop(site, None)
            else:
                _ARMED[site] = prev


def maybe_fail(site: str, **context) -> None:
    """The hook compiled into each fault site.  No-op unless armed."""
    arm_env_faults()
    if site not in _ARMED:  # fast path: plain dict probe, no lock
        return
    with _LOCK:
        armed = _ARMED.get(site)
        if not armed or armed["remaining"] <= 0:
            return
        armed["remaining"] -= 1
        _FIRED[site] += 1
        exc = armed["exc"]
    raise exc(f"injected fault at {site}", site=site, injected=True, **context)


def fault_counters() -> Dict[str, int]:
    """How many times each site has fired (injected faults only)."""
    return dict(_FIRED)


def clear_faults() -> None:
    """Disarm every site and zero the fired counters (tests)."""
    global _ENV_PARSED
    with _LOCK:
        _ARMED.clear()
        _FIRED.clear()
        _ENV_PARSED = True  # a cleared state stays cleared; force re-arm explicitly


# ---------------------------------------------------------------------------
# per-process quarantine of failing (backend, pass-kind) pairs
# ---------------------------------------------------------------------------

_QUARANTINED: Dict[Tuple[str, str], str] = {}


def quarantine(backend: str, kind: str, reason: str = "") -> None:
    """Stop attempting pallas leaves of ``kind`` on ``backend`` this process."""
    with _LOCK:
        _QUARANTINED.setdefault((backend, kind), reason)


def is_quarantined(backend: str, kind: str) -> bool:
    return (backend, kind) in _QUARANTINED


def quarantined() -> Tuple[Tuple[str, str], ...]:
    """Sorted (backend, pass-kind) pairs currently quarantined."""
    return tuple(sorted(_QUARANTINED))


def clear_quarantine() -> None:
    with _LOCK:
        _QUARANTINED.clear()


# ---------------------------------------------------------------------------
# degradation ledger
# ---------------------------------------------------------------------------

DEGRADATION_LOG_MAX = 256
_DEGRADATIONS: collections.deque = collections.deque(maxlen=DEGRADATION_LOG_MAX)


def record_degradation(
    sink: Optional[list],
    *,
    backend: str,
    kind: str,
    index: Optional[int] = None,
    reason: str = "",
) -> None:
    """Record one leaf demotion on the plan's ledger and the global one.

    Deduplicated by (backend, kind, index) so jit retraces of the same
    plan don't multiply entries.
    """
    rec = {"backend": backend, "kind": kind, "pass": index, "reason": reason}
    key = (backend, kind, index)

    def _has(entries) -> bool:
        return any((r["backend"], r["kind"], r["pass"]) == key for r in entries)

    with _LOCK:
        if sink is not None and not _has(sink):
            sink.append(rec)
        if not _has(_DEGRADATIONS):
            _DEGRADATIONS.append(rec)


def degradation_log() -> Tuple[dict, ...]:
    """Process-global record of every leaf demotion (bounded)."""
    return tuple(_DEGRADATIONS)


def clear_degradations() -> None:
    with _LOCK:
        _DEGRADATIONS.clear()


def run_leaf(
    backend: str,
    kind: str,
    attempt: Callable[[], tuple],
    fallback: Callable[[], tuple],
    *,
    degradations: Optional[list] = None,
    index: Optional[int] = None,
):
    """Execute one claimed pallas leaf; demote it only for injected faults.

    The happy path is ``attempt()`` guarded only by host-side Python — a
    dict probe and a try — so the traced jaxpr is identical to calling
    ``attempt()`` directly.

    An injected fault (``injected=True``, armed through
    :func:`inject_fault` or ``REPRO_FAULTS``) takes the drill path: the
    leaf is retried once (a fault armed with ``times=1`` recovers here with
    no degradation); a second injected failure quarantines
    ``(backend, kind)`` for the process, records the demotion, and runs
    ``fallback()`` — the traced XLA execution of the same pass.

    Any other failure — a compiler refusal, a lowering error, a launch
    error — raises :class:`KernelError` at once, carrying the original
    message.  A kernel that does not compile is deterministic, so a retry
    would only repeat it, and an XLA fallback would hide the device: a run
    that exits 0 has run its kernels.
    """
    if is_quarantined(backend, kind):
        record_degradation(
            degradations, backend=backend, kind=kind, index=index, reason="quarantined"
        )
        return fallback()
    try:
        maybe_fail("kernel.launch", backend=backend, pass_kind=kind)
        return attempt()
    except Exception as err:
        if not getattr(err, "injected", False):
            _raise_kernel_error(err, backend, kind)
    try:
        maybe_fail("kernel.launch", backend=backend, pass_kind=kind)
        return attempt()
    except Exception as err:
        if not getattr(err, "injected", False):
            _raise_kernel_error(err, backend, kind)
        # Second injected strike: demote this leaf for good.
        reason = f"{type(err).__name__}: {err}"
        quarantine(backend, kind, reason)
        record_degradation(
            degradations, backend=backend, kind=kind, index=index, reason=reason
        )
        return fallback()


def _raise_kernel_error(err: Exception, backend: str, kind: str):
    """Re-raise a real leaf failure: contract gates (``NotImplementedError``)
    and typed engine errors as they are, anything else as
    :class:`KernelError` with the original message."""
    if isinstance(err, (NotImplementedError, ReproError)):
        raise err
    raise KernelError(
        f"{type(err).__name__}: {err}", backend=backend, pass_kind=kind
    ) from err
