"""Plan-and-execute API: plan cache identity, backend registry, use_backend
scoping, axis-aware transforms, and the cross-backend acceptance sweep."""

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DEMOTED

from repro.core import fft as F

BACKENDS = F.available_backends() + (DEMOTED,)
# 262144 = 2¹⁸: the split regime's linearized pass program, on every backend.
ACCEPTANCE_SIZES = [256, 4096, 131072, 262144]


def _rand_c(rng, shape):
    return (rng.standard_normal(shape) + 1j * rng.standard_normal(shape)).astype(
        np.complex64
    )


# ---------------------------------------------------------------------------
# plan cache + handle identity
# ---------------------------------------------------------------------------


def test_plan_cache_identity():
    spec = F.FFTSpec(n=1024)
    assert F.plan(spec) is F.plan(spec)
    # specs are value-keyed, not object-keyed
    assert F.plan(F.FFTSpec(n=1024)) is F.plan(spec)
    # a different backend resolves to a different handle
    assert F.plan(spec, backend="stockham") is not F.plan(spec, backend="xla")
    # int shorthand plans a forward complex FFT
    assert F.plan(1024) is F.plan(spec)


def test_planned_handle_is_hashable():
    a = F.plan(F.FFTSpec(n=512), backend="xla")
    b = F.plan(F.FFTSpec(n=512), backend="xla")
    assert len({a, b}) == 1
    assert hash(a) == hash(b)
    c = F.plan(F.FFTSpec(n=512), backend="stockham")
    assert a != c


def test_planned_carries_schedule_and_luts():
    p = F.plan(F.FFTSpec(n=4096, batch_hint=2), backend="pallas")
    assert p.fft_plan.n == 4096
    assert p.luts, "LUTs should be pre-materialized at plan time"
    # batch_hint caps the kernel tile so a 2-row batch is not padded to 512
    assert all(bt <= 2 for bt in p.batch_tiles.values())
    assert "4096" in p.describe()


def test_spec_validation():
    F.FFTSpec(n=48)  # non-pow2 1-D lengths are valid (Bluestein route)
    with pytest.raises(ValueError):
        F.FFTSpec(n=0)  # n must be >= 1
    with pytest.raises(ValueError):
        F.FFTSpec(n=48, kind="rfft2", n2=64)  # 2-D row axis is still pow2
    with pytest.raises(ValueError):
        F.FFTSpec(n=64, kind="dct")
    with pytest.raises(ValueError):
        F.FFTSpec(n=64, kind="fft2")  # fft2 needs n2
    with pytest.raises(ValueError):
        F.FFTSpec(n=64, n2=32)  # n2 on a 1-D kind
    with pytest.raises(ValueError):
        F.FFTSpec(n=64, kind="fft2", n2=32, axis=0)  # 2-D kinds: last two axes


def test_registration_invalidates_plan_cache(rng):
    F.plan(F.FFTSpec(n=2048))  # warm the cache with a negotiated plan
    name = "late-registered"
    try:
        F.register_backend(
            name,
            lambda xr, xi, *, inverse, planned: F.fft_xla.stockham_fft(
                xr, xi, inverse=inverse
            ),
            F.BackendCapabilities(
                priority=10_000,
                preferred_platforms=frozenset({"cpu", "tpu", "gpu"}),
            ),
        )
        p_after = F.plan(F.FFTSpec(n=2048))
        assert p_after.backend.name == name, "new high-priority backend should win"
        x = _rand_c(rng, (2, 2048))
        y = np.asarray(p_after(jnp.asarray(x)))
        np.testing.assert_allclose(y, np.fft.fft(x), atol=2e-3 * np.abs(y).max())
    finally:
        # don't leak a session-global negotiation winner into other tests
        F._REGISTRY.pop(name, None)
        F._plan_cached.cache_clear()


# ---------------------------------------------------------------------------
# registry + capability negotiation
# ---------------------------------------------------------------------------


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="unknown FFT backend"):
        F.plan(F.FFTSpec(n=64), backend="nope")
    with pytest.raises(ValueError, match="unknown FFT backend"):
        with F.use_backend("nope"):
            pass  # pragma: no cover


def test_duplicate_registration_rejected():
    with pytest.raises(ValueError, match="already registered"):
        F.register_backend("xla", lambda *a, **k: None)


@pytest.fixture
def fresh_plans():
    F._plan_cached.cache_clear()
    yield
    F._plan_cached.cache_clear()


def test_negotiation_picks_xla_on_cpu_and_gpu():
    spec = F.FFTSpec(n=4096)
    # xla is the one backend that prefers cpu and gpu hosts
    assert F._negotiate(spec, "gpu").name == "xla"
    assert F._negotiate(spec, "cpu").name == "xla"


def test_registered_preferred_backend_beats_default(fresh_plans):
    spec = F.FFTSpec(n=1024)
    calls = []

    def fn(xr, xi, *, inverse, planned):
        calls.append(planned.spec.n)
        return F._xla_backend(xr, xi, inverse=inverse, planned=planned)

    F.register_backend(
        "scratch_cpu",
        fn,
        F.BackendCapabilities(preferred_platforms=frozenset({"cpu"})),
    )
    try:
        # same score as the xla default on cpu — later registration wins
        assert F._negotiate(spec, "cpu").name == "scratch_cpu"
        p = F.plan(spec, tune="off")
        x = jnp.zeros((2, 1024), jnp.float32)
        p.apply_planes(x, x)
        assert calls, "negotiation never routed to the registered backend"
    finally:
        F._REGISTRY.pop("scratch_cpu", None)
        F._plan_cached.cache_clear()


@pytest.fixture
def scratch_backend():
    """Yields a registration helper and cleans the global registry up after."""
    names = []

    def register(name, fn, caps=None):
        names.append(name)
        return F.register_backend(name, fn, caps)

    try:
        yield register
    finally:
        for name in names:
            F._REGISTRY.pop(name, None)
        F._plan_cached.cache_clear()


def test_register_custom_backend(rng, scratch_backend):
    calls = []

    def counting(xr, xi, *, inverse, planned):
        calls.append(planned.spec.n)
        return F.fft_xla.stockham_fft(xr, xi, inverse=inverse)

    scratch_backend("counting-test", counting)
    x = _rand_c(rng, (2, 128))
    y = np.asarray(F.fft(jnp.asarray(x), backend="counting-test"))
    np.testing.assert_allclose(y, np.fft.fft(x), atol=2e-3 * np.abs(y).max())
    assert calls == [128]


def test_capability_rejection(scratch_backend):
    def tiny(xr, xi, *, inverse, planned):
        return F.fft_xla.stockham_fft(xr, xi, inverse=inverse)

    scratch_backend("tiny-test", tiny, F.BackendCapabilities(max_n=64))
    assert F.plan(F.FFTSpec(n=64), backend="tiny-test")
    with pytest.raises(ValueError, match="does not support"):
        F.plan(F.FFTSpec(n=128), backend="tiny-test")


# ---------------------------------------------------------------------------
# use_backend scoping
# ---------------------------------------------------------------------------


def test_use_backend_scopes_and_nests():
    base = F.default_backend()
    with F.use_backend("stockham"):
        assert F.default_backend() == "stockham"
        with F.use_backend("xla"):
            assert F.default_backend() == "xla"
        assert F.default_backend() == "stockham"
    assert F.default_backend() == base


def test_use_backend_restores_on_exception():
    base = F.default_backend()
    with pytest.raises(RuntimeError):
        with F.use_backend("stockham"):
            assert F.default_backend() == "stockham"
            raise RuntimeError("boom")
    assert F.default_backend() == base


def test_use_backend_drives_plan_selection(rng):
    with F.use_backend("stockham"):
        p = F.plan(F.FFTSpec(n=256))
    assert p.backend.name == "stockham"


def test_set_default_backend_deprecated():
    import repro.core.fft as fft_mod

    saved = fft_mod._GLOBAL_DEFAULT
    try:
        with pytest.warns(DeprecationWarning):
            fft_mod.set_default_backend("xla")
        assert F.default_backend() == "xla"
    finally:
        fft_mod._GLOBAL_DEFAULT = saved


# ---------------------------------------------------------------------------
# axis-aware transforms
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("axis", [0, 1, -2])
def test_fft_non_last_axis_matches_jnp(axis, rng):
    x = _rand_c(rng, (64, 32, 3))
    y = np.asarray(F.fft(jnp.asarray(x), axis=axis))
    ref = np.asarray(jnp.fft.fft(jnp.asarray(x), axis=axis))
    np.testing.assert_allclose(y, ref, atol=1e-3 * np.abs(ref).max())


def test_rfft_irfft_non_last_axis(rng):
    x = rng.standard_normal((2, 256, 3)).astype(np.float32)
    Xr, Xi = F.rfft(jnp.asarray(x), axis=1)
    ref = np.fft.rfft(x, axis=1)
    assert Xr.shape == ref.shape
    np.testing.assert_allclose(np.asarray(Xr), ref.real, atol=3e-3 * np.abs(ref).max())
    np.testing.assert_allclose(np.asarray(Xi), ref.imag, atol=3e-3 * np.abs(ref).max())
    back = np.asarray(F.irfft((Xr, Xi), 256, axis=1))
    np.testing.assert_allclose(back, x, atol=2e-4)


def test_ifft_axis_roundtrip(rng):
    x = _rand_c(rng, (4, 128, 2))
    y = F.ifft(F.fft(jnp.asarray(x), axis=1), axis=1)
    np.testing.assert_allclose(np.asarray(y), x, atol=2e-4)


# ---------------------------------------------------------------------------
# acceptance sweep: every registered backend and the demoted pallas plan
# (every leaf on the XLA fallback), 1e-3, incl. a non-last axis
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
@pytest.mark.parametrize("n", ACCEPTANCE_SIZES)
def test_planned_matches_jnp_all_backends(backend, n, rng):
    batch = 1 if n > 2**14 else 3
    x = _rand_c(rng, (batch, n))
    planned = F.plan(F.FFTSpec(n=n, kind="fft"), backend=backend)
    y = np.asarray(planned(jnp.asarray(x)))
    ref = np.asarray(jnp.fft.fft(jnp.asarray(x)))
    assert np.abs(y - ref).max() <= 1e-3 * np.abs(ref).max(), (backend, n)


@pytest.mark.parametrize("backend", BACKENDS, indirect=True)
def test_planned_matches_jnp_non_last_axis(backend, rng):
    x = _rand_c(rng, (2, 4096, 2))
    planned = F.plan(F.FFTSpec(n=4096, kind="fft", axis=1), backend=backend)
    y = np.asarray(planned(jnp.asarray(x)))
    ref = np.asarray(jnp.fft.fft(jnp.asarray(x), axis=1))
    assert np.abs(y - ref).max() <= 1e-3 * np.abs(ref).max(), backend
