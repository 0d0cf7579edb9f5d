"""Chaos suite: every registered fault site either recovers with correct
numerics or raises a typed :class:`~repro.core.faults.ReproError`.

Covers the taxonomy contract (multiple inheritance keeps pre-taxonomy
``except ValueError`` call sites working), deterministic injection
(``inject_fault`` / ``REPRO_FAULTS``), per-leaf degradation to the traced
XLA fallback with quarantine reuse across re-plans, tuning-cache corruption
rebuild from the packaged seed with zero measurements, the pencil
collective site, serving retry/deadline/backpressure, and the no-fault
invariant: the planned-FFT jaxpr is byte-identical with the fault
machinery bypassed entirely."""

import json
import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import distributed, faults, tuning
from repro.core import fft as fft_lib


@pytest.fixture(autouse=True)
def _clean_fault_state():
    """Injection, quarantine and the degradation ledger are process-global;
    every chaos test starts and ends clean."""
    faults.clear_faults()
    faults.clear_quarantine()
    faults.clear_degradations()
    yield
    faults.clear_faults()
    faults.clear_quarantine()
    faults.clear_degradations()


def _ref_fft(x):
    return np.fft.fft(np.asarray(x))


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


def test_taxonomy_builtin_compat():
    """Typed errors keep satisfying the builtin excepts the pre-taxonomy
    code raised."""
    assert issubclass(faults.PlanError, ValueError)
    assert issubclass(faults.KernelError, RuntimeError)
    assert issubclass(faults.TuningCacheError, RuntimeError)
    assert issubclass(faults.CollectiveError, RuntimeError)
    assert issubclass(faults.ServeError, ValueError)
    assert issubclass(faults.ServeError, RuntimeError)
    assert issubclass(faults.NumericsError, ArithmeticError)
    for cls in (
        faults.PlanError,
        faults.KernelError,
        faults.TuningCacheError,
        faults.CollectiveError,
        faults.ServeError,
        faults.NumericsError,
    ):
        assert issubclass(cls, faults.ReproError)


def test_error_carries_context():
    err = faults.KernelError(
        "boom", site="kernel.launch", backend="pallas", pass_kind="fused4", n=256
    )
    assert err.site == "kernel.launch"
    assert err.backend == "pallas"
    assert err.pass_kind == "fused4"
    assert err.context == {"n": 256}
    msg = str(err)
    assert "kernel.launch" in msg and "pallas" in msg and "fused4" in msg


def test_unknown_site_rejected():
    with pytest.raises(faults.PlanError, match="unknown fault site"):
        with faults.inject_fault("bogus.site"):
            pass


def test_env_arming(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "serve.generate:2")
    faults.arm_env_faults(force=True)
    for _ in range(2):
        with pytest.raises(faults.ServeError):
            faults.maybe_fail("serve.generate")
    faults.maybe_fail("serve.generate")  # exhausted: no-op
    assert faults.fault_counters()["serve.generate"] == 2


def test_env_arming_rejects_unknown_site(monkeypatch):
    monkeypatch.setenv("REPRO_FAULTS", "no.such.site")
    with pytest.raises(faults.PlanError, match="unknown fault site"):
        faults.arm_env_faults(force=True)


# ---------------------------------------------------------------------------
# kernel.launch: retry → quarantine → degradation to the XLA fallback
# ---------------------------------------------------------------------------


def test_one_shot_kernel_fault_recovers_cleanly():
    """times=1 is absorbed by the in-place retry: no quarantine, no ledger
    entry, exact happy-path numerics."""
    spec = fft_lib.FFTSpec(n=256, batch_hint=2)
    planned = fft_lib.plan(spec, backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 256), jnp.float32) + 0j
    with faults.inject_fault("kernel.launch", times=1):
        y = planned(x)
    np.testing.assert_allclose(np.asarray(y), _ref_fft(x), rtol=1e-3, atol=1e-3)
    assert planned.degradations == ()
    assert faults.quarantined() == ()


def test_persistent_kernel_fault_degrades_to_xla():
    """A leaf that fails twice is quarantined and demoted to the traced XLA
    fallback; the degraded plan still matches the reference at 1e-3 and
    advertises the demotion."""
    spec = fft_lib.FFTSpec(n=512, batch_hint=3)
    planned = fft_lib.plan(spec, backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 512), jnp.float32) + 0j
    with faults.inject_fault("kernel.launch", times=64):
        y = planned(x)
    np.testing.assert_allclose(np.asarray(y), _ref_fft(x), rtol=1e-3, atol=1e-3)
    degs = planned.degradations
    assert degs, "persistent kernel fault must be recorded on the plan"
    assert all(d["backend"] == "pallas" for d in degs)
    assert any(q[0] == "pallas" for q in faults.quarantined())
    assert "DEGRADED" in planned.describe()
    # the process-global ledger (what ServeSession.health surfaces) agrees
    assert any(d["backend"] == "pallas" for d in faults.degradation_log())


def test_warm_replan_reuses_quarantine_without_reattempting():
    """Once (backend, kind) is quarantined, a NEW plan goes straight to the
    fallback: the kernel is never attempted again, so the armed-fault
    counter does not move."""
    spec = fft_lib.FFTSpec(n=512, batch_hint=5)
    planned = fft_lib.plan(spec, backend="pallas")
    x = jax.random.normal(jax.random.PRNGKey(2), (5, 512), jnp.float32) + 0j
    with faults.inject_fault("kernel.launch", times=64):
        planned(x)
    assert faults.quarantined()
    fired = faults.fault_counters()["kernel.launch"]

    spec2 = fft_lib.FFTSpec(n=512, batch_hint=7)
    planned2 = fft_lib.plan(spec2, backend="pallas")
    x2 = jax.random.normal(jax.random.PRNGKey(3), (7, 512), jnp.float32) + 0j
    with faults.inject_fault("kernel.launch", times=64):
        y2 = planned2(x2)
    np.testing.assert_allclose(np.asarray(y2), _ref_fft(x2), rtol=1e-3, atol=1e-3)
    assert faults.fault_counters()["kernel.launch"] == fired
    assert any(d["reason"] == "quarantined" for d in planned2.degradations)


def test_contract_gates_are_never_demoted():
    """NotImplementedError is a planner contract, not a kernel failure:
    run_leaf re-raises it instead of falling back."""

    def attempt():
        raise NotImplementedError("contract")

    with pytest.raises(NotImplementedError):
        faults.run_leaf("pallas", "direct", attempt, lambda: (0, 0))
    assert faults.quarantined() == ()


def test_real_kernel_failure_raises_and_is_never_demoted():
    """A failure that was not injected — a compiler refusal, say — raises
    KernelError at once with the original message: no retry, no
    quarantine, no fallback, nothing on the degradation ledger."""
    calls = []

    def attempt():
        calls.append(1)
        raise ValueError("block shape (1, 1024) is not tile-aligned")

    def fallback():
        raise AssertionError("a real failure must not reach the fallback")

    with pytest.raises(faults.KernelError, match="not tile-aligned") as info:
        faults.run_leaf("pallas", "direct", attempt, fallback)
    assert info.value.backend == "pallas" and info.value.pass_kind == "direct"
    assert isinstance(info.value.__cause__, ValueError)
    assert calls == [1]
    assert faults.quarantined() == ()
    assert faults.degradation_log() == ()


# ---------------------------------------------------------------------------
# no-fault invariant: the machinery leaves no trace in the jaxpr
# ---------------------------------------------------------------------------


def test_happy_path_jaxpr_identical(monkeypatch):
    """With nothing armed, a planned call's jaxpr is byte-identical to one
    built with run_leaf/maybe_fail bypassed entirely — degradation wiring
    costs nothing at trace time."""
    spec = fft_lib.FFTSpec(n=1024, batch_hint=2)
    planned = fft_lib.plan(spec, backend="pallas")
    x = jnp.zeros((2, 1024), jnp.complex64)
    before_measure = tuning.measure_log()
    guarded = str(jax.make_jaxpr(planned)(x))

    monkeypatch.setattr(
        faults, "run_leaf", lambda b, k, attempt, fallback, **kw: attempt()
    )
    monkeypatch.setattr(faults, "maybe_fail", lambda site, **ctx: None)
    bare = str(jax.make_jaxpr(planned)(x))
    assert guarded == bare
    assert tuning.measure_log() == before_measure


# ---------------------------------------------------------------------------
# tuning cache: corruption, foreign schema, injected read/write faults
# ---------------------------------------------------------------------------


@pytest.fixture
def scratch_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "tuning.json")
    monkeypatch.setenv("REPRO_TUNING_CACHE", path)
    tuning.clear_measure_log()
    yield path
    tuning.cache._loaded_path = None  # drop the memoized view of the tmp path
    tuning.cache._mem = {}
    tuning.clear_measure_log()


def test_corrupt_cache_quarantined_and_rebuilt_from_seed(scratch_cache):
    with open(scratch_cache, "w") as f:
        f.write('{"this is": not json')
    with pytest.warns(RuntimeWarning, match="rebuilding from the packaged seed"):
        entries = tuning.TuningCache()._load()
    assert entries == {}
    assert os.path.exists(scratch_cache + ".corrupt")
    assert not os.path.exists(scratch_cache)
    # the packaged seed still serves through get()
    assert tuning.TuningCache().get("cpu|pallas|plan|fft|n=8192|batch=2")


@pytest.mark.skipif(
    jax.default_backend() != "cpu", reason="seed entries are keyed for cpu"
)
def test_corrupt_cache_plans_seeded_spec_with_zero_measurements(scratch_cache):
    with open(scratch_cache, "w") as f:
        f.write("truncated garbag")
    with pytest.warns(RuntimeWarning):
        fft_lib.plan(
            fft_lib.FFTSpec(n=8192, batch_hint=2), backend="pallas", tune="measure"
        )
    assert tuning.measure_log() == ()


def test_foreign_schema_quarantined(scratch_cache):
    with open(scratch_cache, "w") as f:
        json.dump({"version": 99, "entries": {}}, f)
    with pytest.warns(RuntimeWarning, match="foreign schema"):
        assert tuning.TuningCache()._load() == {}
    assert os.path.exists(scratch_cache + ".corrupt")


def test_legacy_flat_schema_still_readable(scratch_cache):
    with open(scratch_cache, "w") as f:
        json.dump({"a|b|c|d": {"config": {"x": 1}, "mode": "model"}}, f)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # no quarantine warning for legacy files
        assert tuning.TuningCache().get("a|b|c|d") == {
            "config": {"x": 1},
            "mode": "model",
        }


def test_cache_write_round_trips_versioned(scratch_cache):
    c = tuning.TuningCache()
    c.put("k|k|k|k", {"config": {"block": 4}, "mode": "measure"})
    with open(scratch_cache) as f:
        doc = json.load(f)
    assert doc["version"] == tuning.CACHE_SCHEMA_VERSION
    assert doc["entries"]["k|k|k|k"]["mode"] == "measure"
    assert tuning.TuningCache().get("k|k|k|k")["config"]["block"] == 4


def test_injected_cache_read_fault_serves_seed(scratch_cache):
    with open(scratch_cache, "w") as f:
        json.dump({"version": 1, "entries": {"u|u|u|u": {"config": 1}}}, f)
    with faults.inject_fault("tuning.cache_read"):
        c = tuning.TuningCache()
        assert c.get("u|u|u|u") is None  # user file unreadable this once
        assert c.get("cpu|pallas|plan|fft|n=8192|batch=2")  # seed still serves
    assert os.path.exists(scratch_cache)  # the healthy file is NOT quarantined
    assert tuning.TuningCache().get("u|u|u|u") == {"config": 1}


def test_injected_cache_write_fault_degrades_to_memory(scratch_cache):
    c = tuning.TuningCache()
    with faults.inject_fault("tuning.cache_write"):
        c.put("w|w|w|w", {"config": 2, "mode": "model"})
    assert c.get("w|w|w|w") == {"config": 2, "mode": "model"}  # memory kept it
    assert not os.path.exists(scratch_cache)  # nothing half-written


# ---------------------------------------------------------------------------
# pencil collective site
# ---------------------------------------------------------------------------


def test_collective_fault_raises_typed_before_the_wire():
    with faults.inject_fault("pencil.all_to_all"):
        with pytest.raises(faults.CollectiveError) as ei:
            distributed._a2a(jnp.zeros((2, 2)), "x", 0, 0)
    assert ei.value.injected
    assert ei.value.site == "pencil.all_to_all"


# ---------------------------------------------------------------------------
# serving: retry, deadline reaping, backpressure, health
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def serve_engine():
    from repro.configs.base import ModelConfig
    from repro.models import model as M
    from repro.serving.engine import Engine, ServeConfig

    cfg = ModelConfig(
        family="dense", num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
        d_ff=128, vocab_size=256, block_pattern=("spectral", "attn"),
        spectral_filter_len=8, compute_dtype="float32",
    )
    params, _ = M.init_unzipped(jax.random.PRNGKey(0), cfg)
    return Engine(cfg, params, ServeConfig(max_new=8))


@pytest.fixture
def serve_prompts(serve_engine):
    return jax.random.randint(
        jax.random.PRNGKey(1), (3, 10), 4, serve_engine.cfg.vocab_size
    )


def test_transient_prefill_fault_is_retried(serve_engine, serve_prompts):
    from repro.serving.spectral_serve import ServeSession

    sess = ServeSession(serve_engine, slots=2, max_len=32)
    with faults.inject_fault("serve.prefill", times=1):
        slot = sess.submit(serve_prompts[0])
    assert slot == 0
    assert sess.counts["retries"] == 1
    assert len(sess.output(slot)) == 1  # first token sampled despite the fault


def test_persistent_prefill_fault_raises_typed(serve_engine, serve_prompts):
    from repro.serving.spectral_serve import ServeSession

    sess = ServeSession(serve_engine, slots=1, max_len=32, prefill_retries=1)
    with faults.inject_fault("serve.prefill", times=8):
        with pytest.raises(faults.ServeError) as ei:
            sess.submit(serve_prompts[0])
    assert ei.value.injected


def test_insert_and_generate_faults_raise_typed(serve_engine, serve_prompts):
    from repro.serving.spectral_serve import ServeSession

    sess = ServeSession(serve_engine, slots=1, max_len=32)
    with faults.inject_fault("serve.insert"):
        with pytest.raises(faults.ServeError):
            sess.submit(serve_prompts[0])
    sess2 = ServeSession(serve_engine, slots=1, max_len=32)
    sess2.submit(serve_prompts[0])
    with faults.inject_fault("serve.generate"):
        with pytest.raises(faults.ServeError):
            sess2.run(2)


def test_queue_backpressure_and_ticket_drain(serve_engine, serve_prompts):
    from repro.serving.spectral_serve import ServeSession

    sess = ServeSession(serve_engine, slots=1, max_len=32, queue_cap=1)
    slot = sess.submit(serve_prompts[0])
    ticket = sess.submit(serve_prompts[1])
    assert slot == 0 and ticket < 0
    with pytest.raises(faults.ServeError, match="queue"):
        sess.submit(serve_prompts[2])  # beyond the cap: typed rejection
    assert sess.counts["rejected"] == 1
    with pytest.raises(faults.ServeError, match="queued"):
        sess.output(ticket)
    # expire the occupying request so run() reaps it and drains the queue
    sess._deadline[0] = -1.0
    sess.run(2)
    assert sess.counts["expired"] == 1
    assert len(sess.output(ticket)) >= 1


def test_deadline_reaps_expired_slot(serve_engine, serve_prompts):
    from repro.serving.spectral_serve import ServeSession

    sess = ServeSession(serve_engine, slots=1, max_len=32, default_deadline_s=0.0)
    sess.submit(serve_prompts[0])
    sess.run(2)
    assert sess.counts["expired"] == 1
    assert sess.free_slots() == [0]


def test_health_snapshot(serve_engine, serve_prompts):
    from repro.serving.spectral_serve import ServeSession

    sess = ServeSession(serve_engine, slots=2, max_len=32, queue_cap=4)
    sess.submit(serve_prompts[0])
    h = sess.health()
    assert h["slots"] == 2 and h["live"] + h["free"] == 2
    assert h["queue_depth"] == 0 and h["queue_cap"] == 4
    for key in ("counts", "quarantined", "degradations", "fault_counters"):
        assert key in h


# ---------------------------------------------------------------------------
# numerics guards
# ---------------------------------------------------------------------------


def test_check_nan_guard():
    planned = fft_lib.plan(fft_lib.FFTSpec(n=64, batch_hint=1))
    good = jnp.ones((1, 64), jnp.complex64)
    planned(good, check="nan")  # clean input passes
    bad = good.at[0, 3].set(jnp.nan)
    with pytest.raises(faults.NumericsError):
        planned(bad, check="nan")


def test_check_parseval_guard():
    planned = fft_lib.plan(fft_lib.FFTSpec(n=128, batch_hint=2))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 128), jnp.float32) + 0j
    planned(x, check="parseval")  # a correct transform conserves energy
    with pytest.raises(faults.PlanError, match="check"):
        planned(x, check="bogus")
