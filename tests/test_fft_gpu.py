"""The Pallas-on-Triton GPU backend: shared-memory-budgeted claimed leaves,
per-leaf xla fallback, negotiation precedence, crossover tuning, seed cache.

Runs on CPU hosts in Pallas interpret mode (automatic — ``should_interpret``
defaults on when ``jax.default_backend() == "cpu"``); a real GPU exercises
the Triton lowering of the identical plans with zero code changes.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import run_in_subprocess
from repro.analysis import roofline as rl
from repro.core import fft as fft_lib
from repro.core import limits
from repro.core import plan as plan_lib
from repro.core import tuning
from repro.kernels import fft_gpu


@pytest.fixture()
def fresh_plans():
    fft_lib._plan_cached.cache_clear()
    yield
    fft_lib._plan_cached.cache_clear()


def _fft_ref(x, inverse=False):
    return np.fft.ifft(x) if inverse else np.fft.fft(x)


# ---------------------------------------------------------------------------
# numerics: the acceptance sweep under interpret
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n", [256, 4096, 131072])
@pytest.mark.parametrize("kind", ["fft", "ifft"])
def test_pallas_gpu_matches_xla(n, kind, rng):
    spec = fft_lib.FFTSpec(n=n, kind=kind)
    p_gpu = fft_lib.plan(spec, backend="pallas_gpu", tune="off")
    p_xla = fft_lib.plan(spec, backend="xla", tune="off")
    x = rng.standard_normal((3, n)).astype(np.float32)
    xi = rng.standard_normal((3, n)).astype(np.float32)
    yr, yi = p_gpu.apply_planes(jnp.asarray(x), jnp.asarray(xi))
    rr, ri = p_xla.apply_planes(jnp.asarray(x), jnp.asarray(xi))
    ref = np.asarray(rr) + 1j * np.asarray(ri)
    got = np.asarray(yr) + 1j * np.asarray(yi)
    rel = np.abs(got - ref).max() / max(np.abs(ref).max(), 1e-30)
    assert rel < 1e-3, (n, kind, rel)
    # and against numpy, so both backends can't be wrong together
    npref = _fft_ref(x + 1j * xi, inverse=(kind == "ifft"))
    rel_np = np.abs(got - npref).max() / max(np.abs(npref).max(), 1e-30)
    assert rel_np < 1e-3, (n, kind, rel_np)


# ---------------------------------------------------------------------------
# per-leaf claims: unclaimed passes fall back to xla INSIDE the same plan
# ---------------------------------------------------------------------------


def test_mixed_plan_claims_per_leaf():
    # 131072 = 512×256 outside the fused regime: pass 0 is a strided-column
    # transform (disclaimed — Triton leaf wants unit-stride rows), pass 1
    # the natural-order row leaf (claimed).
    p = fft_lib.plan(fft_lib.FFTSpec(n=131072), backend="pallas_gpu", tune="off")
    assert p.pass_claims == ("xla", "pallas_gpu")
    # fused-regime sizes are single-pass and fully claimed
    for n in (256, 4096):
        q = fft_lib.plan(fft_lib.FFTSpec(n=n), backend="pallas_gpu", tune="off")
        assert q.pass_claims == ("pallas_gpu",) * len(q.passes)
    # plans without a claim surface report their own name everywhere
    x = fft_lib.plan(fft_lib.FFTSpec(n=4096), backend="xla", tune="off")
    assert set(x.pass_claims) == {"xla"}


def test_gpu_claims_predicate():
    passes = plan_lib.plan_fft(131072).passes
    assert [fft_gpu.gpu_claims(p) for p in passes] == [False, True]
    assert all(fft_gpu.gpu_claims(p) for p in plan_lib.plan_fft(4096).passes)
    # column passes (axis=-2) are never claimed
    col = next(
        (p for p in plan_lib.plan_fft2(64, 131072).passes if p.axis == -2), None
    )
    assert col is not None and not fft_gpu.gpu_claims(col)


# ---------------------------------------------------------------------------
# jaxpr purity: a claimed plan is pallas_call + shape glue, nothing else
# ---------------------------------------------------------------------------

_GLUE = {
    "reshape",
    "pad",
    "slice",
    "squeeze",
    "device_put",
    "convert_element_type",
    "broadcast_in_dim",
    "pjit",
    "jit",
}


def _collect_prims(jaxpr, acc):
    """All primitive names, descending into pjit bodies but NOT into
    pallas_call kernels (the kernel may use any math it wants)."""
    for e in jaxpr.eqns:
        acc.append(e.primitive.name)
        if e.primitive.name == "pallas_call":
            continue
        for v in e.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _collect_prims(inner, acc)
    return acc


@pytest.mark.parametrize("n", [256, 4096])
def test_claimed_leaf_jaxpr_is_pallas_call_plus_reshapes(n):
    p = fft_lib.plan(fft_lib.FFTSpec(n=n), backend="pallas_gpu", tune="off")
    xr = jnp.zeros((4, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: p.apply_planes(a, b))(xr, xr)
    prims = _collect_prims(jaxpr.jaxpr, [])
    n_calls = prims.count("pallas_call")
    assert n_calls == len(p.passes), (n_calls, len(p.passes))
    stray = [q for q in prims if q != "pallas_call" and q not in _GLUE]
    assert not stray, f"claimed leaf leaked XLA math outside the kernel: {stray}"


def _pallas_eqns(jaxpr, acc):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            acc.append(e)
            continue
        for v in e.params.values():
            inner = getattr(v, "jaxpr", None)
            if inner is not None:
                _pallas_eqns(inner, acc)
    return acc


# n → the GPU kernels its plan runs: dft_matmul (256), fused four-step
# (4096), rows_natural direct (2^17) and fused4 (2^22), Bluestein fused
# fwd/inv (2029) and split-regime chirp stages (40000).
@pytest.mark.parametrize("n", [256, 4096, 2**17, 2**22, 2029, 40000])
def test_gpu_kernels_take_no_scratch(n):
    # The Triton lowering refuses scratch memory (interpret mode accepts
    # it), so no GPU pallas_call may ask for any.
    p = fft_lib.plan(fft_lib.FFTSpec(n=n), backend="pallas_gpu", tune="off")
    x = jax.ShapeDtypeStruct((1, n), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda a, b: p.apply_planes(a, b))(x, x)
    calls = _pallas_eqns(jaxpr.jaxpr, [])
    assert calls, n
    scratch = [e.params["grid_mapping"].num_scratch_operands for e in calls]
    assert scratch == [0] * len(calls), (n, scratch)


# ---------------------------------------------------------------------------
# the shared-memory budget model
# ---------------------------------------------------------------------------


def test_memory_budget_device_resolution():
    assert limits.memory_budget("NVIDIA A100-SXM4-40GB") == 164 * 1024
    assert limits.memory_budget("NVIDIA H100 80GB HBM3") == 228 * 1024
    assert limits.memory_budget("Tesla T4") == 64 * 1024
    assert limits.memory_budget("Tesla V100-SXM2-16GB") == 96 * 1024
    # unknown GPU-ish silicon floors at the paper's 48 KiB budget
    assert limits.memory_budget("NVIDIA GeForce RTX 5090") == limits.GPU_SMEM_DEFAULT
    # non-GPU kinds keep the TPU VMEM budget
    assert limits.memory_budget("TPU v4") == limits.VMEM_BUDGET
    assert limits.memory_budget("cpu") == limits.VMEM_BUDGET
    # None resolves the local device (cpu in this suite)
    assert limits.memory_budget() == limits.VMEM_BUDGET


@pytest.mark.parametrize("budget_kib", [48, 96, 164, 228])
def test_gpu_tiles_respect_any_budget(budget_kib):
    budget = budget_kib * 1024
    for n in (256, 4096, 65536, 131072):
        for p in plan_lib.plan_fft(n).passes:
            if not fft_gpu.gpu_claims(p):
                continue
            bt = plan_lib.pick_batch_tile_gpu(p, budget)
            assert bt >= 1
            assert plan_lib.gpu_smem_bytes(p, bt) <= budget or bt == 1, (
                n, p.kind, bt,
            )


def test_gpu_budget_shrinks_tiles():
    (p,) = plan_lib.plan_fft(4096).passes
    big = plan_lib.pick_batch_tile_gpu(p, 8 * 2**20)
    small = plan_lib.pick_batch_tile_gpu(p, 48 * 1024)
    assert small <= big and small >= 1


# ---------------------------------------------------------------------------
# roofline: shared-memory bytes + global round trips in describe()/report
# ---------------------------------------------------------------------------


def test_gpu_program_report_round_trips():
    rep = rl.gpu_program_report(
        plan_lib.plan_fft(4096).passes, fft_gpu.gpu_claims, batch=2
    )
    assert rep["claims"] == ("pallas_gpu",)
    assert rep["global_round_trips"] == 1  # fused single pass: read + write
    assert rep["smem_bytes_max"] > 0
    assert rep["smem_budget"] == limits.memory_budget()
    mixed = rl.gpu_program_report(
        plan_lib.plan_fft(131072).passes, fft_gpu.gpu_claims, batch=2
    )
    assert mixed["claims"] == ("xla", "pallas_gpu")
    # the disclaimed strided-column pass pays materialized transposes
    assert mixed["global_round_trips"] > 2
    assert mixed["modeled_global_bytes"] > rep["modeled_global_bytes"]


def test_describe_reports_gpu_account():
    d = fft_lib.plan(
        fft_lib.FFTSpec(n=131072), backend="pallas_gpu", tune="off"
    ).describe()
    assert "gpu:" in d and "global round trips" in d
    assert "smem" in d and "claims [xla, pallas_gpu]" in d
    # claim-less backends keep their describe() unchanged
    assert "gpu:" not in fft_lib.plan(
        fft_lib.FFTSpec(n=131072), backend="xla", tune="off"
    ).describe()


def test_xla_gpu_fft_bytes_monotone():
    assert rl.xla_gpu_fft_bytes(8192) > rl.xla_gpu_fft_bytes(4096) > 0
    assert rl.xla_gpu_fft_bytes(4096, batch=8) > rl.xla_gpu_fft_bytes(4096)


# ---------------------------------------------------------------------------
# negotiation precedence (satellite: platform-preferred registration order)
# ---------------------------------------------------------------------------


def test_gpu_negotiation_prefers_later_registered_backend():
    spec = fft_lib.FFTSpec(n=4096)
    # both xla and pallas_gpu prefer "gpu"; the explicitly registered
    # pallas_gpu came later, so the tie breaks toward it
    assert fft_lib._negotiate(spec, "gpu").name == "pallas_gpu"
    # cpu negotiation is untouched: xla is preferred, pallas_gpu merely runs
    assert fft_lib._negotiate(spec, "cpu").name == "xla"


def test_registered_preferred_backend_beats_default(fresh_plans):
    spec = fft_lib.FFTSpec(n=1024)
    calls = []

    def fn(xr, xi, *, inverse, planned):
        calls.append(planned.spec.n)
        return fft_lib._xla_backend(xr, xi, inverse=inverse, planned=planned)

    fft_lib.register_backend(
        "scratch_cpu",
        fn,
        fft_lib.BackendCapabilities(preferred_platforms=frozenset({"cpu"})),
    )
    try:
        # same score as the xla default on cpu — later registration wins
        assert fft_lib._negotiate(spec, "cpu").name == "scratch_cpu"
        p = fft_lib.plan(spec, tune="off")
        x = jnp.zeros((2, 1024), jnp.float32)
        p.apply_planes(x, x)
        assert calls, "negotiation never routed to the registered backend"
    finally:
        fft_lib._REGISTRY.pop("scratch_cpu", None)
        fft_lib._plan_cached.cache_clear()


# ---------------------------------------------------------------------------
# crossover tuning + seed cache
# ---------------------------------------------------------------------------


def test_backend_pick_modes():
    spec = fft_lib.FFTSpec(n=4096, kind="fft", batch_hint=2)
    assert tuning.backend_pick(spec, "gpu", "off") is None
    pick = tuning.backend_pick(spec, "gpu", "model")
    assert pick in ("pallas_gpu", "xla")
    assert tuning.backend_pick(spec, "gpu", "model") == pick  # cached
    # 2-D and real-input specs keep negotiation's answer
    assert tuning.backend_pick(
        fft_lib.FFTSpec(n=64, kind="fft2", n2=4096), "gpu", "model"
    ) is None
    assert tuning.measure_log() == ()  # model mode never timed anything


def test_seed_cache_layers_beneath_user_cache():
    seed = tuning.seed_cache()
    assert seed, "packaged tuning_seed.json missing or empty"
    key = "cpu|pallas_gpu|plan|fft|n=8192|batch=2"
    assert key in seed and seed[key]["mode"] == "measure"
    # the user cache shadows the seed on put()
    tuning.cache.put(key, {"config": {"sentinel": 1}, "mode": "measure"})
    try:
        assert tuning.cache.get(key)["config"] == {"sentinel": 1}
    finally:
        tuning.cache.clear()
    # after clearing the user layer, the seed answers again
    assert tuning.cache.get(key)["mode"] == "measure"


_SEED_BODY = r"""
from repro.core import fft as F
from repro.core import tuning

spec = F.FFTSpec(n=8192, kind="fft", batch_hint=2)
for backend in ("pallas", "pallas_gpu"):
    p = F.plan(spec, backend=backend, tune="measure")
    assert p.tuned is not None, backend
assert tuning.measure_log() == (), tuning.measure_log()
print("SEED_ZERO_MEASURE_OK")
"""


def test_seeded_spec_measures_nothing_in_fresh_process():
    # The acceptance criterion, end to end: a FRESH process (cold interning
    # cache, empty user tuning cache — conftest points REPRO_TUNING_CACHE
    # at a tempdir) plans a seeded spec under tune="measure" with zero
    # device measurements, because the packaged seed already has the
    # measured winner.
    out = run_in_subprocess(_SEED_BODY, devices=1)
    assert "SEED_ZERO_MEASURE_OK" in out
