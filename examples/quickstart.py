"""Quickstart: the plan-and-execute FFT API in five minutes.

  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fft as F
from repro.core import plan
from repro.core.conv import fft_conv

# ---- 1. plan inspection: the paper's kernel-call schedule -----------------
for n in (1024, 65536, 2**20):
    print(plan.describe(n))

# ---- 2. plan-and-execute: resolve a spec once, run it many times ----------
x = (np.random.randn(4, 4096) + 1j * np.random.randn(4, 4096)).astype(np.complex64)
spec = F.FFTSpec(n=4096, kind="fft", batch_hint=4)
planned = F.plan(spec)                  # cached: F.plan(spec) is F.plan(spec)
print(f"planned: {planned.describe()}  tiles={dict(planned.batch_tiles)}")
y = planned(jnp.asarray(x))
print("max err vs numpy:", float(np.abs(np.asarray(y) - np.fft.fft(x)).max()))

# ---- 3. the backend registry: every registered backend runs the same plan --
for backend in F.available_backends():   # pallas runs interpret on CPU
    y = F.plan(spec, backend=backend)(jnp.asarray(x))
    err = np.abs(np.asarray(y) - np.fft.fft(x)).max()
    print(f"backend={backend:9s} max err vs numpy: {err:.2e}")

# ---- 4. scoped backend selection (the deprecated global setter's successor) -
with F.use_backend("stockham"):
    y = F.fft(jnp.asarray(x))            # wrappers are plan-cached too
    print("use_backend('stockham') err:",
          float(np.abs(np.asarray(y) - np.fft.fft(x)).max()))

# ---- 5. axis-aware transforms (no manual swapaxes) -------------------------
xa = (np.random.randn(8, 1024, 3) + 1j * np.random.randn(8, 1024, 3)).astype(np.complex64)
ya = F.fft(jnp.asarray(xa), axis=1)
print("axis=1 err:", float(np.abs(np.asarray(ya) - np.fft.fft(xa, axis=1)).max()))

# ---- 6. real FFT (half the work for real signals) --------------------------
sig = np.random.randn(2, 8192).astype(np.float32)
Xr, Xi = F.rfft(jnp.asarray(sig))
print("rfft bins:", Xr.shape, " roundtrip err:",
      float(jnp.abs(F.irfft((Xr, Xi), 8192) - sig).max()))

# ---- 7. FFT long convolution (the LM-layer integration) --------------------
u = np.random.randn(1, 16, 2048).astype(np.float32)   # (B, D, L)
h = np.random.randn(16, 2048).astype(np.float32)      # per-channel filters
y = fft_conv(jnp.asarray(u), jnp.asarray(h))
print("fft_conv out:", y.shape)

# ---- 8. under jit, composed with autodiff ----------------------------------
g = jax.grad(lambda v: jnp.sum(jnp.abs(F.fft(v)) ** 2))(jnp.asarray(x))
print("grad of spectral energy == 2N·conj(x):",
      bool(jnp.allclose(g, 2 * 4096 * jnp.conj(jnp.asarray(x)), rtol=1e-3)))

# ---- 9. 2-D images: one joint rows+columns pass program --------------------
img = (np.random.randn(128, 1024) + 1j * np.random.randn(128, 1024)).astype(
    np.complex64
)
p2 = F.plan(F.FFTSpec(n=1024, kind="fft2", n2=128))   # ONE compiled program
print("fft2 plan:", p2.describe())
err2 = np.abs(np.asarray(p2(jnp.asarray(img))) - np.fft.fft2(img)).max()
print("fft2 err vs numpy:", float(err2))
real_img = np.random.randn(128, 1024).astype(np.float32)
Br, Bi = F.rfft2(jnp.asarray(real_img))               # real-packing 2-D
print("rfft2 bins:", Br.shape, " roundtrip err:",
      float(jnp.abs(F.irfft2((Br, Bi), 1024, 128) - real_img).max()))

# ---- 10. overlap-save streaming convolution --------------------------------
# Long signals never plan past the fused regime: the signal is blocked into
# overlapping segments batched through ONE cached small plan pair, and
# StreamingConv carries the Lh-1 tail so chunked calls compose exactly.
from repro.core.overlap import StreamingConv, fft_conv_os

sig = np.random.randn(2, 1 << 16).astype(np.float32)
filt = np.random.randn(1025).astype(np.float32)
y_os = fft_conv_os(jnp.asarray(sig), jnp.asarray(filt))
print("fft_conv_os out:", y_os.shape)
sc = StreamingConv(jnp.asarray(filt))                 # block picked from Lh
state = sc.init_state((2,))
chunks = []
for start in range(0, sig.shape[-1], 1 << 14):
    yc, state = sc(jnp.asarray(sig[:, start : start + (1 << 14)]), state)
    chunks.append(yc)
print("streaming == one-shot:",
      bool(jnp.allclose(jnp.concatenate(chunks, -1), y_os, atol=1e-3)))

# ---- 11. autotuning: measured plan tuning with a persistent cache ----------
# Every fixed performance heuristic (overlap-save block, per-pass chunk,
# leaf tile, fused-vs-split crossover) is a searched decision: the roofline
# model prunes the candidates, tune="measure" times the survivors ONCE and
# persists the winner — warm runs (and future processes) hit the cache and
# measure nothing.
from repro.core import tuning

y_tuned = fft_conv_os(jnp.asarray(sig), jnp.asarray(filt), tune="measure")
print("tuned block == one-shot result:",
      bool(jnp.allclose(y_tuned, y_os, atol=1e-3)))
pt = F.plan(F.FFTSpec(n=2**17, kind="fft"), backend="pallas", tune="measure")
print("tuned plan:", pt.describe())                 # tuned choices per pass
print("tuning cache:", tuning.cache_path())         # REPRO_TUNING_CACHE overrides
print("measurements this process:", len(tuning.measure_log()))
pt2 = F.plan(F.FFTSpec(n=2**17, kind="fft"), backend="pallas", tune="measure")
print("second plan is the same handle (zero re-measurement):", pt2 is pt)

# ---- 12. streaming spectral serving: prefill / insert / generate -----------
# The LM engine serves tokens through three compiled phases.  prefill runs
# the prompt once and converts caches to decode layout; insert splices the
# request into a slot of a RUNNING batch (the spectral mixer's stream state
# is re-phased to the batch's chunk clock, so a late joiner decodes exactly
# as it would solo); generate advances every slot in ONE lax.scan — the
# spectral layer's once-per-chunk FFT flush reuses the plan cached at trace
# time, so a warm loop creates zero new plans.
import dataclasses

from repro.configs.base import ModelConfig
from repro.models import model as M
from repro.serving.engine import Engine, ServeConfig
from repro.serving.spectral_serve import ServeSession

cfg = ModelConfig(
    family="dense", num_layers=2, d_model=32, num_heads=2, num_kv_heads=2,
    d_ff=64, vocab_size=128, block_pattern=("spectral", "attn"),
    spectral_filter_len=8, compute_dtype="float32",
)
params, _ = M.init_unzipped(jax.random.PRNGKey(0), cfg)
eng = Engine(cfg, params, ServeConfig(max_new=6))
prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 4, cfg.vocab_size)

sess = ServeSession(eng, slots=2, max_len=16)
s0 = sess.submit(prompts[0])       # prefill + insert into slot 0
sess.run(2)                        # slot 0 decodes alone for 2 steps
s1 = sess.submit(prompts[1])       # joins the RUNNING batch mid-stream
sess.run(5)                        # both slots advance in one scan
print("slot0 tokens:", sess.output(s0)[:6])
print("slot1 tokens:", sess.output(s1)[:6])
solo = eng.generate(prompts)       # whole-batch convenience wrapper
print("mid-stream join == solo decode:",
      sess.output(s1)[:6] == solo[1].tolist())
F.clear_plan_log()
sess.run(3)                        # warm loop: every flush hits the plan cache
print("new FFT plans during warm generate:", len(F.plan_log()))
print("phase seconds:", {k: round(v, 4) for k, v in sess.phase_s.items()})

# ---- 13. distributed pencil FFT: tuned, packed, overlapped -----------------
# Across a mesh the slow tier is the all-to-all transpose, and the schedule
# is a tuned decision exactly like the single-chip pass programs: factor
# balance, split-complex packing (ONE stacked collective per transpose) and
# the chunk count K the inner transposes are double-buffered at.  The pick
# is modeled-only (tune="model") — cache-free and measurement-free, so
# every host of an SPMD mesh derives the identical schedule.
from repro.core import distributed as D

mesh1 = jax.make_mesh((1,), ("x",))      # single-host demo mesh; on a pod
xr = jax.random.normal(jax.random.PRNGKey(2), (2, 4096))
yr, yi = D.pfft_sharded(xr, jnp.zeros_like(xr), mesh1, "x", tune="model")
print("pfft matches jnp.fft:",
      bool(jnp.allclose(yr + 1j * yi, jnp.fft.fft(xr), atol=1e-2)))
# The plan handle prints the pencil schedule like single-device plans do —
# factors, collective count, modeled comm MB per transpose step.  With one
# shard it collapses to the local plan (zero collectives, jaxpr-asserted
# in tests/test_pencil_plan.py); at d=8 the same call emits 3 packed
# all-to-alls where the per-plane path paid 6 (see bench_pfft).
print("d=1:", D.plan_pencil(4096, 1).describe().splitlines()[0])
print("d=8:", D.plan_pencil(1 << 18, 8).describe().splitlines()[0])

# ---- 14. arbitrary lengths: the Bluestein chirp-conv leaf ------------------
# FFTSpec takes ANY n ≥ 1 — primes, 3·2^k, whatever the pulse dictates.
# Non-pow2 lengths compile to Bluestein leaves: chirp pre-multiply, one
# cached pow2 convolution of length next_pow2(2n-1), chirp post-multiply —
# all fused into the same pass-program machinery (2 passes in the fused
# regime), with the chirp spectrum interned on the plan like twiddle LUTs.
pb = F.plan(F.FFTSpec(n=2029))                     # prime length
print(pb.describe())                               # "...; bluestein: pad 4096 (2.02x), ..."
xb = jax.random.normal(jax.random.PRNGKey(4), (2, 2029))
print("prime-n matches jnp.fft:",
      bool(jnp.allclose(pb(xb), jnp.fft.fft(xb), atol=1e-2)))
# rfft/irfft handle odd lengths too, and the roofline's bluestein_report
# costs the pad against a hypothetical mixed-radix transform.
from repro.analysis import roofline as rl

rep = rl.bluestein_report(2029)
print("bluestein tax: pad %d (%.2fx), %.1fx flops vs mixed-radix"
      % (rep["pad"], rep["pad_ratio"], rep["flops_overhead"]))

# ---- 15. fault tolerance: injection, per-leaf degradation, quarantine ------
# Every pallas leaf executes under a retry→quarantine→fallback
# guard (`faults.run_leaf`): a leaf that fails twice is demoted to the
# traced-XLA execution of the SAME pass, the (backend, pass-kind) pair is
# quarantined for the process (warm re-plans skip the kernel entirely),
# and the plan advertises the demotion.  Inject a deterministic kernel
# fault — `inject_fault` in code, `REPRO_FAULTS=kernel.launch:64` from the
# environment — and watch the transform survive it:
from repro.core import faults

with F.use_backend("pallas"):
    pf = F.plan(F.FFTSpec(n=4096, batch_hint=2))
xf = jax.random.normal(jax.random.PRNGKey(5), (2, 4096))
with faults.inject_fault("kernel.launch", times=64):   # every attempt fails...
    yf = pf(xf)                                        # ...the call still succeeds
print("degraded leaf matches jnp.fft:",
      bool(jnp.allclose(yf, jnp.fft.fft(xf), atol=1e-2)))
print(pf.describe())                  # "...; DEGRADED: pass 0 fused4 (pallas→xla)"
print("quarantined:", faults.quarantined())
print("ledger:", faults.degradation_log())
# Opt-in numerics guards ride on execution: check="nan" scans the output,
# check="parseval" verifies energy conservation (NumericsError on drift).
pf2 = F.plan(F.FFTSpec(n=4096, batch_hint=2))
pf2(xf.astype(jnp.complex64), check="parseval")
# Demo only: lift the quarantine so later cells keep using the kernels.
faults.clear_quarantine()
faults.clear_degradations()
