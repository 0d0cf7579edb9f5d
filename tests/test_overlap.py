"""Overlap-save convolution engine: oracles, plan-cache discipline, streaming."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import DEMOTED

from repro.core import fft as fft_lib
from repro.core import plan as plan_lib
from repro.core.conv import fft_conv, next_pow2, toeplitz_conv_ref
from repro.core.overlap import (
    OS_FACTOR,
    StreamingConv,
    clear_pairing_log,
    conv_frames,
    describe_conv,
    fft_conv_os,
    filter_spectrum,
    frame_pairs,
    frame_signal,
    pairing_log,
    pick_block,
)


def _new_specs(snapshot):
    """Specs planned since ``snapshot`` (a set of plan_log entries)."""
    return [
        spec for spec, name in fft_lib.plan_log() if (spec, name) not in snapshot
    ]


# ---------------------------------------------------------------------------
# block sizing + framing
# ---------------------------------------------------------------------------


def test_pick_block_defaults():
    assert pick_block(4097) == min(8192 * OS_FACTOR, plan_lib.FUSED_MAX)
    assert pick_block(129) == 256 * OS_FACTOR
    assert pick_block(1) == 8  # degenerate 1-tap filter still plans rfft
    # filters too long for the FUSED_MAX cap keep 50% valid samples instead
    big = plan_lib.FUSED_MAX // 2 + 1
    assert pick_block(big) == 2 * next_pow2(big)


def test_pick_block_override_and_validation():
    assert pick_block(33, block=128) == 128
    with pytest.raises(ValueError):
        pick_block(33, block=100)  # not a power of two
    with pytest.raises(ValueError):
        pick_block(129, block=128)  # no valid samples per block


def test_frame_signal_windows(rng):
    x = np.arange(10, dtype=np.float32)[None]
    f = np.asarray(frame_signal(jnp.asarray(x), block=6, step=4, num_blocks=3))
    assert f.shape == (1, 3, 6)
    # frame 0 starts with the zero history, frame 1 overlaps frame 0 by 2
    np.testing.assert_array_equal(f[0, 0], [0, 0, 0, 1, 2, 3])
    np.testing.assert_array_equal(f[0, 1], [2, 3, 4, 5, 6, 7])
    np.testing.assert_array_equal(f[0, 2], [6, 7, 8, 9, 0, 0])


# ---------------------------------------------------------------------------
# fft_conv_os oracles
# ---------------------------------------------------------------------------


def test_fft_conv_os_vs_toeplitz(rng):
    x = rng.standard_normal((2, 3, 300)).astype(np.float32)
    h = rng.standard_normal((3, 33)).astype(np.float32)
    y = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h), block=128))
    ref = toeplitz_conv_ref(x, h[None])
    np.testing.assert_allclose(y, ref, atol=2e-3)


def test_fft_conv_os_full_mode(rng):
    x = rng.standard_normal((1, 200)).astype(np.float32)
    h = rng.standard_normal((1, 17)).astype(np.float32)
    y = np.asarray(
        fft_conv_os(jnp.asarray(x), jnp.asarray(h), causal=False, block=64)
    )
    ref = np.convolve(x[0], h[0], mode="full")[None]
    assert y.shape == ref.shape
    np.testing.assert_allclose(y, ref, atol=2e-3)


def test_fft_conv_os_axis(rng):
    x = rng.standard_normal((130, 2)).astype(np.float32)
    h = rng.standard_normal((9,)).astype(np.float32)
    y = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h), axis=0, block=32))
    ref = np.asarray(fft_conv(jnp.asarray(x), jnp.asarray(h), axis=0))
    np.testing.assert_allclose(y, ref, atol=2e-3)


@pytest.mark.parametrize("L", [2**16, 2**18])
def test_fft_conv_os_matches_one_shot(L, rng):
    Lh = 4097
    x = rng.standard_normal((2, L)).astype(np.float32)
    h = rng.standard_normal((Lh,)).astype(np.float32)
    y_one = np.asarray(
        fft_conv(jnp.asarray(x), jnp.asarray(h), overlap_save=False)
    )
    y_os = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h)))
    scale = np.abs(y_one).max()
    np.testing.assert_allclose(y_os, y_one, atol=1e-3 * scale)


@pytest.mark.parametrize("backend", ["pallas", "xla", "stockham", DEMOTED], indirect=True)
def test_fft_conv_os_backends_agree(backend, rng):
    # pallas runs interpret on CPU — the kernel path through the engine is
    # exercised in the pallas-interpret CI job; small block keeps it cheap.
    x = rng.standard_normal((2, 2**13)).astype(np.float32)
    h = rng.standard_normal((129,)).astype(np.float32)
    y = np.asarray(
        fft_conv_os(jnp.asarray(x), jnp.asarray(h), block=2048, backend=backend)
    )
    ref = np.asarray(
        fft_conv(jnp.asarray(x), jnp.asarray(h), overlap_save=False, backend="xla")
    )
    np.testing.assert_allclose(y, ref, atol=2e-3 * np.abs(ref).max())


def test_fft_conv_os_dtype_restored(rng):
    x = jnp.asarray(rng.standard_normal((2, 256)), jnp.bfloat16)
    h = jnp.asarray(rng.standard_normal((17,)), jnp.bfloat16)
    y = fft_conv_os(x, h, block=64)
    assert y.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# paired frames: two real frames per complex transform
# ---------------------------------------------------------------------------


def _conv64(x, h, causal=True):
    """float64 numpy convolution along the last axis, ``h`` broadcast."""
    lead = np.broadcast_shapes(x.shape[:-1], h.shape[:-1])
    x = np.broadcast_to(x, (*lead, x.shape[-1])).astype(np.float64)
    h = np.broadcast_to(h, (*lead, h.shape[-1])).astype(np.float64)
    out = np.stack([np.convolve(a, b) for a, b in zip(x.reshape(-1, x.shape[-1]), h.reshape(-1, h.shape[-1]))])
    out = out.reshape(*lead, -1)
    return out[..., : x.shape[-1]] if causal else out


# case → (x shape, h shape, block, fft_conv_os keywords, the pairing log's
# record: (frames, pairs, pad_frames) over all signals, or None when the
# rfft/irfft path runs)
PAIRED = {
    "nb_even": ((2, 960), (33,), 128, {}, (20, 10, 0)),
    "nb_odd": ((2, 1000), (33,), 128, {}, (22, 12, 2)),
    "nb_one": ((2, 90), (33,), 128, {}, None),
    "per_channel_filters": ((2, 3, 700), (3, 33), 128, {}, (48, 24, 0)),
    "full_mode": ((1, 500), (1, 17), 64, {"causal": False}, (11, 6, 1)),
    "axis_0": ((700, 2), (9,), 32, {"axis": 0}, (60, 30, 0)),
    # many frames a signal: the same pairing loop as a few
    "nb_many": ((2, 40 * 96 + 5), (33,), 128, {}, (82, 42, 2)),
    # one signal through a bank of three filters: framed once, broadcast
    "filter_bank": ((700,), (3, 17), 64, {}, (45, 24, 3)),
    # a step shorter than the overlap: each frame spans several steps
    "step_below_overlap": ((2, 1000), (100,), 128, {}, (70, 36, 2)),
}


@pytest.mark.parametrize("case", sorted(PAIRED))
def test_paired_frames_vs_float64(case, rng):
    xs, hs, block, kw, record = PAIRED[case]
    x = rng.standard_normal(xs).astype(np.float32)
    h = rng.standard_normal(hs).astype(np.float32)
    clear_pairing_log()
    y = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h), block=block, **kw))
    if kw.get("axis") == 0:
        ref = _conv64(x.T, h).T
    else:
        ref = _conv64(x, h, causal=kw.get("causal", True))
    assert y.shape == ref.shape
    np.testing.assert_allclose(y, ref, atol=2e-3)
    want = () if record is None else (dict(zip(("frames", "pairs", "pad_frames"), record)),)
    assert pairing_log() == want


def test_frame_pairs_windows():
    x = np.arange(10, dtype=np.float32)[None] + 1
    (re, im), (sr, si) = frame_pairs(jnp.asarray(x), block=6, step=4, num_blocks=3)
    assert re.shape == im.shape == (2, 1, 6) and sr.shape == si.shape == (2, 1, 1)
    frames = np.asarray(frame_signal(jnp.asarray(x), block=6, step=4, num_blocks=3))[0]
    # frames 0 and 2 in the real plane, frame 1 and a zero frame in the
    # imaginary plane, each divided by the power of two above its peak
    for got, s, want in ((re[0], sr[0], frames[0]), (im[0], si[0], frames[1]), (re[1], sr[1], frames[2])):
        assert float(s[0, 0]) == 2.0 ** (np.floor(np.log2(np.abs(want).max())) + 1)
        np.testing.assert_array_equal(np.asarray(got * s)[0], want)
    np.testing.assert_array_equal(np.asarray(im[1]), 0)
    assert float(si[1, 0, 0]) == 1.0
    # more pairs than the frames need (the sharded variant's rounding):
    # every frame past num_blocks is zeros, at scale 1
    (re3, im3), (sr3, si3) = frame_pairs(jnp.asarray(x), block=6, step=4, num_blocks=3, pairs=3)
    np.testing.assert_array_equal(np.asarray(re3[:2]), np.asarray(re))
    np.testing.assert_array_equal(np.asarray(im3[2]), 0)
    np.testing.assert_array_equal(np.asarray(re3[2]), 0)
    assert float(sr3[2, 0, 0]) == float(si3[2, 0, 0]) == 1.0


def test_pairing_log_counts_the_benchmark_shape():
    """1,024 channels of 2^19 samples, 4,097 taps, block 65,536: 9 frames a
    channel, 5 pairs, the fifth pair's imaginary frame padding."""
    clear_pairing_log()
    x = jax.ShapeDtypeStruct((1024, 2**19), jnp.float32)
    h = jax.ShapeDtypeStruct((4097,), jnp.float32)
    jax.eval_shape(lambda a, b: fft_conv_os(a, b, block=65536), x, h)
    assert pairing_log() == ({"frames": 9216, "pairs": 5120, "pad_frames": 1024},)
    assert describe_conv(2**19, 4097, 65536) == (
        "overlap-save block 65536, step 61440: 9 frame(s) a signal as 5 paired "
        "complex frames (1 pad), fft/ifft n=65536"
    )
    assert describe_conv(50000, 4097, 65536).endswith("1 frame(s) a signal, rfft/irfft n=65536")


def test_block_past_fused_max_keeps_the_rfft_path(rng):
    Lh = plan_lib.FUSED_MAX // 2 + 1  # pick_block → 2·FUSED_MAX
    B = pick_block(Lh)
    assert B > plan_lib.FUSED_MAX
    x = rng.standard_normal((1, 3 * (B - Lh + 1) - 5)).astype(np.float32)
    h = rng.standard_normal((Lh,)).astype(np.float32)
    clear_pairing_log()
    snapshot = set(fft_lib.plan_log())
    y = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h), tune="off", backend="xla"))
    assert pairing_log() == ()
    # no complex transform of the block (the rfft's own inner leaf, of
    # B / 2, is planned here only if no earlier test planned it)
    assert not [s for s in _new_specs(snapshot) if s.kind in ("fft", "ifft") and s.n == B]
    assert {s.kind for s in _new_specs(snapshot) if s.n == B} <= {"rfft", "irfft"}
    N = next_pow2(x.shape[-1] + Lh - 1)
    ref = np.fft.irfft(np.fft.rfft(x.astype(np.float64), N) * np.fft.rfft(h.astype(np.float64), N), N)
    ref = ref[..., : x.shape[-1]]
    np.testing.assert_allclose(y, ref, atol=1e-3 * np.abs(ref).max())


#: What the rfft/irfft path meets on a quiet frame beside a loud one,
#: against the quiet frame's own scale (readings ≤ 1.4e-6 here).
QUIET_TOL = 1e-5


@pytest.mark.parametrize("backend", ["pallas", "xla"])
@pytest.mark.parametrize("quiet, loud", [(2, 3), (3, 2)])
def test_paired_quiet_frame_keeps_its_own_scale(backend, quiet, loud, rng):
    """Frames 2 and 3 share a transform; one is 10^4 times the other.  The
    quiet frame's error, over its own scale, stays where the rfft path
    (one frame per transform) keeps it."""
    Lh, B, nb = 129, 1024, 6
    step = B - Lh + 1
    x = rng.standard_normal((1, nb * step))
    x[:, loud * step : (loud + 1) * step] *= 1e4
    x = jnp.asarray(x, jnp.float32)
    h = jnp.asarray(rng.standard_normal((Lh,)), jnp.float32)
    ref = _conv64(np.asarray(x), np.asarray(h))[0]
    paired = np.asarray(fft_conv_os(x, h, block=B, backend=backend))[0]
    frames = frame_signal(x, B, step, nb)
    Hr, Hi = filter_spectrum(h, B, backend)
    generic = np.asarray(conv_frames(frames, Hr, Hi, overlap=Lh - 1, backend=backend)).reshape(-1)
    single = np.concatenate(
        [
            np.asarray(conv_frames(frames[:, j : j + 1], Hr, Hi, overlap=Lh - 1, backend=backend)).reshape(-1)
            for j in range(nb)
        ]
    )
    q = slice(quiet * step, (quiet + 1) * step)
    scale = np.abs(ref[q]).max()
    for name, y in (("rfft", single), ("paired", paired), ("rfft_batched", generic)):
        assert np.abs(y[q] - ref[q]).max() / scale <= QUIET_TOL, name


# ---------------------------------------------------------------------------
# plan-cache discipline: the acceptance criterion made literal
# ---------------------------------------------------------------------------


def test_plan_cache_stays_fused_for_1m_signal(rng):
    L, Lh = 2**20, 4097
    x = rng.standard_normal((1, L)).astype(np.float32)
    h = rng.standard_normal((Lh,)).astype(np.float32)
    snapshot = set(fft_lib.plan_log())
    y = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h)))
    for spec in _new_specs(snapshot):
        assert max(spec.n, spec.n2 or 0) <= plan_lib.FUSED_MAX, (
            f"overlap-save planned past the fused regime: {spec}"
        )
    # causal outputs only depend on the causal past: the head of the 1M
    # result must equal the (one-shot, fused-regime) conv of the head.
    head = 8192
    ref = np.asarray(
        fft_conv(jnp.asarray(x[..., :head]), jnp.asarray(h), overlap_save=False)
    )
    np.testing.assert_allclose(y[..., :head], ref, atol=1e-3 * np.abs(ref).max())


def test_fft_conv_auto_routes_long_signals(rng):
    L, Lh = 2**17, 4097  # next_pow2(L + Lh - 1) = 2**18 > FUSED_MAX
    x = rng.standard_normal((1, L)).astype(np.float32)
    h = rng.standard_normal((Lh,)).astype(np.float32)
    snapshot = set(fft_lib.plan_log())
    y_auto = np.asarray(fft_conv(jnp.asarray(x), jnp.asarray(h)))
    assert all(
        max(spec.n, spec.n2 or 0) <= plan_lib.FUSED_MAX
        for spec in _new_specs(snapshot)
    )
    y_one = np.asarray(
        fft_conv(jnp.asarray(x), jnp.asarray(h), overlap_save=False)
    )
    np.testing.assert_allclose(y_auto, y_one, atol=1e-3 * np.abs(y_one).max())


def test_fft_conv_short_signals_stay_one_shot(rng):
    # Under the routing threshold nothing changes: the one-shot rfft pair.
    x = rng.standard_normal((2, 1024)).astype(np.float32)
    h = rng.standard_normal((64,)).astype(np.float32)
    snapshot = set(fft_lib.plan_log())
    fft_conv(jnp.asarray(x), jnp.asarray(h))
    kinds = {(s.kind, s.n) for s in _new_specs(snapshot)}
    assert all(n <= plan_lib.FUSED_MAX for _, n in kinds)


# ---------------------------------------------------------------------------
# StreamingConv: chunked == one-shot
# ---------------------------------------------------------------------------


def _stream(sc, x, schedule):
    state = sc.init_state(x.shape[:-1])
    outs, pos = [], 0
    for c in schedule:
        y, state = sc(jnp.asarray(x[..., pos : pos + c]), state)
        outs.append(np.asarray(y))
        pos += c
    assert pos == x.shape[-1]
    return np.concatenate(outs, axis=-1), state


@pytest.mark.parametrize(
    "schedule",
    [
        [640] * 7 + [520],          # ragged final chunk
        [64] * 78 + [8],            # every chunk smaller than Lh
        [1000, 17, 3000, 983],      # mixed, including chunk << Lh
    ],
)
def test_streaming_matches_one_shot(schedule, rng):
    L, Lh = sum(schedule), 129
    x = rng.standard_normal((2, L)).astype(np.float32)
    h = rng.standard_normal((Lh,)).astype(np.float32)
    sc = StreamingConv(jnp.asarray(h))
    y_stream, state = _stream(sc, x, schedule)
    assert state.shape == (2, Lh - 1)
    np.testing.assert_array_equal(np.asarray(state), x[:, -(Lh - 1) :])
    y_one = np.asarray(fft_conv_os(jnp.asarray(x), jnp.asarray(h)))
    scale = max(1.0, np.abs(y_one).max())
    np.testing.assert_allclose(y_stream, y_one, atol=1e-3 * scale)


def test_streaming_per_channel_filters(rng):
    x = rng.standard_normal((2, 3, 500)).astype(np.float32)
    h = rng.standard_normal((3, 33)).astype(np.float32)
    sc = StreamingConv(jnp.asarray(h), block=128)
    y_stream, _ = _stream(sc, x, [200, 300])
    ref = toeplitz_conv_ref(x, h[None])
    np.testing.assert_allclose(y_stream, ref, atol=2e-3)


def test_streaming_one_tap_filter(rng):
    # Lh = 1: zero-width state, pure gain — the degenerate edge.
    x = rng.standard_normal((2, 100)).astype(np.float32)
    sc = StreamingConv(jnp.asarray(np.array([2.0], np.float32)))
    y, state = _stream(sc, x, [60, 40])
    assert state.shape == (2, 0)
    np.testing.assert_allclose(y, 2.0 * x, atol=1e-5)


def test_streaming_rejects_bad_state(rng):
    sc = StreamingConv(jnp.asarray(rng.standard_normal((17,)), jnp.float32))
    with pytest.raises(ValueError):
        sc(jnp.zeros((2, 8)), jnp.zeros((2, 3)))
