"""Plan schedule tests — the paper's kernel-call-count table (§2.3.2/§3)."""

import pytest

from repro.core import faults
from repro.core import plan as P


def test_direct_regime():
    for n in (2, 16, 256, 1024):
        p = P.plan_fft(n)
        assert p.kernel_calls == 1
        assert p.leaf_passes[0].kind == "direct"


def test_fused_regime_one_call():
    for n in (2048, 4096, 16384, 65536):
        p = P.plan_fft(n)
        assert p.kernel_calls == 1, n
        assert p.leaf_passes[-1].kind == "fused4"


def test_split_regimes_match_paper_structure():
    # Above the fused limit each program factor is one HBM round trip: the
    # two-factor program covers every N ≤ 2³² in 2 calls (the paper's ≥ 3
    # beyond 32K, beaten by fusing twiddle + transpose into the kernels).
    assert P.plan_fft(2**17).kernel_calls == 2
    assert P.plan_fft(2**24).kernel_calls == 2
    assert P.plan_fft(2**32).kernel_calls == 2  # 65536 x 65536
    # Beyond two factors natural order needs the explicit digit-reversal
    # relayout pass (3 transform passes + 1 reorder); pencil order skips it.
    assert P.plan_fft(2**33).kernel_calls == 4
    assert len(P.compile_passes(2**33, order="pencil")) == 3


def test_balanced_split():
    for n in (4, 64, 1024, 2**20):
        n1, n2 = P.balanced_split(n)
        assert n1 * n2 == n
        assert n1 >= n2
        assert n1 // n2 in (1, 2)
    n1, n2 = P.balanced_split(2**20, cap=256)
    assert n2 <= 256 and n1 * n2 == 2**20


def test_non_pow2_routes_to_bluestein():
    # Non-pow2 lengths compile to Bluestein chirp-conv leaves instead of
    # being rejected; non-positive lengths still raise.
    pl = P.plan_fft(48)
    assert [p.kind for p in pl.passes] == ["bluestein", "bluestein"]
    with pytest.raises(ValueError):
        P.plan_fft(0)
    with pytest.raises(ValueError):
        P.balanced_split(0)


def test_vmem_budget_respected():
    for n in (2048, 65536):
        p = P.plan_fft(n).leaf_passes[-1]
        bt = P.pick_batch_tile(p)
        assert bt >= P.SUBLANES  # the TPU block's sublane tile
        assert P.vmem_bytes(p, bt) <= P.VMEM_BUDGET


def test_describe_smoke():
    s = P.describe(2**18)
    assert "2 HBM round trip" in s
    assert "twiddle" in s  # pass program lines include the fused epilogue
    assert "MB" in s  # ... and the modeled HBM traffic


def test_pass_program_round_trip_counts():
    # ISSUE-2 acceptance bounds: ≤ 3 / 3 / 4 passes for 2¹⁷ / 2¹⁸ / 2²⁰.
    # The fused program does them all in 2 (twiddle + transpose in-kernel).
    for n, bound in ((2**17, 3), (2**18, 3), (2**20, 4)):
        plan = P.plan_fft(n)
        assert len(plan.passes) == 2 <= bound
        assert plan.hbm_round_trips == len(plan.passes)


def test_pass_program_views_and_twiddle():
    n = 2**18
    f0, f1 = P.program_factors(n)
    assert (f0, f1) == (512, 512)
    col, row = P.plan_fft(n).passes
    # column pass: strided pencils, in-place layout, fused twiddle epilogue
    assert col.view_in == (n // f0, f1, f0)
    assert col.view_out == col.view_in
    assert col.twiddle_after == (f0, f1)
    assert col.order == "pencil"
    # row pass: contiguous pencils, natural-order transpose fused into the
    # strided write (its out view is the column view of the output buffer)
    assert row.view_in == (f0, 1, f1)
    assert row.view_out == (f0, f0, f1)
    assert row.twiddle_after is None
    assert row.order == "natural"


def test_pass_program_factor_consistency():
    for n in (2**17, 2**18, 2**20, 2**24):
        fs = P.program_factors(n)
        assert all(f <= P.FUSED_MAX for f in fs)
        prod = 1
        for f in fs:
            prod *= f
        assert prod == n
        # program transform passes and factors line up 1:1
        ts = [p for p in P.plan_fft(n).passes if p.kind != "reorder"]
        assert tuple(p.n for p in ts) == fs


def test_pass_hbm_bytes_model():
    n = 2**18
    plan = P.plan_fft(n)
    sig = n * 2 * 4  # split-complex f32, batch 1
    for p in plan.passes:
        assert P.pass_hbm_bytes(p, batch=1) >= 2 * sig  # read + write
    # the twiddle LUT is charged once, to the pass that fuses it
    col, row = plan.passes
    assert P.pass_hbm_bytes(col, 1) - P.pass_hbm_bytes(row, 1) >= sig
    assert P.program_hbm_bytes(plan.passes, 2) > P.program_hbm_bytes(plan.passes, 1)


def test_pick_pass_chunk_ragged_widths():
    # Non-pow2 widths: the chunk starts from the largest power of two BELOW
    # the width (the executor pads the last partial chunk), including the
    # pow2-floor boundary width 65537 and 3·2^k widths.
    p = P.plan_fft(2**18).passes[0]  # strided column pass, f=512
    c = P.pick_pass_chunk(p, width=65537)
    assert c & (c - 1) == 0 and c <= 65536
    assert P._pass_chunk_bytes(p, c) <= P.VMEM_BUDGET or c == 1
    for k in (4, 8, 12):
        w = 3 << k  # 3·2^k floors to 2^(k+1)
        c = P.pick_pass_chunk(p, width=w)
        assert c & (c - 1) == 0 and c <= 1 << (k + 1)
    # degenerate width: one pencil column still yields a valid chunk
    assert P.pick_pass_chunk(p, width=1) == 1


def test_pick_pass_chunk_chunk1_degenerate():
    # A binding budget floors at one 128-lane tile, the narrowest column
    # block Mosaic accepts — with and without width override; a width below
    # one tile is taken whole.
    p = P.plan_fft(2**18).passes[0]
    assert P.pick_pass_chunk(p, budget=1) == P.LANES
    assert P.pick_pass_chunk(p, budget=1, width=65537) == P.LANES
    assert P.pick_pass_chunk(p, budget=1, width=3 << 8) == P.LANES
    assert P.pick_pass_chunk(p, budget=1, width=64) == 64


def test_pick_pass_chunk_fits_budget():
    # The VMEM budget is binding down to one 128-lane tile, the narrowest
    # block Mosaic accepts — incl. huge factors like 2²⁶'s 8192×8192
    # program, which interpret-mode CI would never surface.
    for n in (2**17, 2**18, 2**20, 2**26):
        for p in P.plan_fft(n).passes:
            c = P.pick_pass_chunk(p)
            assert c >= P.LANES
            axis = p.view_in[1] if p.view_in[1] > 1 else p.view_in[0]
            assert axis % c == 0
            assert P._pass_chunk_bytes(p, c) <= P.VMEM_BUDGET or c == P.LANES
            # and never past the limit every kernel is compiled with
            assert P._pass_chunk_bytes(p, c) <= P.VMEM_LIMIT
        P.check_tpu_vmem(P.plan_fft(n).passes)


@pytest.mark.parametrize("n", [2**27, 2**28, 2**32])
def test_tpu_vmem_ceiling_is_a_plan_error(n):
    # Past a pencil factor of 8192 even the narrowest chunk overflows the
    # kernel VMEM limit: the plan is refused with the ceiling named, not
    # left for the chip's compiler to reject.
    with pytest.raises(faults.PlanError, match="factors up to 8192"):
        P.check_tpu_vmem(P.plan_fft(n).passes)


def test_tpu_vmem_ceiling_checked_when_planning_for_the_chip():
    from repro.core import fft as F

    spec = F.FFTSpec(n=2**27)
    with pytest.raises(faults.PlanError, match="VMEM"):
        F._build_plan(spec, "pallas", "tpu", "off")
