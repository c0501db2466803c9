"""Unit tests for the roofline derivation layer (HLO parsing + extrapolation)."""
import numpy as np
import pytest

from repro.launch import roofline as rl

SYNTH_HLO = """
HloModule jit_step

%fused (p0: f32[8,128]) -> f32[8,128] {
  %p0 = f32[8,128]{1,0} parameter(0)
  ROOT %r = f32[8,128]{1,0} add(%p0, %p0)
}

ENTRY %main (a: f32[8,128], b: bf16[4,256]) -> f32[8,128] {
  %a = f32[8,128]{1,0} parameter(0)
  %b = bf16[4,256]{1,0} parameter(1)
  %ag = bf16[64,256]{1,0} all-gather(%b), channel_id=1, dimensions={0}
  %ar = f32[8,128]{1,0} all-reduce(%a), channel_id=2, to_apply=%sum
  %ars = f32[8,128]{1,0} all-reduce-start(%a), channel_id=5
  %ard = f32[8,128]{1,0} all-reduce-done(%ars)
  %cp = f32[8,128]{1,0} collective-permute(%ar), channel_id=3, source_target_pairs={{0,1}}
  %a2a = (f32[2,128]{1,0}, f32[2,128]{1,0}) all-to-all(%a, %a), channel_id=4
  ROOT %out = f32[8,128]{1,0} add(%cp, %cp)
}
"""


def test_collective_bytes_parses_operands():
    cb = rl.collective_bytes(SYNTH_HLO)
    f32_a = 8 * 128 * 4
    bf16_b = 4 * 256 * 2
    assert cb["all-gather"] == bf16_b  # operand (not output) bytes
    # all-reduce + all-reduce-start counted, -done skipped
    assert cb["all-reduce"] == 2 * f32_a
    assert cb["collective-permute"] == f32_a
    assert cb["all-to-all"] == 2 * f32_a  # two operands


def test_shape_bytes_tuple_and_dtypes():
    assert rl._shape_bytes("f32[8,128]{1,0}") == 8 * 128 * 4
    assert rl._shape_bytes("(bf16[2,2], s32[3])") == 2 * 2 * 2 + 3 * 4
    assert rl._shape_bytes("pred[7]") == 7


def test_terms_and_dominant():
    t = rl.RooflineTerms(
        flops=197e12, hbm_bytes=819e9 * 2, coll_bytes=50e9 * 0.5, device_kind="TPU v5 lite"
    )
    assert np.isclose(t.compute_s, 1.0)
    assert np.isclose(t.memory_s, 2.0)
    assert np.isclose(t.collective_s, 0.5)
    assert t.dominant == "memory"


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v6 lite", ""])
def test_unknown_device_kind_has_no_peaks(kind):
    """A device that is not in the peaks table is an error, never v5e's numbers."""
    with pytest.raises(ValueError, match="no published peaks"):
        rl.peaks_for(kind)
    with pytest.raises(ValueError, match="no published peaks"):
        rl.terms_from_costs({"flops": 1.0}, SYNTH_HLO, kind)


def test_depth_extrapolation_linear():
    a = rl.RooflineTerms(10.0, 100.0, 5.0, "TPU v5 lite", {"all-reduce": 5, "all-gather": 0, "reduce-scatter": 0, "all-to-all": 0, "collective-permute": 0})
    b = rl.RooflineTerms(16.0, 160.0, 8.0, "TPU v5 lite", {"all-reduce": 8, "all-gather": 0, "reduce-scatter": 0, "all-to-all": 0, "collective-permute": 0})
    t = rl.extrapolate_depth(a, b, n_periods=10)
    # total(P) = A + (P-1)(B-A): 10 + 9*6 = 64
    assert np.isclose(t.flops, 64.0)
    assert np.isclose(t.coll_bytes, 32.0)


def test_seq_extrapolation_recovers_polynomial():
    """cost(P,S) = (3 + 2S) + P·(7 + S + 0.001·S²) recovered exactly."""
    cb0 = {k: 0 for k in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute")}
    def cost(p, s):
        alpha = 3 + 2 * s
        beta = 7 + s + 0.001 * s * s
        return rl.RooflineTerms(
            alpha + p * beta, 2 * (alpha + p * beta), 0.0, "TPU v5 lite", dict(cb0)
        )

    points = {(p, s): cost(p, s) for p in (1, 2) for s in (256, 512, 1024, 2048)}
    t = rl.extrapolate_depth_and_seq(points, n_periods=12, seq_target=32768)
    want = (3 + 2 * 32768) + 12 * (7 + 32768 + 0.001 * 32768**2)
    assert np.isclose(t.flops, want, rtol=1e-6)


def test_nonneg_fit_suppresses_spurious_curvature():
    """A linear metric with padding wiggles must not explode at 32× range."""
    rng = np.random.default_rng(0)
    seqs = [256, 512, 1024, 2048]
    true = lambda s: 1000.0 * s
    vals = [true(s) * (1 + rng.uniform(-0.02, 0.02)) for s in seqs]
    got = rl._nonneg_poly_extrapolate(seqs, vals, 32768)
    assert 0.5 * true(32768) < got < 2.0 * true(32768)


def test_model_flops():
    assert rl.model_flops(1_000_000, 100, "train") == 6e8
    assert rl.model_flops(1_000_000, 100, "prefill") == 2e8
