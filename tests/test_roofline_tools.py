"""Unit tests for the roofline tooling (HLO collective parsing, wire-byte
formulas, MODEL_FLOPS accounting) — the measurement substrate of §Roofline."""

from repro.launch import dryrun as dr

HLO = """
ENTRY %main {
  %ar = f32[128,4096]{1,0} all-reduce(f32[128,4096]{1,0} %x), replica_groups={}
  %ag = bf16[16,512]{1,0} all-gather(bf16[1,512]{1,0} %y), dimensions={0}
  %a2a = bf16[16,8,64]{2,1,0} all-to-all(bf16[16,8,64]{2,1,0} %z)
  %cp = u8[32]{0} collective-permute(u8[32]{0} %w)
  %mm = f32[128,128]{1,0} dot(%a, %b)
}
"""


def test_parse_collectives_sums_output_bytes():
    c = dr.parse_collectives(HLO)
    assert c["all-reduce"] == 128 * 4096 * 4
    assert c["all-gather"] == 16 * 512 * 2
    assert c["all-to-all"] == 16 * 8 * 64 * 2
    assert c["collective-permute"] == 32
    assert c["counts"]["all-reduce"] == 1
    assert c["reduce-scatter"] == 0


def test_wire_bytes_ring_formulas():
    coll = {"all-reduce": 100, "all-gather": 100, "reduce-scatter": 0,
            "all-to-all": 0, "collective-permute": 50}
    n = 16
    f = 15 / 16
    want = 2 * 100 * f + 100 * f + 50
    assert abs(dr.wire_bytes(coll, n) - want) < 1e-9


def test_model_flops_train_matches_6nd():
    """Dense arch: train FLOPs ≈ 6·N·tokens + attention term."""
    f = dr.model_flops("chatglm3-6b", "train_4k")
    n_params = 6.35e9                      # chatglm3-6b ≈ 6.35B (ours)
    tokens = 256 * 4096
    base = 6 * n_params * tokens
    assert f > base * 0.9                  # includes attention on top
    assert f < base * 1.6


def test_model_flops_moe_uses_active_params():
    """kimi: 1.04T total but ~32B active ⇒ train flops ≪ 6·1T·D."""
    f = dr.model_flops("kimi-k2-1t-a32b", "train_4k")
    tokens = 256 * 4096
    assert f < 6 * 100e9 * tokens          # well under a 100B-dense model
    assert f > 6 * 25e9 * tokens           # but at least the ~32B active


def test_model_flops_decode_linear_in_context():
    f32k = dr.model_flops("qwen2.5-14b", "decode_32k")
    # one token per row: decode flops ≈ 2·N·B + attention·context
    assert f32k > 2 * 14e9 * 128


def test_model_flops_swa_bounded():
    """mixtral long_500k decode: SWA caps the attention context at 4096."""
    f = dr.model_flops("mixtral-8x7b", "long_500k")
    # attention term must reflect the window, not the 524288 context
    attn_win = 1 * 4 * 32 * 128 * 4096 * 32       # B·4·H·hd·W·layers
    attn_full = 1 * 4 * 32 * 128 * 524288 * 32
    base = 2 * 12.9e9                              # active params × 1 token
    assert f < base + attn_full * 0.5              # far below full-context
    assert f > base * 0.9
    assert f > attn_win                            # window term is in there


def test_skip_reasons_match_design():
    from repro.configs.shapes import skip_reason
    assert skip_reason("gemma2-27b", "long_500k")
    assert not skip_reason("mamba2-1.3b", "long_500k")
    assert not skip_reason("mixtral-8x7b", "long_500k")
    assert not skip_reason("gemma2-27b", "train_4k")


def test_peaks_keyed_by_device_kind_unknown_kind_raises():
    import pytest
    from repro.launch.peaks import V5E, chip_peaks
    assert chip_peaks(V5E)["peak_flops_bf16"] == 197e12
    assert chip_peaks(V5E)["hbm_bw"] == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        chip_peaks("cpu")
