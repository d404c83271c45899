"""Roofline toolchain unit tests: HLO collective parsing, term math,
model-flops estimates, segment correction arithmetic."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, get_config
from repro.models.spec import count_active_params
from repro.roofline import analysis, hw
from repro.roofline.analysis import parse_collectives, _shape_bytes

HLO = """
  %ar = f32[16,4096,8192]{2,1,0} all-reduce(%x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[512,1848]{1,0} all-gather(%w), dimensions={0}, replica_groups={{0,256}}
  %rs = f32[64,64]{1,0} reduce-scatter(%g), dimensions={0}, replica_groups={{0,1}}
  %a2a = (bf16[8,128]{1,0}, bf16[8,128]{1,0}) all-to-all(%a, %b), replica_groups={{0,1}}
  %cp = u8[100]{0} collective-permute(%c), source_target_pairs={{0,1}}
"""


def test_shape_bytes():
    assert _shape_bytes("f32[16,4096,8192]{2,1,0}") == 16 * 4096 * 8192 * 4
    assert _shape_bytes("bf16[512,1848]{1,0}") == 512 * 1848 * 2
    assert _shape_bytes("(bf16[8,128]{1,0}, bf16[8,128]{1,0})") == 2 * 8 * 128 * 2
    assert _shape_bytes("pred[7]") == 7


def test_parse_collectives_counts_and_bytes():
    st = parse_collectives(HLO, pod_size=256)
    assert st.counts == {"all-reduce": 1, "all-gather": 1,
                         "reduce-scatter": 1, "all-to-all": 1,
                         "collective-permute": 1}
    # the all-gather's replica group {0,256} crosses the pod boundary
    assert st.bytes_dci == 512 * 1848 * 2 * hw.COLLECTIVE_FACTOR["all-gather"]
    # all-reduce counts 2x (ring factor)
    assert st.by_op_bytes["all-reduce"] == 16 * 4096 * 8192 * 4


def test_roofline_terms_math():
    st = parse_collectives("", None)
    rl = analysis.Roofline(
        flops=197e12, bytes_hbm=819e9, collectives=st,
        compute_s=1.0, memory_s=1.0, collective_s=0.0,
        model_flops=197e12 * 4, n_devices=4)
    assert rl.dominant in ("compute", "memory")
    assert rl.useful_ratio == pytest.approx(1.0)
    assert rl.roofline_fraction == pytest.approx(1.0)


@pytest.mark.parametrize("shape_name", list(SHAPES))
def test_model_flops_positive(shape_name):
    cfg = get_config("qwen2-72b")
    f = analysis.model_flops_estimate(cfg, SHAPES[shape_name],
                                      count_active_params(cfg))
    assert f > 0


def test_train_flops_close_to_6nd():
    cfg = get_config("qwen2-72b")
    shape = SHAPES["train_4k"]
    n = count_active_params(cfg)
    f = analysis.model_flops_estimate(cfg, shape, n)
    base = 6.0 * n * shape.global_batch * shape.seq_len
    assert base <= f < 1.35 * base  # attention adds a bounded extra


def test_moe_active_flops_much_smaller_than_total():
    cfg = get_config("arctic-480b")
    from repro.models.spec import count_params
    assert count_active_params(cfg) < 0.05 * count_params(cfg)


def test_segment_cost_correction_arithmetic():
    from repro.roofline.segmented import SegmentCost
    segs = [SegmentCost("dec/G00", 79, 1e12, 1e9, 1e8, 0.0, {})]
    extra_flops = sum(s.flops * s.multiplier for s in segs)
    assert extra_flops == pytest.approx(79e12)


def test_peaks_keyed_by_device_kind():
    """The v5e peaks are found under the kind JAX reports for the chip,
    and any other kind raises instead of borrowing them."""
    v5e = hw.peaks("TPU v5 lite")
    assert v5e.bf16_flops == 197e12 and v5e.hbm_bw == 819e9
    assert hw.PEAK_FLOPS_BF16 == v5e.bf16_flops
    for kind in ("cpu", "TPU v4", "TPU v6 lite"):
        with pytest.raises(KeyError, match="no published peaks"):
            hw.peaks(kind)
