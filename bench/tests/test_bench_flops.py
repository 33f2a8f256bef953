"""The yardstick's arithmetic against counts worked by hand at the cells'
shapes (see the docstrings of flops/ssm.py, flops/moe.py and
kernels/ssd_extend.py for the formulas)."""
import json
from pathlib import Path

import pytest

from bench import device
from bench.flops import moe, ssm
from bench.kernels import ssd_extend

BENCH = Path(__file__).resolve().parents[1]
MAMBA = json.loads((BENCH / "configs" / "mamba2-780m.json").read_text())
GRANITE = json.loads(
    (BENCH / "configs" / "granite-moe-3b-a800m.json").read_text())


def test_mamba2_780m_counts():
    # in_proj 2*1536*6448 + out_proj 2*3072*1536 + conv 2*4*3328
    # + scan 5*48*64*128 + 3*48*64 = 31,247,360 per layer
    assert ssm.layer_flops(MAMBA) == 31_247_360
    assert ssm.decode_flops(MAMBA, 10) == 48 * 31_247_360 + 2 * 1536 * 50280
    assert ssm.decode_flops(MAMBA, 10) == 1_654_333_440
    assert ssm.decode_flops(MAMBA, 1000) == ssm.decode_flops(MAMBA, 10)
    assert ssm.prompt_flops(MAMBA, 256) == 384_122_019_840


def test_granite_moe_3b_a800m_counts():
    # q/k/v/o 12,582,912 + router 122,880 + 8 of 40 experts 37,748,736
    assert moe.layer_flops(GRANITE) == 50_454_528
    # 32 layers at 2000 keys (4*24*64 per key) + unembedding 2*1536*49155
    assert moe.decode_flops(GRANITE, 2000) == 2_158_765_056
    # causal prompt: sum over 1..1536 keys = 1536*1537/2
    assert moe.prompt_flops(GRANITE, 1536) == 2_712_171_193_344


def test_required_moe_flops_count_routed_experts_only():
    dense = dict(GRANITE, moe=dict(GRANITE["moe"], top_k=40))
    assert moe.layer_flops(dense) - moe.layer_flops(GRANITE) == \
        32 * 3 * 2 * 1536 * 512


def test_ssd_extend_costs_at_the_chat_shapes():
    dec = (32, 1, 48, 64, 1, 128)       # decode rows of a mixed step
    chunk = (1, 512, 48, 64, 1, 128)    # the admission chunk
    # 32*48*(5*64*128 + 3*64); 4*(2*32*48*64*128 + 2*32*48*64 + 32*48
    # + 2*32*128)
    assert ssd_extend.flops(*dec) == 63_209_472
    assert ssd_extend.bytes_moved(*dec) == 101_488_640
    assert ssd_extend.flops(*chunk) == 1_011_351_552
    assert ssd_extend.bytes_moved(*chunk) == 16_351_232


def test_peaks_are_keyed_by_device_kind():
    v5e = device.peaks("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")
