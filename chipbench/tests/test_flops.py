import json
import os

from chipbench import flops, peaks

CFG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CFG, name + ".json")) as f:
        return json.load(f)


def test_ernie_by_hand():
    c = cfg("ernie3-base-mlm")
    # per layer 4 * 768^2 + 2 * 768 * 3072 = 7,077,888; 12 layers 84,934,656;
    # head 768^2 = 589,824; tied decoder 768 * 40000 = 30,720,000
    assert flops.ernie_matmul_params(c) == 84_934_656 + 589_824 + 30_720_000
    # 6 * 116,244,480 + 12 * 12 * 768 * 512 = 697,466,880 + 56,623,104
    assert flops.train_flops_per_token(c, 512) == 754_089_984
    assert flops.train_flops_per_token(c, 128) == 697_466_880 + 14_155_776


def test_mistral_by_hand():
    c = cfg("mistral7b-v01-l16")
    # per layer q,o 2 * 4096^2 = 33,554,432; k,v 2 * 4096 * 1024 = 8,388,608;
    # mlp 3 * 4096 * 14336 = 176,160,768: 218,103,808; 16 layers 3,489,660,928;
    # head 4096 * 32000 = 131,072,000
    assert flops.llama_matmul_params(c) == 3_489_660_928 + 131_072_000
    assert flops.serve_flops_per_token(c) == 2 * 3_620_732_928
    # 2k tokens of context, 16 layers: 2 * 16 * 8 * 128 * 2 B = 65,536 B a token
    assert flops.paged_attn_bytes(2048, c) == 65_536 * 2048


def test_flash_by_hand():
    f = flops.flash_attn_flops(16, 12, 512, 64)
    # one matmul 2 * 16 * 12 * 512 * 512 * 64 = 6,442,450,944
    assert f == {"fwd": 12_884_901_888, "dq": 12_884_901_888, "dkdv": 12_884_901_888}
    b = flops.flash_attn_bytes(16, 12, 512, 64)
    t = 16 * 12 * 512 * 64 * 2
    assert b == {"fwd": 4 * t, "dq": 5 * t, "dkdv": 6 * t}
    secs, bound = flops.roofline_seconds(f["fwd"], b["fwd"], peaks.peak_for("TPU v5 lite"))
    assert bound == "compute" and abs(secs - 12_884_901_888 / 197e12) < 1e-12


def test_unknown_device_is_an_error():
    import pytest

    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("cpu")
    assert peaks.peak_for("TPU v5e")["flops_per_s"] == 197e12
