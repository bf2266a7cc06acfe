"""The analytic operation counts against hand counts."""

import json
import os

import pytest

from benchmark.harness import flops

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_bert_base_per_token_by_hand():
    # per token and layer: QKV + output = 4 * 768^2 MACs, FFN = 2 * 768*3072
    # MACs: 7,077,888 MACs = 14,155,776 FLOPs. Attention at s=512: QK^T and PV
    # are 2 * 512 * 768 MACs = 1,572,864 FLOPs. Twelve layers: 188,743,680.
    # Head on 80 of 512 positions: (768^2 + 768*30522) MACs * 2 * 80/512
    # = 7,509,600. Forward 196,253,280; forward + backward is three times.
    c = cfg("bert_base_mlm")
    got = flops.bert_mlm_flops_per_token(
        hidden_size=c["hidden_size"], num_hidden_layers=c["num_hidden_layers"],
        num_attention_heads=c["num_attention_heads"],
        intermediate_size=c["intermediate_size"], vocab_size=c["vocab_size"],
        seq_len=512, max_predictions=80)
    assert got == pytest.approx(3 * 196_253_280)
    # the familiar estimate: 6 * parameters * tokens + 12 * L * s * H, with
    # the 85.1M encoder-matrix parameters (the 23.4M-row embedding table is a
    # look-up, and as the decoder it runs on 80/512 of the positions only)
    encoder = 12 * (4 * 768 ** 2 + 2 * 768 * 3072)
    estimate = 6 * encoder + 12 * 12 * 512 * 768 \
        + 6 * (768 ** 2 + 768 * 30522) * 80 / 512
    assert got == pytest.approx(estimate, rel=1e-12)
    assert 0.85 < got / (6 * 110e6) < 0.95   # a tenth under "6 x 110M"


def test_resnet50_forward_is_4_1_gmacs():
    c = cfg("resnet50_imagenet")
    shape = dict(stage_sizes=c["stage_sizes"], width=c["width"],
                 num_classes=c["num_classes"], image_size=c["image_size"])
    convs = flops.resnet_convs(**shape)
    # 1 stem + 16 blocks x 3 + 4 projections + 1 head = 54 products
    assert len(convs) == 54
    by = {n: (pos, area, cin, cout) for n, pos, area, cin, cout in convs}
    assert by["stem"] == (112 * 112, 49, 3, 64)
    assert by["s0b0.1x1a"] == (56 * 56, 1, 64, 64)
    assert by["s0b0.proj"] == (56 * 56, 1, 64, 256)
    # v1.5: the first 1x1 of a strided block still sees the large map
    assert by["s1b0.1x1a"] == (56 * 56, 1, 256, 128)
    assert by["s1b0.3x3"] == (28 * 28, 9, 128, 128)
    assert by["s3b2.1x1b"] == (7 * 7, 1, 512, 2048)
    assert by["head"] == (1, 1, 2048, 1000)
    fwd = flops.resnet_flops_per_image(train=False, **shape)
    # torchvision's resnet50 (v1.5): 4.09 GMACs a 224 image
    assert fwd / 2 == pytest.approx(4.09e9, rel=0.01)
    assert flops.resnet_flops_per_image(**shape) == 3 * fwd


def test_attention_matmul_flops_matches_the_programs():
    from distributeddeeplearningspark_tpu import metrics

    for train in (True, False):
        assert flops.attention_matmul_flops(32, 12, 512, 64, train=train) == \
            metrics.attention_matmul_flops(32, 12, 512, 64, train=train)


def test_flash_kernels_as_written():
    # b=32, h=12, s=512, d=64: one product = 2*32*12*512*512*64 = 12.885 GFLOP
    k = flops.flash_kernels(32, 12, 512, 64)
    product = 2 * 32 * 12 * 512 * 512 * 64
    tensor = 32 * 512 * 12 * 64 * 2
    assert k["fwd"] == {"ops": 2 * product, "bytes": 4 * tensor}
    assert k["bwd_dq"] == {"ops": 3 * product, "bytes": 5 * tensor}
    assert k["bwd_dkv"] == {"ops": 4 * product, "bytes": 6 * tensor}
    # nine products as written against the model's six: the backward
    # kernels recompute QK^T twice and dP once
    assert sum(v["ops"] for v in k.values()) == \
        1.5 * flops.attention_matmul_flops(32, 12, 512, 64)


def test_least_seconds_says_which_peak_bounds():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes"] == 16e9
    assert v5e["ici_bits_per_s"] == 1600e9
    t, bound = flops.least_seconds(197e12, 1e9, v5e)
    assert (t, bound) == (1.0, "compute")
    t, bound = flops.least_seconds(1e12, 819e9, v5e)
    assert (t, bound) == (1.0, "memory")
    # the flash kernels at s=512, d=64: 18*s/(15*2) = 307 FLOP a byte, over
    # the chip's 240: compute-bound
    k = flops.flash_kernels(32, 12, 512, 64)
    ops = sum(v["ops"] for v in k.values())
    nbytes = sum(v["bytes"] for v in k.values())
    assert ops / nbytes == pytest.approx(18 * 512 / 30)
    assert flops.least_seconds(ops, nbytes, v5e)[1] == "compute"
