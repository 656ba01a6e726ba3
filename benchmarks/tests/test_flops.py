"""lib/flops.py against counts worked by hand for the three
configurations."""
import json
import os

import pytest

from lib import flops

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs")


def config(name):
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        return json.load(f)


def test_resnet50_matches_torchvision():
    cfg = config("resnet50-featurizer")
    # torchvision.models.resnet50: 25,557,032 parameters, 4.09 GMACs with
    # the classifier at 224x224
    assert flops.resnet_param_count(cfg) == 25_557_032
    assert flops.resnet_forward_flops(cfg, True) == pytest.approx(
        2 * 4.089e9, rel=1e-3)
    # the first convolution by hand: 7*7*3*64 MACs at 112x112
    convs, c_out = flops._resnet_convs(cfg)
    assert convs[0] == (7, 3, 64, 112) and c_out == 2048
    assert len(convs) == 1 + 3 * 16 + 4       # stem, 16 blocks, 4 shortcuts


@pytest.mark.parametrize("name,e,layers,total,matmul", [
    # block = 12 e^2 + 9 e; embeddings 50304 e + 1024 e; ln_f 2 e; head 50304 e
    ("gpt2-medium", 1024, 24,
     24 * (12 * 1024 ** 2 + 9 * 1024) + 2 * 50304 * 1024 + 1024 * 1024 + 2048,
     24 * 12 * 1024 ** 2 + 50304 * 1024),
    ("gpt2-large", 1280, 36,
     36 * (12 * 1280 ** 2 + 9 * 1280) + 2 * 50304 * 1280 + 1024 * 1280 + 2560,
     36 * 12 * 1280 ** 2 + 50304 * 1280),
])
def test_gpt2_parameter_counts(name, e, layers, total, matmul):
    counts = flops.lm_param_counts(config(name))
    assert counts["total"] == total
    assert counts["matmul"] == matmul
    assert counts["block"] == 12 * e * e + 9 * e


def test_gpt2_sizes_by_name():
    assert flops.lm_param_counts(config("gpt2-medium"))["total"] == 406_284_288
    assert flops.lm_param_counts(config("gpt2-large"))["total"] == 838_295_040


def test_train_flops_per_token():
    cfg = config("gpt2-medium")
    # 6 per matmul parameter, and 6 * S * e per layer of causal attention
    want = 6 * 353_501_184 + 6 * 1024 * 1024 * 24
    assert flops.lm_train_flops_per_token(cfg, 1024) == want
    assert flops.lm_train_flops_per_token(config("gpt2-large"), 1024) == \
        pytest.approx(4.916e9, rel=1e-3)


def test_flash_attention_work():
    cfg = config("gpt2-medium")
    work = flops.flash_attention_train(cfg, 4, 1024)
    # one causal score-sized matmul: 2 * b * h * S * S * d / 2
    one = 2 * 4 * 16 * 1024 * 1024 * 64 / 2
    assert work["flops"] == 24 * 7 * one
    assert work["bytes"] == 24 * 12 * (2 * 4 * 1024 * 1024)
    # at head size 64 and 1024 positions the two v5e bounds are close
    # (3.66 ms of FLOPs, 2.95 ms of bytes): compute wins, narrowly
    assert 1.0 < (work["flops"] / 197e12) / (work["bytes"] / 819e9) < 1.5


def test_decode_tick_bytes():
    cfg = config("gpt2-medium")
    # bf16 weights once, and K and V rows of 24 layers for the live tokens
    assert flops.decode_tick_bytes(cfg, 8192) == (
        2 * 353_501_184 + 2 * 2 * 24 * 8192 * 1024)
