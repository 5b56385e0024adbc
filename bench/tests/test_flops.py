"""The FLOPs functions against counts made by hand, and the peaks table.

    python -m pytest -q bench/tests/test_flops.py
"""
import json
import pathlib
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import flops  # noqa: E402
import harness  # noqa: E402


def _load(kind, name):
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def test_mamba2_130m_by_hand():
    # per layer and token: in-projections 2*768*(2*1536 + 2*128 + 24),
    # out-projection 2*1536*768, conv 2*4*(1536 + 2*128), SSD 5*24*64*128;
    # 24 layers, then the head 2*768*50,280
    layer = (2 * 768 * 3352 + 2 * 1536 * 768) + 14_336 + 983_040
    assert layer == 8_505_344
    per_token = 24 * layer + 77_230_080
    cfg = _load("configs", "mamba2-130m")
    fam = harness.family(cfg["family"])
    assert fam.forward_per_token(cfg) == per_token == 281_358_336
    traffic = _load("traffic", "train.m8-s512")
    assert fam.train_round_flops(cfg, traffic) == 3 * 16_384 * per_token


def test_resnet20_by_hand():
    # 3 stages of 3 blocks x 2 convs; a 1x1 projection where the width
    # changes; about 41 M multiply-adds, as section 4.2 of the source has it
    stem = 2 * 32 * 32 * 9 * 3 * 16
    stage0 = 6 * (2 * 32 * 32 * 9 * 16 * 16)
    stage1 = (2 * 16 * 16 * 9 * 16 * 32 + 2 * 16 * 16 * 16 * 32
              + 5 * (2 * 16 * 16 * 9 * 32 * 32))
    stage2 = (2 * 8 * 8 * 9 * 32 * 64 + 2 * 8 * 8 * 32 * 64
              + 5 * (2 * 8 * 8 * 9 * 64 * 64))
    head = 2 * 64 * 10
    per_image = stem + stage0 + stage1 + stage2 + head
    assert per_image == 81_626_368
    cfg = _load("configs", "cifar-resnet20")
    fam = harness.family(cfg["family"])
    assert fam.forward_per_image(cfg) == per_image
    traffic = _load("traffic", "train.m10")
    assert fam.train_round_flops(cfg, traffic) == 3 * 160 * per_image


def test_peaks_table():
    assert flops.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(KeyError):
        flops.peak_flops("some other chip")
