"""FLOP and byte counts against a count by hand."""
import json
from pathlib import Path

import pytest

from bench.families import vit

CONFIGS = Path(__file__).resolve().parent / "configs"


def model(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())["model"]


def by_hand(T, d, ff, L, n_patch_px=16 * 16 * 3, n_patches=196, C=1000):
    qkv = 3 * 2 * T * d * d
    attn = 2 * (2 * T * T * d)          # QK^T and AV
    out = 2 * T * d * d
    mlp = 2 * (2 * T * d * ff)
    return L * (qkv + attn + out + mlp) + 2 * n_patches * n_patch_px * d \
        + 2 * d * C


def test_vit_s_full_width():
    got = vit.flops_per_request(model("dynamic-ofa-supernet"), "w1-f1-h1-d1")
    assert got == by_hand(T=197, d=384, ff=1536, L=12)
    assert got == pytest.approx(9.20e9, rel=2e-3)


def test_deit_b_full_width():
    got = vit.flops_per_request(model("deit-b"), "w1-f1-h1-d1")
    assert got == by_hand(T=198, d=768, ff=3072, L=12)
    assert got == pytest.approx(35.3e9, rel=3e-3)


def test_sliced_subnet_by_hand():
    # width 0.75 -> 288, ffn 0.5 -> 768, heads 0.75 of 6 -> 4 (x64 = 256),
    # depth 0.5 -> 6 layers
    T, a, hd, f = 197, 288, 256, 768
    layer = 3 * 2 * T * a * hd + 2 * 2 * T * T * hd + 2 * T * hd * a \
        + 2 * 2 * T * a * f
    expect = 6 * layer + 2 * 196 * 768 * a + 2 * a * 1000
    assert vit.flops_per_request(model("dynamic-ofa-supernet"),
                                 "w0.75-f0.5-h0.75-d0.5") == expect


def test_full_width_bucket32_is_compute_bound_on_v5e():
    peaks = json.loads((CONFIGS.parent / "peaks.json").read_text())
    t, bound = vit.roofline_s(model("deit-b"), "w1-f1-h1-d1", 32,
                                peaks["TPU v5 lite"])
    assert bound == "compute"
    assert t == pytest.approx(32 * 35.3e9 / 197e12, rel=3e-3)
    # weights (86.6M float32) + input + logits
    assert vit.hbm_bytes(model("deit-b"), "w1-f1-h1-d1", 32) == \
        pytest.approx(4 * 86.6e6 + 32 * (224 * 224 * 3 * 4 + 2000), rel=1e-2)
