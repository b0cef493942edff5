"""Each count against a shape worked by hand."""

import json
import os

import pytest

from benchmarks import flops

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_attended_pairs():
    assert flops.attended_pairs(4) == 10                 # 1+2+3+4
    assert flops.attended_pairs(4, window=2) == 7        # 1+2+2+2
    assert flops.attended_pairs(4, window=4) == 10
    assert flops.attended_pairs(4096, 4096) == 4096 * 4097 // 2
    assert flops.attended_pairs(16384, 4096) \
        == 4096 * 4097 // 2 + 12288 * 4096


def test_mistral_parameters():
    c = cfg("mistral-7b-v0.1")
    # a layer: 4096x6144 + 4096x4096 + 4096x28672 + 14336x4096
    layer = 25_165_824 + 16_777_216 + 117_440_512 + 58_720_256
    assert layer == 218_103_808
    assert flops.matmul_params(c) == 4 * layer + 4096 * 32000
    assert flops.total_params(c) == 4 * layer + 2 * 131_072_000 + 9 * 4096
    assert flops.total_params(c) == 1_134_596_096


def test_internlm2_parameters():
    c = cfg("internlm2-1.8b")
    layer = 2048 * 4096 + 2048 * 2048 + 2048 * 16384 + 8192 * 2048
    assert flops.total_params(c) \
        == 24 * layer + 2 * 92544 * 2048 + 49 * 2048 == 1_889_110_016


def test_train_flops_per_token():
    c = cfg("mistral-7b-v0.1")
    att = 4 * 4 * (4096 * 4097 // 2) * 4096       # layers x 4 x pairs x d
    assert flops.attention_flops_fwd(c, 4096) == att
    want = 6 * flops.matmul_params(c) + 3 * att / 4096
    assert flops.train_flops_per_token(c, 4096) == pytest.approx(want)
    assert 6.3e9 < want < 6.5e9


def test_flash_costs_small_shape():
    s = {"batch": 1, "seq": 4, "heads": 2, "kv_heads": 1, "head_dim": 8,
         "window": 0}
    # 10 pairs x 2 heads x 8 dims: QK^T and PV, 2 FLOP each
    f, b = flops.flash_fwd_cost(s)
    assert f == 2 * 10 * 8 * 4 == 640
    q, kv, lse = 4 * 2 * 8 * 2, 4 * 1 * 8 * 2, 4 * 2 * 4
    assert b == 2 * q + 2 * kv + lse
    f, b = flops.flash_bwd_cost(s)
    assert f == 2 * 10 * 8 * 14
    assert b == 2 * (2 * q + 2 * kv + 2 * lse) + q + 2 * kv


def test_flash_call_shape_and_roofline():
    c = cfg("mistral-7b-v0.1")
    s = flops.flash_call_shape(c, 2, 4096)
    assert s == {"batch": 2, "seq": 4096, "heads": 32, "kv_heads": 8,
                 "head_dim": 128, "window": 4096}
    f, b = flops.flash_fwd_cost(s)
    assert f == 2 * 32 * 4 * (4096 * 4097 // 2) * 128
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # compute bound: f/197e12 = 1.395 ms against b/819e9 = 0.21 ms
    share, bound = flops.roofline_share(f, b, 2 * f / 197e12, peak)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = flops.roofline_share(1.0, 819e9, 2.0, peak)
    assert bound == "memory" and share == pytest.approx(50.0)
