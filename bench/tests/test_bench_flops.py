"""Operation counts of the benchmark's models against hand counts."""

import json

import pytest

import _paths
from bench import flops as F

CFG = json.loads((_paths.ROOT / "bench/configs/quadconv_ae.json").read_text())


def test_resnet50_macs_match_the_published_count():
    # He et al. 2015, Table 1: 3.8e9 FLOPs (multiply-adds) for v1; v1.5
    # moves the stride onto the 3x3, which the usual count gives 4.09e9.
    assert F.resnet50_macs() == pytest.approx(4.09e9, rel=5e-3)
    assert F.resnet50_flops() == 2 * F.resnet50_macs()


def test_resnet50_stem_by_hand():
    # 112x112 outputs, 7x7x3 inputs each, 64 channels; nothing else at 32px
    stem = 112 * 112 * 7 * 7 * 3 * 64
    assert F.resnet50_macs(224) > stem
    assert F.resnet50_macs(224, classes=0) == F.resnet50_macs(224) - 2048 * 1000


def test_quadconv_layers_of_the_published_widths():
    layers = F.quadconv_layers(CFG)
    assert layers == [{"points": 1024, "c_in": 4, "c_out": 16},
                      {"points": 256, "c_in": 16, "c_out": 16},
                      {"points": 256, "c_in": 16, "c_out": 16},
                      {"points": 1024, "c_in": 16, "c_out": 16}]


def test_ae_epoch_flops_by_hand():
    # Filter MLP per offset, 3 -> 64 -> 64 -> 64 -> 64 -> O*C, forward:
    mlp64 = 2 * (3 * 64 + 3 * 64 * 64 + 64 * 64)       # O*C = 64
    mlp256 = 2 * (3 * 64 + 3 * 64 * 64 + 64 * 256)     # O*C = 256
    offsets = [1024 ** 2, 256 ** 2, 256 ** 2, 1024 ** 2]
    mlps = [mlp64, mlp256, mlp256, mlp256]
    mlp_fwd = sum(o * m for o, m in zip(offsets, mlps))
    # first MLP layer needs no input gradient: 2x, the others 3x
    first = 2 * 3 * 64
    mlp_train = sum(o * (3 * m - first) for o, m in zip(offsets, mlps))
    contract = lambda b: 2 * b * (1024 ** 2 * 64 + 2 * 256 ** 2 * 256
                                  + 1024 ** 2 * 256)
    heads = lambda b: 2 * b * (1024 * 100 * 2 + 1024 * 16 * 4)
    step = mlp_train + 3 * contract(4) + 3 * heads(4)
    fwd1 = mlp_fwd + contract(1) + heads(1)
    # gather 6: 5 training snapshots in 2 clipped windows of 4, 1 held out
    assert F.ae_epoch_flops(CFG, 6, 4) == 2 * step + fwd1
    assert F.ae_epoch_flops(CFG, 6, 4) == pytest.approx(7.37e11, rel=1e-3)


def test_contraction_calls_and_bytes():
    calls = F.ae_epoch_contractions(CFG, 6, 4)
    assert len(calls) == 4 * 2 + 4
    big = F.quadconv_contract_bytes(4, 1024, 16, 16)
    assert big == 4 * (4 * 1024 * 16 + 1024 + 1024 * 1024 * 256 + 4 * 1024 * 16)
    assert max(c["bytes"] for c in calls) == big


def test_store_bytes():
    assert F.gather_rows_bytes(8, 3 * 224 * 224 * 4) == 2 * 8 * 602112
    assert F.probe_bytes(64, 8) == 64 * 8 + 8 * 12
