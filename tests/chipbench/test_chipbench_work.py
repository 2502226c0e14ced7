"""Work counts against hand counts and the program's own step list."""

from __future__ import annotations

import json

import pytest

from chipbench import work
from chipbench.catalog import Catalog


def _graph(cfg_name: str, **sizes):
    cat = Catalog()
    cell = next(w for w in cat.bench["workloads"] if w["config"] == cfg_name)
    cfg, mix = cat.config(cfg_name), dict(cat.traffic(cell["traffic"]))
    if sizes:
        cfg = json.loads(json.dumps(cfg))
        cfg.pop("program_config", None)
        cfg["config"].update(sizes.pop("config", {}))
        mix.update(sizes)
    return cat.family(cfg["family"]).build(cfg, mix)


def tiny_lm():
    # 1 layer, d=8 (2 heads of 4), F=16, V=32; 2 x 4 tokens
    return _graph("qwen1_5_0_5b_ntx", batch=2, seq=4, config=dict(
        num_hidden_layers=1, hidden_size=8, num_attention_heads=2, num_key_value_heads=2,
        head_dim=4, intermediate_size=16, vocab_size=32))


def test_lm_model_flops_by_hand():
    # matmul params without the embedding: qkv 8*24 + proj 8*8 + fc1 8*16
    # + fc2 16*8 + head 8*32 = 768; 6 * 768 * 8 tokens; attention
    # 12 * H * Dh * S per token: 12 * 2 * 4 * 4 * 8
    assert work.model_flops(tiny_lm()) == 6 * 768 * 8 + 12 * 2 * 4 * 4 * 8


def test_lm_streaming_passes_by_hand_and_in_the_program():
    from repro.lower import lower_training_step

    g = tiny_lm()
    passes = work.streaming_passes(g)
    by = {(p.node, p.kind): p for p in passes}
    # embedding: forward and dW, no dX; every other matmul all three
    assert ("emb", "dx") not in by
    assert len(passes) == 2 + 3 * 5
    emb = by["emb", "fwd"]
    assert (emb.m, emb.n, emb.k) == (8, 8, 32)
    assert emb.flops == 2 * 8 * 8 * 32
    assert emb.bytes == 4 * (8 * 32 + 32 * 8 + 8 * 8)
    dw = by["fc1_0", "dw"]  # A^T (8 x 8 tokens) @ dY (8 tokens x 16)
    assert (dw.m, dw.n, dw.k) == (8, 16, 8)
    dx = by["head", "dx"]  # dY (8 x 32) @ W^T (32 x 8)
    assert (dx.m, dx.n, dx.k) == (8, 8, 32)
    steps = set(lower_training_step(g).meta["steps"])
    assert {f"{p.node}:{p.kind}" for p in passes} <= steps


def test_cnn_counts_by_hand_and_in_the_program():
    from repro.lower import lower_training_step

    g = _graph("googlenet_trunk_224", batch=2, config=dict(image=[16, 16, 3], layers=[
        ["conv0", "conv", {"k": 3, "cout": 8, "stride": 2, "padding": 1}],
        ["relu0", "relu"], ["flat", "flatten"], ["fc", "fc", {"cout": 10}]]))
    passes = work.streaming_passes(g)
    by = {(p.node, p.kind): p for p in passes}
    # conv0 reads the images: forward and dW only; fc all three
    assert sorted(by) == [("conv0", "dw"), ("conv0", "fwd"),
                          ("fc", "dw"), ("fc", "dx"), ("fc", "fwd")]
    conv_macs = 2 * 8 * 8 * 3 * 3 * 3 * 8  # images * 8x8 out * 3x3x3 taps * 8 out
    assert by["conv0", "fwd"].flops == 2 * conv_macs
    # layer tensors, not the im2col matrix: x, w, y in float32
    assert by["conv0", "fwd"].bytes == 4 * (2 * 16 * 16 * 3 + 3 * 3 * 3 * 8 + 2 * 8 * 8 * 8)
    fc_macs = 2 * 512 * 10
    assert work.model_flops(g) == 2 * (2 * conv_macs + 3 * fc_macs)
    steps = set(lower_training_step(g).meta["steps"])
    assert {f"{p.node}:{p.kind}" for p in passes} <= steps


@pytest.mark.parametrize("cfg, flops", [
    # 6 * 394,657,792 matmul params * 512 tokens + 12*24*16*64*128 * 512
    ("qwen1_5_0_5b_ntx", 6 * 394_657_792 * 512 + 12 * 24 * 16 * 64 * 128 * 512),
    # forward MACs per image: conv0 112*112*7*7*3*64, conv1 56*56*9*64*192,
    # conv2 28*28*9*192*256, conv3 28*28*256*64, conv4 14*14*9*64*512,
    # conv5 14*14*512*192, fc 9408*1000; conv0 has no dX; 128 images
    ("googlenet_trunk_224", 2 * 128 * (3 * (118_013_952 + 346_816_512 + 346_816_512
                                           + 12_845_056 + 57_802_752 + 19_267_584
                                           + 9_408_000) - 118_013_952)),
])
def test_full_size_model_flops(cfg, flops):
    assert work.model_flops(_graph(cfg)) == flops


def test_roofline_takes_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    compute = work.Pass("a", "fwd", 1, 1, 1, flops=1000.0, bytes=10.0)  # 10 s vs 1 s
    memory = work.Pass("b", "fwd", 1, 1, 1, flops=100.0, bytes=50.0)  # 1 s vs 5 s
    assert work.roofline_s([compute, memory], peaks) == 15.0
