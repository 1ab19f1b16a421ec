"""The FLOP and byte functions against hand counts, and the traffic
generator's promises."""

import itertools
import json
import os

import pytest

from benchmarks import harness, traffic_gen
from benchmarks.reference import gpt2 as gpt2_ref
from benchmarks.work import gpt2 as gpt2_work
from benchmarks.work import resnet50 as resnet_work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(name):
    return harness.load_json(HERE, "configs", name + ".json")


def test_resnet50_forward_is_the_papers_count():
    """He et al. (table 1) quote 3.8e9 multiply-adds for the 50-layer net
    with the stride in each stage's first 1x1 convolution, which is what
    the program builds. (The "4.1 GFLOPs" of the model zoos is the v1.5
    variant, stride in the 3x3, and also counts a multiply-add once.)"""
    cfg = _cfg("resnet50-train")
    cfg["stem"]["space_to_depth"] = False
    macs = sum(resnet_work.conv_macs(cfg).values()) + resnet_work.head_macs(cfg)
    assert macs == pytest.approx(3.86e9, rel=0.01)
    assert resnet_work.forward_flops_per_image(cfg) == 2.0 * macs
    # by hand: the stem is 7*7*3*64 taps on 112x112 outputs
    assert resnet_work.conv_macs(cfg)["stem"] == 7 * 7 * 3 * 64 * 112 * 112
    # res2a_b: 3x3, 64 -> 64, 56x56
    assert resnet_work.conv_macs(cfg)["res2a_b"] == 9 * 64 * 64 * 56 * 56


def test_resnet50_as_run():
    cfg = _cfg("resnet50-train")
    macs = resnet_work.conv_macs(cfg)
    assert macs["stem"] == 4 * 4 * 12 * 64 * 112 * 112   # the 8x8/2 stem
    train = resnet_work.train_flops_per_image(cfg)
    fwd = resnet_work.forward_flops_per_image(cfg)
    assert train == pytest.approx(3 * fwd - 2 * macs["stem"])
    peak = harness.load_json(HERE, "peaks.json")["devices"]["TPU v5 lite"]
    least = resnet_work.conv_train_least_seconds(cfg, 256, peak)
    # the 1x1 convolutions are bandwidth-bound on this chip (51 FLOP/B at
    # 64 -> 256 channels against a ridge of 240), the 3x3 compute-bound
    assert least["least_s"] >= max(least["flops_s"], least["bytes_s"])
    assert least["least_s"] <= least["flops_s"] + least["bytes_s"]
    assert least["flops_s"] == pytest.approx(
        256 * resnet_work.conv_train_flops_per_image(cfg)
        / peak["bf16_flops_per_s"])


def test_gpt2_large_parameters():
    cfg = _cfg("gpt2-large-serve")
    # 36 blocks (matrices, their biases, two LayerNorms), the final
    # LayerNorm, both embeddings and the untied head with its bias
    by_hand = (36 * (12 * 1280 ** 2 + 9 * 1280 + 5120)
               + 2 * 1280 + 50257 * 1280 + 1024 * 1280
               + 1280 * 50257 + 50257)
    assert gpt2_ref.parameter_count(cfg) == by_hand
    assert by_hand == pytest.approx(838e6, rel=0.005)
    assert gpt2_work.block_matmul_params(cfg) == 36 * 12 * 1280 ** 2
    # a decode step reads every matrix once: 772 M x 4 B = 3.09 GB, and
    # 8 rows x 200 positions of keys and values
    b = gpt2_work.decode_step_bytes(cfg, 1600)
    assert b == (36 * 12 * 1280 ** 2 + 1280 * 50257) * 4 \
        + 1600 * 2 * 36 * 1280 * 4


def test_same_seed_same_requests_and_due_times():
    mix = traffic_gen.load("chat-1k-steady")
    a = list(itertools.islice(traffic_gen.requests(mix, 50257, 2 ** 31 + 5),
                              200))
    b = list(itertools.islice(traffic_gen.requests(mix, 50257, 2 ** 31 + 5),
                              200))
    assert [(r.prompt, r.max_new, r.t_due) for r in a] == \
        [(r.prompt, r.max_new, r.t_due) for r in b]
    # t_due comes from the seed alone: the stream is a pure generator, it
    # is never told of a completion; and it only grows
    assert all(x.t_due < y.t_due for x, y in zip(a, a[1:]))
    assert a[-1].t_due == pytest.approx(200 / mix["arrival"]["rate_per_s"],
                                        rel=0.25)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = traffic_gen.load("chat-1k-backlog")
    n = mix["cycle"]
    a = list(itertools.islice(traffic_gen.requests(mix, 50257, 1), n))
    b = list(itertools.islice(traffic_gen.requests(mix, 50257, 2), n))
    sizes = lambda rs: sorted((len(r.prompt), r.max_new) for r in rs)  # noqa
    assert sizes(a) == sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(r.t_due is None for r in a)
    lens = [len(r.prompt) for r in a]
    outs = [r.max_new for r in a]
    assert min(lens) >= 16 and max(lens) <= 512
    assert min(outs) >= 8 and max(outs) <= 256
    assert max(p + o for p, o in zip(lens, outs)) < 1024
    assert sorted(lens)[n // 2] == pytest.approx(128, rel=0.05)


def test_poisson_gaps_have_the_rates_mean():
    mix = traffic_gen.load("chat-1k-steady")
    rate = mix["arrival"]["rate_per_s"]
    gaps = traffic_gen.quantile_cycle(
        {"distribution": "exponential", "mean": 1 / rate}, mix["cycle"])
    assert sum(gaps) / len(gaps) == pytest.approx(1 / rate)


def test_manifest_names_files_that_exist():
    root = os.path.dirname(HERE)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    for c in manifest["configs"]:
        assert os.path.exists(os.path.join(root, c["file"]))
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(HERE, "traffic",
                                           w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(HERE, "cells",
                                           w["name"] + ".json"))
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        spec = harness.load_json(HERE, "metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(HERE, "readers",
                                           spec["reader"] + ".py"))
