"""``trinity-mini-serve``: the counts of ``work/afmoe.py`` against hand
counts (ISSUE 33's table), the traffic files' promises, the configuration
against the catalog's row, and ``correct`` at rehearsal size (the files'
``rehearsal`` overrides, on the CPU): a sound run is correct; the float8
control and each planted fault is not."""

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import correct, harness, traffic_gen
from benchmarks.reference import afmoe as ref
from benchmarks.work import afmoe as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "trinity-mini-serve-chat8k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg():
    return harness.load_json(HERE, "configs", "trinity-mini-serve.json")


def _ctx(seconds=1.5, seed=2_147_483_659):
    import jax

    manifest = harness.load_manifest()
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=0, rehearsal=True)
    d = jax.devices()[0]
    return harness.Context(
        manifest, harness.find(manifest["workloads"], CELL, "workload"),
        args, {"platform": d.platform, "kind": d.device_kind, "count": 1},
        time.monotonic())


def _clear():
    from deeplearning4j_tpu.optimize import aot_cache

    aot_cache.clear()


# --- counts ------------------------------------------------------------------

def test_parameters_by_hand():
    cfg = _cfg()
    e, v = 2048, 200192
    attention = 2 * e * 4096 + 2 * e * 512 + 4096 * e
    assert work.attention_params(cfg) == attention
    assert attention == pytest.approx(27.26e6, rel=1e-3)
    assert work.expert_params(cfg) == 3 * e * 1024
    assert work.expert_bytes(cfg) == pytest.approx(12.58e6, rel=1e-3)
    expert_layer = 128 * 3 * e * 1024 + 3 * e * 1024 + e * 128 + attention
    assert work.layer_params(cfg, True) == expert_layer
    assert expert_layer == pytest.approx(839.1e6, rel=1e-3)
    dense_layer = 3 * e * 6144 + attention
    assert work.layer_params(cfg, False) == dense_layer
    assert dense_layer == pytest.approx(65.0e6, rel=1e-3)
    assert work.vocabulary_params(cfg) == 2 * v * e
    assert 2 * v * e == pytest.approx(820.0e6, rel=1e-3)
    total = dense_layer + 4 * expert_layer + 2 * v * e
    assert work.parameter_count(cfg) == total
    # bfloat16 but the four routers: 8.48 GB, as the deployment states
    assert work.weight_bytes(cfg) == 2 * total + 2 * 4 * e * 128
    assert work.weight_bytes(cfg) == pytest.approx(8.48e9, rel=2e-3)
    # the reference's tree holds exactly these matrices
    shapes = ref.weight_shapes(cfg)
    matrices = sum(
        int.__mul__(*s) if len(s) == 2 else s[0] * s[1] * s[2]
        for leaves in shapes.values() for s in leaves.values() if len(s) > 1)
    assert matrices == total


def test_decode_step_bytes_and_flops_by_hand():
    cfg = _cfg()
    contexts = [1000] * 16 + [5000] * 16
    position = 2 * 4 * 128 * 2                 # a position's K and V, bytes
    assert work.kv_position_bytes(cfg) == position == 2048
    assert work.positions_read(cfg, 1000) == 5 * 1000
    assert work.positions_read(cfg, 5000) == 4 * 2048 + 5000
    fixed = (2 * (200192 * 2048 + 5 * work.attention_params(cfg)
                  + 3 * 2048 * 6144 + 4 * 3 * 2048 * 1024)
             + 4 * 4 * 2048 * 128)
    assert work.fixed_step_bytes(cfg) == fixed
    assert fixed == pytest.approx(1.22e9, rel=1e-2)
    touched = 4 * 112
    assert work.decode_step_bytes(cfg, contexts, touched) == (
        fixed + touched * 3 * 2048 * 1024 * 2
        + position * 16 * (5000 + 4 * 2048 + 5000))
    # ISSUE 33's step: 5.6 GB of touched experts of 7.5 GB in all
    assert touched * work.expert_bytes(cfg) == pytest.approx(5.64e9, rel=1e-2)
    # a token: its matrices twice over, and 4 d a position a query head
    matrices = (5 * work.attention_params(cfg) + 3 * 2048 * 6144
                + 4 * (9 * 3 * 2048 * 1024 + 2048 * 128))
    assert work.token_matmul_flops(cfg) == 2.0 * (matrices + 200192 * 2048)
    assert work.decode_token_flops(cfg, 5000) == (
        2.0 * (matrices + 200192 * 2048)
        + 4.0 * 128 * 32 * (4 * 2048 + 5000))
    # a prompt: every token through the layers (0.80 GFLOP), the head once
    assert 2.0 * matrices == pytest.approx(0.803e9, rel=1e-2)
    n = 4096
    pairs = (4 * (2048 * 2049 / 2 + (n - 2048) * 2048) + n * (n + 1) / 2)
    assert work.prompt_flops(cfg, n) == (
        2.0 * matrices * n + 2.0 * 200192 * 2048 + 4.0 * 128 * 32 * pairs)
    assert work.prompt_flops(cfg, 100) == (
        2.0 * matrices * 100 + 2.0 * 200192 * 2048
        + 4.0 * 128 * 32 * 5 * 100 * 101 / 2)


def test_configuration_holds_the_catalogs_numbers():
    cfg = _cfg()
    row = None
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"name": "Trinity-Mini"' in line)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == ["num_hidden_layers"] == cfg["reduced"]
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "trinity-mini-serve", "config")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    d = ref.dims(cfg)
    assert cfg["layers_served"] == [1, 4, 5, 6, 7]
    assert d["sliding"] == [True, True, True, True, False]
    assert d["moe"] == [False, True, True, True, True]
    assert d["held"] == (0, 128) and d["top_k"] == 8 and d["window"] == 2048


# --- traffic -----------------------------------------------------------------

def test_traffic_keeps_its_promises():
    cfg = _cfg()
    mix = traffic_gen.load("chat-8k-backlog")
    assert mix["arrival"] == {"process": "closed_loop", "clients": 64,
                              "warm_seconds": 8.0}
    assert mix["arrival"]["clients"] == 2 * cfg["serving"]["max_batch"]
    n = mix["cycle"]
    a = list(itertools.islice(
        traffic_gen.requests(mix, cfg["vocab_size"], 2 ** 31 + 7), n))
    b = list(itertools.islice(traffic_gen.requests(mix, cfg["vocab_size"], 11),
                              n))
    lens = sorted(len(r.prompt) for r in a)
    assert lens == sorted(len(r.prompt) for r in b)
    assert lens[0] >= 256 and lens[-1] == 8192
    assert lens[n // 2] == pytest.approx(2048, rel=0.06)
    # half the prompts outgrow the window: the rings wrap
    assert sum(p > cfg["sliding_window"] for p in lens) >= n // 2 - 1
    outs = sorted(r.max_new for r in a)
    assert outs[0] >= 32 and outs[-1] == 1024
    assert outs[n // 2] == pytest.approx(256, rel=0.06)
    assert max(len(r.prompt) + r.max_new for r in a) <= cfg["serving"][
        "max_len"]
    assert max(max(r.prompt) for r in a) > cfg["vocab_size"] // 2
    assert max(max(r.prompt) for r in a) < cfg["vocab_size"]


# --- correct, at rehearsal size ---------------------------------------------

@pytest.fixture(scope="module")
def driver():
    from benchmarks.drivers import serve_routed

    return serve_routed


@pytest.fixture(scope="module")
def sound(driver):
    """One sound window, kept for the checks that put the reference in
    the program's place."""
    _clear()
    ctx = _ctx()
    obs = driver.measure(ctx, ctx.args.seed, 1.5, False)
    return ctx, obs


def test_sound_run_is_correct(driver):
    _clear()
    ctx = _ctx()
    obs = driver.run(ctx)
    assert obs["correct"], obs["compared"]
    assert obs["failed"] == 0 and obs["attempted"] > 8
    n = obs["counters"]
    assert n["compiles"] == 0
    assert obs["compared"]["answers_of_wrong_length"]["value"] == 0
    # live tokens only: four expert layers, four experts a token
    decoded = n["tokens"] - n["joined"]
    assert n["moe_routed_slots"] == pytest.approx(4 * 4 * decoded, rel=0.02)
    assert 0 < n["moe_experts_touched_pct"] <= 100
    assert n["moe_max_load_over_mean"] >= 1
    assert 0 < n["kv_read_pct"] < 100
    assert obs["notes"]["state_bytes"] == {
        "kv_ring": 4 * 2 * 4 * 16 * 2 * 16 * 4, "kv": 2 * 4 * 256 * 2 * 16 * 4}
    line = harness.result_line(ctx, obs)
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ("control",) + ref.FAULTS)
def test_reference_with_a_fault_is_not_correct(driver, sound, fault):
    """The reference computed in float8, or with one mechanism left out,
    put in the program's place."""
    ctx, obs = sound
    checked = driver.check(ctx, obs["weights"], obs["served"],
                           control=fault == "control",
                           faults=() if fault == "control" else (fault,))
    limits = ctx.cell_file["limits"]
    exact = {"answers_of_wrong_length": 0.0}
    ok, _ = correct.judge({**checked["numbers"], **exact}, limits)
    assert ok, checked
    ok, compared = correct.judge({**checked[fault], **exact}, limits)
    assert not ok, compared
    assert compared["served_logit_gap_mean"]["value"] > \
        compared["served_logit_gap_mean"]["limit"]
    # the widest gap is read too, and not compared (PERF.md section 2)
    assert checked[fault]["served_logit_gap"] >= \
        checked[fault]["served_logit_gap_mean"]


def test_a_ring_read_past_what_the_row_wrote_is_not_correct(driver,
                                                            monkeypatch):
    """A ring read that forgets the ring's contract and attends EVERY
    slot, with prompts shorter than the window: the slots a row has not
    written yet (zeros since its join) take part of the softmax's mass,
    and the served tokens fall away from the reference."""
    from deeplearning4j_tpu.conf import layers_hybrid
    from deeplearning4j_tpu.ops.block_sparse import dense_decode_attention

    def every_slot(q, k_ring, v_ring, positions, groups):
        return dense_decode_attention(
            q, k_ring, v_ring, positions * 0 + k_ring.shape[1] - 1, groups)

    _clear()
    monkeypatch.setattr(layers_hybrid, "window_ring_attention", every_slot)
    ctx = _ctx()
    ctx.traffic = dict(ctx.traffic, prompt_tokens={
        "distribution": "constant", "value": 6, "min": 6, "max": 6})
    obs = driver.run(ctx)
    _clear()
    assert not obs["correct"], obs["compared"]
    assert obs["compared"]["answers_of_wrong_length"]["value"] == 0


def test_rehearsal_command_exits_zero():
    root = os.path.dirname(HERE)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "1.5", "--trace", "1",
         "--rehearse-on-cpu-at-tiny-size"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is False
    assert line["compared"]["served_logit_gap_mean"]["value"] <= \
        line["compared"]["served_logit_gap_mean"]["limit"]
    metrics = line["metrics"]
    assert metrics["compiles_in_window.chat8k"]["value"] == 0
    assert 0 < metrics["moe_experts_touched_pct.chat8k"]["value"] <= 100
    assert metrics["moe_max_load_over_mean.chat8k"]["value"] >= 1
    assert 0 < metrics["kv_read_pct.chat8k"]["value"] < 100
    assert "prefill_ms_per_join.chat8k" in metrics
