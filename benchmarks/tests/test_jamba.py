"""``jamba2-3b-serve``: the counts of ``work/jamba.py`` against hand counts
(ISSUE 35's table), the traffic files' promises, the configuration against
the catalog's row, and ``correct`` at rehearsal size (the files'
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
from benchmarks.reference import jamba as ref
from benchmarks.work import jamba as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "jamba2-3b-serve-reason1k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg():
    return harness.load_json(HERE, "configs", "jamba2-3b-serve.json")


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
    e, d, v = 2560, 5120, 65536
    mamba = (e * 10240 + (d * 4 + d) + d * 192 + (160 * d + d) + d * 16 + d
             + d * e + 192)
    assert work.mamba_params(cfg) == mamba
    assert mamba == pytest.approx(41.24e6, rel=1e-3)
    attention = 2 * e * e + 2 * e * 128
    assert work.attention_params(cfg) == attention
    assert attention == pytest.approx(13.76e6, rel=1e-3)
    assert work.ffn_params(cfg) == 3 * e * 8192
    assert 3 * e * 8192 == pytest.approx(62.91e6, rel=1e-3)
    layers = 26 * (mamba + 3 * e * 8192) + 2 * (attention + 3 * e * 8192)
    assert work.layer_params(cfg) == layers
    assert layers == pytest.approx(2861.5e6, rel=1e-3)
    assert work.embedding_params(cfg) == v * e
    assert v * e == pytest.approx(167.8e6, rel=1e-3)
    total = layers + v * e                  # the tied matrix once
    assert work.parameter_count(cfg) == total
    assert total == pytest.approx(3.03e9, rel=2e-3)
    # bfloat16 but a Mamba mixer's small float32 leaves: 6.06 GB
    small = 26 * (d * 4 + d + d + d * 16 + d + 192)
    assert work.weight_bytes(cfg) == 2 * (total - small) + 4 * small
    assert work.weight_bytes(cfg) == pytest.approx(6.06e9, rel=2e-3)
    # the reference's tree holds exactly these leaves and the norms' gains
    held = sum(
        s[0] * (s[1] if len(s) == 2 else 1)
        for vertex, leaves in ref.weight_shapes(cfg).items()
        for leaf, s in leaves.items() if leaf != "gain")
    assert held == total


def test_state_step_bytes_and_flops_by_hand():
    cfg = _cfg()
    row = work.state_row_bytes(cfg)
    assert row == {"recurrent": 26 * 5120 * 16 * 4,
                   "conv_window": 26 * 3 * 5120 * 4, "kv": 2 * 2 * 128 * 2}
    assert row["recurrent"] == pytest.approx(8.52e6, rel=1e-3)
    assert row["conv_window"] == pytest.approx(1.60e6, rel=2e-3)
    assert row["kv"] == 1024
    # ISSUE 35's cell: 128 rows, a bucket of 8192
    assert 128 * row["recurrent"] == pytest.approx(1.09e9, rel=1e-3)
    assert 128 * row["conv_window"] == pytest.approx(0.20e9, rel=3e-2)
    assert 128 * 8192 * row["kv"] == pytest.approx(1.07e9, rel=4e-3)
    contexts = [1000] * 128
    step = work.decode_step_bytes(cfg, contexts)
    assert step == (work.weight_bytes(cfg)
                    + 2 * 128 * (row["recurrent"] + row["conv_window"])
                    + 1024 * 128 * 1000)
    assert step == pytest.approx(8.8e9, rel=1e-2)
    # half of the rows live: half of the state moves
    assert (work.decode_step_bytes(cfg, contexts[:64])
            - work.weight_bytes(cfg)) * 2 == step - work.weight_bytes(cfg)
    # a token: its matrices twice over, the scans' and taps' multiply-adds,
    # 4 d a position a query head in two layers
    matrices = (work.parameter_count(cfg)
                - 26 * work.mamba_float32_params(cfg))
    assert work.token_matmul_flops(cfg) == 2.0 * matrices
    scan = 6.0 * 16 * 5120 + 2.0 * 4 * 5120
    assert work.scan_flops_per_token(cfg) == scan
    assert work.decode_token_flops(cfg, 1000) == (
        2.0 * matrices + 26 * scan + 4.0 * 128 * 20 * 2 * 1000)
    assert 128 * work.decode_token_flops(cfg, 1000) == pytest.approx(
        0.78e12, rel=2e-2)
    n = 512
    assert work.prompt_flops(cfg, n) == (
        n * (2.0 * (matrices - 65536 * 2560) + 26 * scan)
        + 2.0 * 65536 * 2560 + 4.0 * 128 * 20 * 2 * n * (n + 1) / 2)
    assert work.prompt_flops(cfg, n) == pytest.approx(2.9e12, rel=3e-2)
    # a layer's scan over a bucket: five streams of a position, the state twice
    assert work.prompt_scan_bytes(cfg, 512) == 4 * (
        512 * (3 * 5120 + 2 * 16) + 2 * 16 * 5120)


def test_scan_share_counts_the_buckets_of_the_traced_prompts():
    cfg = _cfg()
    spec = harness.load_json(HERE, "metrics",
                             "ssm_scan_roofline.reason1k.json")

    def op(name, seconds):
        return [seconds, 26, f"%{name} = (f32[1,512,40,128]{{3,2,1,0:T(8,128)"
                f"S(1)}}, f32[1,16,40,128]{{3,2,1,0}}) custom-call(f32[1,8,8,"
                f"512]{{3,2,1,0}} %reshape.1), custom_call_target=\"tpu_custom"
                f"_call\"", seconds]

    # one short name stands for scans of several buckets: the harness keys
    # an operation by its short name, so the buckets come from the requests
    requests = [{"prompt": p, "out": 40, "t_first": t, "t_done": t + 3.0}
                for p, t in ((33, 10.5), (64, 11.0), (65, 12.0), (2048, 13.9),
                             (500, 9.9), (500, 14.0))]
    obs = {"traced": {"t_start": 10.0, "t_stop": 14.0}, "requests": requests,
           "trace": {"fullest": {"ops": {
               "selective_scan.3": op("selective_scan.3", 0.02),
               "selective_scan": op("selective_scan", 0.002),
               "fusion.9": [1.0, 5, "%fusion.9 = f32[1,512,40,128]{3,2,1,0} "
                            "fusion(f32[1,512,5120]{2,1,0} %x)", 1.0]}}}}

    class Ctx:
        config = cfg
        peak = {"hbm_bytes_per_s": 819e9}

    assert [work.prompt_bucket(cfg, p) for p in (1, 64, 65, 2048)] == [
        64, 64, 128, 2048]
    least, taken = work.ssm_scan_roofline(Ctx, obs, spec["params"])
    assert taken == pytest.approx(0.022)
    assert least * 819e9 == 26 * (2 * work.prompt_scan_bytes(cfg, 64)
                                  + work.prompt_scan_bytes(cfg, 128)
                                  + work.prompt_scan_bytes(cfg, 2048))
    obs["trace"]["fullest"]["ops"] = {}
    assert work.ssm_scan_roofline(Ctx, obs, spec["params"]) is None


def test_configuration_holds_the_catalogs_numbers():
    cfg = _cfg()
    row = None
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"name": "AI21-Jamba2-3B"' in line)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    assert cfg["source"] == row["source_url"]
    assert [k for k, v in row["config"].items() if cfg.get(k) != v] == []
    assert cfg["reduced"] == [] and cfg["changed"] == []
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "jamba2-3b-serve", "config")
    assert entry["reduced"] == [] and entry["source"] == cfg["source"]
    d = ref.dims(cfg)
    assert [i for i, a in enumerate(d["attn"]) if a] == [7, 21]
    assert (d["d"], d["n"], d["k"], d["r"], d["head"], d["kv_heads"]) == (
        5120, 16, 4, 160, 128, 1)
    assert cfg["serving"]["max_batch"] == 128
    assert len(manifest["workloads"]) == 5
    assert all(w["chips"] == 1 for w in manifest["workloads"])


# --- traffic -----------------------------------------------------------------

def test_traffic_keeps_its_promises():
    cfg = _cfg()
    mix = traffic_gen.load("reason-1k-backlog")
    assert mix["arrival"] == {"process": "closed_loop", "clients": 256,
                              "warm_seconds": 12.0}
    assert mix["arrival"]["clients"] == 2 * cfg["serving"]["max_batch"]
    n = mix["cycle"]
    assert n == 256
    a = list(itertools.islice(
        traffic_gen.requests(mix, cfg["vocab_size"], 2 ** 31 + 7), n))
    b = list(itertools.islice(traffic_gen.requests(mix, cfg["vocab_size"], 11),
                              n))
    lens = sorted(len(r.prompt) for r in a)
    assert lens == sorted(len(r.prompt) for r in b)
    assert lens[0] >= 32 and lens[-1] == 2048
    assert lens[n // 2] == pytest.approx(256, rel=0.06)
    outs = sorted(r.max_new for r in a)
    assert outs[0] >= 128 and outs[-1] == 4096
    assert outs[n // 2] == pytest.approx(1024, rel=0.06)
    assert max(len(r.prompt) + r.max_new for r in a) <= cfg["serving"][
        "max_len"]
    assert max(max(r.prompt) for r in a) > cfg["vocab_size"] // 2
    assert max(max(r.prompt) for r in a) < cfg["vocab_size"]


# --- correct, at rehearsal size ---------------------------------------------

@pytest.fixture(scope="module")
def driver():
    from benchmarks.drivers import serve_ssm

    return serve_ssm


@pytest.fixture(scope="module")
def sound(driver):
    """One sound window, kept for the checks that put the reference in
    the program's place."""
    _clear()
    ctx = _ctx()
    obs = driver.measure(ctx, ctx.args.seed, 3.0, False)
    return ctx, obs


def test_sound_run_is_correct(driver):
    _clear()
    ctx = _ctx(seconds=4.0)
    obs = driver.run(ctx)
    assert obs["correct"], obs["compared"]
    assert obs["failed"] == 0 and obs["attempted"] >= 4
    n = obs["counters"]
    assert n["compiles"] == 0
    assert obs["compared"]["answers_of_wrong_length"]["value"] == 0
    # live tokens only: the 26 Mamba layers
    decoded = n["tokens"] - n["joined"]
    assert n["ssm_state_updates"] == pytest.approx(26 * decoded, rel=0.02)
    assert n["kv_read_pct"] == 100.0        # the CPU's masked read
    assert obs["notes"]["state_bytes"] == {
        "recurrent": 26 * 4 * 16 * 512 * 4, "conv_window": 26 * 4 * 3 * 512 * 4,
        "kv": 2 * 2 * 4 * 256 * 64 * 4}
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
    # the tokens the join's states decide are read apart
    assert "join_logit_gap_mean" in compared


def test_a_state_kept_from_the_rows_last_tenant_is_not_correct(driver,
                                                               monkeypatch):
    """A join that adds the prompt's scan state to what the row held
    instead of writing it whole: every row's second tenant decodes from
    two requests' states, and the served tokens fall away from the
    reference."""
    from deeplearning4j_tpu.conf.layers_ssm import MambaMixerLayer

    whole = MambaMixerLayer.cache_join

    def added(self, cache, block, rows, length):
        out = whole(self, cache, block, rows, length)
        kept = cache["state"].at[rows].add(block["state"], mode="drop")
        return {"state": kept, "conv": out["conv"]}

    _clear()
    monkeypatch.setattr(MambaMixerLayer, "cache_join", added)
    ctx = _ctx(seconds=6.0)
    obs = driver.run(ctx)
    _clear()
    assert not obs["correct"], obs["compared"]
    assert obs["compared"]["answers_of_wrong_length"]["value"] == 0


def test_rehearsal_command_exits_zero():
    root = os.path.dirname(HERE)
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", CELL,
         "--seed", "2147483659", "--seconds", "3", "--trace", "1",
         "--rehearse-on-cpu-at-tiny-size"],
        cwd=root, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] is True and line["correct"] is False
    assert line["compared"]["served_logit_gap_mean"]["value"] <= \
        line["compared"]["served_logit_gap_mean"]["limit"]
    metrics = line["metrics"]
    assert metrics["compiles_in_window.reason1k"]["value"] == 0
    assert 0 < metrics["prefill_live_pct.reason1k"]["value"] <= 100
    assert metrics["kv_read_pct.reason1k"]["value"] == 100
    assert "prefill_ms_per_join.reason1k" in metrics
    # no device trace on the CPU: the trace readers find nothing and say so
    assert "ssm_scan_roofline.reason1k" not in metrics
