"""``gigachat35-serve``: the counts of ``work/gigachat35.py`` against hand
counts (the 13.8 GB a decode step moves), the configuration against the
catalog's row, the per-layer readers on synthetic traces, and ``correct``
at rehearsal size (the files' ``rehearsal`` overrides, on the CPU): a sound
run is correct; the float8 control and each planted fault is not."""

import argparse
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import correct, harness
from benchmarks.reference import gigachat35 as ref
from benchmarks.work import gigachat35 as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "gigachat35-serve-reason1k"
# the catalog of published configurations, where the machine has one
CATALOG = os.environ.get("ARCHITECTURES_CATALOG", "")
E = 7168


def _cfg():
    return harness.load_json(HERE, "configs", "gigachat35-serve.json")


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
    delta = E * 24576 + E * 128 + 8192 * E
    assert work.delta_matrix_params(cfg) == delta
    assert delta == pytest.approx(235.8e6, rel=1e-3)
    assert work.delta_float32_params(cfg) == 4 * 16384 + 64 + 64 + 128
    latent = (E * 1536 + 1536 * 64 * 192 + E * 576 + 512 * 64 * 256
              + 2 * 8192 * E)
    assert work.latent_matrix_params(cfg) == latent
    assert latent == pytest.approx(159.8e6, rel=1e-3)
    assert work.dense_ffn_params(cfg) == 3 * E * 18432
    assert 3 * E * 18432 == pytest.approx(396.4e6, rel=1e-3)
    expert = 3 * E * 2048
    assert work.expert_params(cfg) == work.shared_params(cfg) == expert
    assert expert == pytest.approx(44.04e6, rel=1e-3)
    assert work.router_params(cfg) == E * 256 == pytest.approx(1.84e6,
                                                                rel=4e-3)
    # the dense layer, 3 delta-rule expert layers, the latent expert layer
    moe = 16 * expert + expert + E * 256
    assert delta + 3 * E * 18432 == pytest.approx(632.2e6, rel=1e-3)
    assert delta + moe == pytest.approx(986.3e6, rel=1e-3)
    assert latent + moe == pytest.approx(910.3e6, rel=1e-3)
    head = E * 16032
    assert work.head_params(cfg) == head
    assert 2 * head == pytest.approx(229.8e6, rel=1e-3)
    total = work.parameter_count(cfg)
    assert total == pytest.approx(4.73e9, rel=2e-3)
    assert work.weight_bytes(cfg) == pytest.approx(9.46e9, rel=2e-3)
    # the reference's tree holds exactly these leaves, the experts' bias
    # and the norms' gains
    held = sum(
        s[0] * (s[1] if len(s) >= 2 else 1) * (s[2] if len(s) == 3 else 1)
        for vertex, leaves in ref.weight_shapes(cfg).items()
        for leaf, s in leaves.items()
        if leaf not in ("gain", "b", "q_norm", "kv_norm"))
    assert held == total - 1536 - 512


def test_state_step_bytes_and_flops_by_hand():
    cfg = _cfg()
    row = work.state_row_bytes(cfg)
    assert row == {"recurrent": 4 * 64 * 128 * 128 * 4,
                   "conv_window": 4 * 3 * 16384 * 4, "latent": 576 * 2}
    # the cell: 128 rows, a latent bucket of 8192
    assert 128 * row["recurrent"] == pytest.approx(2.15e9, rel=2e-3)
    assert 128 * row["conv_window"] == pytest.approx(0.10e9, rel=1e-2)
    assert 128 * 8192 * row["latent"] == pytest.approx(1.21e9, rel=2e-3)
    touched = 4 * 16 * 0.983
    step = work.decode_step_bytes(cfg, [1000] * 128, touched)
    parts = {"experts": touched * 2 * 3 * E * 2048,
             "states": 2 * 128 * row["recurrent"],
             "delta matrices": 2 * 4 * 235.8e6,
             "dense": 2 * 396.4e6, "shared": 2 * 4 * 44.04e6,
             "latent matrices": 2 * 159.8e6, "head": 2 * E * 16032,
             "latent cache": 128 * 1000 * 576 * 2,
             "rings": 2 * 128 * row["conv_window"],
             "routers": 4 * 4 * E * 256}
    assert parts["experts"] == pytest.approx(5.54e9, rel=2e-3)
    assert parts["states"] == pytest.approx(4.29e9, rel=2e-3)
    assert step == pytest.approx(sum(parts.values()), rel=2e-3)
    assert step == pytest.approx(13.8e9, rel=5e-3)
    # a token: its matrices twice over (8 of 256 experts, whatever is held),
    # the recurrence and the taps, the absorbed read
    assert work.delta_flops_per_token(cfg) == (7.0 * 64 * 128 * 128
                                               + 2.0 * 4 * 16384)
    read = 64 * (2.0 * 576 + 2.0 * 512) * 1000 + 2.0 * 512 * 64 * 256
    assert work.decode_attention_flops(cfg, 1000) == read
    matrices = (4 * 235.8e6 + 159.8e6 + 396.4e6
                + 4 * (8 + 1) * 44.04e6 + 4 * E * 256 + E * 16032)
    assert work.token_matmul_flops(cfg) == pytest.approx(2 * matrices,
                                                         rel=1e-3)
    assert work.decode_token_flops(cfg, 1000) == pytest.approx(
        2 * matrices + 4 * work.delta_flops_per_token(cfg) + read, rel=1e-3)
    n = 256
    assert work.prompt_flops(cfg, n) == pytest.approx(
        n * (2 * (matrices - E * 16032) + 4 * work.delta_flops_per_token(cfg))
        + 2 * E * 16032 + 64 * (2 * 192 + 2 * 128) * n * (n + 1) / 2,
        rel=1e-3)


def _traced_obs(ops):
    """Four decode windows of K = 4 steps over a traced second, 128 rows
    live all through it at a context of 1,000."""
    runs = [(i * 1e8, i * 1e8 + 9e7) for i in range(4)]
    return {"traced": {"t_start": 10.0, "t_stop": 11.0,
                       "layer_counts": {"moe_experts_touched": 16 * 63.0}},
            "requests": [{"prompt": 500, "out": 1001, "t_first": 9.0,
                          "t_done": 12.0}] * 128,
            "trace": {"window_s": 1.0,
                      "fullest": {"programs": {"jit_fn(7)": runs},
                                  "ops": ops}}}


def test_shares_read_what_their_patterns_find():
    """The three roofline shares on a synthetic trace: the bytes their
    work function counts over the time of the operations their metric
    file's patterns find, and nothing where the patterns find nothing."""
    cfg = _cfg()

    class Ctx:
        config = cfg
        peak = {"hbm_bytes_per_s": 819e9}

    def op(text, seconds):
        return [seconds, 16, text, seconds]

    ops = {
        "delta_rule_step_kernel.44": op(
            "%delta_rule_step_kernel.44 = (f32[128,64,128]{2,1,0}, "
            "f32[128,64,128,128]{3,2,1,0}) custom-call(...)", 0.04),
        "paged_decode_attention.3": op(
            "%paged_decode_attention.3 = f32[128,64,512]{2,1,0} "
            "custom-call(...)", 0.005),
        "touched_experts_ffn.1": op(
            "%touched_experts_ffn.1 = f32[128,7168]{1,0} custom-call(bf16"
            "[128,7168]{1,0} %a, f32[128,16]{1,0} %w, bf16[16,7168,2048]{2,1,0}"
            " %g, bf16[16,7168,2048]{2,1,0} %u, bf16[16,2048,7168]{2,1,0} %d)",
            0.1),
        "fusion.2": op("%fusion.2 = f32[128,7168]{1,0} fusion(...)", 0.3)}
    obs = _traced_obs(ops)
    # (share, context) a live row: 128 rows at the mid-window context
    live = sum(s for s, _c in work._traced(obs)[2])
    assert live == pytest.approx(128)

    def read(name):
        spec = harness.load_json(HERE, "metrics", name + ".json")
        return getattr(work, spec["params"]["fn"])(Ctx, obs, spec["params"])

    least, taken = read("delta_state_roofline.giga1k")
    assert taken == pytest.approx(0.04)
    assert least * 819e9 == pytest.approx(2 * 16 * 128 * 4 * 64 * 128 * 128
                                          * 4)
    least, taken = read("latent_read_roofline.giga1k")
    context = sum(s * c for s, c in work._traced(obs)[2])
    assert taken == pytest.approx(0.005)
    assert least * 819e9 == pytest.approx(16 * 576 * 2 * context)
    least, taken = read("moe_expert_roofline.giga1k")
    assert taken == pytest.approx(0.1)
    assert least * 819e9 == pytest.approx(16 * 63 * 2 * 3 * E * 2048)
    obs["trace"]["fullest"]["ops"] = {"fusion.2": ops["fusion.2"]}
    for name in ("delta_state_roofline.giga1k", "latent_read_roofline.giga1k",
                 "moe_expert_roofline.giga1k"):
        assert read(name) is None, name


def test_configuration_holds_the_catalogs_numbers():
    cfg = _cfg()
    row = None
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"name": "GigaChat3.5-432B-A28B"' in line)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    assert cfg["source"] == row["source_url"]
    changed = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert changed == ["vocab_size", "num_hidden_layers",
                       "num_nextn_predict_layers"]
    assert set(changed) <= set(cfg["reduced"])
    assert {k: row["config"][k] for k in cfg["reduced"]} == cfg["published"]
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "gigachat35-serve", "config")
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]
    d = ref.dims(cfg)
    assert d["latent"] == [False, True, False, False, False]
    assert d["moe"] == [False, True, True, True, True]
    assert d["held"] == (0, 16) and cfg["vocab_size"] * 8 == 128256
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["chips"], cell["traffic"]) == (1, "reason-1k-backlog")


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
    # live tokens only: the 4 delta-rule layers
    decoded = n["tokens"] - n["joined"]
    assert n["delta_state_updates"] == pytest.approx(4 * decoded, rel=0.02)
    assert n["kv_read_pct"] == 100.0        # the CPU's masked read
    assert obs["notes"]["state_bytes"] == {
        "recurrent": 4 * 4 * 4 * 32 * 32 * 4,
        "conv_window": 4 * 4 * 3 * (2 * 2 * 32 + 4 * 32) * 4,
        "latent": 4 * 256 * 128 * 4}
    line = harness.result_line(ctx, obs)
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
    assert metrics["compiles_in_window.giga1k"]["value"] == 0
    assert "prefill_ms_per_join.giga1k" in metrics
    # no device trace on the CPU: the trace readers find nothing and say so
    for name in ("delta_state_roofline.giga1k", "latent_read_roofline.giga1k",
                 "moe_expert_roofline.giga1k"):
        assert name not in metrics
