"""``lfm2-8b-a1b-serve``: the counts of ``work/lfm2.py`` against hand counts
(the 5.7 GB a decode step moves), the configuration against the catalog's
row, the per-layer readers on synthetic traces, and ``correct`` at
rehearsal size (the files' ``rehearsal`` overrides, on the CPU): a sound
run is correct; the float8 control and each planted fault is not."""

import argparse
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import correct, harness
from benchmarks.readers import counter_share
from benchmarks.reference import lfm2 as ref
from benchmarks.work import lfm2 as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "lfm2-8b-a1b-serve-reason1k"
# the catalog of published configurations, where the machine has one
CATALOG = os.environ.get("ARCHITECTURES_CATALOG", "")
E = 2048


def _cfg():
    return harness.load_json(HERE, "configs", "lfm2-8b-a1b-serve.json")


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
    conv = E * 6144 + E * E
    assert work.conv_matrix_params(cfg) == conv
    assert conv + 3 * E == pytest.approx(16.79e6, rel=1e-3)
    assert work.conv_float32_params(cfg) == 3 * E
    attn = 2 * E * 2048 + 2 * E * 512
    assert work.attn_matrix_params(cfg) == attn
    assert attn == pytest.approx(10.49e6, rel=1e-3)
    expert = 3 * E * 1792
    assert work.expert_params(cfg) == expert
    assert expert == pytest.approx(11.01e6, rel=1e-3)
    assert 32 * expert + E * 32 == pytest.approx(352.4e6, rel=1e-3)
    assert work.dense_ffn_params(cfg) == 3 * E * 7168
    assert 3 * E * 7168 == pytest.approx(44.04e6, rel=1e-3)
    assert work.router_params(cfg) == E * 32
    assert work.head_params(cfg) == 65536 * E == pytest.approx(134.2e6,
                                                               rel=1e-3)
    total = work.parameter_count(cfg)
    assert total == 6 * (conv + 3 * E) + 2 * attn + 2 * 3 * E * 7168 \
        + 6 * (32 * expert + E * 32) + 65536 * E
    assert total == pytest.approx(2.458e9, rel=1e-3)
    assert work.weight_bytes(cfg) == pytest.approx(4.92e9, rel=1e-3)
    # the whole published model by the same counts: 18 convolutions, 6
    # attention layers, 2 dense and 22 expert layers, one tied matrix
    whole = (18 * (conv + 3 * E) + 6 * attn + 2 * 3 * E * 7168
             + 22 * (32 * expert + E * 32) + 65536 * E)
    assert whole == pytest.approx(8.340e9, rel=1e-3)
    # the reference's tree holds exactly these leaves, the experts' bias
    # and the norms' gains
    held = sum(
        s[0] * (s[1] if len(s) >= 2 else 1) * (s[2] if len(s) == 3 else 1)
        for vertex, leaves in ref.weight_shapes(cfg).items()
        for leaf, s in leaves.items()
        if leaf not in ("gain", "b", "q_norm", "k_norm"))
    assert held == total


def test_state_step_bytes_and_flops_by_hand():
    cfg = _cfg()
    row = work.state_row_bytes(cfg)
    assert row == {"conv_window": 6 * 2 * E * 4, "kv": 2 * 2 * 512 * 2}
    # the cell: 128 rows, a KV bucket of 8192
    assert 128 * 8192 * row["kv"] == pytest.approx(4.29e9, rel=2e-3)
    assert 128 * row["conv_window"] == pytest.approx(0.0126e9, rel=1e-2)
    touched = 6 * 32
    step = work.decode_step_bytes(cfg, [1400] * 128, touched)
    parts = {"experts": touched * 2 * 3 * E * 1792,
             "convolutions": 2 * 6 * (E * 6144 + E * E),
             "attention": 2 * 2 * (2 * E * 2048 + 2 * E * 512),
             "dense": 2 * 2 * 3 * E * 7168, "head": 2 * 65536 * E,
             "routers": 4 * 6 * E * 32, "kv": 128 * 1400 * row["kv"],
             "rings": 2 * 128 * row["conv_window"]}
    assert parts["experts"] == pytest.approx(4.23e9, rel=2e-3)
    assert (parts["convolutions"] + parts["attention"] + parts["dense"]
            + parts["head"] + parts["routers"]) == pytest.approx(0.69e9,
                                                                 rel=1e-2)
    assert parts["kv"] == pytest.approx(0.73e9, rel=1e-2)
    assert step == pytest.approx(sum(parts.values()), rel=1e-9)
    assert step == pytest.approx(5.67e9, rel=5e-3)
    # a token: its matrices twice over (4 of 32 experts), the taps and
    # gates, the attention over its context
    assert work.conv_flops_per_token(cfg) == 8.0 * E
    matrices = (6 * (E * 6144 + E * E) + 2 * (2 * E * 2048 + 2 * E * 512)
                + 2 * 3 * E * 7168 + 6 * (4 * 3 * E * 1792 + E * 32)
                + 65536 * E)
    assert work.token_matmul_flops(cfg) == 2 * matrices
    assert work.decode_token_flops(cfg, 1000) == (
        2 * matrices + 6 * 8.0 * E + 2 * 32 * 4.0 * 64 * 1000)
    n = 256
    assert work.prompt_flops(cfg, n) == pytest.approx(
        n * (2 * (matrices - 65536 * E) + 6 * 8.0 * E) + 2 * 65536 * E
        + 2 * 32 * 4.0 * 64 * n * (n + 1) / 2, rel=1e-12)


def _traced_obs(ops):
    """Four decode windows of K = 4 steps over a traced second, 128 rows
    live all through it at a context of 1,000."""
    runs = [(i * 1e8, i * 1e8 + 9e7) for i in range(4)]
    return {"traced": {"t_start": 10.0, "t_stop": 11.0,
                       "layer_counts": {"moe_experts_touched": 16 * 192.0}},
            "requests": [{"prompt": 500, "out": 1001, "t_first": 9.0,
                          "t_done": 12.0}] * 128,
            "trace": {"window_s": 1.0,
                      "fullest": {"programs": {"jit_fn(7)": runs},
                                  "ops": ops}}}


def test_shares_read_what_their_patterns_find():
    """The two roofline shares on a synthetic trace: the bytes their work
    function counts over the time of the operations their metric file's
    patterns find (and not the prompt's grouped kernel), and nothing where
    the patterns find nothing."""
    cfg = _cfg()

    class Ctx:
        config = cfg
        peak = {"hbm_bytes_per_s": 819e9}

    def op(text, seconds):
        return [seconds, 16, text, seconds]

    stacks = ("bf16[32,2048,1792]{2,1,0} %g, bf16[32,2048,1792]{2,1,0} %u, "
              "bf16[32,1792,2048]{2,1,0} %d")
    ops = {
        "touched_experts_ffn.1": op(
            "%touched_experts_ffn.1 = f32[128,2048]{1,0} custom-call(bf16"
            f"[128,2048]{{1,0}} %a, f32[128,32]{{1,0}} %w, {stacks})", 0.1),
        "grouped_experts_ffn.2": op(
            "%grouped_experts_ffn.2 = f32[8192,2048]{1,0} custom-call(bf16"
            f"[8192,2048]{{1,0}} %a, {stacks})", 0.5),
        "fusion.425": op(
            "%fusion.425 = f32[128,6144]{1,0} fusion(bf16[2048,6144]{1,0} "
            "%w, f32[128,2048]{1,0} %u), kind=kOutput", 0.02),
        "convert_add_fusion.4": op(
            "%convert_add_fusion.4 = f32[128,2048]{1,0} fusion(f32[128,6144]"
            "{1,0} %f, bf16[2048,2048]{1,0} %w), kind=kOutput", 0.01),
        "fusion.429": op(
            "%fusion.429 = (f32[128,4096]{1,0}, f32[128,4096]{1,0}) fusion("
            "f32[128,4096]{1,0} %r), kind=kLoop", 0.002),
        "slice-done.12": op(
            "%slice-done.12 = bf16[512,6144]{1,0} slice-done(%s)", 0.003),
        "fusion.2": op("%fusion.2 = f32[128,2048]{1,0} fusion(...)", 0.3)}
    obs = _traced_obs(ops)

    def read(name):
        spec = harness.load_json(HERE, "metrics", name + ".json")
        return getattr(work, spec["params"]["fn"])(Ctx, obs, spec["params"])

    least, taken = read("moe_expert_roofline.lfm1k")
    assert taken == pytest.approx(0.1)
    assert least * 819e9 == pytest.approx(16 * 192 * 2 * 3 * E * 1792)
    least, taken = read("shortconv_roofline.lfm1k")
    assert taken == pytest.approx(0.035)
    live = sum(s for s, _c in work._traced(obs)[2])
    assert live == pytest.approx(128)
    assert least * 819e9 == pytest.approx(
        16 * (6 * 2 * (E * 6144 + E * E) + 2 * 128 * 6 * 2 * E * 4))
    obs["trace"]["fullest"]["ops"] = {"fusion.2": ops["fusion.2"]}
    for name in ("moe_expert_roofline.lfm1k", "shortconv_roofline.lfm1k"):
        assert read(name) is None, name


def test_experts_read_share_reads_the_window_counters():
    class Ctx:
        config = _cfg()

    spec = harness.load_json(HERE, "metrics",
                             "moe_experts_read_pct.lfm1k.json")
    obs = {"counters": {"moe_experts_read": 32 * 600 - 3,
                        "moe_expert_layer_steps": 600}}
    assert counter_share.read(Ctx, obs, spec["params"]) == pytest.approx(
        100.0 * (32 * 600 - 3) / (32 * 600))
    assert counter_share.read(Ctx, {"counters": {}}, spec["params"]) is None


def test_configuration_holds_the_catalogs_numbers():
    cfg = _cfg()
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "lfm2-8b-a1b-serve", "config")
    assert entry["reduced"] == cfg["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    d = ref.dims(cfg)
    assert [i for i, a in enumerate(d["attn"]) if a] == [2, 6]
    assert d["moe"] == [False, False] + [True] * 6
    assert d["held"] == (0, 32) and d["head"] == 64
    assert len(cfg["layer_types"]) == cfg["published"]["num_hidden_layers"]
    cell = harness.find(manifest["workloads"], CELL, "workload")
    assert (cell["chips"], cell["traffic"]) == (1, "reason-1k-backlog")
    row = None
    if CATALOG and os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"name": "LFM2-8B-A1B"' in line)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    assert cfg["source"] == row["source_url"]
    changed = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert changed == ["num_hidden_layers"]
    assert {k: row["config"][k] for k in cfg["reduced"]} == cfg["published"]


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
    # live tokens only: the 6 convolutions
    decoded = n["tokens"] - n["joined"]
    assert n["shortconv_state_updates"] == pytest.approx(6 * decoded,
                                                         rel=0.02)
    assert n["kv_read_pct"] == 100.0        # the CPU's masked read
    assert obs["notes"]["state_bytes"] == {
        "conv_window": 6 * 4 * 2 * 256 * 4, "kv": 2 * 4 * 256 * 2 * 128 * 4}
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
    assert metrics["compiles_in_window.lfm1k"]["value"] == 0
    assert "prefill_ms_per_join.lfm1k" in metrics
    assert "moe_experts_read_pct.lfm1k" in metrics
    # no device trace on the CPU: the trace readers find nothing and say so
    for name in ("moe_expert_roofline.lfm1k", "shortconv_roofline.lfm1k",
                 "decode_step_roofline.lfm1k"):
        assert name not in metrics
