"""``minicpm-sala-serve``: the counts of ``work/minicpm_sala.py`` against
hand counts, the traffic file's promises, the configuration against the
catalog's row, and ``correct`` at rehearsal size (the files' ``rehearsal``
overrides, on the CPU): a sound run is correct; the control and each
planted fault is not."""

import argparse
import itertools
import json
import os
import subprocess
import sys
import time

import pytest

from benchmarks import correct, harness, traffic_gen
from benchmarks.reference import minicpm_sala as ref
from benchmarks.work import minicpm_sala as work

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "minicpm-sala-serve-doc16k"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _cfg():
    return harness.load_json(HERE, "configs", "minicpm-sala-serve.json")


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
    e, f, v = 4096, 16384, 73448
    lightning = 5 * e * e + 3 * e * f
    sparse = 3 * e * e + 2 * e * 256 + 3 * e * f
    assert work.layer_params(cfg, "lightning-attn") == lightning
    assert work.layer_params(cfg, "minicpm4") == sparse
    assert lightning == pytest.approx(285.2e6, rel=1e-3)
    assert sparse == pytest.approx(253.7e6, rel=1e-3)
    assert work.vocabulary_params(cfg) == 2 * v * e
    assert 2 * v * e == pytest.approx(601.7e6, rel=1e-3)
    assert work.block_matmul_params(cfg) == 9 * lightning + 3 * sparse
    assert ref.parameter_count(cfg, matrices_only=True) == (
        9 * lightning + 3 * sparse + 2 * v * e)
    # the gains: two norms a layer and the final one, q and k (and o)
    gains = 25 * e + 9 * 3 * 128 + 3 * 2 * 128
    assert ref.parameter_count(cfg) == (9 * lightning + 3 * sparse
                                        + 2 * v * e + gains)
    # bfloat16: 7.86 GB, as the deployment states
    assert 2 * ref.parameter_count(cfg, matrices_only=True) == pytest.approx(
        7.86e9, rel=2e-3)


def test_decode_step_bytes_and_flops_by_hand():
    cfg = _cfg()
    contexts = [20000] * 16
    lane = 2 * 128 * 2                       # a position's K (or V), bytes
    attended = 64 + 2048 + 64 * 64           # 6,208
    assert work.attended_positions(cfg, 20000) == attended
    assert work.attended_positions(cfg, 8192) == 8192       # dense
    assert work.attended_positions(cfg, 8300) == attended    # 97 candidates
    state = 16 * 32 * 128 * 128 * 4
    assert work.linear_state_step_bytes(cfg, 16) == (
        2 * state + 3 * 16 * 32 * 128 * 4)
    assert work.sparse_attn_step_bytes(cfg, contexts) == 16 * (
        (20000 // 16) * 2 * 128 * 4 + 2 * attended * lane)
    weights = (work.block_matmul_params(cfg) + 4096 * 73448) * 2
    assert weights == pytest.approx(7.26e9, rel=2e-3)
    assert work.decode_step_bytes(cfg, contexts) == (
        weights + 9 * work.linear_state_step_bytes(cfg, 16)
        + 3 * work.sparse_attn_step_bytes(cfg, contexts))
    # a token: every matrix and the head twice over, nine states (4 d^2 a
    # head), three selections (2 d a compressed key a head) and three
    # attentions (4 d a position a head)
    assert work.decode_token_flops(cfg, 20000) == (
        2.0 * (work.block_matmul_params(cfg) + 4096 * 73448)
        + 9 * 4.0 * 32 * 128 * 128
        + 3 * (4.0 * 128 * 32 * attended + 2.0 * 128 * 32 * 1250))


def test_configuration_holds_the_catalogs_numbers():
    cfg = _cfg()
    row = None
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(json.loads(line) for line in f
                       if '"name": "MiniCPM-SALA"' in line)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    assert cfg["source"] == row["source_url"]
    differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differs == ["num_hidden_layers"] == cfg["reduced"]
    manifest = harness.load_manifest()
    entry = harness.find(manifest["configs"], "minicpm-sala-serve", "config")
    assert entry["reduced"] == cfg["reduced"]
    served = [cfg["mixer_types"][i] for i in cfg["layers_served"]]
    assert served == (["minicpm4"] + ["lightning-attn"] * 6
                      + ["minicpm4"] * 2 + ["lightning-attn"] * 3)
    assert cfg["layers_served"] == list(range(9, 21))


# --- traffic -----------------------------------------------------------------

def test_traffic_keeps_its_promises():
    cfg = _cfg()
    mix = traffic_gen.load("doc-16k-decode")
    n = mix["arrival"]["clients"]
    assert n == cfg["serving"]["max_batch"] == mix["cycle"]
    a = list(itertools.islice(traffic_gen.requests(mix, 73448, 2 ** 31 + 7),
                              n))
    b = list(itertools.islice(traffic_gen.requests(mix, 73448, 11), n))
    lens = sorted(len(r.prompt) for r in a)
    assert lens == sorted(len(r.prompt) for r in b)
    # every context is beyond dense_len from its first decode step on
    assert lens[0] >= 12288 > cfg["sparse_config"]["dense_len"]
    assert lens[-1] <= 24576
    assert lens[n // 2] == pytest.approx(16384, rel=0.06)
    # no answer can end inside warm-up and window: 4,096 tokens at the
    # 110 tokens/s a row the HBM peak allows (7.26 GB a step) are 37 s
    assert {r.max_new for r in a} == {4096}
    assert 4096 / (819e9 / 7.26e9) > 30 + 2
    assert max(len(r.prompt) + r.max_new for r in a) <= cfg["serving"][
        "max_len"]
    assert max(r.prompt[i] for r in a for i in (0, -1)) < 73448


# --- correct, at rehearsal size ---------------------------------------------

@pytest.fixture(scope="module")
def driver():
    from benchmarks.drivers import serve_sessions

    return serve_sessions


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
    assert obs["failed"] == 0 and obs["attempted"] == 4
    assert obs["counters"]["compiles"] == 0
    assert obs["notes"]["answers_ended_in_window"] == 0
    assert obs["compared"]["rows_short_of_window"]["value"] == 0
    # the selection was live on every query of the window
    assert obs["counters"]["sparse_dense_fallback_queries"] == 0
    assert 0 < obs["counters"]["sparse_attended_pct"] < 60
    assert obs["counters"]["recurrent_state_updates"] > 0
    assert obs["notes"]["checked_tokens_of_the_window"] > 100
    line = harness.result_line(ctx, obs)
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}


@pytest.mark.parametrize("fault", ["control", "no_decay", "no_topk",
                                   "dense_attention"])
def test_reference_with_a_fault_is_not_correct(driver, sound, fault):
    """The reference computed in float8, with the decay left out, with
    the top-k dropped (window and first block only) or with dense
    attention in the selection's place, put in the program's place."""
    ctx, obs = sound
    rows = driver.sample(obs["served"], ctx.args.seed, 2)
    checked = driver.check(ctx, obs["weights"], rows,
                           control=fault == "control",
                           faults=() if fault == "control" else (fault,))
    limits = ctx.cell_file["limits"]
    short = {"rows_short_of_window": 0.0}
    ok, _ = correct.judge({**checked["numbers"], **short}, limits)
    assert ok, checked
    ok, compared = correct.judge({**checked[fault], **short}, limits)
    assert not ok, compared
    assert compared["served_logit_gap_mean"]["value"] > \
        compared["served_logit_gap_mean"]["limit"]
    # the widest gap is read too, and not compared (PERF.md section 2)
    assert checked[fault]["served_logit_gap"] >= \
        checked[fault]["served_logit_gap_mean"]


def test_a_released_rows_state_carried_into_its_next_tenant(driver,
                                                            monkeypatch):
    """A join that adds to the row's old recurrent state instead of
    overwriting it: rows turn over here (short answers), and the second
    tenants' tokens fall away from the reference."""
    from deeplearning4j_tpu.conf.layers_hybrid import LightningAttentionLayer

    def carried(self, cache, block, rows, length):
        old = cache["state"]
        return {"state": old.at[rows].add(block["state"], mode="drop")}

    _clear()
    ctx = _ctx()
    ctx.traffic = dict(ctx.traffic, output_tokens={
        "distribution": "constant", "value": 24, "min": 24, "max": 24})
    monkeypatch.setattr(LightningAttentionLayer, "cache_join", carried)
    obs = driver.measure(ctx, ctx.args.seed, 1.5, False)
    _clear()
    assert obs["notes"]["answers_ended_in_window"] > 0
    rows = [(p, o, 0) for p, o in obs["ended"][-4:]]
    checked = driver.check(ctx, obs["weights"], rows)
    ok, compared = correct.judge(
        {**checked["numbers"], "rows_short_of_window": 0.0},
        ctx.cell_file["limits"])
    assert not ok, compared


def test_a_row_that_stalls_is_short_of_the_window(driver, monkeypatch):
    """Every third decode window runs and its tokens are thrown away
    where the engine would account them: the rows hold fewer than K a
    whole decode window."""
    from deeplearning4j_tpu.parallel.generation import GenerationEngine

    real = GenerationEngine._decode_window
    calls = {"n": 0}

    def stalled(self, parent):
        calls["n"] += 1
        if calls["n"] % 3:
            return real(self, parent)
        self._state, *_ = self._dec.decode_fn(
            self._S, self.config.fused_steps)(self._net_params(), self._state)

    _clear()
    monkeypatch.setattr(GenerationEngine, "_decode_window", stalled)
    obs = driver.run(_ctx())
    _clear()
    assert not obs["correct"]
    assert obs["compared"]["rows_short_of_window"]["value"] == 4


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
    assert line["metrics"]["compiles_in_window.doc16k"]["value"] == 0
    assert line["metrics"]["sparse_attended_pct.doc16k"]["value"] < 60
