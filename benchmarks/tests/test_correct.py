"""``correct`` at a size a test run can hold (the files' ``rehearsal``
overrides, on the CPU): a sound run is correct; the control (the reference
in the configuration's ``control_dtype`` put in the program's place) is
not; and a run whose timed path is broken underneath is not, once for each
fault a cell can have. These drive the driver's own ``run`` and skip only
the harness's look for a chip.

The limits of this size are the cells' ``rehearsal`` limits: the small
batch makes BatchNorm ill-conditioned (float32 rounding alone moves the
second step's loss by percents here), so only the median leaf's gradient
and change and the first step's BatchNorm statistics separate sound from
broken here.
The cells' own limits were read on the chip at the cells' own size
(PERF.md)."""

import argparse
import time

import pytest

from benchmarks import correct, harness


def _ctx(cell, seconds=1.5, seed=2_147_483_659, traffic=None):
    """``traffic`` puts another mix under the cell (the open loop kept
    for a later cell runs through the same driver)."""
    from benchmarks import traffic_gen

    manifest = harness.load_manifest()
    args = argparse.Namespace(workload=cell, seed=seed, seconds=seconds,
                              trace=0, rehearsal=True)
    import jax

    d = jax.devices()[0]
    ctx = harness.Context(
        manifest, harness.find(manifest["workloads"], cell, "workload"),
        args, {"platform": d.platform, "kind": d.device_kind, "count": 1},
        time.monotonic())
    if traffic is not None:
        ctx.traffic = traffic_gen.load(traffic, rehearsal=True)
    return ctx


def _clear():
    from deeplearning4j_tpu.optimize import aot_cache

    aot_cache.clear()


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------

TRAIN = "resnet50-train-b256"


@pytest.fixture(scope="module")
def train_driver():
    from benchmarks.drivers import train_fit

    return train_fit


def test_train_sound_run_is_correct(train_driver):
    _clear()
    obs = train_driver.run(_ctx(TRAIN))
    assert obs["correct"], obs["compared"]
    assert obs["attempted"] > 0
    assert obs["counters"]["compiles"] == 0
    line = harness.result_line(_ctx(TRAIN), obs)
    assert line["correct"] is False and line["rehearsal"] is True
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"train_images_per_s", "setup_s"}


def test_train_control_is_not_correct(train_driver):
    """The reference computed in float8 (the precision below the
    configuration's bfloat16) fails BatchNorm's statistics of the first
    step, the one number of first order in the forward pass's rounding."""
    _clear()
    ctx = _ctx(TRAIN)
    ref = ctx.module("reference")
    from benchmarks import traffic_gen

    weights = ref.init_weights(ctx.config, ctx.args.seed)
    batches = traffic_gen.image_batches(ctx.traffic, ctx.config, 1,
                                        ctx.args.seed)
    good = train_driver.follow(ctx, weights, batches, 3)
    low = train_driver.follow(
        ctx, weights, batches, 3,
        q=ref.lower_precision(ctx.config["control_dtype"]))
    ok, compared = correct.judge(correct.training_numbers(low, good),
                                 ctx.cell_file["limits"])
    assert not ok
    assert compared["bn_state_gap_median_leaf"]["value"] > \
        compared["bn_state_gap_median_leaf"]["limit"]


def _break_fit(monkeypatch, fault):
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    original = ComputationGraph._fit_batch_async

    def broken(self, ds):
        return fault(self, ds, original)

    monkeypatch.setattr(ComputationGraph, "_fit_batch_async", broken)


def test_train_step_that_returns_its_state_unchanged(train_driver,
                                                     monkeypatch):
    import jax
    import jax.numpy as jnp

    def fault(net, ds, original):
        keep = jax.tree_util.tree_map(
            jnp.copy, (net.params, net.state, net.opt_state))
        loss = original(net, ds)
        net.params, net.state, net.opt_state = keep
        return loss

    _clear()
    _break_fit(monkeypatch, fault)
    obs = train_driver.run(_ctx(TRAIN))
    assert not obs["correct"]
    # the listener reads inside the step, before the fault puts the old
    # state back: it sees one step's change where the reference has three
    assert obs["compared"]["change_norm_gap_median_leaf"]["value"] > 0.5


def test_train_half_of_the_batch_left_out(train_driver, monkeypatch):
    from deeplearning4j_tpu.datasets.dataset import DataSet

    def fault(net, ds, original):
        half = ds.features.shape[0] // 2
        return original(net, DataSet(ds.features[:half], ds.labels[:half]))

    _clear()
    _break_fit(monkeypatch, fault)
    obs = train_driver.run(_ctx(TRAIN))
    assert not obs["correct"]
    assert obs["compared"]["grad_norm_gap_median_leaf"]["value"] > \
        obs["compared"]["grad_norm_gap_median_leaf"]["limit"]


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

SERVE = "gpt2-large-serve-backlog"
# the cell's own closed loop, and the open loop kept for a later cell
MIXES = [None, "chat-1k-steady"]


@pytest.fixture(scope="module")
def serve_driver():
    from benchmarks.drivers import serve_generation

    return serve_generation


@pytest.mark.parametrize("mix", MIXES)
def test_serve_sound_run_is_correct(serve_driver, mix):
    _clear()
    ctx = _ctx(SERVE, traffic=mix)
    obs = serve_driver.run(ctx)
    assert obs["correct"], obs["compared"]
    assert obs["failed"] == 0 and obs["attempted"] > 0
    assert obs["counters"]["compiles"] == 0
    assert obs["compared"]["answers_of_wrong_length"]["value"] == 0
    if mix is not None:     # the open loop: tails from the due times
        assert obs["end_to_end"]["serve_ttft_p95_ms"] > 0
        assert obs["end_to_end"]["serve_tpot_p95_ms"] > 0
        assert min(obs["late"]) >= 0
    line = harness.result_line(ctx, obs)
    assert list(line)[-1] == "compared"
    assert "setup_s" in line["metrics"]


def test_serve_control_is_not_correct(serve_driver):
    """The tokens the reference puts first when the operands of its
    matrix products are float8 lie below the float32 reference's best
    somewhere in a few hundred positions."""
    _clear()
    ctx = _ctx(SERVE, seconds=2.0)
    ctx.cell_file["checked_requests"] = 40
    obs = serve_driver.measure(ctx, ctx.args.seed, 2.0, False)
    checked = serve_driver.check(ctx, obs["weights"], obs["served"],
                                 control=True)
    assert checked["checked_tokens"] > 200
    lengths = {"answers_of_wrong_length": float(obs["wrong_length"])}
    ok, _ = correct.judge({**checked["numbers"], **lengths},
                          ctx.cell_file["limits"])
    assert ok
    ok, compared = correct.judge({**checked["control"], **lengths},
                                 ctx.cell_file["limits"])
    assert not ok, compared
    assert compared["served_logit_gap"]["value"] > \
        compared["served_logit_gap"]["limit"]


@pytest.mark.parametrize("mix", MIXES)
def test_serve_token_altered_where_it_is_produced(serve_driver, mix,
                                                  monkeypatch):
    from deeplearning4j_tpu.nn import decoding

    original = decoding._sample_tokens

    def altered(logits, step_keys, temps):
        tok = original(logits, step_keys, temps)
        return (tok + 1) % logits.shape[-1]

    _clear()
    monkeypatch.setattr(decoding, "_sample_tokens", altered)
    obs = serve_driver.run(_ctx(SERVE, traffic=mix))
    _clear()
    assert not obs["correct"]
    assert obs["compared"]["served_logit_gap"]["value"] > \
        obs["compared"]["served_logit_gap"]["limit"]


def test_serve_answer_cut_short_where_it_is_produced(serve_driver,
                                                     monkeypatch):
    """An engine that ends every answer one token early finishes sooner
    and reads faster: the exact count of answers of the wrong length
    fails it."""
    from deeplearning4j_tpu.parallel.generation import GenerationEngine

    original = GenerationEngine.submit

    def short(self, prompt, max_new_tokens=None, **kw):
        return original(self, prompt,
                        max_new_tokens=max(1, max_new_tokens - 1), **kw)

    _clear()
    monkeypatch.setattr(GenerationEngine, "submit", short)
    obs = serve_driver.run(_ctx(SERVE))
    _clear()
    assert not obs["correct"]
    assert obs["compared"]["answers_of_wrong_length"]["value"] > 0
