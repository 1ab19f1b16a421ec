"""Iteration-level continuous batching for autoregressive decode
(``nn.decoding`` + ``parallel.generation``).

The invariants pinned here are the acceptance criteria of the decode
subsystem: greedy generation through the KV cache matches the full
no-cache forward exactly; continuous scheduling (token-granularity
join/leave, fused-K windows, bucket growth) NEVER changes any
sequence's tokens relative to the sequential one-request-at-a-time
reference; warmup makes mixed-length traffic zero-recompile; finished
sequences free their rows immediately; admission control (400/503/
deadline/breaker-shed) matches the serving batcher's semantics; and the
program linter's donation audit proves every decode/prefill executable
writes the KV cache in place.

All cache assertions read COUNTER DELTAS — the AOT executable cache and
the telemetry registry are process-global and shared across the session.
"""

import functools
import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.nn.decoding import (
    TransformerDecoder,
    bucket_for,
    pow2_ladder,
)
from deeplearning4j_tpu.optimize import aot_cache
from deeplearning4j_tpu.parallel.batcher import (
    BadRequestError,
    DeadlineExpiredError,
    ServerOverloadedError,
)
from deeplearning4j_tpu.parallel.generation import (
    GenerationConfig,
    GenerationEngine,
)
from deeplearning4j_tpu.resilience.breaker import (
    CircuitBreaker,
    CircuitOpenError,
)
from deeplearning4j_tpu.resilience.faults import FaultPlan
from deeplearning4j_tpu.telemetry import REGISTRY
from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

pytestmark = pytest.mark.decode

VOCAB = 32
MAX_LEN = 32
MAX_BATCH = 4
K = 2


@functools.lru_cache(maxsize=None)
def _decoder() -> TransformerDecoder:
    """One warmed decoder for the whole module (executables are shared
    through the process-global AOT cache anyway; warming once keeps the
    suite fast)."""
    m = TransformerEncoder(vocab_size=VOCAB, embed_dim=16, n_heads=2,
                           n_layers=2, max_len=MAX_LEN, causal=True,
                           lm_head=True, seed=7)
    dec = m.decoder(max_batch=MAX_BATCH, kv_bucket_min=16,
                    prompt_bucket_min=4)
    dec.warm_all(fused_steps=(1, K))
    return dec


def _engine(**over):
    cfg = dict(max_batch=MAX_BATCH, fused_steps=K, kv_bucket_min=16,
               prompt_bucket_min=4)
    cfg.update(over)
    return GenerationEngine(_decoder(), GenerationConfig(**cfg))


# --- bucket math -----------------------------------------------------------

def test_pow2_ladder_and_bucket_for():
    assert pow2_ladder(8, 64) == [8, 16, 32, 64]
    assert pow2_ladder(32, 48) == [32, 48]  # capped at (and including) hi
    assert pow2_ladder(64, 32) == [32]
    assert bucket_for(9, [8, 16, 32]) == 16
    assert bucket_for(16, [8, 16, 32]) == 16
    with pytest.raises(ValueError):
        bucket_for(33, [8, 16, 32])


# --- KV-cache math against the no-cache oracle -----------------------------

def test_greedy_generate_matches_full_forward_oracle():
    """The KV-cached prefill+decode path must produce exactly the tokens
    the full no-cache forward picks: grow the sequence one token at a
    time through ``net.output`` and argmax the last position."""
    dec = _decoder()
    prompt = [3, 9, 1, 14, 2]
    out = dec.generate(prompt, max_new=6)
    seq = list(prompt)
    ref = []
    for _ in range(6):
        y = np.asarray(dec.net.output(np.asarray([seq], np.int32)))
        ref.append(int(np.argmax(y[0, len(seq) - 1])))
        seq.append(ref[-1])
    assert out == ref


def test_fused_k1_vs_k4_token_identical():
    dec = _decoder()
    prompt = [5, 6, 7, 8, 2, 11]
    a = dec.generate(prompt, max_new=9, fused_steps=1)
    b = dec.generate(prompt, max_new=9, fused_steps=K)
    assert a == b


def test_generate_stops_at_eos():
    dec = _decoder()
    ref = dec.generate([4, 8, 15], max_new=8)
    eos = ref[2]
    out = dec.generate([4, 8, 15], max_new=8, eos_id=eos)
    assert out == ref[:ref.index(eos) + 1]
    assert out[-1] == eos


def test_temperature_sampling_deterministic_per_seed():
    dec = _decoder()
    a = dec.generate([1, 2, 3], max_new=8, temperature=0.9, seed=123)
    b = dec.generate([1, 2, 3], max_new=8, temperature=0.9, seed=123)
    assert a == b  # same seed replays the same per-request stream
    assert all(0 <= t < VOCAB for t in a)
    greedy = dec.generate([1, 2, 3], max_new=8)
    assert len(a) == len(greedy) == 8


def test_unsupported_graphs_rejected():
    """Graphs the decode path cannot serve faithfully refuse at
    construction: classifier heads (pooling), MoE FFNs (cross-row
    routing breaks the row-independence the bit-identity pin rests on),
    and non-causal attention."""
    with pytest.raises(ValueError, match="lm_head"):
        TransformerEncoder(vocab_size=16, causal=True).decoder()
    moe = TransformerEncoder(vocab_size=16, embed_dim=8, n_heads=2,
                             n_layers=1, max_len=16, causal=True,
                             lm_head=True, moe_experts=2)
    with pytest.raises(ValueError, match="MoELayer"):
        moe.decoder(max_batch=2)
    with pytest.raises(ValueError, match="causal"):
        TransformerEncoder(vocab_size=16, lm_head=True)


def test_request_validation():
    dec = _decoder()
    with pytest.raises(ValueError, match="at least one token"):
        dec.validate_request([], 4)
    with pytest.raises(ValueError, match="token ids"):
        dec.validate_request([VOCAB], 4)
    with pytest.raises(ValueError, match="exceeds max_len"):
        dec.validate_request([1] * 30, 8)


# --- continuous scheduling == sequential reference -------------------------

def test_continuous_engine_token_identical_to_sequential():
    """Five requests churn through four cache rows (join/leave mid-
    flight, mixed prompt/output lengths) and every sequence's greedy
    tokens equal the sequential one-at-a-time reference exactly."""
    dec = _decoder()
    prompts = [[3, 9, 1], [5, 6, 7, 8, 2, 11], [1], [14, 13, 12, 2],
               [9, 9, 2, 3, 4, 5, 6, 1]]
    mns = [6, 9, 4, 12, 5]
    refs = [dec.generate(p, mn) for p, mn in zip(prompts, mns)]
    with _engine() as eng:
        eng.warmup()
        reqs = [eng.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, mns)]
        outs = [eng.result(r) for r in reqs]
    assert outs == refs


def test_sampled_engine_matches_sequential_reference():
    """Per-sequence PRNG keys make even temperature sampling immune to
    co-tenant churn: engine output equals the sequential reference for
    the same (seed, temperature)."""
    dec = _decoder()
    ref = dec.generate([2, 4, 6], max_new=7, temperature=0.8, seed=42)
    with _engine() as eng:
        out = eng.generate([2, 4, 6], max_new_tokens=7, temperature=0.8,
                           seed=42)
    assert out == ref


def test_late_join_completes_before_earlier_longer_sequence():
    """Token-granularity admission: a short request submitted AFTER a
    long one is already decoding joins the running batch at the next
    iteration and finishes first — no request-granularity drain wait.
    The loop runs a window ahead, so the long request's launches are: its
    prefill, the first window and the one ahead of it (the first is then
    read: three tokens), the third window (the second is read: five
    tokens), the fourth. That FIFTH launch is held open by a delay fault
    with five tokens accounted, and the test reads the handle's token
    count and submits under the engine's own condition, so no accounting
    runs between the reading and the enqueue: nothing here depends on
    how fast a toy window is."""
    dec = _decoder()
    long_ref = dec.generate([7, 3], max_new=24)
    short_ref = dec.generate([9, 9, 2], max_new=3)
    plan = FaultPlan().inject("decode.launch", on_calls=[5], action="delay",
                              delay_s=0.5)
    with plan.armed(), _engine() as eng:
        long_req = eng.submit([7, 3], max_new_tokens=24)
        deadline = time.monotonic() + 30
        with eng._cond:
            # wait until the long request is genuinely mid-generation
            while len(long_req.out) < 4:
                assert time.monotonic() < deadline, "long request never started"
                eng._cond.wait(0.001)
            assert long_req.t_done is None and len(long_req.out) == 5
            short_req = eng.submit([9, 9, 2], max_new_tokens=3)
        assert eng.result(short_req) == short_ref
        assert eng.result(long_req) == long_ref
    assert short_req.t_done < long_req.t_done, \
        "late-joining short request should retire first"


def test_eos_retirement_frees_rows():
    dec = _decoder()
    ref = dec.generate([4, 8, 15], max_new=8)
    eos = ref[2]
    with _engine() as eng:
        before = eng.stats()
        out = eng.generate([4, 8, 15], max_new_tokens=8, eos_id=eos)
        after = eng.stats()
    assert out == ref[:ref.index(eos) + 1]
    assert after["rows_in_use"] == 0
    assert after["retired_total"] == before["retired_total"] + 1


# --- zero-recompile invariant ----------------------------------------------

def test_warmup_then_mixed_traffic_zero_recompiles():
    """After ``warmup()`` a mixed sweep — short and long prompts, short
    and long outputs, KV bucket growth 16→32, join groups of 1..4 —
    never misses the AOT cache."""
    with _engine() as eng:
        eng.warmup()
        miss0 = aot_cache.stats()["misses"]
        reqs = [eng.submit([1 + i % 7] * (1 + 3 * i), max_new_tokens=3 + i)
                for i in range(4)]
        for r in reqs:
            eng.result(r)
        # long prompt: prompt bucket 32 forces a KV grow hop mid-service
        eng.generate([2] * 20, max_new_tokens=8)
        assert eng.stats()["kv_bucket"] == 32
        assert aot_cache.stats()["misses"] == miss0, \
            "mixed-length traffic recompiled after warmup"


def test_warmup_is_idempotent():
    eng = _engine()
    try:
        assert eng.warmup()["compiled"] == 0  # module decoder pre-warmed
    finally:
        eng.close()


# --- admission control / resilience ----------------------------------------

def test_bad_request_rejected_at_submit():
    with _engine() as eng:
        with pytest.raises(BadRequestError):
            eng.submit([], max_new_tokens=4)
        with pytest.raises(BadRequestError):
            eng.submit([VOCAB + 1], max_new_tokens=4)
        with pytest.raises(BadRequestError):
            eng.submit([1] * 31, max_new_tokens=8)
        with pytest.raises(BadRequestError):
            eng.submit([1], max_new_tokens=4, temperature=-1.0)
        with pytest.raises(BadRequestError):
            eng.submit([1], max_new_tokens=4, eos_id=VOCAB + 5)


def test_queue_full_rejects_with_503_semantics():
    eng = _engine(max_queue=2)
    eng._ensure_thread = lambda: None  # keep requests queued
    try:
        eng.submit([1], max_new_tokens=2)
        eng.submit([2], max_new_tokens=2)
        with pytest.raises(ServerOverloadedError):
            eng.submit([3], max_new_tokens=2)
    finally:
        eng.close()


def test_expired_deadline_fails_queued_request():
    eng = _engine()
    eng._ensure_thread = lambda: None
    try:
        req = eng.submit([1, 2], max_new_tokens=4, timeout_ms=5)
        time.sleep(0.02)
        eng._expire_queued_locked(time.monotonic())
        with pytest.raises(DeadlineExpiredError):
            eng.result(req)
    finally:
        eng.close()


def test_deadline_mid_generation_frees_row():
    """A deadline that expires while the sequence is decoding fails the
    request at the next read-back and releases its cache row (the
    in-graph ``gen_release`` mask keeps the dead row a no-op). A window
    is in flight then, with the dead row in it: ``gen_release`` lands
    behind it, what it emitted for the row is dropped (the handle holds
    a prefix of the reference and never grows again), and the row
    serves the next request as a clean one."""
    dec = _decoder()
    ref = dec.generate([1, 2, 3], max_new=28)
    plan = FaultPlan(seed=3)
    plan.inject("decode.launch", probability=1.0, action="delay",
                delay_s=0.02)
    with _engine() as eng:
        with plan.armed():
            req = eng.submit([1, 2, 3], max_new_tokens=28, timeout_ms=60)
            with pytest.raises(DeadlineExpiredError):
                eng.result(req)
        held = list(req.out)
        assert 0 < len(held) < 28 and held == ref[:len(held)]
        deadline = time.monotonic() + 5
        while eng.stats()["rows_in_use"] and time.monotonic() < deadline:
            time.sleep(0.005)
        st = eng.stats()
        assert st["rows_in_use"] == 0
        # the window in flight at the expiry was read and dropped
        assert st["windows_ahead_total"] >= 1
        assert st["tokens_total"] == len(held)
        assert eng.generate([1, 2, 3], max_new_tokens=28) == ref
        assert req.out == held


# --- the loop runs one window ahead of what it has read ---------------------

def _wait_for(what, seconds=30.0):
    deadline = time.monotonic() + seconds
    while not what():
        assert time.monotonic() < deadline, "never happened"
        time.sleep(0.002)


def _held_at_launch(n_call, delay_s=0.6):
    """A plan that holds the ``n_call``-th launch of the engine (prefills
    and decode windows count alike) open for ``delay_s``."""
    return FaultPlan().inject("decode.launch", on_calls=[n_call],
                              action="delay", delay_s=delay_s)


def test_launch_precedes_the_read_of_the_window_before():
    """One request of 24 tokens at K = 2. Its launches: the prefill (one
    token), windows 1 and 2 (the first iteration launches two, then reads
    window 1: three tokens), window 3 (window 2 is read: five tokens),
    window 4, held open by a delay fault. While it is held the host has
    accounted windows 1 and 2 only, though window 3 was launched an
    iteration ago: it is in flight, unread, and window 4 is being
    launched onto the state it returns. When the launch returns, the
    read-back in the same span is window 3's, and window 4 is read an
    iteration later: the tokens are the reference's."""
    from deeplearning4j_tpu import telemetry

    dec = _decoder()
    ref = dec.generate([7, 3], max_new=24)
    telemetry.spans.reset()
    plan = _held_at_launch(5)
    with plan.armed(), _engine() as eng:
        req = eng.submit([7, 3], max_new_tokens=24)
        _wait_for(lambda: plan.fired("decode.launch"))
        with eng._cond:
            seen = (len(req.out), eng._windows_total, len(eng._flight),
                    eng._windows_ahead_total)
        assert eng.result(req) == ref
        st = eng.stats()
    # five tokens accounted, four windows launched (the fourth is in the
    # fault), two of them in flight: the one before it was not read first
    assert seen == (5, 4, 2, 3)
    # 23 tokens after the prefill's: 12 windows, all but the first ahead
    assert (st["windows_total"], st["windows_ahead_total"],
            st["windows_empty_total"]) == (12, 11, 0)
    evs = telemetry.events()
    decodes = sorted((e for e in evs if e["name"] == "gen.decode"),
                     key=lambda e: e["start_ns"])
    held = [e for e in decodes if e["duration_ns"] >= 0.5e9]
    assert len(held) == 1 and decodes.index(held[0]) == 2
    for e in decodes[1:-1]:     # the steady state: launch, then read
        kids = {c["name"]: c for c in evs if c["parent_id"] == e["id"]}
        launch, read = kids["gen.decode.launch"], kids["gen.decode.readback"]
        assert launch["start_ns"] + launch["duration_ns"] <= read["start_ns"]
    # each span accounts one window of K tokens: the held one the window
    # launched before it
    assert [e["attrs"]["emitted"] for e in decodes] == [K] * 11 + [1]


def test_a_row_that_ends_by_length_is_rejoined_with_no_window_between():
    """Four rows, five requests, all enqueued before the loop admits.
    The first ends by length in the second window (5 tokens at K = 2); the
    host knows that when the second window is launched, so the fifth
    request's join goes behind it, before it is read, into the same row,
    and the third window decodes four rows again: the batch never shows
    the hole."""
    from deeplearning4j_tpu import telemetry

    dec = _decoder()
    prompts = [[3, 9, 1], [5, 6, 7, 8], [1, 2], [14, 13, 12, 2], [9, 9, 2]]
    mns = [5, 16, 16, 16, 6]
    refs = [dec.generate(p, mn) for p, mn in zip(prompts, mns)]
    telemetry.spans.reset()
    with _engine() as eng:
        with eng._cond:             # the loop cannot admit before all five
            reqs = [eng.submit(p, max_new_tokens=mn)
                    for p, mn in zip(prompts, mns)]
        assert [eng.result(r) for r in reqs] == refs
        st = eng.stats()
    assert reqs[4].row == reqs[0].row
    assert st["joins_ahead_total"] == 1 and st["windows_empty_total"] == 0
    decodes = sorted((e for e in telemetry.events()
                      if e["name"] == "gen.decode"),
                     key=lambda e: e["start_ns"])
    # windows 1 and 2 hold the first request, 3 to 5 the fifth in its row
    # (its six tokens: the prefill's, then 2 + 2 + 1)
    assert [e["attrs"]["rows"] for e in decodes[:5]] == [MAX_BATCH] * 5
    assert [e["attrs"]["emitted"] for e in decodes[:5]] == [8, 8, 8, 8, 7]
    # the old request's last tokens came from the window the join went
    # behind; the new one's first token was read after that window
    assert reqs[0].t_done <= reqs[4].t_first


def test_stop_token_with_a_window_ahead_emits_the_reference_and_no_more():
    """A row that ends EARLY is seen at the read-back, one window late:
    the window already in flight carries the dead row, which emits
    nothing (masked in-graph). Alone in the batch that is one empty
    window, the price the class docstring names; the answer is the
    reference's up to the stop token, and never grows."""
    dec = _decoder()
    ref = dec.generate([4, 8, 15], max_new=20)
    eos = ref[2]
    want = ref[:ref.index(eos) + 1]
    assert len(want) <= 3
    with _engine() as eng:
        req = eng.submit([4, 8, 15], max_new_tokens=20, eos_id=eos)
        assert eng.result(req) == want
        _wait_for(lambda: not eng._flight)
        st = eng.stats()
        assert req.out == want
    assert st["rows_in_use"] == 0 and st["retired_total"] == 1
    assert st["tokens_total"] == len(want)
    if len(want) > 1:   # it ended inside window 1, with window 2 launched
        assert (st["windows_total"], st["windows_empty_total"]) == (2, 1)


def test_raise_at_a_launch_with_a_window_in_flight_fails_each_request_once():
    """The fourth launch (window 3, with window 2 in flight) raises: the
    state and the window in flight on it are discarded, both requests fail
    with that error, each counted once, and the engine serves again."""
    dec = _decoder()
    ref = dec.generate([2, 4, 6], max_new=9)
    err_key = 'dl4j_decode_requests_total{status="error"}'
    before = REGISTRY.snapshot(run_collectors=False).get(err_key, 0)
    plan = FaultPlan().inject("decode.launch", on_calls=[4], action="raise")
    eng = GenerationEngine(
        _decoder(), GenerationConfig(max_batch=MAX_BATCH, fused_steps=K,
                                     kv_bucket_min=16, prompt_bucket_min=4),
        breaker=None, retry=None)
    try:
        with plan.armed():
            with eng._cond:
                reqs = [eng.submit([1, 2], max_new_tokens=20),
                        eng.submit([3, 4, 5], max_new_tokens=20)]
            for r in reqs:
                with pytest.raises(Exception, match="injected"):
                    eng.result(r)
            assert all(len(r.out) == 3 for r in reqs)   # window 1 was read
            assert not eng._flight and not eng._unread
            assert eng.stats()["rows_in_use"] == 0
            assert eng.generate([2, 4, 6], max_new_tokens=9) == ref
        after = REGISTRY.snapshot(run_collectors=False).get(err_key, 0)
        assert after - before == 2
    finally:
        eng.close()


def test_close_with_a_window_in_flight():
    """``close()`` while a launch is held open and another window is in
    flight: it returns, the loop's thread goes, every handle is failed
    once with the shutdown error and holds what was accounted before."""
    plan = _held_at_launch(5, delay_s=0.4)
    eng = _engine()
    with plan.armed():
        with eng._cond:
            reqs = [eng.submit([7, 3], max_new_tokens=24),
                    eng.submit([1], max_new_tokens=24)]
        _wait_for(lambda: plan.fired("decode.launch"))
        with eng._cond:
            assert len(eng._flight) == 2
        thread = eng._thread
        t0 = time.monotonic()
        eng.close()
        assert time.monotonic() - t0 < 4 and not thread.is_alive()
    for r in reqs:
        assert len(r.out) == 5
        with pytest.raises(RuntimeError, match="closed"):
            eng.result(r)
    assert not eng._flight and eng.stats()["rows_in_use"] == 0


@pytest.mark.parametrize("max_new,windows", [(1, 0), (2, 1), (3, 1), (5, 2),
                                             (6, 3)])
def test_an_engine_going_idle_launches_no_empty_window(max_new, windows):
    """The last row is certain to end in the window in flight (the host
    counts its tokens), so nothing is launched ahead of it into an empty
    batch: just the windows the answer needs at K = 2."""
    ref = _decoder().generate([5, 6, 7], max_new=max_new)
    with _engine() as eng:
        assert eng.generate([5, 6, 7], max_new_tokens=max_new) == ref
        time.sleep(0.05)
        st = eng.stats()
    assert (st["windows_total"], st["windows_empty_total"]) == (windows, 0)
    assert st["windows_ahead_total"] == max(windows - 1, 0)


def test_kv_bucket_hop_planned_from_the_upper_bound_is_never_late():
    """KV ladder [16, 32], a prompt of 12 and 18 new tokens: window j is
    planned when the host has READ windows up to j - 2 only, from the
    bound ``n + planned - 1``: it writes positions up to ``12 + 2j``, so
    the third window's plan must hop (18 > 16), before the second is
    read. A late hop would clamp a write and change the tokens."""
    from deeplearning4j_tpu import telemetry

    dec = _decoder()
    prompt = list(range(1, 13))
    ref = dec.generate(prompt, max_new=18)
    telemetry.spans.reset()
    with _engine() as eng:
        assert eng.stats()["buckets"]["kv"] == [16, 32]
        assert eng.generate(prompt, max_new_tokens=18) == ref
        assert eng.stats()["kv_bucket"] == 32
    evs = telemetry.events()
    plans = sorted((e for e in evs if e["name"] == "gen.decode.plan"
                    and e["attrs"]["rows"]), key=lambda e: e["start_ns"])
    assert [e["attrs"]["grew"] for e in plans] == (
        [False, False, True] + [False] * 6)
    decodes = sorted((e for e in evs if e["name"] == "gen.decode"),
                     key=lambda e: e["start_ns"])
    # the hop was planned in the second iteration, whose span read window 1
    assert plans[2]["parent_id"] == decodes[1]["id"]
    assert [e["attrs"]["kv_bucket"] for e in decodes] == [16, 16] + [32] * 7


def test_breaker_trips_open_and_sheds_then_recovers():
    """Persistent decode-path failure trips the circuit open (every
    in-flight request fails, like the batcher failing its batch), open
    sheds at submit with 503 semantics, and a half-open probe closes it
    once the fault clears."""
    breaker = CircuitBreaker(name="decode-test", failure_threshold=2,
                             recovery_timeout_s=0.15, success_threshold=1)
    eng = GenerationEngine(
        _decoder(), GenerationConfig(max_batch=MAX_BATCH, fused_steps=K,
                                     kv_bucket_min=16, prompt_bucket_min=4),
        breaker=breaker, retry=None)
    plan = FaultPlan(seed=11)
    plan.inject("decode.launch", probability=1.0, action="raise")
    try:
        with plan.armed():
            for _ in range(2):
                req = eng.submit([1, 2], max_new_tokens=4)
                with pytest.raises(Exception):
                    eng.result(req)
            deadline = time.monotonic() + 5
            while breaker.state != "open" and time.monotonic() < deadline:
                time.sleep(0.01)
            assert breaker.state == "open"
            with pytest.raises(CircuitOpenError):
                eng.submit([1, 2], max_new_tokens=4)
            rec = REGISTRY.snapshot(run_collectors=False)
        time.sleep(0.2)  # recovery window, fault now disarmed
        out = eng.generate([1, 2], max_new_tokens=4)  # half-open probe
        assert len(out) == 4
        assert breaker.state == "closed"
        assert rec.get('dl4j_decode_requests_total{status="shed"}', 0) >= 1
    finally:
        eng.close()


def test_close_fails_pending_requests():
    eng = _engine()
    eng._ensure_thread = lambda: None
    req = eng.submit([1], max_new_tokens=2)
    eng.close()
    with pytest.raises(RuntimeError, match="closed"):
        eng.result(req)
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit([1], max_new_tokens=2)


# --- telemetry / stats / lint ----------------------------------------------

def test_decode_telemetry_series():
    snap0 = REGISTRY.snapshot(run_collectors=False)
    with _engine() as eng:
        eng.generate([1, 2, 3, 4], max_new_tokens=6)
        snap1 = REGISTRY.snapshot(run_collectors=True)
    d_tokens = (snap1["dl4j_decode_tokens_total"]
                - snap0.get("dl4j_decode_tokens_total", 0))
    assert d_tokens >= 6
    assert "dl4j_decode_batch_occupancy" in snap1
    assert "dl4j_decode_kv_rows_in_use" in snap1
    assert snap1["dl4j_decode_token_seconds"]["count"] > 0
    assert snap1["dl4j_decode_first_token_seconds"]["count"] > 0
    ok_key = 'dl4j_decode_requests_total{status="ok"}'
    assert snap1.get(ok_key, 0) >= snap0.get(ok_key, 0) + 1
    # how often the loop ran ahead: 6 tokens at K = 2 are a prefill and
    # three windows, of which the last two were launched with one unread
    d = {name: snap1.get(f"dl4j_decode_{name}_total", 0)
         - snap0.get(f"dl4j_decode_{name}_total", 0)
         for name in ("windows", "windows_ahead", "joins_ahead",
                      "windows_empty")}
    assert d == {"windows": 3, "windows_ahead": 2, "joins_ahead": 0,
                 "windows_empty": 0}


# --- program spans of the engine (always recorded, telemetry never enabled) -

def _drive_four_clients(eng, per_client=3):
    """Four threads submit beside the loop; returns the handles."""
    handles, lock = [], threading.Lock()

    def client(i):
        for j in range(per_client):
            h = eng.submit([1 + i, 2, 3, 4 + j], max_new_tokens=5 + j)
            with lock:
                handles.append(h)
            eng.result(h)

    threads = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    return handles


@pytest.fixture
def engine_spans():
    """(events of one four-client run, the engine's stats when it ended,
    the handles): the ring records though telemetry was never enabled."""
    from deeplearning4j_tpu import telemetry

    assert not telemetry.enabled()
    telemetry.spans.reset()
    with _engine() as eng:
        handles = _drive_four_clients(eng)
        stats = eng.stats()
    return telemetry.events(), stats, handles


def test_engine_spans_names_ids_and_parents_nest_per_thread(engine_spans):
    evs, _stats, _handles = engine_spans
    gen = [e for e in evs if e["name"].startswith("gen.")]
    by_id = {e["id"]: e for e in gen}
    assert len(by_id) == len(gen), "span ids repeat"
    loop = {e["thread"] for e in gen if e["name"] == "gen.decode"}
    assert len(loop) == 1, "one decode-loop thread"
    callers = {e["thread"] for e in gen if e["name"] == "gen.submit"}
    assert len(callers) == 4 and not callers & loop
    parents_of = {
        "gen.wait": {"gen.admit"},
        "gen.grow": {"gen.prefill.stage", "gen.decode.plan"},
        **{f"gen.prefill.{c}": {"gen.prefill"}
           for c in ("stage", "launch", "readback", "account")},
        **{f"gen.decode.{c}": {"gen.decode"}
           for c in ("plan", "launch", "readback", "account", "release")},
        **{f"gen.submit.{c}": {"gen.submit"}
           for c in ("validate", "key", "prefix", "enqueue")}}
    for e in gen:
        if e["name"] in ("gen.admit", "gen.prefill", "gen.decode",
                         "gen.submit"):
            assert e["parent_id"] is None and e["depth"] == 0, e
            continue
        parent = by_id[e["parent_id"]]
        assert parent["name"] in parents_of[e["name"]], (e, parent)
        assert parent["thread"] == e["thread"]
        assert parent["start_ns"] <= e["start_ns"]
        assert (e["start_ns"] + e["duration_ns"]
                <= parent["start_ns"] + parent["duration_ns"])
        if e["thread"] in loop:   # spans of one iteration share its n
            assert e["attrs"]["n"] == parent["attrs"]["n"]
    kids = {}
    for e in gen:
        kids.setdefault(e["parent_id"], []).append(e)
    names = {i: [c["name"] for c in sorted(cs, key=lambda c: c["start_ns"])]
             for i, cs in kids.items()}
    # a decode span reads back and accounts EXACTLY ONE window, after the
    # launches it makes: none where no row could still emit (the last of
    # a burst), the window ahead in the steady state, two with nothing in
    # flight (the first of a burst); every launch was planned, and a plan
    # may decide against a launch
    decodes = sorted((e for e in gen if e["name"] == "gen.decode"),
                     key=lambda e: e["start_ns"])
    launches = []
    for e in decodes:
        mine = names[e["id"]]
        assert mine.count("gen.decode.readback") == 1, mine
        assert mine.count("gen.decode.account") == 1, mine
        n_launch = mine.count("gen.decode.launch")
        assert n_launch <= 2 and mine.count("gen.decode.plan") in (
            n_launch, n_launch + 1), mine
        assert set(mine) <= {f"gen.decode.{c}" for c in (
            "plan", "launch", "readback", "account", "release")}
        tail = mine[mine.index("gen.decode.readback"):]
        assert tail[:2] == ["gen.decode.readback", "gen.decode.account"]
        assert "gen.decode.launch" not in tail and (
            "gen.decode.plan" not in tail), mine
        launches.append(n_launch)
    assert sum(launches) == len(decodes), "every window launched is read"
    assert launches[0] == 2 and launches[-1] == 0
    assert launches.count(1) > len(launches) / 2, launches
    # a prefill is staged and launched in one span; its first tokens are
    # read in the same span with nothing in flight, else in a second span
    # of the same iteration, after that iteration's decode span
    prefills = sorted((e for e in gen if e["name"] == "gen.prefill"),
                      key=lambda e: e["start_ns"])
    whole = ["gen.prefill.stage", "gen.prefill.launch",
             "gen.prefill.readback", "gen.prefill.account"]
    pending = []
    for e in prefills:
        mine = [n for n in names[e["id"]] if n != "gen.grow"]
        if mine == whole[:2]:
            pending.append(e)
        elif mine == whole[2:]:
            first = pending.pop(0)
            assert first["attrs"]["n"] == e["attrs"]["n"]
            assert "joins" not in e["attrs"]
            between = [d for d in decodes if first["start_ns"]
                       < d["start_ns"] < e["start_ns"]]
            assert len(between) == 1 and names[between[0]["id"]].count(
                "gen.decode.launch") == 1, "read after the next launch"
        else:
            assert mine == whole, mine
    assert not pending
    assert any(names[e["id"]] == whole[2:] for e in prefills)
    for e in gen:
        if e["name"] == "gen.submit":
            assert sorted(names[e["id"]]) == [
                "gen.submit.enqueue", "gen.submit.key",
                "gen.submit.validate"]


def test_engine_spans_carry_the_counts(engine_spans):
    evs, stats, handles = engine_spans
    decode = [e for e in evs if e["name"] == "gen.decode"]
    prefill = [e for e in evs if e["name"] == "gen.prefill"]
    assert sum(e["attrs"]["emitted"] for e in decode) == (
        stats["tokens_total"] - stats["joined_total"])
    assert sum(e["attrs"].get("joins", 0) for e in prefill) == (
        stats["joined_total"]) == len(handles)
    assert all(set(e["attrs"]) == {"n", "k", "kv_bucket", "rows", "emitted"}
               and e["attrs"]["k"] == K
               and 1 <= e["attrs"]["rows"] <= MAX_BATCH for e in decode)
    assert all(e["attrs"]["kind"] == "cold" for e in prefill)
    assert len(decode) == stats["windows_total"]
    plans = [e for e in evs if e["name"] == "gen.decode.plan"]
    assert sum(e["attrs"]["rows"] > 0 for e in plans) == len(decode)
    for name in ("gen.admit", "gen.prefill.account", "gen.decode.plan",
                 "gen.decode.account", "gen.submit.enqueue"):
        waits = [e["attrs"]["lock_wait_us"] for e in evs
                 if e["name"] == name]
        assert waits and all(w >= 0 for w in waits), name
    for name in ("gen.prefill.readback", "gen.decode.readback",
                 "gen.submit.key"):
        assert all(e["attrs"]["sync"] is True for e in evs
                   if e["name"] == name)
    admits = [e for e in evs if e["name"] == "gen.admit"]
    assert sum(e["attrs"].get("joins", 0) for e in admits) == len(handles)


def _loop_thread_coverage(evs):
    """Share of the loop thread's time, from the first admit to the end
    of the last decode window, that lies inside a top-level span."""
    loop = next(e["thread"] for e in evs if e["name"] == "gen.decode")
    tops = sorted((e for e in evs if e["thread"] == loop
                   and e["parent_id"] is None), key=lambda e: e["start_ns"])
    last = max(i for i, e in enumerate(tops) if e["name"] == "gen.decode")
    tops = tops[:last + 1]
    wall = (tops[-1]["start_ns"] + tops[-1]["duration_ns"]
            - tops[0]["start_ns"])
    inside = sum(e["duration_ns"] for e in tops)
    assert inside <= wall
    return inside / wall


def test_engine_loop_thread_is_covered_by_spans(engine_spans):
    """A stretch of the loop no span covers shows in EVERY run; a loop
    thread descheduled between two spans (60 ms of toy windows under six
    workers: one lost time slice is a tenth of them) shows in one. So a
    low reading is taken again, twice at most."""
    from deeplearning4j_tpu import telemetry

    share = _loop_thread_coverage(engine_spans[0])
    for _ in range(2):
        if share >= 0.95:
            break
        telemetry.spans.reset()
        with _engine() as eng:
            _drive_four_clients(eng)
        share = _loop_thread_coverage(telemetry.events())
    assert share >= 0.95, share


def test_handle_times_are_ordered(engine_spans):
    _evs, _stats, handles = engine_spans
    assert len(handles) == 12
    for h in handles:
        assert h.error is None
        assert h.t0 <= h.t_join <= h.t_first <= h.t_done


def test_failed_handle_gets_t_done_too():
    eng = _engine()
    h = eng.submit([1, 2, 3], max_new_tokens=4)
    eng.close()
    assert h.event.is_set() and h.t_done is not None and h.t_done >= h.t0


def test_generation_panel_renders():
    from deeplearning4j_tpu.ui.server import UIServer

    with _engine() as eng:
        eng.generate([5, 5], max_new_tokens=3)
    panel = UIServer.get_instance()._generation_panel()
    assert "Generation (continuous batching)" in panel
    assert "dl4j_decode_tokens_total" in panel


def test_stats_shape():
    with _engine() as eng:
        eng.generate([1, 2], max_new_tokens=3)
        st = eng.stats()
    assert st["rows"] == MAX_BATCH
    assert st["joined_total"] >= 1 and st["retired_total"] >= 1
    assert st["tokens_total"] >= 3
    assert st["prefill_seconds"] > 0 and st["decode_seconds"] > 0
    assert st["buckets"]["kv"] == [16, 32]
    assert "misses" in st["aot_cache"]
    # 3 tokens at K = 2: the prefill and one window, which ends the row by
    # length, so none is launched ahead of it into an empty batch
    assert (st["windows_total"], st["windows_ahead_total"],
            st["joins_ahead_total"], st["windows_empty_total"]) == (
                1, 0, 0, 0)


def test_donation_audit_covers_decode_kinds():
    """PRG201: the program linter's train-kind set includes
    ``decode_step*``/``prefill*`` and every compiled decode/join
    executable aliases its state buffers (the KV cache is donated, not
    copied)."""
    from deeplearning4j_tpu.analysis import program

    assert "decode_step" in program.TRAIN_KIND_PREFIXES
    assert "prefill" in program.TRAIN_KIND_PREFIXES
    _decoder()  # ensure the executables exist in this process
    audit = program.donation_audit()
    kinds = {k: v for k, v in audit.items()
             if k[1].startswith(("decode_step", "prefill"))}
    assert kinds, "no decode executables were audited"
    for key, rep in kinds.items():
        assert rep["aliases"] > 0, f"{key[1]} does not donate its KV state"
        assert rep["findings"] == 0


# --- prefix caching ---------------------------------------------------------

def test_prefix_cache_radix_unit():
    """Trie mechanics in isolation: page-aligned match with pins,
    limit/fits backoff, insert-once, LRU eviction of refcount-0 leaves
    only."""
    from deeplearning4j_tpu.parallel.prefix_cache import PrefixCache

    made = []

    def slicer(start, stop):
        made.append((start, stop))
        # pages are host copies in cache layout: [tokens, heads * head_dim]
        return {"l": {"k": np.full((stop - start, 8), float(start)),
                      "v": np.full((stop - start, 8), float(start))}}

    pc = PrefixCache(page_tokens=4, max_pages=2)
    toks = list(range(12))
    path = pc.insert(toks, 12, slicer)          # 3 pages, over budget
    assert made == [(0, 4), (4, 8), (8, 12)]
    assert pc.stats()["pages"] == 3              # all pinned: no eviction
    pc.release(path)
    m, nodes = pc.match(toks, limit=11)          # page-aligned, <= limit
    assert m == 8 and len(nodes) == 2
    assert nodes[0].kv["l"]["k"][0, 0] == 0.0
    blk = pc.assemble(nodes, 16)["l"]            # pages end to end, padded
    assert blk["k"].shape == (16, 8)
    assert blk["v"][:, 3].tolist() == [0.0] * 4 + [4.0] * 4 + [0.0] * 8
    m2, nodes2 = pc.match(toks, limit=11, fits=lambda mm: mm <= 4)
    assert m2 == 4 and len(nodes2) == 1          # fits() backs off a page
    pc.release(nodes + nodes2)
    pc.insert(toks, 12, slicer)                  # re-pin forces eviction
    assert pc.stats()["pages"] <= 3
    assert made == [(0, 4), (4, 8), (8, 12)]     # nothing re-sliced


def test_prefix_hit_token_identical_to_cold_miss():
    """The tentpole determinism contract: requests sharing a cached
    prefix produce EXACTLY the tokens of a cold-cache run and of the
    sequential reference — the cached pages are bit-identical to the
    prefill they came from."""
    dec = _decoder()
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [shared + [i + 1, i + 2] for i in range(5)]
    refs = [dec.generate(p, 6) for p in prompts]
    with _engine(prefix_cache=True, prefix_page=4) as eng:
        cold = [eng.generate(p, max_new_tokens=6) for p in prompts]
        hot = [eng.generate(p, max_new_tokens=6) for p in prompts]
        st = eng.stats()
    assert cold == refs and hot == refs
    assert st["prefix_cache"]["hits"] >= 5      # the whole second sweep
    assert st["prefix_cache"]["pages"] >= 2


def test_prefix_pages_released_on_all_edges():
    """Leak contract: after finish, queued-deadline expiry, dispatch
    failure and close, every pin is returned — the tree's pages are all
    refcount-0 (evictable) again."""
    def pinned(pc):
        with pc._lock:
            total, stack = 0, [pc._root]
            while stack:
                nd = stack.pop()
                for ch in nd.children.values():
                    stack.append(ch)
                    total += ch.refs
            return total

    prompt = [5, 4, 3, 2, 1, 6, 7, 8, 2, 2]
    eng = GenerationEngine(
        _decoder(),
        GenerationConfig(max_batch=MAX_BATCH, fused_steps=K,
                         kv_bucket_min=16, prompt_bucket_min=4,
                         prefix_cache=True, prefix_page=4),
        retry=None)
    try:
        pc = eng._prefix
        eng.generate(prompt, max_new_tokens=4)          # normal finish
        eng.generate(prompt, max_new_tokens=4)          # a hit finishes too
        assert pinned(pc) == 0
        # queued-deadline expiry: loop suppressed so the request expires
        # in the queue holding its pins
        eng._ensure_thread = lambda: None
        req = eng.submit(prompt, max_new_tokens=4, timeout_ms=1)
        assert pinned(pc) > 0
        time.sleep(0.01)
        eng._ensure_thread = type(eng)._ensure_thread.__get__(eng)
        with eng._cond:
            eng._expire_queued_locked(time.monotonic())
        with pytest.raises(DeadlineExpiredError):
            eng.result(req)
        assert pinned(pc) == 0
        # dispatch failure: breaker path fails the in-flight row
        plan = FaultPlan(seed=5)
        plan.inject("decode.launch", probability=1.0, action="raise")
        with plan.armed():
            req = eng.submit(prompt, max_new_tokens=4)
            with pytest.raises(Exception):
                eng.result(req)
        assert pinned(pc) == 0
        # close with a pinned request still queued
        eng._ensure_thread = lambda: None
        req = eng.submit(prompt, max_new_tokens=4)
        assert pinned(pc) > 0
    finally:
        eng.close()
    assert pinned(pc) == 0


def test_speculation_fields_are_gone_from_the_config():
    """Speculative decoding went with PR 31: a caller that still passes
    its options learns at once, by the field's name."""
    for field in ("draft_conf", "spec_tokens"):
        with pytest.raises(TypeError, match=field):
            GenerationConfig(**{field: None})


def test_prefix_telemetry_series():
    snap0 = REGISTRY.snapshot(run_collectors=False)
    shared = [4, 4, 4, 4, 8, 8, 8, 8]
    with _engine(prefix_cache=True, prefix_page=4) as eng:
        eng.generate(shared + [1], max_new_tokens=5)
        eng.generate(shared + [2], max_new_tokens=5)
        snap1 = REGISTRY.snapshot(run_collectors=False)
    for name in ("dl4j_prefix_cache_hits_total",
                 "dl4j_prefix_cache_misses_total",
                 "dl4j_prefix_cache_hit_tokens_total"):
        assert snap1.get(name, 0) > snap0.get(name, 0), name
    assert "dl4j_prefix_cache_pages" in snap1


def test_generation_panel_includes_prefix():
    from deeplearning4j_tpu.ui.server import UIServer

    with _engine(prefix_cache=True, prefix_page=4) as eng:
        eng.generate([6, 6, 6, 6, 2], max_new_tokens=4)
    panel = UIServer.get_instance()._generation_panel()
    assert "Generation — prefix cache" in panel
    assert "dl4j_prefix_cache_pages" in panel


# --- Pallas attention kernels through the decode path -----------------------

@functools.lru_cache(maxsize=None)
def _kern_decoder() -> TransformerDecoder:
    """The target model with ``use_kernels=True``: same weights (seed 7)
    as ``_decoder()``, attention envelopes tuned BEFORE warm_all so the
    warmed executables bake the winners and carry the ``kern:`` tokens."""
    from deeplearning4j_tpu import kernels

    m = TransformerEncoder(vocab_size=VOCAB, embed_dim=16, n_heads=2,
                           n_layers=2, max_len=MAX_LEN, causal=True,
                           lm_head=True, seed=7, use_kernels=True)
    dec = m.decoder(max_batch=MAX_BATCH, kv_bucket_min=16,
                    prompt_bucket_min=4)
    kernels.autotune_decoder(dec, max_candidates=1, trials=1)
    dec.warm_all(fused_steps=(1, K))
    return dec


def _kern_engine(**over):
    cfg = dict(max_batch=MAX_BATCH, fused_steps=K, kv_bucket_min=16,
               prompt_bucket_min=4)
    cfg.update(over)
    return GenerationEngine(_kern_decoder(), GenerationConfig(**cfg))


def test_kernels_decoder_token_identical_and_zero_recompile():
    """use_kernels greedy decode (flash prefill; the decode step picks
    its attention by platform and shape, tuned or not)
    is token-identical to the stock decoder for every prompt, at K=1
    and fused K, with ZERO recompiles after warmup — and every step key
    carries both attention kernel tokens."""
    dec = _decoder()
    kdec = _kern_decoder()
    tag = kdec._ktag()
    assert "kern:flash_attention:" in tag
    assert "kern:paged_decode_attention:" in tag
    prompts = [[3, 9, 1], [5, 6, 7, 8, 2, 11], [1], [9] * 12]
    mns = [6, 9, 4, 8]
    m0 = aot_cache.stats()["misses"]
    for p, mn in zip(prompts, mns):
        ref = dec.generate(p, mn)
        assert kdec.generate(p, mn) == ref
        assert kdec.generate(p, mn, fused_steps=K) == ref
    assert aot_cache.stats()["misses"] == m0, \
        "kernel-routed decode recompiled after warmup"


def test_kernels_engine_continuous_matches_sequential():
    """Continuous batching over the kernel-routed decoder: mixed
    prompt/output lengths churn rows at ragged per-row cache occupancy
    (the paged gather's DMA-skip sees every row at a different page
    count) and each sequence equals the STOCK sequential reference."""
    dec = _decoder()
    prompts = [[3, 9, 1], [5, 6, 7, 8, 2, 11], [1], [14, 13, 12, 2],
               [9, 9, 2, 3, 4, 5, 6, 1]]
    mns = [6, 9, 4, 12, 5]
    refs = [dec.generate(p, mn) for p, mn in zip(prompts, mns)]
    with _kern_engine() as eng:
        warm = eng.warmup()
        assert warm["kernels"]["enabled"]
        assert "kern:flash_attention:" in warm["kernels"]["tag"]
        m0 = aot_cache.stats()["misses"]
        reqs = [eng.submit(p, max_new_tokens=mn)
                for p, mn in zip(prompts, mns)]
        outs = [eng.result(r) for r in reqs]
        st = eng.stats()
    assert outs == refs
    assert aot_cache.stats()["misses"] == m0
    assert st["kernels"]["enabled"] and "kern:" in st["kernels"]["tag"]


def test_kernels_prefix_attached_pages_token_identical():
    """Prefix-cache hits attach cached KV pages and decode continues at
    an offset position — the paged kernel's gather must read attached
    pages exactly like prefilled ones (cold run, hot run, and the stock
    sequential reference all agree)."""
    dec = _decoder()
    shared = [3, 1, 4, 1, 5, 9, 2, 6]
    prompts = [shared + [i + 1, i + 2] for i in range(4)]
    refs = [dec.generate(p, 6) for p in prompts]
    with _kern_engine(prefix_cache=True, prefix_page=4) as eng:
        cold = [eng.generate(p, max_new_tokens=6) for p in prompts]
        hot = [eng.generate(p, max_new_tokens=6) for p in prompts]
        st = eng.stats()
    assert cold == refs and hot == refs
    assert st["prefix_cache"]["hits"] >= 4


def test_kernel_bearing_decode_kinds_donate_and_audit_clean():
    """PRG201/PRG207 satellite: every kernel-bearing decode/prefill
    executable compiled this process donates its KV state and carries
    zero lint findings (PRG207 verified the tokens at compile time)."""
    from deeplearning4j_tpu.analysis import program

    _kern_decoder()  # ensure the executables exist in this process
    audit = program.donation_audit()
    kinds = {k: v for k, v in audit.items()
             if "kern:" in k[1]
             and k[1].startswith(("decode_step", "prefill"))}
    assert kinds, "no kernel-bearing decode executable was audited"
    for key, rep in kinds.items():
        assert rep["aliases"] > 0, f"{key[1]} does not donate its KV state"
        assert rep["findings"] == 0, f"{key[1]} has lint findings"


def test_kernels_retune_mints_new_decoder_executable():
    """A retune bumps the tuning digest, every ``kern:``-keyed step
    re-mints (AOT misses), and the retuned flash prefill is still
    token-identical. Runs LAST of the kernel-decode tests: it leaves
    the tuning table mutated."""
    from deeplearning4j_tpu import kernels

    dec = _decoder()
    kdec = _kern_decoder()
    prompt = [2, 4, 6]
    ref = dec.generate(prompt, 5)
    assert kdec.generate(prompt, 5) == ref
    tag0 = kdec._ktag()
    kid = "flash_attention"
    # the prompt's own bucket (4) at join width 1: the envelope the
    # prefill of this very request routes
    env = next(e for k_, e in kernels.decoder_envelopes(kdec)
               if k_ == kid and e.tq == 4 and e.b == 1)
    # at this size the kernel has one legal tiling: the retune records it
    # again at another time, which is a new winner table all the same
    cur = tuple(kernels.TUNING.winner(kid, env.key)["tiling"])
    m0 = aot_cache.stats()["misses"]
    kernels.TUNING.record(kid, env.key, cur, 1.0)
    assert kdec._ktag() != tag0
    assert kdec.generate(prompt, 5) == ref
    assert aot_cache.stats()["misses"] > m0, \
        "a retuned kernel must be a NEW executable"


def test_donation_audit_covers_prefix_kinds():
    """PRG201 satellite: the prefix cache's decode-state consumers are
    in the audit's train-kind set, every compiled one donates, and the
    suffix prefill (shared refcounted pages) is deliberately exempt."""
    from deeplearning4j_tpu.analysis import program

    for kind in ("prefix_attach", "prefix_join"):
        assert kind in program.TRAIN_KIND_PREFIXES
    assert not any("gen_prompt_sfx".startswith(p)
                   for p in program.TRAIN_KIND_PREFIXES)
    with _engine(prefix_cache=True, prefix_page=4) as eng:
        eng.generate([1, 2, 3, 4, 5], max_new_tokens=4)
        eng.generate([1, 2, 3, 4, 6], max_new_tokens=4)
    audit = program.donation_audit()
    kinds = {k: v for k, v in audit.items()
             if k[1].startswith(("prefix_attach", "prefix_join"))}
    assert kinds, "no prefix executables were audited"
    for key, rep in kinds.items():
        assert rep["aliases"] > 0, f"{key[1]} does not donate its state"
        assert rep["findings"] == 0
