#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, in ONE process, through the entry points
a user calls, at the full width of one model each, and checks what comes
out by the repo's own means:

- ``train``: ``zoo.graphs.ResNet50`` at its published widths, exactly
  ``bench.py``'s configuration (batch 256, 224x224x3 uint8 in, 1000
  classes, f32 params with the bf16 compute policy, space-to-depth stem,
  Adam) through ``ComputationGraph.fit``: one compiling step, six more.
- ``serve``: ``TransformerEncoder(lm_head=True, causal=True).decoder()``
  -> ``GenerationEngine`` at the widths of GPT-2 small (12 layers, 768
  wide, 12 heads of 64, feed-forward 3072, vocabulary 50257, 1024
  positions, ``max_batch`` 8; seeded random weights): ``warmup()``, then
  ten requests of mixed prompt and output lengths submitted while others
  run; greedy output must be token-identical to ``dec.generate``.
- ``kernels``: every registry kernel built with ``backend="tpu"`` at an
  envelope of those two models, compiled by Mosaic (never interpreted),
  run, and compared with the kernel's own ``reference()``.
- ``four_chip``: the train path under ``ParallelWrapper`` (exact
  data-parallel, then ZeRO) with placement checks, when jax shows four
  or more chips; otherwise the phase says it does not apply.

It refuses to run without a chip: anything but ``platform == "tpu"`` is
exit code 2 before any work, never a CPU run, and it sets no
``JAX_PLATFORMS`` itself. The chip belongs to one process: this script
starts no child process. A failed check raises, so no phase failure can
end in exit code 0, and the last line of stdout is the result object
only when every phase passed.

``--dry-run-on-cpu-at-tiny-size`` runs the same control flow on the CPU
at toy sizes (what tier-1 exercises). It proves the script, not the
chip: its last line says ``"ok": false`` and ``"dry_run": true``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

DRY_RUN_FLAG = "--dry-run-on-cpu-at-tiny-size"
NO_CHIP_EXIT = 2


class SmokeFailure(RuntimeError):
    """A check of the smoke did not hold."""


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the dry run."""

    # train
    img: int
    classes: int
    batch: int            # per chip
    steps: int            # after the compiling step
    # serve
    vocab: int
    embed: int
    heads: int
    layers: int
    ffn: int
    max_len: int
    max_batch: int
    kv_bucket_min: int
    prompt_bucket_min: int
    requests: tuple       # (prompt length, max_new_tokens)
    # kernels: (m, k, n) matmul problems and the attention geometry
    conv_mkn: tuple
    dense_mkn: tuple
    attn_bhd: tuple
    attn_t: int
    paged_s: int


REAL = Sizes(
    img=224, classes=1000, batch=256, steps=6,
    vocab=50257, embed=768, heads=12, layers=12, ffn=3072, max_len=1024,
    max_batch=8, kv_bucket_min=1024, prompt_bucket_min=128,
    requests=((5, 96), (40, 64), (130, 80), (300, 48), (17, 72),
              (513, 24), (64, 40), (200, 16), (9, 56), (700, 12)),
    # ResNet-50's res4 1x1 reduce conv at batch 256; the decoder's first
    # feed-forward matmul at max_batch rows
    conv_mkn=(256 * 14 * 14, 1024, 256), dense_mkn=(8, 768, 3072),
    attn_bhd=(8, 12, 64), attn_t=1024, paged_s=1024)

TINY = Sizes(
    img=32, classes=10, batch=4, steps=6,
    vocab=128, embed=32, heads=2, layers=2, ffn=64, max_len=64,
    max_batch=4, kv_bucket_min=32, prompt_bucket_min=16,
    requests=((3, 24), (9, 16), (17, 20), (5, 12), (30, 6), (12, 10),
              (7, 14), (20, 4)),
    conv_mkn=(64, 128, 128), dense_mkn=(8, 128, 256),
    attn_bhd=(1, 2, 16), attn_t=128, paged_s=64)

# max |got - ref| / max |ref| against the kernel's reference() run under
# jax.default_matmul_precision("highest"), by the dtype the kernel
# computes in. The kernels feed the matrix unit at its default precision
# (one bf16 pass, also for float32 operands), so float32 allows for that,
# and four times as much through the backward's chain of recomputed
# matmuls; bfloat16 allows two ulps of its 8-bit mantissa; the int8
# kernel accumulates exactly and differs only in its float32 epilogue.
# Each line also prints what stock XLA at default precision loses against
# the same reference (``xla_default_err``), as the yardstick.
TOLERANCE = {"float32": 5e-3, "float32-grad": 2e-2, "bfloat16": 2.0 ** -6,
             "int8": 1e-5}


class Reporter:
    """Every line names the platform and the device kind."""

    def __init__(self, dev: dict, dry_run: bool):
        from deeplearning4j_tpu.util.device import banner

        self.dev = dev
        self.prefix = banner(dev)
        if dry_run:
            self.prefix = "DRY RUN (not a chip run) " + self.prefix

    def say(self, phase: str, **fields) -> None:
        body = " ".join(f"{k}={v}" for k, v in fields.items())
        print(f"[{phase}] {self.prefix} | {body}", flush=True)


class CompileCacheCounter:
    """jax's own persistent-compilation-cache events."""

    REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
    HITS = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == self.REQUESTS:
            self.requests += 1
        elif event == self.HITS:
            self.hits += 1


def peak_bytes():
    """Peak device bytes per device, or "not reported" (the CPU backend
    has no memory_stats)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    if any(s is None for s in stats):
        return "not reported"
    return [int(s["peak_bytes_in_use"]) for s in stats]


def release() -> dict:
    """Drop every compiled program and dead buffer before the next model
    is built (16 GB of device memory). Returns the AOT counters the
    phase ended with — ``aot_cache.clear()`` resets them."""
    import jax

    from deeplearning4j_tpu.optimize import aot_cache

    stats = aot_cache.stats()
    aot_cache.clear()
    jax.clear_caches()
    gc.collect()
    return stats


def platforms_of(tree) -> set:
    import jax

    return {d.platform for leaf in jax.tree_util.tree_leaves(tree)
            for d in leaf.devices()}


def image_batches(z: Sizes, rows: int, n: int, seed: int):
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet

    rng = np.random.default_rng(seed)
    return [DataSet(
        rng.integers(0, 256, (rows, z.img, z.img, 3), dtype=np.uint8),
        np.eye(z.classes, dtype=np.float32)[
            rng.integers(0, z.classes, rows)]) for _ in range(n)]


def resnet50(z: Sizes):
    """``bench.py``'s network: published widths, bf16 policy, s2d stem."""
    from deeplearning4j_tpu.conf.updaters import Adam
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.zoo.graphs import ResNet50

    model = ResNet50(num_classes=z.classes, height=z.img, width=z.img,
                     updater=Adam(learning_rate=1e-3))
    model.stem_space_to_depth = True
    cfg = dataclasses.replace(model.conf(), compute_dtype="bfloat16")
    return ComputationGraph(cfg).init()


def check_aot(stats: dict, phase: str) -> None:
    check(stats["fallbacks"] == 0,
          f"{phase}: {stats['fallbacks']} cached executables fell back to "
          f"the plain jit (a silent recompile)")
    check(stats["overflows"] == 0,
          f"{phase}: the AOT cache overflowed {stats['overflows']} times")


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def phase_train(out: Reporter, z: Sizes) -> None:
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class Losses(TrainingListener):
        """The loss of every step, as the device array fit() hands out
        (no host read inside the loop)."""

        def __init__(self):
            self.losses = []

        def iteration_done(self, model, iteration, epoch, score):
            self.losses.append(score)

    t0 = time.perf_counter()
    net = resnet50(z)
    losses = Losses()
    net.set_listeners(losses)
    batches = image_batches(z, z.batch, 3, seed=42)
    net.fit(batches[0])                         # the compiling step
    jax.block_until_ready(net.params)
    setup_s = time.perf_counter() - t0
    warm = aot_cache.stats()
    check(warm["misses"] >= 1, "train: the first step compiled nothing")

    it = ListDataSetIterator([batches[1 + i % 2] for i in range(z.steps)])
    net.fit(it, epochs=1)
    jax.block_until_ready(net.params)
    after = aot_cache.stats()

    values = np.asarray([float(v) for v in losses.losses])
    check(len(values) == 1 + z.steps,
          f"train: {len(values)} steps ran, expected {1 + z.steps}")
    check(bool(np.all(np.isfinite(values))),
          f"train: non-finite loss in {values.tolist()}")
    want = {out.dev["platform"]}
    check(platforms_of(net.params) == want,
          f"train: parameters live on {platforms_of(net.params)}")
    check(platforms_of(losses.losses) == want,
          f"train: the returned loss lives on "
          f"{platforms_of(losses.losses)}")
    recompiles = after["misses"] - warm["misses"]
    check(recompiles == 0,
          f"train: {recompiles} compiles after the compiling step")
    check_aot(after, "train")
    out.say("train", model="ResNet50", batch=z.batch,
            input=f"{z.img}x{z.img}x3:uint8", classes=z.classes,
            policy="f32-params/bf16-compute", steps=len(values),
            setup_s=round(setup_s, 1), executables=warm["misses"],
            compiles_after_warmup=recompiles,
            aot_fallbacks=after["fallbacks"],
            aot_overflows=after["overflows"], peak_bytes=peak_bytes(),
            check="loss-finite,params-and-loss-on-device",
            first_loss=round(float(values[0]), 4),
            last_loss=round(float(values[-1]), 4))


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

def wait_until(predicate, seconds: float, what: str) -> None:
    deadline = time.monotonic() + seconds
    while not predicate():
        check(time.monotonic() < deadline, f"serve: timed out waiting "
                                           f"for {what}")
        time.sleep(0.002)


def finished(eng, handle, what: str) -> list:
    """The tokens of a finished request. A dispatch failure lands in
    ``handle.error``; ``result()`` raises it."""
    check(handle.event.wait(600.0), f"serve: {what} never finished")
    return eng.result(handle)


def first_difference(a: list, b: list) -> int:
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return min(len(a), len(b))


def phase_serve(out: Reporter, z: Sizes) -> None:
    import numpy as np

    from deeplearning4j_tpu.optimize import aot_cache
    from deeplearning4j_tpu.parallel.generation import (
        GenerationConfig,
        GenerationEngine,
    )
    from deeplearning4j_tpu.zoo.graphs import TransformerEncoder

    t0 = time.perf_counter()
    model = TransformerEncoder(
        vocab_size=z.vocab, embed_dim=z.embed, n_heads=z.heads,
        n_layers=z.layers, ffn_dim=z.ffn, max_len=z.max_len, lm_head=True,
        causal=True, seed=0)
    dec = model.decoder(max_batch=z.max_batch,
                        kv_bucket_min=z.kv_bucket_min,
                        prompt_bucket_min=z.prompt_bucket_min)
    cfg = GenerationConfig(max_batch=z.max_batch,
                           kv_bucket_min=z.kv_bucket_min,
                           prompt_bucket_min=z.prompt_bucket_min)
    rng = np.random.default_rng(11)
    prompts = [(rng.integers(0, z.vocab, n).tolist(), mn)
               for n, mn in z.requests]

    with GenerationEngine(dec, cfg) as eng:
        warm = eng.warmup()
        setup_s = time.perf_counter() - t0
        warmed = aot_cache.stats()

        # Wave 1, checked token for token: each request is admitted
        # while the ones before it are decoding, one join at a time, so
        # the engine runs exactly the executables dec.generate runs
        # (same prompt bucket, join width 1) on the same values.
        handles, while_running = [], 0
        for i, (prompt, max_new) in enumerate(prompts):
            while_running += eng.stats()["rows_in_use"] > 0
            handles.append(eng.submit(prompt, max_new_tokens=max_new))
            wait_until(lambda: eng.stats()["joined_total"] > i, 600.0,
                       f"request {i} to join the running batch")
        got = [finished(eng, h, f"request {i}")
               for i, h in enumerate(handles)]
        # Wave 2, all at once: prompts join in groups padded to the
        # group's widest bucket — the other join widths of the ladder.
        burst = [finished(eng, h, f"burst request {i}")
                 for i, h in enumerate(
                     [eng.submit(prompt, max_new_tokens=max_new)
                      for prompt, max_new in prompts])]
        stats = eng.stats()

        # the sequential one-request-at-a-time reference, through the
        # same warmed executables
        want = [dec.generate(prompt, max_new, fused_steps=cfg.fused_steps)
                for prompt, max_new in prompts]
        after = aot_cache.stats()

    check(while_running >= len(prompts) // 2,
          f"serve: only {while_running} requests were submitted while "
          f"others were running")
    for i, (g, w) in enumerate(zip(got, want)):
        check(g == w,
              f"serve: request {i} differs from dec.generate at token "
              f"{first_difference(g, w)} of {len(w)}: engine "
              f"{g[:8]} vs sequential {w[:8]}")
    for i, (b, (_, max_new)) in enumerate(zip(burst, prompts)):
        check(len(b) == max_new and all(0 <= t < z.vocab for t in b),
              f"serve: burst request {i} returned {len(b)} tokens of "
              f"{max_new}, or one outside the vocabulary")
    check(platforms_of(dec.params) == {out.dev["platform"]},
          f"serve: weights live on {platforms_of(dec.params)}")
    recompiles = after["misses"] - warmed["misses"]
    check(recompiles == 0, f"serve: {recompiles} compiles after warm-up")
    check_aot(after, "serve")
    out.say("serve", model="TransformerEncoder(lm_head,causal)",
            layers=z.layers, width=z.embed, heads=z.heads, ffn=z.ffn,
            vocab=z.vocab, positions=z.max_len, max_batch=z.max_batch,
            kv_buckets=warm["kv_buckets"],
            prompt_buckets=warm["prompt_buckets"],
            join_buckets=warm["join_buckets"], setup_s=round(setup_s, 1),
            executables=warm["compiled"],
            warmup_compile_s=warm["compile_seconds"],
            requests=len(got), submitted_while_running=while_running,
            burst_requests=len(burst),
            burst_identical_to_sequential=sum(
                b == w for b, w in zip(burst, want)),
            tokens=stats["tokens_total"],
            compiles_after_warmup=recompiles,
            aot_fallbacks=after["fallbacks"],
            aot_overflows=after["overflows"], peak_bytes=peak_bytes(),
            check="every-result-returned,token-identical-to-dec.generate")


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def rel_err(got, ref) -> float:
    import jax
    import numpy as np

    worst = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        g = np.asarray(g, np.float64)
        r = np.asarray(r, np.float64)
        check(g.shape == r.shape, f"kernels: shape {g.shape} vs {r.shape}")
        check(bool(np.all(np.isfinite(g))), "kernels: non-finite output")
        worst = max(worst, float(np.max(np.abs(g - r)))
                    / max(float(np.max(np.abs(r))), 1e-30))
    return worst


def run_kernel(out: Reporter, kernel_id: str, env, label: str,
               args=None, grad: bool = False) -> None:
    """Sweep the kernel's tilings for ``env`` (refusals counted, first
    message kept), build the winner, prove it is compiled and not
    interpreted, run it, compare with ``reference()``."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.analysis import program

    kernel = kernels.REGISTRY.get(kernel_id)
    check(kernel is not None and kernel.supports(env),
          f"kernels: {kernel_id} does not support {env.key}")
    tuned = kernels.autotune(kernel, env, max_candidates=8, trials=1,
                             record=False)
    refused = tuned.refused
    fn = kernel.build(env, tuned.tiling)
    ref_fn = kernel.reference(env)
    if args is None:
        args = kernel.make_inputs(env, seed=0)
    if grad:
        # the custom-VJP backward: gradients of a weighted readout
        fwd, ref_fwd = fn, ref_fn
        weights = jax.random.normal(jax.random.PRNGKey(7), args[0].shape,
                                    jnp.float32)

        def wrap(f):
            def loss(q, k, v, *rest):
                return jnp.sum(f(q, k, v, *rest).astype(jnp.float32)
                               * weights)
            return jax.grad(loss, argnums=(0, 1, 2))

        fn, ref_fn = wrap(fwd), wrap(ref_fwd)
    flags = program.pallas_interpret_flags(fn, *args)
    interpreted = env.backend != "tpu"
    check(flags and set(flags) == {interpreted},
          f"kernels: {kernel_id} {label} built with interpret={flags}")
    got = jax.block_until_ready(jax.jit(fn)(*args))
    stock = jax.block_until_ready(jax.jit(ref_fn)(*args))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(ref_fn)(*args))
    err = rel_err(got, want)
    tol = TOLERANCE[env.dtype + ("-grad" if grad else "")]
    check(err <= tol, f"kernels: {kernel_id} {label} differs from its "
                      f"reference by {err:.3e} (tolerance {tol:.3e})")
    out.say("kernels", kernel=kernel_id, case=label, envelope=env.key,
            tiling=list(tuned.tiling), candidates=len(tuned.trials),
            refused=len(refused),
            first_refusal=(json.dumps(refused[0]["error"][:300])
                           if refused else "none"),
            interpret=interpreted, pallas_calls=len(flags),
            rel_err=f"{err:.2e}", tolerance=f"{tol:.2e}",
            xla_default_err=f"{rel_err(stock, want):.2e}",
            check="compiled,ran,matches-reference")


def phase_kernels(out: Reporter, z: Sizes) -> None:
    import jax.numpy as jnp

    from deeplearning4j_tpu import kernels
    from deeplearning4j_tpu.kernels import AttentionEnvelope, MatmulEnvelope

    backend = kernels.backend()
    if out.dev["platform"] == "tpu":
        check(backend == "tpu", f"kernels: backend() is {backend!r} on a "
                                f"TPU")
    m, k, n = z.conv_mkn
    run_kernel(out, "matmul_bias_act", MatmulEnvelope(
        m=m, k=k, n=n, dtype="bfloat16", backend=backend, act="relu"),
        "resnet50-res4-1x1-reduce")
    run_kernel(out, "conv_bn_act", MatmulEnvelope(
        m=m, k=k, n=n, dtype="bfloat16", backend=backend),
        "resnet50-res4-1x1-reduce")
    m, k, n = z.dense_mkn
    run_kernel(out, "matmul_bias_act", MatmulEnvelope(
        m=m, k=k, n=n, dtype="float32", backend=backend, act="gelu"),
        "decoder-ff1")
    run_kernel(out, "matmul_bias_act_int8", MatmulEnvelope(
        m=m, k=k, n=n, dtype="int8", backend=backend, act="gelu"),
        "decoder-ff1")
    b, h, d = z.attn_bhd
    for masked in (False, True):
        env = AttentionEnvelope(b=b, h=h, tq=z.attn_t, tk=z.attn_t, d=d,
                                dtype="float32", backend=backend,
                                causal=True, masked=masked)
        mask = "key-mask" if masked else "no-mask"
        run_kernel(out, "flash_attention", env, f"forward-{mask}")
        run_kernel(out, "flash_attention", env, f"backward-{mask}",
                   grad=True)
    env = AttentionEnvelope(b=b, h=h, tq=1, tk=z.paged_s, d=d,
                            dtype="float32", backend=backend, causal=True)
    paged = kernels.REGISTRY.get("paged_decode_attention")
    q, kc, vc, _ = paged.make_inputs(env, seed=0)
    for share in (0.25, 1.0):
        pos = jnp.full((b,), int(z.paged_s * share) - 1, jnp.int32)
        run_kernel(out, "paged_decode_attention", env,
                   f"occupancy-{int(share * 100)}%", args=(q, kc, vc, pos))


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------

def phase_four_chip(out: Reporter, z: Sizes) -> None:
    import jax
    import numpy as np

    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.parallel import (
        ParallelWrapper,
        TrainingMode,
        mesh as mesh_mod,
    )
    from deeplearning4j_tpu.sharding import ZeroSpec

    n = len(jax.devices())
    if n < 4:
        out.say("four_chip", status="not-applicable",
                reason=f"needs-4-devices-found-{n}")
        return
    devices = set(jax.devices())
    net = resnet50(z)
    batches = image_batches(z, z.batch * n, 2, seed=5)
    # the same network: exact data-parallel first, then ZeRO carries on
    # from the parameters the first wrapper wrote back
    for name, kw in (("data-parallel", {}),
                     ("zero", {"zero_optimizer": True})):
        t0 = time.perf_counter()
        pw = ParallelWrapper(net, training_mode=TrainingMode.SHARED_GRADIENTS,
                             **kw)
        check(pw.workers == n, f"four_chip: {pw.workers} workers on {n} "
                               f"devices")
        pw.fit(batches[0])                     # the compiling step
        setup_s = time.perf_counter() - t0
        steps = 3
        pw.fit(ListDataSetIterator([batches[i % 2] for i in range(steps)]))
        loss = float(net.score_value)
        check(np.isfinite(loss), f"four_chip {name}: loss {loss}")

        # placement, on the wrapper's live device trees
        for leaf in jax.tree_util.tree_leaves(pw._params):
            check(leaf.sharding.device_set == devices,
                  f"four_chip {name}: a parameter lives on "
                  f"{len(leaf.sharding.device_set)} of {n} devices")
        sharded = mesh_mod.shard_batch(pw.mesh, batches[0].features)
        homes = {s.device for s in sharded.addressable_shards}
        check(homes == devices and all(
            s.data.shape[0] == z.batch for s in sharded.addressable_shards),
            f"four_chip {name}: the batch sits on {len(homes)} devices")
        in_use = [d.memory_stats() for d in jax.devices()]
        if all(s is not None for s in in_use):
            check(all(s["bytes_in_use"] > 0 for s in in_use),
                  f"four_chip {name}: a device holds nothing: "
                  f"{[s['bytes_in_use'] for s in in_use]}")
        opt_share = "replicated"
        if kw:
            spec = ZeroSpec(net.opt_state, n)
            per_device = {d: 0 for d in devices}
            for leaf in jax.tree_util.tree_leaves(pw._opt):
                for s in leaf.addressable_shards:
                    per_device[s.device] += s.data.nbytes
            held = set(per_device.values())
            check(held == {spec.bytes_per_device()},
                  f"four_chip zero: optimizer bytes per device {held}, "
                  f"ZeroSpec says {spec.bytes_per_device()}")
            opt_share = round(spec.bytes_per_device()
                              / max(spec.total_bytes(), 1), 4)
            check(abs(opt_share - 1.0 / n) < 0.01,
                  f"four_chip zero: each device holds {opt_share} of the "
                  f"optimizer state, expected about {1.0 / n}")
        out.say("four_chip", mode=name, model="ResNet50",
                batch_per_chip=z.batch, steps=1 + steps,
                setup_s=round(setup_s, 1), loss=round(loss, 4),
                param_devices=n, batch_devices=len(homes),
                optimizer_share_per_device=opt_share,
                peak_bytes=peak_bytes(),
                check="loss-finite,params-batch-and-memory-on-all-devices")
        del pw, sharded
        check_aot(release(), f"four_chip {name}")


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(DRY_RUN_FLAG, dest="dry_run", action="store_true",
                    help="run the control flow on the CPU at toy sizes; "
                         "proves the script, never the chip, and cannot "
                         "print the passing result")
    args = ap.parse_args(argv)

    from deeplearning4j_tpu.util.device import describe

    dev = describe()
    if args.dry_run:
        if dev["platform"] != "cpu":
            print(f"chip_smoke: {DRY_RUN_FLAG} is for the CPU; jax reports "
                  f"{dev['platform']!r}", file=sys.stderr)
            return NO_CHIP_EXIT
    elif dev["platform"] != "tpu":
        print(f"chip_smoke: no chip. jax reports platform="
              f"{dev['platform']!r} device_kind={dev['kind']!r} "
              f"count={dev['count']}; this script never runs on anything "
              f"but a TPU.", file=sys.stderr)
        return NO_CHIP_EXIT
    z = TINY if args.dry_run else REAL
    out = Reporter(dev, args.dry_run)

    from deeplearning4j_tpu import kernels, native
    from deeplearning4j_tpu.optimize import aot_cache

    t_start = time.perf_counter()
    cache_dir = aot_cache.place_compile_cache()
    cache = CompileCacheCounter()
    out.say("env", kernels_backend=kernels.backend(),
            native_available=native.available(),
            compile_cache_dir=cache_dir,
            compile_cache_dir_from_env=bool(
                os.environ.get(aot_cache.COMPILE_CACHE_ENV)),
            compile_cache_entries_at_start=(
                len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
                else 0))

    phase_train(out, z)
    release()
    phase_serve(out, z)
    release()
    phase_kernels(out, z)
    release()
    phase_four_chip(out, z)

    out.say("done", seconds=round(time.perf_counter() - t_start, 1),
            compile_cache_requests=cache.requests,
            compile_cache_hits=cache.hits,
            compile_cache_entries_at_end=(
                len(os.listdir(cache_dir)) if os.path.isdir(cache_dir)
                else 0))
    result = {"ok": not args.dry_run, "device": dev}
    if args.dry_run:
        result["dry_run"] = True
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
