"""Driver of the training cells: ``ComputationGraph.fit(iterator)`` on one
chip.

Set-up builds ONE object, the graph with its compiled step and its state,
from the benchmark's seeded weights, drives it through its first steps
through the window's own call (``fit`` over an iterator of host batches)
while a listener reads what the comparison needs, and hands that same
object to the window. The window is one ``fit`` over an iterator that
cycles the seeded host batches until the deadline; the rate is all images
of all steps over the whole time until ``block_until_ready`` returns.

Once the window has closed and the memory has been read, the program's
state is freed and the plain reference follows the same first steps.
"""

from __future__ import annotations

import gc
import json
import time


class _HostBatches:
    """The window's iterator: fresh ``DataSet`` wrappers over the same
    host arrays (so every step copies its batch to the device, as an
    input pipeline would), until the deadline. Stops the profiler window
    when it is due: ``fit`` holds the thread, the iterator is the only
    benchmark code it calls."""

    def __init__(self, batches, seconds, trace):
        self.batches = batches
        self.seconds = seconds
        self.trace = trace
        self.steps = 0

    def __iter__(self):
        from deeplearning4j_tpu.datasets.dataset import DataSet

        from benchmarks import harness

        begin = time.monotonic()
        deadline = begin + self.seconds
        while time.monotonic() < deadline:
            with harness.annotate("next_batch"):
                if self.trace.due():
                    self.trace.stop()
                elif self.trace.may_open(time.monotonic() - begin):
                    self.trace.open()
                images, labels = self.batches[self.steps % len(self.batches)]
                ds = DataSet(images, labels)
            self.steps += 1
            with harness.annotate("fit_between_steps"):
                yield ds

    def reset(self):
        pass


def set_up(ctx, seed: int) -> dict:
    """The one object the window will drive: built from the seed, driven
    through its first ``checked_steps`` steps by the window's own call,
    with what the comparison reads of them."""
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    from benchmarks import traffic_gen

    cfg, mix = ctx.config, ctx.traffic
    ref = ctx.module("reference")
    chips = ctx.cell["chips"]
    checked = int(mix["checked_steps"])
    weights = ref.init_weights(cfg, seed)
    net = ctx.module("model").build(cfg, weights, ref.init_bn_state(cfg))
    batches = traffic_gen.image_batches(mix, cfg, chips, seed)
    beta1 = cfg["optimizer"]["beta1"]
    first_grad = jax.jit(lambda opt: ref.leaf_norms(
        {k: {kk: vv["m"] / (1.0 - beta1) for kk, vv in v.items()}
         for k, v in opt.items()}))
    change = jax.jit(lambda new, old: ref.leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, new, old)))
    # a copy: the next step donates the state it was given
    keep = jax.jit(lambda t: jax.tree_util.tree_map(lambda x: x + 0, t))

    class FirstSteps(TrainingListener):
        """Reads, on the device and without a host sync, each step's loss,
        the first gradient as Adam got it (its first moment after one
        step is ``(1 - beta1) g``), BatchNorm's running statistics after
        the first step, and the parameters' change."""

        def __init__(self):
            self.loss, self.grad, self.change, self.bn = [], None, None, None

        def iteration_done(self, model, iteration, epoch, score):
            self.loss.append(score)
            if len(self.loss) == 1:
                self.grad = first_grad(model.opt_state)
                self.bn = keep(model.state)
            if len(self.loss) == checked:
                self.change = change(model.params, weights)

    seen = FirstSteps()
    net.set_listeners(seen)
    net.fit(ListDataSetIterator(
        [DataSet(*batches[i % len(batches)]) for i in range(checked)]))
    jax.block_until_ready(net.params)
    net.set_listeners()
    program = {"loss": [float(x) for x in seen.loss],
               "grad_norm": {k: float(v) for k, v in seen.grad.items()},
               "change_norm": {k: float(v) for k, v in seen.change.items()},
               "bn_state": ref.flat_arrays(seen.bn)}
    return {"net": net, "weights": weights, "batches": batches,
            "program": program, "checked": checked}


def follow(ctx, weights, batches, checked, **kw) -> dict:
    """The plain reference over the same first steps."""
    import jax.numpy as jnp

    return ctx.module("reference").follow_steps(
        ctx.config, weights, [(jnp.asarray(i), jnp.asarray(l))
                              for i, l in batches[:checked]], **kw)


def run(ctx) -> dict:
    import jax

    from deeplearning4j_tpu.optimize import aot_cache

    from benchmarks import correct, harness

    aot_cache.place_compile_cache()
    chips = ctx.cell["chips"]
    batch = int(ctx.traffic["batch_per_chip"]) * chips

    # ---- set-up ---------------------------------------------------------
    up = set_up(ctx, ctx.args.seed)
    net, weights, batches = up["net"], up["weights"], up["batches"]
    program, checked = up["program"], up["checked"]
    executables = aot_cache.stats()["misses"]
    setup_s = ctx.setup_seconds()
    print(f"# set-up {setup_s:.2f} s, {executables} executable(s), first "
          f"losses {program['loss']}", flush=True)

    # ---- the window -----------------------------------------------------
    traced_s = float(ctx.cell_file["trace_seconds"])
    trace = harness.TraceWindow(
        bool(ctx.args.trace), traced_s, ctx.rehearsal,
        after=min(float(ctx.cell_file["trace_after_seconds"]),
                  max(0.0, 0.5 * (ctx.args.seconds - traced_s))))
    # the traced window opens inside the one fit, once the steps run at
    # their steady pace
    it = _HostBatches(batches, ctx.args.seconds, trace)
    setup_s = ctx.setup_seconds()
    before = aot_cache.stats()
    t0 = time.monotonic()
    net.fit(it)
    jax.block_until_ready(net.params)
    elapsed = time.monotonic() - t0
    trace.stop()
    after = aot_cache.stats()
    images = it.steps * batch
    memory = harness.memory_peak_bytes()

    # ---- free the program, then the reference ---------------------------
    del net, up
    aot_cache.clear()
    gc.collect()
    summary = trace.reduce()
    t_ref = time.monotonic()
    reference = follow(ctx, weights, batches, checked)
    numbers = correct.training_numbers(program, reference)
    ok, compared = correct.judge(numbers, ctx.cell_file["limits"])
    ok = ok and it.steps > 0

    return {
        "end_to_end": {"train_images_per_s": images / elapsed,
                       "setup_s": setup_s},
        "correct": ok, "attempted": it.steps, "failed": 0,
        "compared": compared, "memory": memory, "trace": summary,
        "counters": {"compiles": after["misses"] - before["misses"],
                     "aot_fallbacks": after["fallbacks"],
                     "executables": executables},
        "window": {"seconds": elapsed, "steps": it.steps, "images": images,
                   "batch": batch, "chips": chips},
        "notes": {"reference_s": time.monotonic() - t_ref,
                  "window_s": elapsed, "steps": it.steps,
                  "program": {"loss": program["loss"]},
                  "reference": {"loss": reference["loss"]},
                  # read and printed, held to no limit (the cell's file
                  # says why): they have no upper reading
                  "not_compared": {k: v for k, v in numbers.items()
                                   if k not in compared}},
    }


def calibrate(ctx, args) -> list:
    """Program, control and planted fault against the reference, seed by
    seed, in one process (``benchmarks/calibrate.py``)."""
    from deeplearning4j_tpu.optimize import aot_cache

    from benchmarks import correct

    aot_cache.place_compile_cache()
    ref = ctx.module("reference")
    q = ref.lower_precision(ctx.config["control_dtype"])
    batch = int(ctx.traffic["batch_per_chip"]) * ctx.cell["chips"]
    rows = []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.monotonic()
        up = set_up(ctx, seed)
        weights, batches, checked = (up["weights"], up["batches"],
                                     up["checked"])
        program = up["program"]
        del up
        gc.collect()
        reference = follow(ctx, weights, batches, checked)
        row = {"seed": seed,
               "program": correct.training_numbers(program, reference),
               "loss": {"program": program["loss"],
                        "reference": reference["loss"]},
               "worst_grad_leaves": correct.worst_leaves(
                   program["grad_norm"], reference["grad_norm"]),
               "worst_change_leaves": correct.worst_leaves(
                   program["change_norm"], reference["change_norm"])}
        if i < args.control_seeds:
            control = follow(ctx, weights, batches, checked, q=q)
            row["control"] = correct.training_numbers(control, reference)
            row["control_worst_grad_leaves"] = correct.worst_leaves(
                control["grad_norm"], reference["grad_norm"])
            half = follow(ctx, weights, batches, checked, rows=batch // 2)
            row["half_batch"] = correct.training_numbers(half, reference)
            # the second witness of a wide worst leaf: the reference
            # itself with its operands in the configuration's own type
            own = follow(ctx, weights, batches, checked,
                         q=ref.lower_precision(ctx.config["compute_dtype"]))
            row["reference_in_compute_dtype"] = correct.training_numbers(
                own, reference)
            row["reference_in_compute_dtype_worst_grad_leaves"] = \
                correct.worst_leaves(own["grad_norm"],
                                     reference["grad_norm"])
        row["seconds"] = time.monotonic() - t0
        print("calibrate", json.dumps(row), flush=True)
        rows.append(row)
    return rows
