"""Chip timing of ``RoutedExpertsLayer``'s three expert products (PR 33,
PR 36; PERF.md §6) at Trinity-Mini's widths: hidden 2048, 128 experts of
1024, top 8, bfloat16 matrices. For a step of ``n`` live rows, the whole
layer (route, experts, shared expert) with every token through every
expert held, with the slots grouped by expert (``jax.lax.ragged_dot``)
and with the touched experts read where they lie by the Pallas kernel
(``ops.routed_experts.touched_experts_ffn``): where they cross is where
``conf.layers_moe.EVERY_EXPERT_SLOTS``'s edges belong. Times are the
program's on the DEVICE (a trace with the host tracer off, as the harness
traces), beside the host's clock over the same runs. Run it through the
chip tool from the root of a checkout; it writes
``chiprun_out/moe_crossover.json`` (``_parts.json``). ``tiny`` rehearses on the CPU (no
device plane there: the device time is left out, and the kernel's column
is whatever the CPU lowers to).

``parts`` takes the kernel apart instead, alone in a program, at each
``rows``: whole; its copies alone (the products left out); its products
alone (every step the same expert: the pipeline fetches it once); and
whole with an expert's hidden width in 2 and in 4 blocks a grid step
(``ops.routed_experts.STEP_BYTES_MAX``).

``prompt`` times a PROMPT's grouped product alone in a program, at
``tokens`` (default 512, 1,024, 2,048, 4,096: ``conf.layers_moe
.ROUTED_ROWS_MAX`` is the largest slice) at Trinity-Mini's widths and at
GigaChat3.5's (hidden 7168, 16 of 256 experts of 2048 held, top 8, the
SwiGLU clamped at 10): ``jax.lax.ragged_dot`` (``ops.routed_experts
.grouped_experts_ragged``) against the Pallas kernel over tiles of one
expert (``grouped_experts_ffn``) at each row tile of ``tm`` (default 128,
256, 512; the kernel takes ``ops.routed_experts.ROW_TILE``), with the
tiles' padding (the list's length over ``ceil(slots held / tm)``), the
products' TFLOP/s and the largest gap between the two sums. It writes
``chiprun_out/moe_crossover_prompt.json``.

    python tools/chip/moe_crossover.py [rows=8,16,32,64,128,256] [parts] [tiny]
    python tools/chip/moe_crossover.py prompt [tokens=...] [tm=...] [tiny]
"""
import functools
import glob
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())      # run from the root of a checkout

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.conf import layers_moe
from deeplearning4j_tpu.ops import routed_experts

applies = routed_experts.touched_experts_applies

TINY = "tiny" in sys.argv
ROWS = [8, 16, 32, 64, 128, 256]
TOKENS = [512, 1024, 2048, 4096]
TILES = [128, 256, 512]
for a in sys.argv[1:]:
    if a.startswith("rows="):
        ROWS = [int(x) for x in a[5:].split(",")]
    if a.startswith("tokens="):
        TOKENS = [int(x) for x in a[7:].split(",")]
    if a.startswith("tm="):
        TILES = [int(x) for x in a[3:].split(",")]


def device_us(run, reps):
    """Mean device time of the one program ``run()`` launches ``reps``
    times, or ``None`` where the trace has no device plane."""
    from benchmarks import trace_reduce as tr

    d = os.path.abspath(".bench_trace/moe")
    shutil.rmtree(d, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.host_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=options)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    try:
        path = glob.glob(d + "/plugins/profile/*/*.xplane.pb")[0]
        programs = tr.summarize(tr.read_planes(path))["fullest"]["programs"]
    except Exception as ex:  # noqa: BLE001 — the CPU has no device plane
        print(f"# no device time: {ex!r}", flush=True)
        return None
    runs = max(programs.values(), key=lambda r: sum(b - a for a, b in r))
    return round(sum(b - a for a, b in runs) / len(runs) * 1e-3, 1)


def time_both(rec, name, f, reps):
    """``f()`` launched ``reps`` times in a row (the same operands each
    time: the device runs its programs in turn), on both clocks."""
    def runs():
        for _ in range(reps):
            y = f()
        jax.block_until_ready(y)

    runs()
    t = time.perf_counter()
    runs()
    rec[name + "_host_us"] = round((time.perf_counter() - t) / reps * 1e6, 1)
    rec[name + "_us"] = device_us(runs, reps)


def kernel_parts(layer, params, d):
    """The kernel alone in a program, whole and taken apart, a record a
    row count."""
    from jax.experimental import pallas as pl

    re_ = routed_experts
    whole_body, whole_list = re_._touched_experts_kernel, re_.touched_list
    step_bytes = re_.STEP_BYTES_MAX

    def copies_alone(ids_ref, count_ref, x_ref, w_ref, wg_ref, wu_ref,
                     wd_ref, o_ref):
        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _zero():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[0:8, 0:128] += (wg_ref[0, 0:8, 0:128] + wu_ref[0, 0:8, 0:128]
                              + wd_ref[0, 0:8, 0:128]).astype(jnp.float32)

    def one_expert(sizes):
        ids, count = whole_list(sizes)
        return ids * 0, count

    stacks = [params[k] for k in ("Wg", "Wu", "Wd")]
    for n in ROWS:
        x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32)
        *_, w, chosen, sizes = layer._held_slots(params, x,
                                                jnp.ones((n,), bool))
        by_row, xb = layer._by_row(w, chosen), x.astype(stacks[0].dtype)
        rec = {"rows": n, "experts_touched": int(jnp.sum(sizes > 0))}
        for name, body, lister, limit in (
                ("whole", whole_body, whole_list, step_bytes),
                ("copies_alone", copies_alone, whole_list, step_bytes),
                ("products_alone", whole_body, one_expert, step_bytes),
                ("whole_2_blocks", whole_body, whole_list, step_bytes // 2),
                ("whole_4_blocks", whole_body, whole_list, step_bytes // 4)):
            if TINY and name == "copies_alone":
                continue        # its slices want whole tiles
            re_._touched_experts_kernel, re_.touched_list = body, lister
            re_.STEP_BYTES_MAX = limit
            f = jax.jit(lambda *a: re_.touched_experts_ffn.__wrapped__(
                *a, interpret=None))
            time_both(rec, name, lambda: f(xb, *stacks, by_row, sizes),
                      2 if TINY else 20)
        re_._touched_experts_kernel, re_.touched_list = whole_body, whole_list
        re_.STEP_BYTES_MAX = step_bytes
        yield rec


def layer_rows(layer, params, d, e, k):
    """The whole layer by each of its three products, a record a row
    count."""
    for n in ROWS:
        x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32)
        live = jnp.ones((n,), bool)
        rec = {"rows": n, "slots_an_expert": n * k / e}
        for name, slots, kernel in (("every_expert", (0, 1 << 30), False),
                                    ("grouped", (1 << 30, 1 << 30), False),
                                    ("kernel", (0, 1 << 30), True)):
            layers_moe.EVERY_EXPERT_SLOTS = slots
            routed_experts.touched_experts_applies = (
                applies if kernel else lambda *a: False)
            f = jax.jit(lambda p, x: layer.forward_live(p, x, live))
            rec["experts_touched"] = int(
                f(params, x)[1]["moe_experts_touched"])
            time_both(rec, name, lambda: f(params, x)[0], 2 if TINY else 20)
        yield rec


def prompt_products():
    """A prompt slice's grouped product alone in a program, by
    ``ragged_dot`` and by the kernel at each row tile, a record a
    configuration and token count."""
    re_ = routed_experts
    widths = {"trinity-mini": (2048, 128, 1024, 8, (0, 0), 0.0),
              "gigachat35": (7168, 256, 2048, 8, (0, 16), 10.0)}
    if TINY:
        widths = {"tiny": (64, 16, 32, 4, (0, 0), 0.0),
                  "tiny-held": (64, 32, 32, 4, (0, 4), 10.0)}
    for name, (d, e, h, k, held, limit) in widths.items():
        layer = layers_moe.RoutedExpertsLayer(
            n_out=d, n_experts=e, n_hidden=h, top_k=k, route_scale=2.826,
            weight_dtype="bfloat16", experts_held=held, swiglu_limit=limit)
        params = layer.init(jax.random.PRNGKey(41),
                            type("T", (), {"size": d})())
        stacks = [params[key] for key in ("Wg", "Wu", "Wd")]
        first, n_held = layer._held()
        for n in TOKENS:
            x = jax.random.normal(jax.random.PRNGKey(n), (n, d), jnp.float32)
            experts, mine, w, _, sizes = layer._held_slots(
                params, x, jnp.ones((n,), bool))
            group = jnp.where(mine, experts - first, n_held).reshape(-1)
            operands = (x.astype(stacks[0].dtype), *stacks, group,
                        w.reshape(-1), sizes)
            held_slots = int(jnp.sum(sizes))
            flop = 2.0 * held_slots * h * 3 * d
            rec = {"config": name, "tokens": n, "slots_held": held_slots,
                   "experts_touched": int(jnp.sum(sizes > 0)),
                   "tm_chosen": re_.ROW_TILE, "gflop": flop * 1e-9}
            reps = 2 if TINY else 10
            ragged = jax.jit(lambda *a: re_.grouped_experts_ragged(
                *a, k, limit))
            want = ragged(*operands)
            time_both(rec, "ragged", lambda: ragged(*operands), reps)
            interpret = jax.default_backend() != "tpu"
            for tm in TILES:
                kernel = functools.partial(
                    re_._grouped_tiles, top_k=k, tm=tm, limit=limit,
                    interpret=interpret)
                got = kernel(*operands)
                ids, count, slot, valid, _ = re_.tile_layout(group, sizes, tm)
                rec[f"tm{tm}_padding"] = int(count) / max(
                    1, -(-held_slots // tm))
                rec[f"tm{tm}_gap"] = float(jnp.max(jnp.abs(got - want)))
                time_both(rec, f"tm{tm}", lambda: kernel(*operands), reps)
                # the kernel alone, its rows laid out beforehand
                xp = operands[0][slot // k]
                wp = jnp.where(valid, w.reshape(-1)[slot], 0.0)[:, None]
                alone = jax.jit(lambda *a: re_.tiles_ffn(
                    *a, limit=limit, interpret=interpret))
                time_both(rec, f"tm{tm}_kernel_alone",
                          lambda: alone(xp, wp, *stacks, ids, count), reps)
            for key in ["ragged"] + [f"tm{tm}" for tm in TILES]:
                if rec.get(key + "_us"):
                    rec[key + "_tflops"] = flop / rec[key + "_us"] * 1e-6
            yield rec


def main():
    if "prompt" in sys.argv:
        out = {"device": jax.devices()[0].device_kind, "rows": []}
        for rec in prompt_products():
            print(json.dumps(rec), flush=True)
            out["rows"].append(rec)
        os.makedirs("chiprun_out", exist_ok=True)
        with open("chiprun_out/moe_crossover_prompt.json", "w") as f:
            json.dump(out, f, indent=1)
        return
    d, e, h, k = (64, 16, 32, 4) if TINY else (2048, 128, 1024, 8)
    layer = layers_moe.RoutedExpertsLayer(
        n_out=d, n_experts=e, n_hidden=h, top_k=k, n_shared_hidden=h,
        route_scale=2.826, weight_dtype="bfloat16")
    params = layer.init(jax.random.PRNGKey(33), type("T", (), {"size": d})())
    parts = "parts" in sys.argv
    out = {"device": jax.devices()[0].device_kind, "hidden": d, "experts": e,
           "expert_hidden": h, "top_k": k, "rows": []}
    for rec in (kernel_parts(layer, params, d) if parts
                else layer_rows(layer, params, d, e, k)):
        print(json.dumps(rec), flush=True)
        out["rows"].append(rec)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_crossover%s.json"
              % ("_parts" if parts else ""), "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
