"""Chip timing of the decode step's attention (PR 30; PERF.md §5), alone and
inside the real ``decode_fn(1024, 4)`` program of gpt2-large-serve's 36
layers. Run it through the chip tool from the root of a checkout; it
writes ``chiprun_out/attn_bench.json``. ``tiny`` rehearses the control flow
on the CPU (interpreter, toy sizes: no timing there means anything).

Stage 1: one layer's attention at [8, 1024, 1280] float32, 36 in a chain:
the masked read against the paged kernel at several pages, at the cell's
mix of positions, all rows full, all rows short.
Stage 2: the decode-window program (K = 4) with the masked read and with
the kernel at several pages. ``trace`` adds every operation's device time.
Stage 3 (PR 38): one layer's paged read of a bfloat16 cache alone, at the
attention layer of jamba2-3b-serve (128 rows, 20 heads over ONE KV head of
128, bucket 8,192, about 1,070 live positions a row) and of
trinity-mini-serve's full layer (32 rows, 32 heads over 4 KV heads, bucket
16,384, about 2,800), at each page by each product form: ``highest`` (the
page cast to float32, both products at HIGHEST) and ``parts``
(``exact_parts_dot``); ``parent=<dir>`` adds that checkout's kernel as it
stands (``today``, page 128).

    python tools/chip/attn_bench.py [stage1] [stage2] [stage3] [trace] [pages=128,256] [parent=<dir>] [tiny]
"""
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.getcwd())      # run from the root of a checkout

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import attention as A

OUT = {"device": None, "stage1": [], "stage2": [], "stage3": []}
TINY = "tiny" in sys.argv
MIXES = {
    "cell": [3, 9, 17, 40], "full": [63] * 4, "short": [7] * 4,
} if TINY else {
    "cell": [40, 95, 130, 160, 210, 290, 420, 560],     # 1,905 live
    "full": [1023] * 8,
    "short": [127] * 8,
}


def emit(stage, rec):
    OUT[stage].append(rec)
    print(json.dumps(rec), flush=True)


def traced(label, run, top=12):
    """Device time of every operation ``run()`` launches, from a trace
    with the host tracer off (as the harness traces)."""
    import glob
    import shutil

    from benchmarks import trace_reduce as tr

    d = os.path.abspath(".bench_trace/attn")
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
        summ = tr.summarize(tr.read_planes(path))
    except Exception as ex:  # noqa: BLE001
        print(f"# trace {label}: {ex!r}", flush=True)
        return
    progs = {n: [round((b - a) * 1e-6, 3) for a, b in runs]
             for n, runs in summ["fullest"]["programs"].items()}
    ops = [[k, round(t * 1e6, 1), n, txt[:150]]
           for k, t, n, txt in tr.top_ops_text(summ, top, 150)]
    rec = {"traced": label, "programs_ms": progs, "busy_s": summ["busy_s"],
           "top_ops_us_total_count": ops}
    OUT.setdefault("traces", []).append(rec)
    print(json.dumps(rec), flush=True)


def stage1(pages):
    b, s, h, d, layers = (4, 64, 2, 64, 2) if TINY else (8, 1024, 20, 64, 36)
    e = h * d
    rng = np.random.default_rng(30)
    caches = [(jnp.asarray(rng.normal(size=(b, s, e)).astype(np.float32)),
               jnp.asarray(rng.normal(size=(b, s, e)).astype(np.float32)))
              for _ in range(4)]
    q0 = jnp.asarray(rng.normal(size=(b, h, d)).astype(np.float32))

    variants = {"masked": A.decode_attention}
    for pg in pages:
        variants[f"hk{pg}"] = functools.partial(
            A.paged_decode_attention, page=pg, interpret=TINY)

    ref = {}
    for name, fn in variants.items():
        def chain(q, caches, pos, fn=fn):
            for i in range(layers):
                k, v = caches[i % len(caches)]
                q = q + 1e-3 * fn(q, k, v, pos)
            return q

        try:
            t0 = time.time()
            f = jax.jit(chain)
            one = jax.jit(lambda q, k, v, pos, fn=fn: fn(q, k, v, pos))
            for mix, pos in MIXES.items():
                pos = jnp.asarray(pos, jnp.int32)
                o = np.asarray(one(q0, *caches[0], pos))
                if name == "masked":
                    ref[mix] = o
                gap = float(np.max(np.abs(o - ref[mix])))
                f(q0, caches, pos).block_until_ready()
                n = 30
                t = time.perf_counter()
                for _ in range(n):
                    r = f(q0, caches, pos)
                r.block_until_ready()
                us = (time.perf_counter() - t) / n / layers * 1e6
                emit("stage1", {"variant": name, "mix": mix,
                                "us_a_layer": round(us, 2),
                                "max_abs_gap_to_masked": gap})
                if "trace" in sys.argv and name in ("masked", "hk128"):
                    traced(f"stage1 {name} {mix}", lambda: [
                        f(q0, caches, pos) for _ in range(3)
                    ][-1].block_until_ready(), top=6)
            print(f"# {name}: {time.time() - t0:.1f} s", flush=True)
        except Exception as ex:  # noqa: BLE001 — a refused variant is a result
            emit("stage1", {"variant": name, "error": str(ex)[:400]})


def stage2(pages):
    from benchmarks import run as bench_run  # noqa: F401  (path set-up)
    from benchmarks.models import gpt2 as model
    from benchmarks.reference import gpt2 as ref

    cfg = json.load(open("benchmarks/configs/gpt2-large-serve.json"))
    if TINY:
        over = cfg["rehearsal"]
        cfg = {**cfg, **over,
               "serving": {**cfg["serving"], **over["serving"]}}
    t0 = time.time()
    weights = ref.init_weights(cfg, 30)
    dec, _ = model.build(cfg, weights)
    jax.block_until_ready(weights)
    print(f"# weights: {time.time() - t0:.1f} s", flush=True)
    s, k = cfg["n_positions"], 4

    def fresh(pos):
        st = dec.new_state(s)
        b = dec.max_batch
        pos = jnp.asarray(pos, jnp.int32)
        return dict(st, positions=pos, prompt_lens=jnp.maximum(pos, 1),
                    max_new=jnp.full((b,), 1 << 20, jnp.int32),
                    active=jnp.ones((b,), bool),
                    tokens=jnp.arange(b, dtype=jnp.int32) + 5)

    for name in ["masked"] + [f"page{p}" for p in pages]:
        if name == "masked":
            A.decode_page = lambda s_, e_, i_: None
        else:
            pg = int(name[4:])
            A.decode_page = lambda s_, e_, i_, pg=pg: pg
        try:
            fn = jax.jit(lambda p, st: dec._decode_window(p, st, k),
                         donate_argnums=(1,))
            tc = time.time()
            for mix, pos in MIXES.items():
                if mix == "full":
                    pos = [s - 5] * len(pos)    # four steps end at s - 1
                st = fresh(pos)
                pos0 = np.asarray(pos, np.int32)
                st, toks, em, counts = fn(dec.params, st)
                jax.block_until_ready(toks)
                if mix == "cell":
                    print(f"# {name}: compiled in {time.time() - tc:.1f} s",
                          flush=True)
                first = np.asarray(toks)[:, :4].tolist()
                n = 12
                t = time.perf_counter()
                for _ in range(n):
                    st = dict(st, positions=pos0)
                    st, toks, em, counts = fn(dec.params, st)
                jax.block_until_ready(toks)
                ms = (time.perf_counter() - t) / n * 1e3
                emit("stage2", {
                    "variant": name, "mix": mix,
                    "window_ms": round(ms, 3),
                    "counts": {n_: int(c) for n_, c in counts.items()},
                    "first_tokens": first})
                if "trace" in sys.argv:
                    def three(st=st):
                        for _ in range(3):
                            st = dict(st, positions=pos0)
                            st, toks, _, _ = fn(dec.params, st)
                        jax.block_until_ready(toks)
                        return st

                    traced(f"stage2 {name} {mix}", three)
                del st
        except Exception as ex:  # noqa: BLE001
            emit("stage2", {"variant": name, "error": str(ex)[:600]})


def _highest_dot(x, y, y_contract):
    """The products of the kernel before PR 38: the bfloat16 page cast to
    float32, at HIGHEST."""
    return jax.lax.dot_general(
        x.astype(jnp.float32), y.astype(jnp.float32),
        (((1,), (y_contract,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _parent_kernel(parent):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "parent_attention", os.path.join(parent, "deeplearning4j_tpu", "ops",
                                         "attention.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.paged_decode_attention.__wrapped__


SHAPES3 = {     # rows, heads, KV heads, head size, bucket, live positions
    "jamba": (128, 20, 1, 128, 8192, (32, 2108)),
    "trinity": (32, 32, 4, 128, 16384, (256, 5344)),
}
SHAPES3_TINY = {"jamba": (4, 5, 1, 128, 256, (0, 130)),
                "trinity": (3, 8, 4, 128, 256, (0, 200))}


def stage3(pages, parent=None):
    """Each variant is a chain of ``layers`` reads over two cache pairs in
    one program; positions from ``--seed`` 38, uniform over the range, so
    their mean is the cell's."""
    layers, n = (2, 2) if TINY else (8, 20)
    for name, (b, h, g, d, s, span) in (SHAPES3_TINY if TINY
                                         else SHAPES3).items():
        e = g * d
        rng = np.random.default_rng(38)
        pos = jnp.asarray(rng.integers(*span, size=b), jnp.int32)
        keys = jax.random.split(jax.random.PRNGKey(38), 5)
        caches = [(jax.random.normal(keys[2 * i], (b, s, e), jnp.bfloat16),
                   jax.random.normal(keys[2 * i + 1], (b, s, e),
                                     jnp.bfloat16)) for i in range(2)]
        q0 = jax.random.normal(keys[4], (b, h, d), jnp.float32)
        live = int(np.sum(np.asarray(pos) + 1))
        variants = [(pg, form) for pg in pages for form in ("highest",
                                                            "parts")]
        if parent:
            variants.insert(0, (128, "today"))
        ref = None
        for pg, form in variants:
            if s % pg or s < 2 * pg:
                continue
            kernel = A.paged_decode_attention.__wrapped__
            if form == "today":
                kernel = _parent_kernel(parent)
            fn = functools.partial(kernel, page=pg, interpret=TINY, groups=g)
            keep = A.exact_parts_dot
            if form == "highest":
                A.exact_parts_dot = _highest_dot
            try:
                def chain(q, caches, pos, fn=fn):
                    for i in range(layers):
                        k, v = caches[i % 2]
                        q = q + 1e-3 * fn(q, k, v, pos)
                    return q

                f = jax.jit(chain)
                o = np.asarray(jax.jit(fn)(q0, *caches[0], pos))
                ref = o if ref is None else ref
                f(q0, caches, pos).block_until_ready()
                t = time.perf_counter()
                for _ in range(n):
                    r = f(q0, caches, pos)
                r.block_until_ready()
                us = (time.perf_counter() - t) / n / layers * 1e6
                steps = int(np.sum(np.asarray(pos) // pg + 1))
                read = steps * pg
                emit("stage3", {
                    "shape": name, "page": pg, "form": form,
                    "us_a_layer": round(us, 2), "grid_steps": steps,
                    "us_a_step": round(us / steps, 4),
                    "read_over_live": round(read / live, 4),
                    "live_GBps": round(2 * live * e * 2 / us * 1e-3, 1),
                    "read_GBps": round(2 * read * e * 2 / us * 1e-3, 1),
                    "max_abs_gap_to_first": float(np.max(np.abs(o - ref)))})
            except Exception as ex:  # noqa: BLE001 — a refused variant is a result
                emit("stage3", {"shape": name, "page": pg, "form": form,
                                "error": str(ex)[:400]})
            finally:
                A.exact_parts_dot = keep
        del caches


def main():
    args = sys.argv[1:]
    pages = [16, 32] if TINY else [64, 128, 256, 512]
    parent = None
    for a in args:
        if a.startswith("pages="):
            pages = [int(x) for x in a[6:].split(",")]
        if a.startswith("parent="):
            parent = a[7:]
    d = jax.devices()[0]
    OUT["device"] = {"platform": d.platform, "kind": d.device_kind}
    print("# device", OUT["device"], flush=True)
    if "stage1" in args:
        stage1(pages)
    if "stage2" in args:
        stage2(pages if TINY else [p for p in pages if p in (128, 256)])
    if "stage3" in args:
        stage3(pages if any(a.startswith("pages=") for a in args)
               else [32, 64] if TINY else [128, 256, 512, 1024], parent)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/attn_bench.json", "w") as f:
        json.dump(OUT, f, indent=1)


if __name__ == "__main__":
    main()
