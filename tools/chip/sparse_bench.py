"""Chip timing of ONE sparse layer's decode read (PR 34; PERF.md §6) at the
``minicpm-sala-serve-doc16k`` cell's shapes: 16 rows, a bucket of 32,768,
contexts 12 k to 27 k, 32 query heads over 2 KV heads of 128, bfloat16
caches, float32 compressed keys. The two halves apart and together:

- the choice: the block scores alone, then with ``lax.top_k`` (a sort of
  512 scores on the TPU) and with ``block_sparse.rank_blocks``;
- the read: ``gathered_decode_attention`` (gathers, concatenations, one
  softmax) against the kernel ``sparse_read_attention`` at a few sizes of
  the running softmax's chunk;
- the whole of ``sparse_decode_attention`` as a TPU lowers it, against
  scores + ``top_k`` + the gathered read (what it replaced).

Times are the program's on the DEVICE (a trace with the host tracer off,
as the harness traces). It also says whether the kernel's output and
counts and the sortless choice are the gathered path's ON THE CHIP. Run it
through the chip tool from the root of a checkout; it writes
``chiprun_out/sparse_bench.json``. ``tiny`` rehearses on the CPU (the
kernel through the interpreter; no device plane: no time).

    python tools/chip/sparse_bench.py [chunks=1024,2048,4096] [ops] [tiny]
"""
import glob
import json
import os
import shutil
import sys

sys.path.insert(0, os.getcwd())      # run from the root of a checkout

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.ops import block_sparse as bs

TINY = "tiny" in sys.argv
OPS = "ops" in sys.argv
CHUNKS = [1024, 2048, 4096]
for a in sys.argv[1:]:
    if a.startswith("chunks="):
        CHUNKS = [int(x) for x in a[7:].split(",")]


def device_us(run, top=0):
    """Mean device time of the one program ``run()`` launches again and
    again (and its ``top`` operations by time), or ``None`` where the
    trace has no device plane."""
    from benchmarks import trace_reduce as tr

    d = os.path.abspath(".bench_trace/sparse")
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
    except Exception as ex:  # noqa: BLE001 — the CPU has no device plane
        print(f"# no device time: {ex!r}", flush=True)
        return None, []
    runs = max(summ["fullest"]["programs"].values(),
               key=lambda r: sum(b - a for a, b in r))
    ops = [[k, round(t * 1e6 / len(runs), 1), n // len(runs), txt]
           for k, t, n, txt in tr.top_ops_text(summ, top, 160)]
    return round(sum(b - a for a, b in runs) / len(runs) * 1e-3, 1), ops


def main():
    b, s, h, g, d = (4, 512, 4, 2, 8) if TINY else (16, 32768, 32, 2, 128)
    spec = (bs.SparseSpec(kernel=8, stride=4, block=8, window=32,
                          init_blocks=1, topk=4, dense_len=64)
            if TINY else bs.SparseSpec())
    reps = 2 if TINY else 20
    rng = np.random.default_rng(34)
    lo, hi = (200, 500) if TINY else (12288, 27000)
    pos = jnp.asarray(rng.integers(lo, hi, size=b), jnp.int32)
    key = jax.random.split(jax.random.PRNGKey(34), 3)
    q = 1.6 * jax.random.normal(key[0], (b, h, d), jnp.float32)
    k = jax.random.normal(key[1], (b, s, g * d), jnp.float32).astype(
        jnp.bfloat16)
    v = jax.random.normal(key[2], (b, s, g * d), jnp.float32).astype(
        jnp.bfloat16)
    ck = jax.jit(lambda k: bs.compress_keys(k, spec))(k)
    topk = min(spec.topk, s // spec.block)
    out = {"device": jax.devices()[0].device_kind, "rows": b, "bucket": s,
           "positions": np.asarray(pos).tolist(), "parts": []}
    interpret = jax.default_backend() != "tpu"

    def scores(q, ck, pos):
        return bs.block_scores(q[:, None], ck, pos[:, None], spec, g)[:, 0]

    def sorted_choice(q, ck, pos):
        vals, idx = jax.lax.top_k(scores(q, ck, pos), topk)
        return idx.astype(jnp.int32), vals >= 0.0

    def ranked_choice(q, ck, pos):
        return bs.rank_blocks(scores(q, ck, pos), topk)

    def timed(name, fn, *args, **more):
        f = jax.jit(fn)
        res = f(*args)
        jax.block_until_ready(res)

        def runs():
            for _ in range(reps):
                r = f(*args)
            jax.block_until_ready(r)

        us, ops = device_us(runs, 12 if OPS else 0)
        rec = {"part": name, "device_us": us, **more}
        if ops:
            rec["ops_us_count"] = ops
        print(json.dumps(rec), flush=True)
        out["parts"].append(rec)
        return res

    timed("scores", scores, q, ck, pos)
    idx, ok = timed("scores+top_k", sorted_choice, q, ck, pos)
    idx2, ok2 = timed("scores+rank_blocks", ranked_choice, q, ck, pos)
    same = bool((idx == idx2).all() and (ok == ok2).all())
    o, at, rd = timed(
        "gathered", lambda *a: bs.gathered_decode_attention(*a, spec, g),
        q, k, v, idx, ok, pos)
    for chunk in CHUNKS:
        o2, at2, rd2 = timed(
            "kernel", lambda *a: bs.sparse_read_attention(
                *a, spec, g, interpret=interpret, chunk=chunk),
            q, k, v, idx2, ok2, pos, chunk=chunk)
        out["parts"][-1].update(
            gap_to_gathered=float(jnp.abs(o - o2).max()),
            attended_equal=bool((at == at2).all()),
            read_over_attended=float(rd2.sum() / at2.sum()))
        print(json.dumps(out["parts"][-1]), flush=True)
    timed("whole: scores+top_k+gathered",
          lambda q, k, v, ck, pos: bs.gathered_decode_attention(
              q, k, v, *sorted_choice(q, ck, pos), pos, spec, g),
          q, k, v, ck, pos)
    if not interpret:
        timed("whole: sparse_decode_attention",
              lambda *a: bs.sparse_decode_attention(*a, spec, g),
              q, k, v, ck, pos)
    out["rank_blocks_is_top_k"] = same
    out["gathered_read_over_attended"] = float(rd.sum() / at.sum())
    print(json.dumps({"rank_blocks_is_top_k": same}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/sparse_bench.json", "w") as f:
        json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
