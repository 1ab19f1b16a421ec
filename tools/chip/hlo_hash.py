"""sha256 of the StableHLO the serving cells' programs lower to FOR THE TPU,
from this CPU host, no weights and no chip: a change that claims to move
no number shows equal hashes on the parent and on itself.

    python tools/chip/hlo_hash.py [checkout]     # default: this checkout

Per serving configuration of ``BENCHMARK.json`` (at the cell's own shapes,
from ``ShapeDtypeStruct``s): ``decode_fn(S, K)``, ``prompt_fn`` at the first
prompt bucket and join width 1, and the ``join_fn`` that follows it. One
line each, ``<config> <program> <sha256>``; the text goes to
``chiprun_out/hlo/<label>/`` when ``--keep <label>`` is given. A Pallas
kernel's body is hashed as MLIR without locations
(:func:`without_locations`).
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import re
import sys


def without_locations(txt):
    """``txt`` with every Mosaic kernel's serialized body replaced by its
    MLIR printed WITHOUT debug locations: the bytecode carries the file
    path, line and Python call stack of every operation, so it differs
    between two checkouts (and after a docstring edit) though no operation
    does. StableHLO's own text prints no locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def plain(m):
        with mlir.JaxIrContext() as ctx:
            ctx.allow_unregistered_dialects = True
            return ir.Module.parse(base64.b64decode(m.group(1))) \
                .operation.get_asm(enable_debug_info=False)

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', plain, txt)


def programs(cfg):
    import importlib

    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.graph import ComputationGraph

    model = importlib.import_module(f"benchmarks.models.{cfg['model']}")
    # the tree init() would build, as avals: build() checks the weights
    # against exactly this, so it stands in for them
    if cfg["model"] == "gpt2":
        from deeplearning4j_tpu.zoo.graphs import TransformerEncoder
        conf = TransformerEncoder(
            vocab_size=cfg["vocab_size"], embed_dim=cfg["n_embd"],
            n_heads=cfg["n_head"], n_layers=cfg["n_layer"],
            ffn_dim=cfg["n_inner"] or 4 * cfg["n_embd"],
            max_len=cfg["n_positions"], lm_head=True, causal=True,
            seed=0).conf()
    else:
        conf = model.zoo(cfg).conf()
    avals = jax.eval_shape(lambda: ComputationGraph(conf).init().params)
    dec, gen = model.build(cfg, avals)
    sds = jax.ShapeDtypeStruct
    s, k = dec.kv_ladder[-1], gen.fused_steps
    tp, bp = dec.prompt_ladder[0], 1
    row = lambda dt, *tail: sds((bp,) + tail, dt)  # noqa: E731
    i32 = row(jnp.int32)
    yield f"decode_fn({s},{k})", dec.decode_fn(s, k), (
        avals, dec._struct_of(s))
    yield f"prompt_fn({tp},{bp})", dec.prompt_fn(tp, bp), (
        avals, row(jnp.int32, tp), i32, i32, i32, row(jnp.float32),
        row(jnp.uint32, 2))
    yield f"join_fn({s},{tp},{bp})", dec.join_fn(s, tp, bp), (
        dec._struct_of(s), dec._kv_struct(bp, tp), i32, i32, i32, i32, i32,
        row(jnp.float32), row(jnp.uint32, 2), row(jnp.bool_))


def main(argv):
    keep = None
    if "--keep" in argv:
        i = argv.index("--keep")
        keep = argv[i + 1]
        del argv[i:i + 2]
    here = os.getcwd()
    root = os.path.abspath(argv[0] if argv else here)
    sys.path.insert(0, root)
    os.chdir(root)
    for name in ("gpt2-large-serve", "minicpm-sala-serve",
                 "trinity-mini-serve", "jamba2-3b-serve", "gigachat35-serve",
                 "lfm2-8b-a1b-serve"):
        path = os.path.join(root, "benchmarks", "configs", name + ".json")
        if not os.path.exists(path):    # a parent checkout lacks the newest
            print(name, "not in this checkout", flush=True)
            continue
        with open(path) as f:
            cfg = json.load(f)
        for label, step, args in programs(cfg):
            txt = without_locations(step.jit_fn.trace(*args).lower(
                lowering_platforms=("tpu",)).as_text())
            if keep:
                d = os.path.join(here, "chiprun_out", "hlo", keep)
                os.makedirs(d, exist_ok=True)
                with open(os.path.join(d, f"{name}.{label}.txt"), "w") as f:
                    f.write(txt)
            print(name, label, hashlib.sha256(txt.encode()).hexdigest(),
                  flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
