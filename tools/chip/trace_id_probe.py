"""What the runtime exposes of a loaded executable, beside what the trace's
`XLA Modules` line prints in brackets, on a small GPT-2-style decoder: run on
the chip, twice in one call (the second process loads its executables from the
compile cache: does the compiled text keep its `op_name` metadata then?).

    python tools/chip/trace_id_probe.py

Prints, per executable of `aot_cache.programs()`: kind, module name,
dispatches, every candidate identifier the Python API hands out, what
`hlo_modules()`, `to_string()` and `parse_scopes` cost, and then the traced
modules' names and `device_time.by_scope`'s table. `PERF.md` §7 records the
answer (PR 37): the `benchmark` PR that keys traced operations by program
needs the identifier.
"""
import glob
import os
import shutil
import sys
import time

sys.path.insert(0, os.getcwd())

import jax  # noqa: E402
import numpy as np  # noqa: E402

from deeplearning4j_tpu.optimize import aot_cache  # noqa: E402
from deeplearning4j_tpu.telemetry import device_time  # noqa: E402
from deeplearning4j_tpu.zoo.graphs import TransformerEncoder  # noqa: E402


def varint(blob: bytes, i: int):
    v, shift = 0, 0
    while True:
        b = blob[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def top_level_varints(blob: bytes) -> dict:
    """Field number -> value of the varint fields at the top level of a
    serialized protobuf message (`HloModuleProto.id` is field 5)."""
    out, i = {}, 0
    while i < len(blob):
        key, i = varint(blob, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            out[field], i = varint(blob, i)
        elif wire == 2:
            n, i = varint(blob, i)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            break
    return out


def main():
    aot_cache.place_compile_cache()
    print("device:", jax.devices()[0].platform, jax.devices()[0].device_kind)
    zoo = TransformerEncoder(vocab_size=512, embed_dim=256, n_heads=4,
                             n_layers=2, max_len=256, lm_head=True,
                             causal=True, seed=0)
    dec = zoo.decoder(max_batch=4, kv_bucket_min=256, prompt_bucket_min=32)
    prompt = list(np.arange(1, 20) % 500)
    dec.generate(prompt, 9, fused_steps=4)          # compiles or loads
    print("aot:", aot_cache.stats())
    trace_dir = os.path.join(os.getcwd(), ".bench_trace_probe")
    shutil.rmtree(trace_dir, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for _ in range(3):
        dec.generate(prompt, 9, fused_steps=4)
    jax.profiler.stop_trace()
    stats = jax.devices()[0].memory_stats() or {}
    print("memory before clear:", {k: stats.get(k) for k in (
        "bytes_in_use", "bytes_reserved")})
    for key, exe in list(aot_cache._EXECUTABLES.items()):
        rt = exe.runtime_executable()
        t0 = time.monotonic()
        mod = rt.hlo_modules()[0]
        t1 = time.monotonic()
        text = mod.to_string()
        t2 = time.monotonic()
        table = device_time.parse_scopes(text)
        t3 = time.monotonic()
        ids = {}
        for attr in ("fingerprint", "name", "unique_id", "id"):
            for obj, label in ((rt, "rt"), (mod, "hlo"), (exe, "compiled")):
                try:
                    v = getattr(obj, attr)
                    v = v() if callable(v) else v
                    ids[f"{label}.{attr}"] = v.decode(
                        "ascii", "backslashreplace") if isinstance(
                        v, bytes) else v
                except Exception:
                    pass
        try:
            proto = mod.as_serialized_hlo_module_proto()
            ids["proto.varints"] = {k: v for k, v in top_level_varints(
                proto).items() if k in (5, 6, 13, 14)}
            ids["proto.bytes"] = len(proto)
        except Exception as e:
            ids["proto"] = repr(e)
        print(f"PROGRAM {key[1]} dispatches "
              f"{aot_cache.STATS.dispatches.get(key, 0)} hlo_modules "
              f"{1e3 * (t1 - t0):.1f} ms to_string {1e3 * (t2 - t1):.1f} ms "
              f"({len(text)} chars, op_name in text: "
              f"{text.count('op_name=')}) parse {1e3 * (t3 - t2):.1f} ms "
              f"({len(table)} instructions) ids {ids}")
        print("   rt attrs:", [a for a in dir(rt) if not a.startswith("_")])
        print("   hlo attrs:", [a for a in dir(mod) if not a.startswith("_")])
    t0 = time.monotonic()
    aot_cache.clear()
    print(f"clear() {1e3 * (time.monotonic() - t0):.1f} ms, kept "
          f"{len(aot_cache.programs())}")
    del dec
    import gc
    gc.collect()
    stats = jax.devices()[0].memory_stats() or {}
    print("memory after clear:", {k: stats.get(k) for k in (
        "bytes_in_use", "bytes_reserved")})
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    lines = device_time.read_device_lines(files[-1])
    for dev in lines.values():
        print("TRACED MODULES:", sorted({n for n, _a, _b in dev["modules"]}))
        for n, a, b in dev["ops"][:3]:
            print("   op:", n[:200])
    result = device_time.by_scope(files[-1], aot_cache.programs())
    print(device_time.format_table(result))
    shutil.rmtree(trace_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
