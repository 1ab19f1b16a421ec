"""Batch fill of the decode windows in the traced part of the window, in
%: tokens the decode windows emitted (all tokens less one per join, which
the prefill emits) over decode-window runs x K x ``max_batch``. The runs
are counted in the trace (``params`` as ``program_time``)."""

from benchmarks.readers.program_time import runs_of


def read(ctx, obs, params):
    traced = obs.get("traced")
    runs = runs_of(obs, params)
    if not traced or not runs:
        return None
    s = ctx.config["serving"]
    decoded = traced["tokens"] - traced["joined"]
    return 100.0 * decoded / (len(runs) * s["fused_steps"] * s["max_batch"])
