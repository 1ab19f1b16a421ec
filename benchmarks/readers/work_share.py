"""A share of a peak, in %, from a function of the configuration's
``work`` module: ``params["fn"]`` names it. The function gets the run's
context, what the driver observed and ``params``, and returns
``(least_seconds, seconds_taken)`` or ``None`` when the trace holds
nothing for it to read. A share is never 0 and never rounded down to
100: either would hide a fault of the count."""


def read(ctx, obs, params):
    if obs.get("trace") is None or ctx.peak is None:
        return None
    out = getattr(ctx.module("work"), params["fn"])(ctx, obs, params)
    if out is None:
        return None
    least, taken = out
    if taken <= 0 or least <= 0:
        return None
    return 100.0 * least / taken
