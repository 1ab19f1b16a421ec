"""A count the driver took over the whole window (``obs["counters"]``)
over another times a number of the configuration, in %: ``key`` over
``over`` x ``config[per]``."""


def read(ctx, obs, params):
    counters = obs["counters"]
    num, den = counters.get(params["key"]), counters.get(params["over"])
    if num is None or not den:
        return None
    return 100.0 * float(num) / (float(den) * float(ctx.config[params["per"]]))
