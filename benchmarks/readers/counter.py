"""A count the driver took over the whole window (``obs["counters"]``)."""


def read(ctx, obs, params):
    value = obs["counters"].get(params["key"])
    return None if value is None else float(value)
