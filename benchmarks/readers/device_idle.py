"""The device's idle share of the traced window, in %: 1 - the union of
the intervals in which an operation ran over the window, on the
fullest-used chip."""


def read(ctx, obs, params):
    trace = obs.get("trace")
    return None if trace is None else 100.0 * trace["idle_share"]
