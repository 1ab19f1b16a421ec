"""Mean device time of one run of a compiled program, in ms.

``params``: ``pattern`` (regular expression on the program's name in the
trace's ``XLA Modules`` line), ``pick`` (``all`` | ``most_runs`` |
``most_time``: which fingerprint, where several shapes share a name),
``divide_by`` (optional path into the configuration, e.g.
``serving.fused_steps``: the program runs that many steps).
"""

from benchmarks import trace_reduce


def runs_of(obs, params):
    trace = obs.get("trace")
    if trace is None:
        return []
    return trace_reduce.program_runs(trace, params["pattern"],
                                     params.get("pick", "all"))


def read(ctx, obs, params):
    runs = runs_of(obs, params)
    if not runs:
        return None
    ms = 1e-6 * sum(b - a for a, b in runs) / len(runs)
    if "divide_by" in params:
        node = ctx.config
        for key in params["divide_by"].split("."):
            node = node[key]
        ms /= float(node)
    return ms
