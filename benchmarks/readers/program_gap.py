"""Mean idle time of the device between the end of one run of a program
and the start of the next, in ms: of each interval between consecutive
runs, the part in which no operation ran. ``params`` as ``program_time``.
"""

from benchmarks import trace_reduce
from benchmarks.readers.program_time import runs_of


def read(ctx, obs, params):
    runs = runs_of(obs, params)
    if len(runs) < 2:
        return None
    busy = obs["trace"]["fullest"]["busy"]
    idle = 0.0
    for (_a, end), (start, _b) in zip(runs, runs[1:]):
        if start <= end:
            continue
        covered = trace_reduce.total(trace_reduce.clip(busy, end, start))
        idle += (start - end) - covered
    return 1e-6 * idle / (len(runs) - 1)
