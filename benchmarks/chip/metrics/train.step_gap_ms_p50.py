"""Median device gap between consecutive training steps: from the end of
one run of the step executable to the start of the next, in the trace."""
import numpy as np

import trace_reduce as TR

STEP_MODULE = "jit_step"


def read(ctx):
    ev = ctx["events"]
    gaps = []
    for plane in TR.device_planes(ev):
        runs = TR.module_runs(ev, plane, STEP_MODULE)
        gaps += [b[0] - a[1] for a, b in zip(runs, runs[1:])]
    if not gaps:
        return None
    return float(np.median(gaps)) / 1e6
