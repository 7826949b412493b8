"""Host time per call: the part of a whole profiled call in which no
operation ran on the device, in ms.  It covers the entry points' host
code: traffic generation and steering, dispatch, and the gathering of
results.  Read where the traced run profiles a whole call."""
from bench import tracefile


def read(run):
    if run.trace is None or run.traced is None \
            or not run.traced.traced_whole:
        return None
    lo, hi = tracefile.window(run.trace)
    return ((hi - lo) - tracefile.busy_ns(run.trace, lo, hi)) / 1e6
