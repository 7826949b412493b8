"""Share of the profiled slice in which no operation ran on the device:
1 - (union of device op intervals) / (slice), in %."""
from bench import tracefile


def read(run):
    if run.trace is None or not run.trace.devices():
        return None
    lo, hi = tracefile.window(run.trace)
    return 100.0 * (1.0 - tracefile.busy_ns(run.trace, lo, hi) / (hi - lo))
