"""Share of device busy time spent in the Pallas kernels (``kernels/*``:
payload store and fetch, CRC, ACL match, Maglev select) in the profiled
slice, found in the trace by their Mosaic custom-call target, in %.  Not
read from a trace that lost kernel launches, nor from one in which a
device's ops go unnamed.  On several chips, a share of the time of all."""
from bench import tracefile


def read(run):
    if run.trace is None or not run.trace.devices() \
            or not run.trace_complete \
            or not tracefile.named_on_every_device(run.trace):
        return None
    lo, hi = tracefile.window(run.trace)
    busy = tracefile.busy_ns(run.trace, lo, hi) * len(run.trace.devices())
    return 100.0 * tracefile.kernel_ns(run.trace, lo, hi) / busy
