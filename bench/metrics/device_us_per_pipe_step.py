"""Device busy time per pipe-step executed (drain steps included) in the
profiled slice, in us: the engine's cost of advancing one switch pipe by
one chunk.  On several chips the busy time is summed over them, as the
pipe-steps are summed over every chip's pipes: device time per
pipe-step, which reads alike on one chip and on four."""
from bench import tracefile


def read(run):
    if run.trace is None or not run.trace.devices() or run.traced is None:
        return None
    lo, hi = tracefile.window(run.trace)
    return tracefile.busy_ns(run.trace, lo, hi) \
        * len(run.trace.devices()) / 1e3 / run.traced.traced_steps
