"""Device-idle time inside the ``repro.prepare`` spans of a profiled
whole call, in ms: traffic generation, the chain's build and steering on
the host.  Read where the traced run profiles a whole call."""
from bench import stages


def read(run):
    return stages.phase_idle_ms(run, ("repro.prepare",))
