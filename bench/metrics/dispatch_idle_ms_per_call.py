"""Device-idle time inside the ``repro.dispatch`` spans of a profiled
whole call, in ms: grouping, concatenation, fault masks, padding and the
enqueue of the compiled engine.  Read where the traced run profiles a
whole call."""
from bench import stages


def read(run):
    return stages.phase_idle_ms(run, ("repro.dispatch",))
