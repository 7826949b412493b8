"""Device-idle time inside the ``repro.finalize`` and ``repro.nf_cycles``
spans of a profiled whole call, in ms: the gathering of telemetry,
counters and NF counters, the regrouping per scenario point, and the
chain's cycle-cost probe.  Read where the traced run profiles a whole
call."""
from bench import stages


def read(run):
    return stages.phase_idle_ms(run, ("repro.finalize", "repro.nf_cycles"))
