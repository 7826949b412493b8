"""Share of the counted device op time of the profiled slice under none
of the five stage scopes (split, lane, nf, ring, merge), in %: the
engine's telemetry (``engine.tally``), loop control, traffic generation
and steering on the device, and eager result ops.  Not read from a trace
that lost kernel launches, nor from a program without the scopes."""
from bench import stages


def read(run):
    return stages.other_share(run)
