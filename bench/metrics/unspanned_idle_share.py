"""Share of a profiled whole call's device-idle time inside no
``repro.*`` span, in %: host time the program's phases do not name.
Read where the traced run profiles a whole call."""
from bench import stages


def read(run):
    return stages.unspanned_share(run)
