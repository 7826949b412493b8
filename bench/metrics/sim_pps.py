"""Alive offered packets simulated per second of the window (host clock):
all packets of all whole calls, over the wall time the calls took.  A
call ends when all it returned is ready on the device; the benchmark's
copies of the checked pipes, made between calls, are not the simulator's
time and are left out."""


def read(run):
    return sum(c.packets for c in run.calls) / run.calls_s
