"""Alive offered packets simulated per second of the window (host clock):
all packets of all whole calls, over the wall time from the first call's
start to the last call's end."""


def read(run):
    return sum(c.packets for c in run.calls) / run.window_s
