"""Device time of a sharded call's traffic program (``traffic.draw``,
``traffic.steer`` and ``traffic.exchange``: each chip's share of the
draw, its steering, and the moves of packets to the chips that hold
their pipes) per pipe-step of a profiled whole call, in us, over the
chips whose ops carry the program's names.  Not read from a trace that
lost kernel launches, nor from a cell without the traffic program."""
from bench import traffic_stages


def read(run):
    return traffic_stages.us_per_pipe_step(run, traffic_stages.SCOPES)
