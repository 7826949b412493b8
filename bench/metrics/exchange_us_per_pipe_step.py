"""Device time of a sharded call's packet exchange (``traffic.exchange``:
the rounds of ``all_to_all`` that move each packet to the chip that
holds its pipe, and the gathers around them) per pipe-step of a
profiled whole call, in us, over the chips whose ops carry the traffic
program's names.  Not read from a trace that lost kernel launches, nor
from a cell without the traffic program."""
from bench import traffic_stages


def read(run):
    return traffic_stages.us_per_pipe_step(run, ("exchange",))
