"""Device time of the in-flight window ring (``engine.ring``: its read and
write) per pipe-step of the profiled slice, in us: the counted device
ops whose op name carries the stage's scope, over the slice's pipe-
steps.  Not read from a trace that lost kernel launches, nor from a
program without the engine's stage scopes.  On several chips the ops'
time is summed over the chips, as the pipe-steps are over every chip's
pipes."""
from bench import stages


def read(run):
    return stages.stage_us_per_pipe_step(run, "ring")
