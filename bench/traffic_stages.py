"""Where a profiled call spent its device time building its traffic.

A point sharded over several chips draws, steers and exchanges its
traffic in a program of its own (DESIGN.md §12), whose ops carry the
``traffic.draw``, ``traffic.steer`` and ``traffic.exchange`` scopes
(DESIGN.md §14).  As for the engine's stages (``stages.py``), a TPU
trace names each op by its HLO text alone, so the op names come from the
program's compiled text: the program's own function
(``scenarios.traffic_programs``, which ``run_matrix`` calls itself)
builds the traffic program the cell's driver runs, on the arguments the
driver passes, and its compile loads it from the compile cache.  Each
op of the trace is looked up there by its instruction name and result
dimensions.  An op whose name and dimensions the engine's text holds
too could belong to either program, and counts for neither.  A
control-flow op (the exchange's loop over its rounds) counts what its
body ops leave of its span, so each device ns counts once.

The stage readers read nothing where any chip's ops come unnamed: they
sum the stages' time over the chips and divide by every chip's
pipe-steps, so a chip left out would read low.  The traffic program
runs the same ops on every chip, each on an equal share of the call, so
the chips whose ops carry its names stand for the others: these readers
sum the time of those chips and divide it by those chips' own
pipe-steps.  An op of the program's names that one chip alone carries
is an op of that chip's own programs (the first chip runs the host's
eager ops) with the same name and dimensions, and counts for neither.
The readers read nothing where no chip carries the names, where a chip
lacks some of the program's ops that other chips carry (a partly named
chip: the trace sums an op's time over the chips, so its share cannot
be set apart), or where the trace lost kernel launches.

Every function returns None for a program without the traffic program
(one chip, or a program older than it).
"""
from __future__ import annotations

import collections
import functools
import json
import re

from bench import stages

SCOPES = ("draw", "steer", "exchange")
SCOPE = re.compile(r"traffic\.(draw|steer|exchange)\b")


def scope_of(op_name: str) -> str | None:
    """The traffic scope named in an op name, or None."""
    m = SCOPE.search(op_name)
    return m.group(1) if m else None


def traffic_names(cell) -> dict:
    """``stages.op_names`` of the compiled traffic program the cell runs,
    compiled from the configuration on call 0's arguments; once per
    cell.  Empty where the cell's program has no traffic program."""
    return _traffic_names(json.dumps(cell.config, sort_keys=True),
                          json.dumps(cell.traffic, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _traffic_names(config: str, traffic: str) -> dict:
    return stages.op_names(_traffic_text(json.loads(config),
                                         json.loads(traffic)))


def _traffic_text(config: dict, traffic: dict) -> str:
    """The compiled text of the traffic program the cell's driver runs,
    built by the program's own function; "" where there is none."""
    if config["engine"] != "run_matrix":
        return ""
    from bench.drivers import MatrixDriver
    try:
        from repro.scenarios import traffic_programs
    except ImportError:     # a program that draws every point on one chip
        return ""
    programs = traffic_programs([MatrixDriver(config, traffic, 0).spec(0)])
    if not programs:
        return ""
    (fn, args), = programs
    return fn.lower(*args).compile().as_text()


def scope_ns(trace, names: dict, engine: dict) -> tuple[dict, list] | None:
    """Device ns of the traffic program's ops by scope, summed over the
    chips whose ops carry the program's names, and those chips; None
    where no chip does, or where a chip lacks some of the program's ops
    that other chips carry.  An op that one chip alone carries, where
    several carry the names, is that chip's own and left out.  Ops under
    no traffic scope count as ``other``."""
    found = {op: own for op in trace.op_ns
             if (own := stages._own(op, names)) is not None}
    mine = {op: own for op, own in found.items()
            if stages._own(op, engine) is None}
    carried = [ops for d in trace.devices()
               if (ops := trace.device_ops.get(d, set()) & mine.keys())]
    chips = collections.Counter(op for ops in carried for op in ops)
    shared = {op for op, n in chips.items() if n > 1 or len(carried) == 1}
    named = [d for d in trace.devices()
             if trace.device_ops.get(d, set()) & shared]
    if not named or any(shared - trace.device_ops[d] for d in named):
        return None
    mine = {op: mine[op] for op in shared}
    traced = {name: trace.op_ns[op] for op, (name, _) in found.items()}
    out = dict.fromkeys(SCOPES + ("other",), 0.0)
    for op, (_, (_, op_name, children)) in mine.items():
        ns = trace.op_ns[op] - sum(traced.get(c, 0) for c in children)
        out[scope_of(op_name) or "other"] += ns
    return out, named


def us_per_pipe_step(run, scopes) -> float | None:
    """Device time of the traffic program's ops under ``scopes`` per
    pipe-step of the profiled whole call, in us, over the chips whose
    ops carry the program's names and their own pipe-steps."""
    trace, call = run.trace, run.traced
    if trace is None or call is None or not call.traced_whole \
            or not call.traced_steps or not run.trace_complete \
            or not trace.devices():
        return None
    names = traffic_names(run.cell)
    if not names:
        return None
    read = scope_ns(trace, names, stages.engine_names(run.cell))
    if read is None:
        return None
    by_scope, named = read
    steps = call.traced_steps * len(named) / len(trace.devices())
    return sum(by_scope[s] for s in scopes) / 1e3 / steps
