"""Where in the program a profiled slice spent its device and idle time.

The program names its own parts (DESIGN.md §14):

  * device ops carry the engine stage that made them in their op name
    metadata, from a ``jax.named_scope`` in ``engine.scan_step``
    (``engine.split``, ``engine.lane``, ``engine.nf``, ``engine.ring``,
    ``engine.merge``, ``engine.tally``);
  * the entry points' host phases are profiler spans on the thread that
    calls them (``repro.prepare``, ``repro.dispatch``,
    ``repro.finalize``, ``repro.nf_cycles``), which ``tracefile.load``
    keeps among the host events of that thread.

A TPU trace names each device op by its HLO text alone, without its op
name metadata.  So the op names come from the compiled engine's own
text (``Compiled.as_text()``): the program's own functions
(``scenarios.engine_programs``, ``switchsim.stream.segment_program``,
which the entry points call themselves) build the engine program the
cell's driver runs, on the arguments the driver passes; its compile
loads it from the compile cache.  Each op of the trace is looked up
there by its instruction name and result dimensions.  An op of another
program (traffic generation, eager result ops) is not found there, or
not with its dimensions, and counts as no stage.  A module other than
the one that ran leaves the engine's loop unfound, or leaves it the
time of body ops it does not know, and then reads nothing.

Each device ns counts once.  A control-flow op (``while``,
``conditional``, ``call``) spans the ops of its body, which the trace
lists too; it counts only the time they leave of its span, which the
module's text tells apart: loop control, or body work the trace does
not list op by op.  A fusion carries the op name of its root
instruction, so a fusion that crosses two stages counts for one of
them.

Every function returns None where the program names nothing: a trace
of a program without these scopes or spans reads nothing.
"""
from __future__ import annotations

import functools
import json
import re

from bench import tracefile

STAGES = ("split", "lane", "nf", "ring", "merge")
SCOPE = re.compile(r"engine\.(split|lane|nf|ring|merge|tally)\b")
PHASE_PREFIX = "repro."
# an HLO control-flow op: ``%while.697 = (...) while(%t), body=...``; a
# Pallas kernel is a ``custom-call(``, which is no control flow
CONTROL_TEXT = re.compile(r"(?<![\w-])(while|conditional|call)\(")
# a compiled module's text: the first line of each computation, and each
# instruction with its op name and the computations it calls
COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (\S+) (.*)$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
CALLED = re.compile(r"(?:condition|body|to_apply|true_computation|"
                    r"false_computation)=(%[\w.\-]+)|"
                    r"branch_computations=\{([^}]*)\}")
LAYOUT = re.compile(r"\{[^}]*\}")
# the share of the engine's outer loop's span that no body op of the
# module accounts for, above which the module is taken for another than
# the one that ran; the loop's own control reads 0.001% (.dc) and 0.014%
# (the stream) of it on a TPU v5e
LOOP_OWN = 0.01


def is_control(text: str) -> bool:
    """Whether an op (its HLO text) is a control-flow op, whose body runs
    as ops of their own."""
    return tracefile.PALLAS not in text and bool(CONTROL_TEXT.search(text))


def stage_of(op_name: str) -> str | None:
    """The engine stage named in an op name (``tally`` included), or
    None for an op outside every stage scope."""
    m = SCOPE.search(op_name)
    return m.group(1) if m else None


def _dims(shape: str) -> str:
    """A result shape without its layouts, which a trace and a module's
    text may print differently."""
    return LAYOUT.sub("", shape)


def op_names(hlo_text: str) -> dict:
    """Instruction name -> (result dimensions, op name, the instructions
    of the computations it runs, for a control-flow op) of a compiled
    module's text."""
    ops, members, calls, comp = {}, {}, {}, None
    for line in hlo_text.splitlines():
        inst = INSTRUCTION.match(line)
        if inst is None:
            head = COMPUTATION.match(line)
            comp = head.group(1) if head else comp
            continue
        name, shape, rest = inst.groups()
        op_name = OP_NAME.search(rest)
        ops[name] = (_dims(shape), op_name.group(1) if op_name else "")
        members.setdefault(comp, []).append(name)
        if is_control(f"{shape} {rest}"):
            calls[name] = [c.strip() for one, many in CALLED.findall(rest)
                           for c in ([one] if one else many.split(","))]
    return {name: (dims, op_name, tuple(
        m for c in calls.get(name, ()) for m in members.get(c, ())))
        for name, (dims, op_name) in ops.items()}


def _own(op: str, names: dict):
    """The compiled module's entry for a trace's op (its HLO text), or
    None where the module has no instruction of that name and result
    dimensions: an op of another program."""
    name, _, rest = op.partition(" = ")
    entry = names.get(name)
    if entry is None or entry[0] != _dims(rest.split(" ", 1)[0]):
        return None
    return name, entry


def outer_loops(names: dict) -> set:
    """The module's control-flow ops that no other control op runs: the
    engine's loop over its steps."""
    inner = {c for _, _, children in names.values() for c in children}
    return {name for name, (_, _, children) in names.items()
            if children and name not in inner}


def stage_ns(trace, names: dict) -> dict | None:
    """Device ns by stage, summed over the devices used, each ns
    counted once: an op of the engine's module counts its own time, so a
    control-flow op counts what its body ops' time leaves of its span
    (loop control, or a body the trace does not list op by op), for the
    stage it belongs to.  A control-flow op of another program is left
    out: its body ops count.  Ops under no stage (and under
    ``engine.tally``) are ``other``.

    None where no op carries a stage scope, and where the module is not
    the one that ran: a device's ops lack the engine's outer loop (as
    ops that come without their HLO text do), or the loop keeps more
    than ``LOOP_OWN`` of its span for itself, the time of body ops the
    module does not know."""
    if trace is None or not trace.devices():
        return None
    mine = {op: found for op in trace.op_ns
            if (found := _own(op, names)) is not None}
    traced = {name: trace.op_ns[op] for op, (name, _) in mine.items()}
    loops = outer_loops(names) & set(traced)
    for dev in trace.devices():
        ran = {mine[op][0] for op in trace.device_ops.get(dev, ())
               if op in mine}
        if not loops & ran:
            return None
    out = dict.fromkeys(STAGES + ("other",), 0.0)
    named = False
    for op, ns in trace.op_ns.items():
        if op in mine:
            name, (_, op_name, children) = mine[op]
            own = ns - sum(traced.get(c, 0) for c in children)
            if name in loops and own > LOOP_OWN * ns:
                return None
            ns, stage = own, stage_of(op_name)
        elif is_control(trace.op_text.get(op, op)):
            continue
        else:
            stage = None
        named = named or stage is not None
        out[stage if stage in STAGES else "other"] += ns
    return out if named else None


def engine_names(cell) -> dict:
    """``op_names`` of the compiled engine program the cell runs (the
    materialized engine, or the stream's segment program), compiled
    from the configuration on call 0's traffic; once per cell."""
    return _engine_names(json.dumps(cell.config, sort_keys=True),
                         json.dumps(cell.traffic, sort_keys=True))


@functools.lru_cache(maxsize=None)
def _engine_names(config: str, traffic: str) -> dict:
    return op_names(_engine_text(json.loads(config), json.loads(traffic)))


def _engine_text(config: dict, traffic: dict) -> str:
    """The compiled text of the program the cell's driver runs, built by
    the program's own functions on the arguments the driver passes; ""
    from a program without them."""
    from bench.drivers import MatrixDriver, SliceSource, StreamDriver
    try:
        from repro.scenarios import engine_programs
        from repro.switchsim.stream import segment_program
    except ImportError:     # a program older than its stage scopes
        return ""

    if config["engine"] == "run_stream":
        drv = StreamDriver(config, traffic, 0)
        drv.program_facts()     # builds the driver's chain and source
        fn, args = segment_program(
            drv.park, drv.chain,
            SliceSource(drv._source, drv.offset(0), drv.steps),
            window=config["window"], segment_len=config["segment_len"],
            backend=config["backend"], reservoir=config["reservoir"],
            reservoir_seed=config["reservoir_seed"])
    else:
        (fn, args), = engine_programs([MatrixDriver(config, traffic,
                                                    0).spec(0)])
    return fn.lower(*args).compile().as_text()


def _stage_ns(run) -> dict | None:
    """``stage_ns`` of a complete profiled slice, else None."""
    if run.trace is None or not run.trace_complete \
            or not run.trace.devices():
        return None
    return stage_ns(run.trace, engine_names(run.cell))


def stage_us_per_pipe_step(run, stage: str) -> float | None:
    """Device time of one stage per pipe-step of the profiled slice, in
    us; read only from a trace that holds every kernel launch.  On
    several chips the stage's time is summed over them, as the
    pipe-steps are summed over every chip's pipes."""
    if run.traced is None or not run.traced.traced_steps:
        return None
    by_stage = _stage_ns(run)
    if by_stage is None:
        return None
    return by_stage[stage] / 1e3 / run.traced.traced_steps


def other_share(run) -> float | None:
    """Share of the counted device op time under none of the five stage
    scopes, in %."""
    by_stage = _stage_ns(run)
    if by_stage is None:
        return None
    total = sum(by_stage.values())
    return 100.0 * by_stage["other"] / total if total else None


def phase_spans(trace) -> dict:
    """Host spans of the program's phases, by name."""
    out: dict = {}
    for name, s, e in trace.host:
        if name.startswith(PHASE_PREFIX):
            out.setdefault(name, []).append((s, e))
    return out


def idle_ns(trace, intervals, lo: int, hi: int) -> float:
    """Device-idle ns of ``[lo, hi)`` inside the union of ``intervals``,
    averaged over the devices used (all of it where no device ran)."""
    merged = tracefile.union(intervals)
    devs = trace.devices()
    if not devs:
        return float(tracefile.overlap(merged, lo, hi))
    return sum(tracefile.overlap(merged, gs, ge) for d in devs
               for gs, ge in tracefile.gaps(trace.busy[d], lo, hi)) \
        / len(devs)


def _whole_call(run):
    """The trace and window of a profiled whole call whose program names
    its phases, else None."""
    if run.trace is None or run.traced is None \
            or not run.traced.traced_whole:
        return None
    spans = phase_spans(run.trace)
    if not spans:
        return None
    return spans, tracefile.window(run.trace)


def phase_idle_ms(run, names) -> float | None:
    """Device-idle time inside the spans of the named phases during a
    profiled whole call, in ms: the idle time inside the union of those
    spans, so a span nested in another of them counts once, and phases
    with no span read 0.  A span of another phase nested in one of them
    counts for both metrics."""
    found = _whole_call(run)
    if found is None:
        return None
    spans, (lo, hi) = found
    return idle_ns(run.trace, [iv for n in names for iv in spans.get(n, [])],
                   lo, hi) / 1e6


def unspanned_share(run) -> float | None:
    """Share of the profiled whole call's device-idle time inside no
    ``repro.*`` span, in %."""
    found = _whole_call(run)
    if found is None:
        return None
    spans, (lo, hi) = found
    idle = idle_ns(run.trace, [(lo, hi)], lo, hi)
    if idle <= 0:
        return None
    inside = idle_ns(run.trace, [iv for ivs in spans.values() for iv in ivs],
                     lo, hi)
    return 100.0 * (idle - inside) / idle
