"""Sweep runner: one vmapped XLA program per trace-compatible group.

The seed-era benches each hand-rolled a Python loop around the scanned
engine — one ``run_pipes`` dispatch per sweep point, one compile per
distinct (cfg, chain, shape) even when points only differed in traffic.
This runner is the single sweep path (DESIGN.md §8):

  1. every scenario point is expanded to its (P_i, T, chunk, ...) traces;
  2. points whose ``compile_key`` matches are **batched**: their pipe axes
     are concatenated into one (sum P_i, T, chunk, ...) stack and executed
     by ONE ``engine.run_pipes`` call — pipes share nothing, so a flat
     vmapped pipe axis is indifferent to which scenario each pipe belongs
     to, and one compile covers the whole group (workload / seed / flow
     axes share a compile this way);
  3. per-scenario results are regrouped from the engine's per-pipe
     counters/telemetry/occupancy slices;
  4. shape-changing axes (capacity, recirc_frac, chunk, window, chain)
     land in different groups and rely on the engine's ``lru_cache`` keyed
     compile cache — a re-run with the same key never re-traces.

``verify_oracle`` re-runs any point through the host-loop reference
(``simulate_loop``) pipe by pipe and asserts counters + telemetry equality
— the engine≡loop invariant the repo's tests enforce, exposed here so
every benchmark asserts it the same way.
"""
from __future__ import annotations

import dataclasses
import functools
import time

import jax
import jax.numpy as jnp

from repro.distributed.sharding import divides_axis
from repro.scenarios.spec import (ScenarioSpec, build_chain, compile_key,
                                  make_packets, sharded_traffic, steer)
from repro.switchsim import engine as E
from repro.switchsim import fabric
from repro.switchsim import faults as F
from repro.switchsim.results import flat_summary
from repro.switchsim.simulate import simulate_loop
from repro.switchsim.telemetry import LinkTelemetry, sum_telemetry
from repro.core import counters as C
from repro.core.packet import PacketBatch
from repro.traffic import generator as T


@dataclasses.dataclass
class ScenarioResult:
    """One executed scenario point (cross-pipe aggregates + per-pipe
    breakdowns), plus the derived goodput-gain dict and enough context
    (chain cycle costs, steering stats) for the benches' model glue."""

    spec: ScenarioSpec
    counters: dict
    telemetry: LinkTelemetry
    per_pipe_counters: list[dict]
    per_pipe_telemetry: list[LinkTelemetry]
    per_pipe_peak_occupancy: list[int]
    nf_counters: dict
    per_pipe_nf_counters: list[dict]
    per_pipe_occ_series: object   # (P, steps) parked-slot occupancy
    gain: dict
    steer_stats: dict
    nf_cycles: tuple[float, ...]
    wall_s: float       # this point's share of its group's wall time
    group_size: int     # points that shared the compiled program
    group_wall_s: float
    # the prepared traffic/chain/traces this result was computed from;
    # verify_oracle reuses it instead of regenerating (repr-noise excluded)
    prepared: "_Prepared" = dataclasses.field(default=None, repr=False)
    # the point's merged output, (P, T, rows, ...) device arrays: the
    # engine's own arrays (sharding included) when the point ran alone in
    # its group, its pipe slice of them otherwise
    merged: PacketBatch = dataclasses.field(default=None, repr=False)

    @property
    def peak_occupancy(self) -> int:
        return max(self.per_pipe_peak_occupancy)

    @property
    def alive_offered(self) -> int:
        """Offered packets that reached a pipe (steering overflow excluded)."""
        return (sum(self.steer_stats["per_pipe_arrivals"])
                - self.steer_stats["overflow"])

    def summary(self) -> dict:
        """The shared flat-dict view (``switchsim.results.flat_summary``)
        every result type exposes — what bench row-building reads."""
        return flat_summary(self.counters, self.telemetry,
                            peak_occupancy=self.peak_occupancy,
                            nf_counters=self.nf_counters)


@dataclasses.dataclass
class _Prepared:
    spec: ScenarioSpec
    pkts: object
    chain: object
    traces: object
    steer_stats: dict
    n_pipes: int
    faults: F.FaultArrays = None  # per-pipe masks over the steered steps
    steps: int = 0                # per-pipe steps of the steered traces
    counts: object = None         # per-pipe arrivals, drawn per shard

    def stats(self) -> dict:
        """``steer``'s stats, from the per-shard counts where the point
        was drawn per shard."""
        if self.steer_stats is None:
            self.steer_stats = T.steer_stats(self.counts,
                                             self.steps * self.spec.chunk)
        return self.steer_stats


def _prepare(spec: ScenarioSpec) -> _Prepared:
    """A point's chain, steps and fault masks, and on one device its
    traffic, drawn eagerly and steered (``make_packets``, ``steer``).
    A point sharded over several devices draws nothing here: its compile
    group's traffic program draws, steers and exchanges it per shard
    (``_draw``), and the chain's rules, where they come from the
    traffic, from the source IPs drawn alone."""
    if spec.devices > 1:
        steps = T.pipe_trace_steps(spec.packets, spec.pipes, spec.chunk)
        fa = F.resolve(spec.fault, pipes=spec.pipes, steps=steps)
        return _Prepared(spec, None, build_chain(spec, None), None, None,
                         spec.pipes, fa, steps)
    pkts = make_packets(spec)
    chain = build_chain(spec, pkts)
    traces, stats = steer(spec, pkts)
    steps = jax.tree.leaves(traces)[0].shape[1]
    fa = F.resolve(spec.fault, pipes=spec.pipes, steps=steps)
    return _Prepared(spec, pkts, chain, traces, stats, spec.pipes, fa, steps)


def _cat_pipe_axis(traces_list):
    return jax.tree.map(
        lambda *xs: jnp.concatenate(xs, axis=0), *traces_list)


def _grouped(specs):
    """The prepared points and their compile groups (``compile_key`` ->
    member indices), under the ``repro.prepare`` and ``repro.dispatch``
    spans (DESIGN.md §14)."""
    prepared = []
    for s in specs:
        with jax.profiler.TraceAnnotation("repro.prepare"):
            prepared.append(_prepare(s))
    with jax.profiler.TraceAnnotation("repro.dispatch"):
        groups: dict = {}
        for i, p in enumerate(prepared):
            key = compile_key(p.spec, p.chain, p.steps)
            groups.setdefault(key, []).append(i)
    return prepared, groups


def _traffic(key, members, prepared):
    """The group's per-shard traffic program (``spec.sharded_traffic``)
    as ``(fn, args)``, or None where the group draws eagerly: on one
    device, where its pipes do not divide the devices (the engine then
    runs it on one, ``fabric.resolve_devices``), or where a member's
    packets do not."""
    devices = key[-1]
    specs = [prepared[i].spec for i in members]
    pipes = sum(s.pipes for s in specs)
    if devices == 1 or not divides_axis(pipes, devices) \
            or any(s.packets % devices for s in specs):
        return None
    fabric.resolve_devices(pipes, devices)  # raises where fewer are visible
    return sharded_traffic(specs, devices)


def _draw(key, members, prepared):
    """Give the group's members their traces: by the group's traffic
    program, whose stacked traces are returned, else eagerly (None)."""
    program = _traffic(key, members, prepared)
    if program is None:
        for i in members:
            p = prepared[i]
            if p.traces is None:
                p.pkts = make_packets(p.spec)
                p.traces, p.steer_stats = steer(p.spec, p.pkts)
        return None
    fn, args = program
    traces, counts = fn(*args)
    lo = 0
    for i, n in zip(members, counts):
        p = prepared[i]
        hi = lo + p.n_pipes
        p.traces = traces if len(members) == 1 else jax.tree.map(
            lambda a, lo=lo, hi=hi: a[lo:hi], traces)
        p.counts, lo = n, hi
    return traces


def _group_args(key, members, prepared, traces=None) -> tuple[tuple, dict]:
    """``engine.run_pipes``'s arguments for one compile group: its
    members' traces (``traces``, where its traffic program stacked them)
    and fault masks stacked on one pipe axis."""
    (cfg, chain, window, _chunk, _steps, _pmax, explicit_drops,
     _lane, backend, devices) = key
    with jax.profiler.TraceAnnotation("repro.dispatch"):
        stacked = (traces if traces is not None else
                   _cat_pipe_axis([prepared[i].traces for i in members]))
        # fault masks ride the same stacked pipe axis as the traces —
        # healthy members contribute all-True columns, so one compiled
        # program serves faulted and healthy points alike (DESIGN.md §10)
        stacked_faults = F.concat([prepared[i].faults for i in members])
    # ``devices`` shards the group's *concatenated* pipe axis
    # (switchsim.fabric): the group stays ONE program whose shards may
    # each hold pipes from different scenario points — the per-scenario
    # regrouping in run_matrix gathers across shard boundaries
    # transparently (DESIGN.md §12).
    return (cfg, chain, stacked), dict(
        window=window, explicit_drops=explicit_drops, backend=backend,
        faults=stacked_faults, devices=devices)


def _engine_args(specs):
    """Each compile group's key, members and ``run_pipes`` arguments,
    its traffic drawn (under ``repro.prepare``) on the way."""
    prepared, groups = _grouped(specs)
    for key, members in groups.items():
        with jax.profiler.TraceAnnotation("repro.prepare"):
            traces = _draw(key, members, prepared)
        yield prepared, key, members, _group_args(key, members, prepared,
                                                  traces)


def traffic_programs(specs) -> list[tuple]:
    """The per-shard traffic programs ``run_matrix(specs)`` runs, one per
    compile group that draws per shard (``spec.sharded_traffic``), each
    as ``(fn, args)``; ``fn.lower(*args).compile().as_text()`` names the
    ``traffic.draw``, ``traffic.steer`` and ``traffic.exchange`` scope of
    every device op (DESIGN.md §14).  Draws nothing."""
    prepared, groups = _grouped(specs)
    return [program for key, members in groups.items()
            if (program := _traffic(key, members, prepared)) is not None]


def engine_programs(specs) -> list[tuple]:
    """The engine programs ``run_matrix(specs)`` runs, one per compile
    group, each as ``engine.pipes_program`` gives it: ``(fn, args)``,
    whose ``fn.lower(*args).compile().as_text()`` names the stage of
    every device op (DESIGN.md §14).  The arguments hold the groups'
    traffic, drawn as ``run_matrix`` draws it."""
    return [E.pipes_program(*args, **kw)
            for _, _, _, (args, kw) in _engine_args(specs)]


def run_matrix(specs, time_runs: bool = False,
               time_repeats: int = 1) -> list[ScenarioResult]:
    """Execute scenario points, batching trace-compatible ones.

    Returns results in the order of ``specs``.  ``time_runs`` re-executes
    each compiled group ``time_repeats`` times after warm-up and
    attributes the mean group wall time evenly across its points (a
    per-point wall clock would defeat the shared-compile batching; the
    engine-vs-loop speedup bench times the engine directly where exact
    per-run numbers matter).
    """
    # host phases as disjoint profiler spans (DESIGN.md §14); run_pipes
    # opens its own dispatch and finalize spans between these
    results: list = [None] * len(specs)
    for prepared, key, members, (args, kw) in _engine_args(specs):
        chain, backend = args[1], kw["backend"]
        run = functools.partial(E.run_pipes, *args, **kw)
        res = run()
        if time_runs:
            jax.block_until_ready(res.merged.payload)
            t0 = time.perf_counter()
            for _ in range(max(time_repeats, 1)):
                timed = run()
                jax.block_until_ready(timed.merged.payload)
            group_wall = (time.perf_counter() - t0) / max(time_repeats, 1)
        else:
            group_wall = 0.0
        with jax.profiler.TraceAnnotation("repro.nf_cycles"):
            nf_cycles = chain.cycle_costs(backend=backend)
        with jax.profiler.TraceAnnotation("repro.finalize"):
            offset = 0
            for i in members:
                p = prepared[i]
                lo, hi = offset, offset + p.n_pipes
                offset = hi
                per_ctr = res.per_pipe_counters[lo:hi]
                per_tel = res.per_pipe_telemetry[lo:hi]
                per_nf = res.per_pipe_nf_counters[lo:hi]
                tel = sum_telemetry(per_tel)
                agg = {name: sum(c[name] for c in per_ctr) for name in C.NAMES}
                nf_agg = {name: sum(c[name] for c in per_nf)
                          for name in (per_nf[0] if per_nf else {})}
                results[i] = ScenarioResult(
                    spec=p.spec,
                    counters=agg,
                    telemetry=tel,
                    per_pipe_counters=per_ctr,
                    per_pipe_telemetry=per_tel,
                    per_pipe_peak_occupancy=res.per_pipe_peak_occupancy[lo:hi],
                    nf_counters=nf_agg,
                    per_pipe_nf_counters=per_nf,
                    per_pipe_occ_series=res.per_pipe_occ_series[lo:hi],
                    gain=E.goodput_gain_from_telemetry(tel),
                    steer_stats=p.stats(),
                    nf_cycles=nf_cycles,
                    wall_s=group_wall / len(members),
                    group_size=len(members),
                    group_wall_s=group_wall,
                    prepared=p,
                    merged=(res.merged if len(members) == 1 else
                            jax.tree.map(lambda a, lo=lo, hi=hi: a[lo:hi],
                                         res.merged)),
                )
            assert offset == len(res.per_pipe_counters)
    return results


class OracleMismatch(AssertionError):
    """Engine diverged from the host-loop reference on a scenario point."""


def verify_oracle(result: ScenarioResult, faults=True,
                  pipes=None) -> None:
    """Assert engine ≡ host loop (counters + telemetry + NF counters) for
    one point, on every pipe or on the pipe indices in ``pipes``.

    Re-runs ``simulate_loop`` per pipe on the pipe's flat trace (dead
    padding rows are no-ops for the loop exactly as for the engine), on
    the point's own backend (the loop dispatches the same primitives), and
    compares against the engine's per-pipe counters and telemetry.
    Raises ``OracleMismatch`` on any difference.

    ``faults`` controls whether the spec's fault event is mirrored into
    the loop (the default; the engine≡loop invariant must hold *through*
    fault events).  Pass ``faults=False`` to re-run the loop healthy —
    useful only for demonstrating that a fault actually changed behaviour.

    **Per-shard semantics** (``spec.devices`` > 1, DESIGN.md §12): the
    fabric shards the pipe axis contiguously, so the per-pipe check below
    *is* the per-shard check — each device's pipe slice is verified
    independently against its own host-loop re-run, with no cross-shard
    state to reconcile.  Mismatch messages name the shard the diverging
    pipe ran on so multi-device failures localize to a device.
    """
    spec = result.spec
    # reuse the traffic/chain/traces the result was computed from; a
    # result reconstructed without them (deserialized, hand-built) still
    # verifies via deterministic re-preparation
    p = result.prepared
    if p is None:
        prepared, groups = _grouped([spec])
        for key, members in groups.items():
            _draw(key, members, prepared)
        p = prepared[0]
    cfg = spec.park_config()
    from repro.core.packet import from_time_major
    # contiguous shard of each pipe index, for mismatch localization
    # (devices that didn't divide the pipe axis ran replicated on shard 0)
    per_shard = (spec.pipes // spec.devices
                 if spec.pipes % spec.devices == 0 else spec.pipes)
    for pipe in (range(spec.pipes) if pipes is None else pipes):
        shard = pipe // max(per_shard, 1)
        where = (f"{spec.name} pipe {pipe} (shard {shard}/{spec.devices})"
                 if spec.devices > 1 else f"{spec.name} pipe {pipe}")
        flat = from_time_major(jax.tree.map(lambda a: a[pipe], p.traces))
        loop = simulate_loop(cfg, p.chain, flat, window=spec.window,
                             chunk=spec.chunk,
                             explicit_drops=spec.explicit_drops,
                             backend=spec.backend_config(),
                             faults=spec.fault if faults else None,
                             fault_pipe=pipe)
        if loop.counters != result.per_pipe_counters[pipe]:
            raise OracleMismatch(
                f"{where}: counters diverged\n"
                f"  engine: {result.per_pipe_counters[pipe]}\n"
                f"  loop:   {loop.counters}")
        if loop.telemetry != result.per_pipe_telemetry[pipe]:
            raise OracleMismatch(
                f"{where}: telemetry diverged\n"
                f"  engine: {result.per_pipe_telemetry[pipe]}\n"
                f"  loop:   {loop.telemetry}")
        if loop.nf_counters != result.per_pipe_nf_counters[pipe]:
            raise OracleMismatch(
                f"{where}: NF counters diverged\n"
                f"  engine: {result.per_pipe_nf_counters[pipe]}\n"
                f"  loop:   {loop.nf_counters}")


def default_rows(result: ScenarioResult, family: str) -> list[tuple]:
    """Generic schema-v2 artifact rows for one point: the goodput headline
    plus the counters that have historically caught regressions.  Curated
    benches format their own richer rows; the nightly matrix driver
    (benchmarks/run.py) emits these."""
    s, sm = result.spec, result.summary()
    derived = (f"wire_bytes={sm['wire_bytes']};srv_bytes={sm['srv_bytes']};"
               f"ret_bytes={sm['ret_bytes']};splits={sm['splits']};"
               f"merges={sm['merges']};"
               f"premature={sm['premature_evictions']};"
               f"peak_occ={sm['peak_occupancy']};"
               f"overflow={result.steer_stats['overflow']}")
    rows = [
        (f"{family}/{s.name}/goodput_gain",
         round(result.gain["goodput_gain"], 4), derived, s.name),
        (f"{family}/{s.name}/link_byte_saving",
         round(result.gain["link_byte_saving"], 4),
         f"naive={result.gain['link_byte_saving_naive']:.4f}", s.name),
    ]
    if s.recirc:
        rows.append((
            f"{family}/{s.name}/recirculations", sm["recirculations"],
            f"budget_drops={sm['recirc_budget_drops']};"
            f"recirc_bytes={sm['tel_recirc_bytes']}", s.name))
    return rows
