"""Device-resident, multi-pipe PayloadPark simulation engine.

The seed ``simulate()`` drove one ``ParkState`` through a host-side Python
chunk loop with per-chunk ``int(jnp.sum(...))`` syncs — every chunk paid a
dispatch + device->host round trip, and only one pipe existed.  This module
compiles the whole split -> NF-chain -> merge timeline into ONE XLA program:

  * ``lax.scan`` over time steps.  The carry holds ``(ParkState, NF-chain
    states, in-flight ring buffer, step index)``; the per-step ys carry the
    merged chunk plus int32 per-link byte/packet tallies (wire in,
    switch->server, server->switch, recirculation port, merged out —
    ``switchsim.telemetry.LinkTelemetry``, DESIGN.md §7), so accounting
    lives on-device and is aggregated once at the end.
  * The in-flight window — the paper's split->merge time delta (~30 us, §4)
    — is a ``window``-deep ring of packet chunks indexed by ``t % window``
    with ``dynamic_index_in_dim`` / ``dynamic_update_index_in_dim``; chunk
    ``t`` is split at step ``t`` and its NF output merges at ``t + window``,
    exactly the seed loop's timeline.
  * ``vmap`` over a leading pipe axis replicates the engine per ingress
    shard — one ``ParkState`` per pipe, mirroring the paper's per-port pipes
    that let one ToR switch service up to 8 NF servers (§6.3.2).  Pipes
    share nothing (the hardware pipes share nothing either); cross-pipe
    goodput is aggregated host-side after the single device program returns.
  * The recirculation lane (``cfg.recirculation``, paper §6.2.5, DESIGN.md
    §6) is a second ring in the carry: Split outputs that want another
    pipeline pass (partial park with row width remaining, or an
    occupied-slot skip) detour into a ``recirc_slots``-wide lane instead of
    forwarding, re-enter through ``core.park.recirc_fn`` at the next step,
    and only then travel to the NF server.  Lane width is the
    recirculation port's bandwidth share (``recirc_frac`` of the per-step
    chunk); candidates beyond it forward as-is and are counted
    ``recirc_budget_drops``.

Semantics with recirculation off are bit-identical to the seed loop
(``simulate.simulate_loop``): padding chunks are all-dead (``alive=False``)
and every Split/Merge/NF state update is predicated on ``alive``, so the
padded steps are exact no-ops on the switch state.  With recirculation on,
``simulate_loop`` mirrors the lane host-side and stays the executable
oracle.  ``tests/test_engine.py`` / ``tests/test_recirc.py`` assert
wire-level equality for both modes.

Design notes: DESIGN.md §3 (engine), §6 (recirculation).
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import coerce_backend
from repro.core import counters as C
from repro.core.packet import PacketBatch, gather_rows
from repro.core.park import (ParkConfig, init_state, merge_fn,
                             occupancy, recirc_fn, split_fn)
from repro.nf.chain import Chain, to_explicit_drops
from repro.switchsim import faults as F
from repro.switchsim.results import EngineResult, PipesResult
from repro.switchsim.telemetry import (TEL_FIELDS, LinkTelemetry,
                                       sum_telemetry)
from repro.traffic import stream as stream_mod

__all__ = [
    "EngineResult", "PipesResult", "run_engine", "run_pipes",
    "goodput_gain", "goodput_gain_from_telemetry", "recirc_slots",
    "recirc_select", "scan_step", "init_carry",
]


def _alive_bytes(p: PacketBatch) -> jax.Array:
    return jnp.sum(jnp.where(p.alive, p.pkt_len(), 0))


def _alive_pkts(p: PacketBatch) -> jax.Array:
    return jnp.sum(p.alive.astype(jnp.int32))


def recirc_slots(cfg: ParkConfig, chunk: int) -> int:
    """Recirculation-lane width: the per-step packet budget of the
    recirculation port, ``floor(recirc_frac * chunk)`` — the port owns a
    fixed share of the pipe's per-step capacity (paper §6.2.5).  0 (either
    recirculation off, or a share smaller than one packet) disables the
    lane entirely; Split then parks single-pass only."""
    if not cfg.recirculation:
        return 0
    # epsilon guards binary-representation error (0.29 * 100 == 28.999...),
    # so exact fractional shares floor to the intended slot count
    return math.floor(cfg.recirc_frac * chunk + 1e-9)


def recirc_select(cfg: ParkConfig, out: PacketBatch, budget: int):
    """Admit up to ``budget`` recirculation candidates from a Split output.

    Candidates (DESIGN.md §6):
      * continuation — parked (ENB=1) with payload remaining: the row still
        has ``park_bytes - pass_bytes`` spare width for a second pass;
      * retry — Split disabled on an occupied slot (ENB=0 with an eligible
        payload): a second pass re-attempts the claim.

    Admitted packets (first ``budget`` in arrival order) detour into the
    lane instead of forwarding — one extra step of latency; denied
    candidates forward as-is (the paper's ENB=0 fallback) and are counted
    by the caller via the returned ``n_denied``.

    Returns ``(forwarded, lane, n_denied)`` where ``lane`` is a
    ``budget``-row PacketBatch (dead rows beyond the admitted count).
    """
    cont = out.alive & out.pp_valid & (out.pp_enb == 1) & (out.payload_len > 0)
    retry = out.alive & out.pp_valid & (out.pp_enb == 0) & \
        (out.payload_len >= cfg.min_park_len)
    cand = cont | retry
    pos = jnp.cumsum(cand) - 1
    admit = cand & (pos < budget)
    b = out.alive.shape[0]
    # Invert: lane_src[pos] = row index; empty lane slots gather a dead row.
    dest = jnp.where(admit, pos, budget)
    lane_src = jnp.full((budget,), b, jnp.int32)
    lane_src = lane_src.at[dest].set(jnp.arange(b, dtype=jnp.int32),
                                     mode="drop")
    lane = gather_rows(out, lane_src)
    forwarded = out.replace(alive=out.alive & ~admit)
    return forwarded, lane, jnp.sum(cand & ~admit)


def _cat_rows(a: PacketBatch, b: PacketBatch) -> PacketBatch:
    return jax.tree.map(lambda x, y: jnp.concatenate([x, y], axis=0), a, b)


def init_carry(cfg: ParkConfig, chain: Chain, chunk_like: PacketBatch,
               window: int, recirc: int):
    """Fresh scan carry (ParkState, NF-chain states, in-flight ring,
    recirculation lane, step index) for a pipe whose per-step chunks have
    ``chunk_like``'s (chunk, ...) geometry.  Shared by the materialized
    scan and the streaming driver — the streaming segment program threads
    exactly this carry across segments (donated, DESIGN.md §13)."""
    # All-dead chunks are all-zeros in every field (alive=False == 0),
    # so a zeros ring is a ring of dead chunks.  With a recirculation
    # lane the NF-bound chunks are ``recirc`` rows wider.
    ring = jax.tree.map(
        lambda a: jnp.zeros(
            (max(window, 1), a.shape[0] + recirc) + a.shape[1:], a.dtype),
        chunk_like)
    lane0 = jax.tree.map(
        lambda a: jnp.zeros((recirc,) + a.shape[1:], a.dtype),
        chunk_like) if recirc else ()
    return (init_state(cfg), chain.init_state(), ring, lane0,
            jnp.zeros((), jnp.int32))


def scan_step(cfg: ParkConfig, chain: Chain, window: int,
              explicit_drops: bool, backend, collect_sent: bool,
              recirc: int):
    """The per-step body both engines scan: carry, (chunk, masks), drain ->
    carry, telemetry ys.  Factored out of the materialized scan so the
    streaming segment program (``switchsim.stream``) runs the IDENTICAL
    step — segment-replay bit-exactness holds by construction, not by
    parallel maintenance of two bodies.

    ``recirc`` is the recirculation-lane width (0 = lane off; the step body
    is then exactly the seed timeline, keeping the bit-exactness oracle).

    Fault injection (DESIGN.md §10) rides the scan as extra xs — per-step
    ``server_up``/``lb_up`` bools — plus a traced ``drain`` scalar.  With
    all-True masks every fault operation is a bit-exact no-op, so the SAME
    compiled program serves healthy and faulted runs; fault timing is data.
    """

    def step(carry, xs, drain):
        state, cstates, ring, lane, t = carry
        cin, s_up, l_up = xs
        wire_b = _alive_bytes(cin)
        wire_p = _alive_pkts(cin)
        if recirc:
            # Second pass for packets re-injected at the previous step
            # (their wire bytes were paid on first arrival).
            state, rout = recirc_fn(cfg, state, lane, backend=backend)
        state, out = split_fn(cfg, state, cin, backend=backend)
        if recirc:
            out, lane, n_denied = recirc_select(cfg, out, recirc)
            state = dataclasses.replace(
                state, counters=C.bump(state.counters,
                                       "recirc_budget_drops", n_denied))
            # recirculation-port traffic = what enters the lane this step
            rec_b, rec_p = _alive_bytes(lane), _alive_pkts(lane)
            nf_in = _cat_rows(rout, out)
        else:
            rec_b = rec_p = jnp.zeros((), jnp.int32)
            nf_in = out
        # to_server telemetry is tallied on nf_in BEFORE the kill: the
        # switch still transmits to a dead server (the link is up, the
        # host is not), so the forward link carries the bytes either way
        to_srv_p, to_srv_b = _alive_pkts(nf_in), _alive_bytes(nf_in)
        # Server fault (DESIGN.md §10): packets forwarded while this
        # pipe's server is down are lost at send time.  The chain still
        # runs on the step (dead rows are no-ops on NF state — a down
        # server processes nothing).
        killed = nf_in.alive & ~s_up
        state = dataclasses.replace(
            state, counters=C.bump(state.counters, "fault_drops",
                                   jnp.sum(killed)))
        srv_in = nf_in.replace(alive=nf_in.alive & s_up)
        cstates, nf_out, dropped, _cycles = chain.run(
            cstates, srv_in, backend=backend, ctx={"lb_up": l_up})
        if explicit_drops:
            nf_out = to_explicit_drops(nf_out, dropped)
        # Drain-vs-drop rule: with drain, the failover agent turns each
        # killed packet's parked payload into an OP=drop notification on
        # the return path (the §6.2.4 machinery frees the slot at
        # Merge); without it the slots leak until expiry-based eviction.
        nf_out = to_explicit_drops(nf_out, killed & drain)
        if window == 0:
            returning = nf_out
        else:
            slot = jnp.mod(t, window)
            returning = jax.tree.map(
                lambda r: jax.lax.dynamic_index_in_dim(
                    r, slot, axis=0, keepdims=False), ring)
            ring = jax.tree.map(
                lambda r, v: jax.lax.dynamic_update_index_in_dim(
                    r, v, slot, axis=0), ring, nf_out)
        state, m = merge_fn(cfg, state, returning, backend=backend)
        # Per-link telemetry ys, keyed by LinkTelemetry field names
        # (DESIGN.md §7); summed host-side in int64 by _finalize.
        ys = dict(
            merged=m, occ=occupancy(state),
            wire_pkts=wire_p, wire_bytes=wire_b,
            to_server_pkts=to_srv_p,
            to_server_bytes=to_srv_b,
            from_server_pkts=_alive_pkts(returning),
            from_server_bytes=_alive_bytes(returning),
            recirc_pkts=rec_p, recirc_bytes=rec_b,
            merged_pkts=_alive_pkts(m), merged_bytes=_alive_bytes(m),
        )
        if collect_sent:
            ys["sent"] = nf_in
        return (state, cstates, ring, lane, t + 1), ys

    return step


def _build_scan(cfg: ParkConfig, chain: Chain, window: int,
                explicit_drops: bool, backend, collect_sent: bool,
                recirc: int):
    """Single-pipe scan body: trace (T+pad, chunk, ...) -> ys + final."""
    step = scan_step(cfg, chain, window, explicit_drops, backend,
                     collect_sent, recirc)

    def run(trace: PacketBatch, server_up: jax.Array, lb_up: jax.Array,
            drain: jax.Array):
        chunk_like = jax.tree.map(lambda a: a[0], trace)
        carry0 = init_carry(cfg, chain, chunk_like, window, recirc)
        (state, cstates, _, _, _), ys = jax.lax.scan(
            lambda c, xs: step(c, xs, drain), carry0,
            (trace, server_up, lb_up))
        return state, cstates, ys

    return run


@lru_cache(maxsize=None)
def _compiled(cfg: ParkConfig, chain: Chain, window: int,
              explicit_drops: bool, backend, collect_sent: bool,
              pipes: bool, recirc: int, devices: int = 1):
    # ``backend`` is a concrete (platform-resolved) BackendConfig, so the
    # cache key — like the jit static args — specializes per backend.
    # ``devices`` > 1 shard_maps the vmapped pipe axis over the fabric
    # mesh (switchsim.fabric, DESIGN.md §12); the caller has already
    # resolved it through ``fabric.resolve_devices``.
    run = _build_scan(cfg, chain, window, explicit_drops, backend,
                      collect_sent, recirc)
    if pipes:
        run = jax.vmap(run)
        if devices > 1:
            from repro.switchsim.fabric import shard_over_switch
            run = shard_over_switch(run, devices)
    return jax.jit(run)


def _pad_trace(trace: PacketBatch, window: int, axis: int = 0) -> PacketBatch:
    """Append ``window`` all-dead chunks (zeros) along the time axis so the
    last in-flight chunks drain through the scan."""
    if window == 0:
        return trace

    def pad(a):
        shape = list(a.shape)
        shape[axis] = window
        return jnp.concatenate([a, jnp.zeros(shape, a.dtype)], axis=axis)

    return jax.tree.map(pad, trace)


def _sum_telemetry(ys: dict) -> LinkTelemetry:
    """Total LinkTelemetry across every remaining axis (time, and pipes
    when present), summed in int64 so totals are exact."""
    return LinkTelemetry(**{
        name: int(np.asarray(ys[name], np.int64).sum())
        for name in TEL_FIELDS})


def _per_pipe_telemetry(ys: dict) -> list[LinkTelemetry]:
    """One LinkTelemetry per pipe: sum (P, T) ys over the time axis only."""
    sums = {name: np.asarray(ys[name], np.int64).sum(axis=-1)
            for name in TEL_FIELDS}
    n_pipes = next(iter(sums.values())).shape[0]
    return [LinkTelemetry(**{name: int(sums[name][p]) for name in TEL_FIELDS})
            for p in range(n_pipes)]


def _finalize(ys: dict, window: int, collect_sent: bool, time_axis: int):
    """Slice the warm-up/drain steps off the merged/sent ys."""
    t_pad = ys["wire_bytes"].shape[-1]
    t_real = t_pad - window

    def slice_time(a, start, stop):
        idx = [slice(None)] * a.ndim
        idx[time_axis] = slice(start, stop)
        return a[tuple(idx)]

    merged = jax.tree.map(
        lambda a: slice_time(a, window, t_pad), ys["merged"])
    sent = None
    if collect_sent:
        sent = jax.tree.map(lambda a: slice_time(a, 0, t_real), ys["sent"])
    occ = np.asarray(ys["occ"], np.int64).max() if ys["occ"].size else 0
    return merged, sent, int(occ)


def _pad_masks(fa: F.FaultArrays, pad: int):
    """Extend the fault masks with all-True columns over the drain/warm-up
    padding steps — faults live within the offered trace (faults.py)."""
    ones = np.ones((fa.pipes, pad), bool)
    return (jnp.asarray(np.concatenate([fa.server_up, ones], axis=1)),
            jnp.asarray(np.concatenate([fa.lb_up, ones], axis=1)),
            jnp.asarray(fa.drain))


def _nf_counters(chain: Chain, cstates) -> dict[str, int]:
    return {k: int(v) for k, v in chain.state_counters(cstates).items()}


def run_engine(
    cfg: ParkConfig,
    chain: Chain,
    trace,
    window: int = 1,
    explicit_drops: bool = False,
    backend=None,
    collect_sent: bool = False,
    faults=None,
) -> EngineResult:
    """Run one pipe over a trace source under one jit.

    ``trace`` is a ``traffic.stream.TraceSource`` — or a time-major
    (T, chunk, ...) ``PacketBatch``, which is the trivial one-shot source
    (``MaterializedSource``) and is coerced through it.  This entry point
    materializes the whole source; ``switchsim.stream.run_stream`` is the
    constant-memory path for sources too long to materialize.

    Bit-identical to ``simulate.simulate_loop`` on the same trace (the seed
    Python loop), but the whole timeline is a single compiled program.
    With ``cfg.recirculation`` the trace is padded one extra step so the
    recirculation lane drains, and NF-bound chunks gain ``recirc_slots``
    leading lane rows.  ``backend`` selects the hot-path primitive
    implementations (``repro.backend``, DESIGN.md §9) for Split/Merge,
    header validation and the NF chain alike.  ``faults`` is a
    ``switchsim.faults.FaultSpec`` (or pre-lowered ``FaultArrays``);
    None/NO_FAULT runs healthy through the same compiled program.
    """
    backend = coerce_backend(backend)
    trace = stream_mod.as_source(trace).materialize()
    chunk = jax.tree.leaves(trace)[0].shape[1]
    steps = jax.tree.leaves(trace)[0].shape[0]
    lane = recirc_slots(cfg, chunk)
    pad = window + (1 if lane else 0)
    fa = F.resolve(faults, pipes=1, steps=steps)
    s_up, l_up, drain = _pad_masks(fa, pad)
    trace = _pad_trace(trace, pad, axis=0)
    fn = _compiled(cfg, chain, window, explicit_drops, backend,
                   collect_sent, pipes=False, recirc=lane)
    state, cstates, ys = fn(trace, s_up[0], l_up[0], drain[0])
    merged, sent, occ = _finalize(ys, window, collect_sent, time_axis=0)
    tel = _sum_telemetry(ys)
    return EngineResult(
        merged=merged, sent=sent, state=state,
        counters=C.as_dict(state.counters),
        srv_bytes=tel.srv_bytes, srv_fwd_bytes=tel.to_server_bytes,
        wire_bytes=tel.wire_bytes, ret_bytes=tel.merged_bytes,
        peak_occupancy=occ, telemetry=tel,
        occ_series=np.asarray(ys["occ"], np.int64),
        nf_counters=_nf_counters(chain, cstates),
    )


def _as_pipe_traces(traces) -> PacketBatch:
    """Coerce ``run_pipes``'s accepted trace spellings to (P, T, chunk, ...):
    a pre-stacked PacketBatch passes through; a TraceSource becomes one
    pipe; a sequence of per-pipe sources is materialized and stacked."""
    if isinstance(traces, PacketBatch):
        return traces
    if isinstance(traces, stream_mod.TraceSource):
        traces = [traces]
    if isinstance(traces, (list, tuple)):
        mats = [stream_mod.as_source(t).materialize() for t in traces]
        return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *mats)
    raise TypeError(
        f"traces must be a PacketBatch, a TraceSource or a sequence of "
        f"TraceSources; got {type(traces).__name__}")


def run_pipes(
    cfg: ParkConfig,
    chain: Chain,
    traces,
    window: int = 1,
    explicit_drops: bool = False,
    backend=None,
    collect_sent: bool = False,
    faults=None,
    devices: int = 1,
) -> PipesResult:
    """Run P independent pipes over per-pipe trace sources, vmapped.

    ``traces`` is a sequence of per-pipe ``traffic.stream.TraceSource``s
    (equal geometry, stacked after materialization), a single source
    (one pipe), or the pre-stacked (P, T, chunk, ...) ``PacketBatch`` the
    sources materialize to.

    Each pipe owns a fresh ``ParkState`` and NF-chain state (the paper's
    per-port pipes share nothing, §6.3.2); one compiled program drives all
    of them.  Byte totals and counters are aggregated across pipes.
    ``backend``/``faults`` behave exactly as in ``run_engine``
    (``FaultArrays`` here may carry per-pipe masks stacked by the scenario
    runner across batched scenario points).

    ``devices`` > 1 shards the pipe axis over that many devices via
    ``switchsim.fabric`` (mesh axis ``"switch"``, DESIGN.md §12).  Results
    are bit-identical for any device count (shard-count invariance); the
    request falls back to 1 with a warning when the pipe count does not
    divide it, and raises when fewer devices are visible.
    """
    backend = coerce_backend(backend)
    traces = _as_pipe_traces(traces)
    n_pipes = jax.tree.leaves(traces)[0].shape[0]
    chunk = jax.tree.leaves(traces)[0].shape[2]
    steps = jax.tree.leaves(traces)[0].shape[1]
    lane = recirc_slots(cfg, chunk)
    pad = window + (1 if lane else 0)
    fa = F.resolve(faults, pipes=n_pipes, steps=steps)
    s_up, l_up, drain = _pad_masks(fa, pad)
    traces = _pad_trace(traces, pad, axis=1)
    if devices != 1:
        from repro.switchsim import fabric
        devices = fabric.resolve_devices(n_pipes, devices)
    fn = _compiled(cfg, chain, window, explicit_drops, backend,
                   collect_sent, pipes=True, recirc=lane, devices=devices)
    state, cstates, ys = fn(traces, s_up, l_up, drain)
    merged, sent, occ = _finalize(ys, window, collect_sent, time_axis=1)
    per_tel = _per_pipe_telemetry(ys)
    tel = sum_telemetry(per_tel)
    occ_pp = np.asarray(ys["occ"], np.int64)  # (P, T+pad)
    per_occ = [int(v) for v in occ_pp.max(axis=-1)] if occ_pp.size \
        else [0] * n_pipes
    ctr = np.asarray(state.counters, np.int64)  # (P, C.NUM)
    agg = dict(zip(C.NAMES, (int(v) for v in ctr.sum(axis=0))))
    per_pipe = [dict(zip(C.NAMES, (int(v) for v in ctr[p])))
                for p in range(n_pipes)]
    per_nf = [_nf_counters(chain, jax.tree.map(lambda a: a[p], cstates))
              for p in range(n_pipes)]
    nf_agg = {k: sum(d[k] for d in per_nf)
              for k in (per_nf[0] if per_nf else {})}
    return PipesResult(
        merged=merged, sent=sent, state=state,
        counters=agg, srv_bytes=tel.srv_bytes,
        srv_fwd_bytes=tel.to_server_bytes, wire_bytes=tel.wire_bytes,
        ret_bytes=tel.merged_bytes, peak_occupancy=occ, telemetry=tel,
        occ_series=occ_pp, nf_counters=nf_agg,
        per_pipe_counters=per_pipe,
        per_pipe_srv_bytes=[t.srv_bytes for t in per_tel],
        per_pipe_wire_bytes=[t.wire_bytes for t in per_tel],
        per_pipe_telemetry=per_tel,
        per_pipe_peak_occupancy=per_occ,
        per_pipe_occ_series=occ_pp,
        per_pipe_nf_counters=per_nf,
    )


def goodput_gain(res: EngineResult) -> dict[str, Any]:
    """Server-link byte saving vs the non-parking baseline.

    Parking carries headers + un-parked tails + the 7-byte PP header
    (``srv_bytes``, both directions as measured).  Two baselines:

    * **drop-aware** (the headline ``goodput_gain``): forward trip carries
      every offered packet whole (``wire_bytes``); the return trip only the
      NF-chain survivors at full size (``ret_bytes``).  A no-parking
      deployment of the same chain drops the same packets server-side, so
      this is the byte count it would actually put on the link.  (Exact up
      to premature-eviction losses, which kill packets the baseline would
      have returned; in healthy operation those are zero.)
    * **naive** (``*_naive``, the seed formula): ``2 * wire_bytes`` — it
      pretends the chain-dropped packets made the return trip too, padding
      the baseline with bytes no deployment would carry and skewing the
      gain whenever the chain drops (e.g. NAT overflow, firewall rules).

    Positive saving = goodput gain on the switch<->server link (the
    paper's §6.1 metric, byte form).
    """
    return _gain_from_bytes(res.wire_bytes, res.srv_bytes, res.ret_bytes)


def goodput_gain_from_telemetry(tel: LinkTelemetry) -> dict[str, Any]:
    """``goodput_gain`` computed straight from a LinkTelemetry — the
    per-scenario (or per-pipe/per-server) form used by the scenario runner,
    which regroups a flat vmapped pipe axis into per-scenario telemetry
    sums before any EngineResult exists (DESIGN.md §8)."""
    return _gain_from_bytes(tel.wire_bytes, tel.srv_bytes, tel.merged_bytes)


def _gain_from_bytes(wire_bytes: int, srv_bytes: int,
                     ret_bytes: int) -> dict[str, Any]:
    naive = 2 * wire_bytes
    baseline = wire_bytes + ret_bytes
    srv = srv_bytes
    return dict(
        baseline_link_bytes=baseline,
        baseline_naive_link_bytes=naive,
        parked_link_bytes=srv,
        link_byte_saving=1.0 - srv / baseline if baseline else 0.0,
        link_byte_saving_naive=1.0 - srv / naive if naive else 0.0,
        goodput_gain=(baseline / srv - 1.0) if srv else 0.0,
        goodput_gain_naive=(naive / srv - 1.0) if srv else 0.0,
    )
