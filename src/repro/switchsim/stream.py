"""Streaming steady-state driver: constant-memory runs over chunked sources.

The materialized engine (``switchsim.engine``) compiles the whole timeline
into one ``lax.scan`` — which also means the whole trace, its merged output
and every per-step ys live at once.  That caps a run at what fits in memory
(~minutes of simulated traffic) and makes steady-state questions — tail
latency under diurnal load, occupancy drift over millions of packets —
unanswerable.  This module is the long-haul path (DESIGN.md §13):

  * The trace arrives as a ``traffic.stream.TraceSource``; only one
    ``segment_len``-step slice of packets is ever live.
  * One SEGMENT program is jitted with ``donate_argnums`` on the carry —
    the switch state, NF-chain state, in-flight ring, recirculation lane
    and telemetry accumulators are donated back each call, so device
    memory for a 10^9-step run equals that of a single segment.
  * The per-step body is ``engine.scan_step`` — the *same* traced function
    the materialized engine scans.  Segment-replay bit-exactness
    (``replay_oracle``) therefore holds by construction: there is one step
    body, not two maintained in parallel.
  * What survives a segment is O(1): a (len(TEL_FIELDS),) int32 telemetry
    sum (accumulated host-side in int64 across segments), the per-step
    occupancy series of that segment (summarized to min/mean/max/last),
    and a fixed-size reservoir of sojourn-time samples.

Latency model (recorded deviation, DESIGN.md §13): the simulator is
step-quantized, so per-packet sojourn is reconstructed, not measured.  A
packet split at step ``t`` merges at ``t + window``; the paper puts the
split->merge dwell at ~30 us (§4), so one step is ``30 us / window`` and a
merged row's sojourn is ``window`` steps — ``window + 1`` for rows that
took the recirculation lane (one extra pass; lane rows lead each merged
chunk, so the extra step is statically position-determined).  Serialization
adds 0.8 ns/byte (10 Gbps).  All integer ns: the reservoir, the quantiles
and the offline oracle (tests/test_streaming.py) compute on exact ints.

The reservoir is Algorithm R with a counter-based splitmix32 coin: sample
number ``n`` lands in slot ``n`` while filling, then in slot
``splitmix32(seed ^ n * phi) % (n + 1)`` (kept only if ``< K``).  Within a
step the chunk's samples are inserted in row order with last-writer-wins
slot conflicts (a deterministic scatter-max), which is exactly sequential
Algorithm R under that coin — replayable bit-for-bit, no RNG state in the
carry.  Expected quantile error is the classic reservoir bound
O(sqrt(q(1-q)/K)); K=4096 puts ~1 sigma at p99 under 0.16 pp of rank.

Faults are NOT supported on this path (recorded deviation): fault windows
are phrased over a whole materialized run; streaming runs are healthy,
masks pinned all-True.  Use ``run_engine``/``run_pipes`` for fault studies.
"""
from __future__ import annotations

import dataclasses
import warnings
from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import coerce_backend
from repro.core import counters as C
from repro.core.park import ParkConfig
from repro.nf.chain import Chain
from repro.switchsim.engine import (_nf_counters, init_carry, recirc_slots,
                                    scan_step)
from repro.switchsim.results import StreamResult
from repro.switchsim.telemetry import TEL_FIELDS, LinkTelemetry
from repro.traffic.stream import (MaterializedSource, SyntheticSource,
                                  TraceSource, as_source, splitmix32)

__all__ = ["run_stream", "replay_oracle", "StreamOracleMismatch",
           "sojourn_ns", "step_ns_for", "SPLIT_MERGE_NS"]

# Paper §4: the split->merge dwell a parked payload spends in the switch is
# ~30 us end to end; the scan spreads it over ``window`` steps.
SPLIT_MERGE_NS = 30_000


def step_ns_for(window: int) -> int:
    """Integer ns one scan step stands for under the §4 dwell model."""
    return max(1, round(SPLIT_MERGE_NS / max(window, 1)))


def sojourn_ns(pkt_len, recirculated, window: int, step_ns: int):
    """Reconstructed per-packet sojourn in integer ns: dwell steps
    (``window``, +1 for a recirculation-lane pass) plus 0.8 ns/byte
    serialization (10 Gbps).  Pure integer math — the offline oracle in
    tests recomputes it exactly."""
    steps = jnp.asarray(window, jnp.int32) + jnp.asarray(
        recirculated, jnp.int32)
    return steps * jnp.int32(step_ns) + \
        (jnp.asarray(pkt_len, jnp.int32) * 4) // 5


def _reservoir_insert(vals, n, sample, alive, seed: int):
    """One chunk of samples through Algorithm R, sequential semantics.

    ``vals`` is the (K,) int32 reservoir, ``n`` the int32 count of samples
    seen so far, ``sample``/``alive`` the chunk's candidate rows.  Sample
    number ``m`` (0-based, global) goes to slot ``m`` while ``m < K``, else
    to ``splitmix32(seed ^ m*phi) % (m+1)`` and is kept only if that lands
    below K.  Row-order conflicts resolve last-writer-wins via a
    deterministic scatter-max over row indices — identical to processing
    the rows one at a time.
    """
    k = vals.shape[0]
    rows = alive.shape[0]
    pos = jnp.cumsum(alive.astype(jnp.int32)) - 1
    m = n + pos  # global sample number of each alive row
    h = splitmix32(jnp.uint32(seed) ^
                   (m.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)))
    j = jnp.where(m < k, m,
                  (h % jnp.maximum(m + 1, 1).astype(jnp.uint32))
                  .astype(jnp.int32))
    write = alive & (j < k)
    dest = jnp.where(write, j, k)
    winner = jnp.full((k + 1,), -1, jnp.int32)
    winner = winner.at[dest].max(jnp.arange(rows, dtype=jnp.int32))[:k]
    take = winner >= 0
    vals = jnp.where(take, sample[jnp.where(take, winner, 0)], vals)
    return vals, n + jnp.sum(alive.astype(jnp.int32))


@lru_cache(maxsize=None)
def _segment_program(cfg: ParkConfig, chain: Chain, window: int,
                     explicit_drops: bool, backend, recirc: int,
                     step_ns: int, res_seed: int):
    """The donated-carry segment: scan ``engine.scan_step`` over a
    (S, chunk, ...) slice, fold each step's merged chunk into the
    reservoir, and return O(1) per-segment aggregates.

    jit specializes per segment shape (the steady segment, one ragged
    tail, one drain pad), so the cache key here is the compile config
    only — mirroring ``engine._compiled``.
    """
    step = scan_step(cfg, chain, window, explicit_drops, backend,
                     collect_sent=False, recirc=recirc)

    def seg(carry, trace, server_up, lb_up, drain):
        core, vals, n = carry

        def body(c, xs):
            core, vals, n = c
            core, ys = step(core, xs, drain)
            m = ys["merged"]
            lane_rows = jnp.arange(m.alive.shape[0]) < recirc
            sample = sojourn_ns(m.pkt_len(), lane_rows, window, step_ns)
            vals, n = _reservoir_insert(vals, n, sample, m.alive, res_seed)
            tel = jnp.stack([ys[f] for f in TEL_FIELDS])
            return (core, vals, n), (tel, ys["occ"])

        (core, vals, n), (tels, occ) = jax.lax.scan(
            body, (core, vals, n), (trace, server_up, lb_up))
        # int32 per-segment totals (bounded by the run_stream guard);
        # run_stream accumulates them host-side in int64 across segments.
        return (core, vals, n), tels.sum(axis=0), occ

    return jax.jit(seg, donate_argnums=(0,))


def _occ_summary(start: int, occ: np.ndarray) -> dict:
    return dict(start=int(start), steps=int(occ.shape[0]),
                min=int(occ.min()), mean=float(occ.mean()),
                max=int(occ.max()), last=int(occ[-1]))


def _quantiles_us(vals: np.ndarray, n: int) -> dict:
    """Tail-latency block from the reservoir: nearest-rank quantiles of the
    valid prefix (slots fill in order while n < K), reported in µs."""
    k = vals.shape[0]
    out = dict(samples=int(n), reservoir=int(k))
    valid = np.sort(vals[:min(n, k)].astype(np.int64))
    if valid.size:
        for name, q in (("p50_us", 0.50), ("p99_us", 0.99),
                        ("p999_us", 0.999)):
            out[name] = float(np.quantile(valid, q, method="nearest")) / 1e3
    return out


def run_stream(
    cfg: ParkConfig,
    chain: Chain,
    source,
    window: int = 1,
    segment_len: int = 256,
    explicit_drops: bool = False,
    backend=None,
    reservoir: int = 4096,
    reservoir_seed: int = 0x5EED,
) -> StreamResult:
    """Run one pipe over a ``TraceSource`` at constant memory.

    The source is consumed ``segment_len`` steps at a time through one
    jitted segment program whose carry (switch state, NF-chain state,
    in-flight ring, recirculation lane, reservoir) is donated back each
    call; after the last segment a drain pad of all-dead chunks flushes the
    in-flight window (and, with recirculation, the lane) exactly as the
    materialized engine's trace padding does.  Counters, telemetry,
    nf_counters and peak occupancy are bit-identical to
    ``run_engine(cfg, chain, source.materialize(), ...)`` — enforced by
    ``replay_oracle`` and tests/test_streaming.py.

    On top of the materialized facts, the stream keeps what a materialized
    run cannot afford at this length: a ``reservoir``-slot sample of
    per-packet sojourn times (p50/p99/p999 in the ``latency`` block) and
    per-segment occupancy summaries (``occ_segments``).

    Faults are not supported here (healthy masks only); use the
    materialized entry points for fault studies.
    """
    backend = coerce_backend(backend)
    source = as_source(source)
    if source.steps < 1:
        raise ValueError("streaming needs a source with >= 1 step")
    if segment_len < 1:
        raise ValueError(f"segment_len must be >= 1, got {segment_len}")
    if reservoir < 1:
        raise ValueError(f"reservoir must be >= 1, got {reservoir}")
    chunk = source.chunk
    # Per-segment telemetry sums are int32 on device: bound the worst-case
    # byte sum (every row alive at max frame size) under 2^31.
    frame = source.pmax + 64
    if segment_len * chunk * frame >= 2**31:
        raise ValueError(
            f"segment_len {segment_len} overflows int32 telemetry "
            f"(chunk={chunk}, pmax={source.pmax}); use shorter segments")
    lane = recirc_slots(cfg, chunk)
    pad = window + (1 if lane else 0)
    step_ns = step_ns_for(window)
    fn = _segment_program(cfg, chain, window, explicit_drops, backend,
                          lane, step_ns, reservoir_seed)
    chunk_like = jax.tree.map(lambda a: a[0], source.segment(0, 1))
    carry = (init_carry(cfg, chain, chunk_like, window, lane),
             jnp.zeros((reservoir,), jnp.int32),
             jnp.zeros((), jnp.int32))
    drain = jnp.asarray(False)
    tel_total = np.zeros((len(TEL_FIELDS),), np.int64)
    occ_segments: list[dict] = []
    peak = 0
    n_segments = 0
    with warnings.catch_warnings():
        # The CPU backend does not donate and warns on every call; there the
        # fallback is a copy and the run stays correct.  On an accelerator a
        # failed donation breaks the constant-memory contract, so its
        # warning stays visible.
        if jax.default_backend() == "cpu":
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
        for start in range(0, source.steps, segment_len):
            n = min(segment_len, source.steps - start)
            ones = jnp.ones((n,), bool)
            carry, tel, occ = fn(carry, source.segment(start, n),
                                 ones, ones, drain)
            tel_total += np.asarray(tel, np.int64)
            occ = np.asarray(occ, np.int64)
            occ_segments.append(_occ_summary(start, occ))
            peak = max(peak, int(occ.max()))
            n_segments += 1
        if pad:
            dead = jax.tree.map(
                lambda a: jnp.zeros((pad,) + a.shape, a.dtype), chunk_like)
            ones = jnp.ones((pad,), bool)
            carry, tel, occ = fn(carry, dead, ones, ones, drain)
            tel_total += np.asarray(tel, np.int64)
            occ = np.asarray(occ, np.int64)
            occ_segments.append(_occ_summary(source.steps, occ))
            peak = max(peak, int(occ.max()))
    (state, cstates, _, _, _), vals, n_samples = carry
    tel = LinkTelemetry(**{f: int(v)
                           for f, v in zip(TEL_FIELDS, tel_total)})
    return StreamResult(
        state=state,
        counters=C.as_dict(state.counters),
        telemetry=tel,
        nf_counters=_nf_counters(chain, cstates),
        peak_occupancy=peak,
        latency=_quantiles_us(np.asarray(vals), int(n_samples)),
        occ_segments=occ_segments,
        steps=source.steps,
        segments=n_segments,
        segment_len=segment_len,
    )


class StreamOracleMismatch(AssertionError):
    """Streaming and materialized engines disagreed on exact facts."""


def _prefix_source(source: TraceSource, steps: int) -> TraceSource:
    """The same source truncated to its first ``steps`` steps — without
    materializing when the source can re-scope itself."""
    if steps == source.steps:
        return source
    if not 0 < steps <= source.steps:
        raise ValueError(f"prefix {steps} outside (0, {source.steps}]")
    if isinstance(source, SyntheticSource):
        # chunk t is a pure function of (seed, t): re-scoping the length
        # changes nothing about the steps that remain
        return dataclasses.replace(source, steps=steps)
    return MaterializedSource(source.segment(0, steps))


def replay_oracle(
    cfg: ParkConfig,
    chain: Chain,
    source,
    window: int = 1,
    segment_len: int = 64,
    segments: int = 4,
    explicit_drops: bool = False,
    backend=None,
) -> dict:
    """The segment-replay bit-exactness gate (DESIGN.md §13).

    Streams the first ``segments`` consecutive segments of ``source`` and
    runs the materialized engine (``run_pipes``, one pipe) over the same
    concatenated chunks; counters, full per-link telemetry, NF-private
    counters and peak occupancy must match EXACTLY — the streaming path
    shares ``engine.scan_step``, so any drift is a carry-threading or
    accumulation bug, never tolerance.  Raises ``StreamOracleMismatch``
    with every differing fact; returns a small report when clean.
    """
    from repro.switchsim.engine import run_pipes
    source = as_source(source)
    steps = min(source.steps, segment_len * segments)
    prefix = _prefix_source(source, steps)
    sres = run_stream(cfg, chain, prefix, window=window,
                      segment_len=segment_len,
                      explicit_drops=explicit_drops, backend=backend)
    mres = run_pipes(cfg, chain, prefix, window=window,
                     explicit_drops=explicit_drops, backend=backend)
    diffs = []
    for name, a, b in (("counters", sres.counters, mres.counters),
                       ("telemetry", sres.telemetry.as_dict(),
                        mres.telemetry.as_dict()),
                       ("nf_counters", sres.nf_counters, mres.nf_counters)):
        for k in sorted(set(a) | set(b)):
            if a.get(k) != b.get(k):
                diffs.append(f"{name}.{k}: stream={a.get(k)} "
                             f"materialized={b.get(k)}")
    if sres.peak_occupancy != mres.peak_occupancy:
        diffs.append(f"peak_occupancy: stream={sres.peak_occupancy} "
                     f"materialized={mres.peak_occupancy}")
    if diffs:
        raise StreamOracleMismatch(
            f"segment replay diverged over {steps} steps "
            f"({len(diffs)} facts):\n  " + "\n  ".join(diffs))
    return dict(steps=steps, packets=steps * source.chunk,
                segments=min(segments,
                             -(-steps // segment_len)),
                wire_bytes=sres.wire_bytes)
