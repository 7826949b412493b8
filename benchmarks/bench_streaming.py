"""Streaming steady-state benchmark: constant-memory long-haul runs
(DESIGN.md §13).

The materialized benches top out at 16384 packets per run — the whole
trace, its merged output and the per-step ys must fit in memory at once.
This bench drives the streaming engine (``switchsim.stream.run_stream``)
over a ``SyntheticSource`` at least **16x** that size (default 262144
packets; the nightly ladder runs >= 1e6) with a diurnal load profile and a
million-flow pool, and reports what only a steady-state run can:

  * **throughput** — steady-state packets/second through the donated-carry
    segment program (compiles excluded: the warm-up run compiles both the
    steady segment and the drain-pad shapes);
  * **tail latency** — p50/p99/p999 sojourn time from the deterministic
    reservoir sample (integer-ns model, see switchsim/stream.py);
  * **memory** — peak RSS, and the RSS growth between a short
    multi-segment run (steady-state buffers already allocated; on CPU the
    donated inputs are copied, so ~2 segments are transiently live) and
    the full run.  Constant memory means running 8-16x more segments
    grows RSS by ~nothing — far below materializing the full trace;
    ``constant_memory_ok`` is the gated verdict.

``--oracle`` additionally replays the first segments against the
materialized engine (``stream.replay_oracle``) and emits the bit-exactness
row compare.py gates exactly.

    PYTHONPATH=src python benchmarks/bench_streaming.py
    PYTHONPATH=src python benchmarks/bench_streaming.py --tiny --oracle \
        --json BENCH_streaming.json
    PYTHONPATH=src python benchmarks/bench_streaming.py --steps 4096  # nightly

Tiny geometry: 32 steps x chunk 64 (2048 packets), segment 8, reservoir
512, capacity 256 — the CI smoke whose artifact is the committed baseline.
"""
from __future__ import annotations

import resource
import time

try:
    from benchmarks.artifacts import write_bench_json
    from benchmarks.common import (check_flags, make_parser, print_rows,
                                   single_backend)
except ImportError:  # run as a script: benchmarks/ itself is on sys.path
    from artifacts import write_bench_json
    from common import check_flags, make_parser, print_rows, single_backend

from repro.core.park import ParkConfig
from repro.nf.chain import Chain
from repro.nf.nat import Nat
from repro.switchsim.engine import goodput_gain_from_telemetry
from repro.switchsim.stream import replay_oracle, run_stream
from repro.traffic.stream import DiurnalLoad, SyntheticSource

# full-run geometry: 1024 steps x chunk 256 = 262144 packets, 16x the
# largest materialized bench (bench_pipeline: 16384)
FULL = dict(steps=1024, chunk=256, pmax=2048, capacity=4096, window=2,
            segment_len=128, reservoir=4096, flows=1_000_000,
            load_period=512)
TINY = dict(steps=32, chunk=64, pmax=512, capacity=256, window=2,
            segment_len=8, reservoir=512, flows=10_000, load_period=32)


def _rss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _trace_mb(steps: int, chunk: int, pmax: int) -> float:
    """Rough footprint of materializing the whole trace (payload dominates)."""
    return steps * chunk * (pmax + 64) / (1024.0 * 1024.0)


def workload(g: dict, seed: int = 0):
    """The streamed deployment at geometry ``g``: (ParkConfig, chain,
    source) — a NAT behind a recirculating switch under diurnal load."""
    cfg = ParkConfig(capacity=g["capacity"], max_exp=2, pmax=g["pmax"],
                     recirculation=True, recirc_frac=0.25)
    source = SyntheticSource(
        steps=g["steps"], chunk=g["chunk"], pmax=g["pmax"], seed=seed,
        flows=g["flows"], load=DiurnalLoad(period=g["load_period"]))
    return cfg, Chain((Nat(),)), source


def bench(g: dict, oracle: bool, backend=None):
    cfg, chain, source = workload(g)

    def run(steps):
        import dataclasses
        src = (source if steps == source.steps
               else dataclasses.replace(source, steps=steps))
        return run_stream(cfg, chain, src, window=g["window"],
                          segment_len=g["segment_len"],
                          reservoir=g["reservoir"], backend=backend)

    # warm-up over one segment: compiles the steady segment AND the drain
    # pad (pad geometry is steps-independent), so the timed run is pure
    # steady-state execution.  The source's own generator program is warmed
    # separately — its jit cache is per-instance.
    run(g["segment_len"])
    source.segment(0, g["segment_len"])
    # RSS baseline AFTER a short multi-segment run: the steady-state
    # working set (segment buffers, transient donation copies) is already
    # at its high-water mark, so the full run — 8-16x more segments —
    # must not grow RSS beyond allocator noise
    run(min(g["steps"], 4 * g["segment_len"]))
    rss_before = _rss_mb()
    t0 = time.perf_counter()
    res = run(g["steps"])
    wall = time.perf_counter() - t0
    rss_after = _rss_mb()

    packets = res.steps * g["chunk"]
    growth = rss_after - rss_before
    trace_mb = _trace_mb(g["steps"], g["chunk"], g["pmax"])
    # constant memory: the full run may not cost more than a fraction of
    # what materializing its trace would (generous floor for allocator
    # noise on small smokes)
    bound_mb = max(64.0, trace_mb / 8.0)
    const_ok = int(growth < bound_mb)
    lat = res.latency
    gain = goodput_gain_from_telemetry(res.telemetry)

    rows = [
        ("streaming/steady/packets", packets,
         f"steps={res.steps};chunk={g['chunk']};"
         f"segments={res.segments};segment_len={res.segment_len}", None),
        ("streaming/steady/pps", round(packets / wall),
         f"wall_s={wall:.3f};donated_carry=1", None),
        ("streaming/steady/wall_s", round(wall, 3),
         f"packets={packets}", None),
        ("streaming/steady/p50_us", lat.get("p50_us"),
         f"samples={lat['samples']};reservoir={lat['reservoir']}", None),
        ("streaming/steady/p99_us", lat.get("p99_us"),
         f"samples={lat['samples']}", None),
        ("streaming/steady/p999_us", lat.get("p999_us"),
         f"samples={lat['samples']}", None),
        ("streaming/steady/latency_samples", lat["samples"],
         f"reservoir={lat['reservoir']}", None),
        ("streaming/steady/peak_occupancy", res.peak_occupancy,
         f"capacity={g['capacity']}", None),
        ("streaming/steady/goodput_gain",
         round(gain["goodput_gain"], 4),
         f"wire_bytes={res.wire_bytes};srv_bytes={res.srv_bytes}", None),
        ("streaming/steady/peak_rss_mb", round(rss_after, 1),
         f"before={rss_before:.1f}", None),
        ("streaming/steady/rss_growth_mb", round(growth, 1),
         f"bound={bound_mb:.1f};materialized_trace={trace_mb:.1f}", None),
        ("streaming/steady/constant_memory_ok", const_ok,
         f"growth={growth:.1f}MB;bound={bound_mb:.1f}MB", None),
    ]
    if oracle:
        rep = replay_oracle(cfg, chain, source, window=g["window"],
                            segment_len=g["segment_len"], segments=4,
                            backend=backend)
        rows.append((
            "streaming/steady/replay_identical", 1,
            f"segments={rep['segments']};steps={rep['steps']};"
            f"counters+telemetry+nf+peak_occ bit-exact vs materialized",
            None))
    if not const_ok:
        raise SystemExit(
            f"constant-memory bound violated: RSS grew {growth:.1f} MB "
            f"over the full run (bound {bound_mb:.1f} MB; materializing "
            f"the trace would take ~{trace_mb:.1f} MB)")
    summary = dict(
        packets=packets, pps=round(packets / wall),
        p50_us=lat.get("p50_us"), p99_us=lat.get("p99_us"),
        p999_us=lat.get("p999_us"), peak_rss_mb=round(rss_after, 1),
        rss_growth_mb=round(growth, 1), constant_memory_ok=bool(const_ok),
        geometry={k: v for k, v in g.items()},
    )
    return rows, summary


def main() -> None:
    ap = make_parser(__doc__)
    ap.add_argument("--steps", type=int, default=None,
                    help="override the trace length in steps (nightly "
                         "ladder: 4096 steps x chunk 256 > 1e6 packets)")
    ap.add_argument("--segment-len", type=int, default=None,
                    help="override the streaming segment length")
    ap.add_argument("--reservoir", type=int, default=None,
                    help="override the latency-reservoir slot count")
    args = ap.parse_args()
    check_flags(ap, args)
    backend = single_backend(ap, args)
    g = dict(TINY if args.tiny else FULL)
    for k, flag in (("steps", args.steps), ("segment_len", args.segment_len),
                    ("reservoir", args.reservoir)):
        if flag is not None:
            g[k] = flag
    if g["steps"] % g["segment_len"]:
        ap.error(f"--steps ({g['steps']}) must be a multiple of "
                 f"--segment-len ({g['segment_len']}) so the timed run "
                 f"has no ragged tail compile")
    rows, summary = bench(g, oracle=args.oracle, backend=backend)
    print_rows(rows)
    if args.json:
        resolved = None
        if backend is not None:
            from repro.backend import as_config
            resolved = as_config(backend).concrete().default
        write_bench_json(args.json, "streaming", rows, summary=summary,
                         backend=resolved)


if __name__ == "__main__":
    main()
