"""Multi-pipe scanned-engine benchmark: packets/sec and goodput gain.

Measures what the seed host-loop could not: the compiled engine's packet
rate at 1/2/4/8 pipes (the paper's ToR switch services up to 8 NF servers,
one per-port pipe each, §6.3.2) and the goodput gain realized on the
switch<->server links, both measured (byte counts from the simulation) and
predicted (the calibrated analytic model fed with the measured digest).

Since the scenario-matrix refactor (DESIGN.md §8) the sweep itself —
expansion, trace steering, engine execution, per-point regrouping — is the
``repro.scenarios`` runner; this bench only defines its grid from the CLI
flags and formats the rows, so the pipes sweep here, the nightly matrix
and CI smokes all execute through the same code path.

At 1 pipe it also verifies the engine is wire-identical to the seed Python
chunk loop on the same trace and reports the speedup over it.

Two effects worth knowing when reading the numbers:
  * pipes are vmapped — on a single CPU device they serialize, so wall-clock
    pps does NOT scale with pipe count here; the model-predicted aggregate
    goodput (``model_goodput_gbps``, per-port links and servers) is the
    multi-server scaling metric.  On parallel hardware the pipe axis maps to
    independent compute.
  * per-pipe NF state is replicated (each pipe fronts its own server), so a
    single pipe's NAT flow table runs hotter at high flow counts than split
    pipes.  NAT flow expiry (EXP-style, see ``nf/nat.py``) reclaims idle
    mappings, so >=16k-flow single-pipe traces suffer only *transient* drops
    while slots age out; ``goodput_gain`` is drop-aware (the baseline
    charges the return trip only for chain survivors; the old 2x-wire
    figure is reported as ``naive``).

``--recirc`` runs the paper §6.2.5 experiment instead: a table-occupancy
sweep comparing goodput gain with the recirculation lane off vs on
(retry + 352B rows under a recirculation-port budget), asserting the gain
is strictly higher at high occupancy — the Fig. 13 direction (13% -> 28%).

``--backend`` makes the dataplane backend (repro.backend, DESIGN.md §9) a
sweep axis: one value runs the whole sweep on that backend (rows keep their
historical names, the artifact records the backend as provenance); several
values record ref-vs-Pallas throughput side by side (``pipes2`` next to
``pipes2_pallas_interpret``).  ``--oracle`` additionally verify_oracle's
every point — engine≡loop counters+telemetry on that point's backend.

``--devices`` runs the fabric scaling sweep instead (switchsim.fabric,
DESIGN.md §12): each pipes point is re-run with its pipe axis sharded over
every requested device count (1 is auto-included as the invariance
reference), timing rows land as ``fabric/pipes{p}_dev{d}/pps`` and every
device count's counters/telemetry/occupancy are asserted bit-identical to
the single-device run (``shard_invariance_identical`` rows; the bench
exits non-zero on any divergence).  ``--host-devices N`` applies the
SNIPPETS.md ``--xla_force_host_platform_device_count`` recipe via
``repro.distributed.force_host_devices`` before jax initializes, so
CPU-only hosts (CI included) exercise real multi-device sharding.

    PYTHONPATH=src python benchmarks/bench_pipeline.py --pipes 1 2 4 8
    PYTHONPATH=src python benchmarks/bench_pipeline.py --pipes 2 --tiny
    PYTHONPATH=src python benchmarks/bench_pipeline.py --recirc
    PYTHONPATH=src python benchmarks/bench_pipeline.py --pipes 1 2 \
        --backend ref pallas_interpret
    PYTHONPATH=src python benchmarks/bench_pipeline.py --pipes 2 --tiny \
        --backend pallas_interpret --oracle
    PYTHONPATH=src python benchmarks/bench_pipeline.py --pipes 8 \
        --host-devices 8 --devices 1 2 8 --oracle --json BENCH_fabric.json

Prints ``name,value,derived`` CSV rows like benchmarks/run.py.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

try:
    from benchmarks.artifacts import write_bench_json
    from benchmarks.common import check_flags, make_parser, print_rows
except ImportError:  # run as a script: benchmarks/ itself is on sys.path
    from artifacts import write_bench_json
    from common import check_flags, make_parser, print_rows

import repro.scenarios as S
from repro.core.packet import to_time_major, wire_bytes
from repro.hostmodel import HostModel, pcie_reduction
from repro.switchsim import engine as E
from repro.switchsim import perfmodel as P
from repro.switchsim.simulate import simulate_loop


def _cat(batches):
    return jax.tree.map(lambda *xs: jnp.concatenate(xs), *batches)


def _time(fn, repeats: int) -> float:
    fn()  # warm-up / compile
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def bench(pipes_list, n_pkts, chunk, window, capacity, pmax, repeats,
          verify: bool = True, explicit_drops: bool = False,
          backends=("ref",), oracle: bool = False):
    specs = S.pipeline_grid(pipes_list, packets=n_pkts, chunk=chunk,
                            window=window, pmax=pmax, capacity=capacity,
                            explicit_drops=explicit_drops, backends=backends)
    results = S.run_matrix(specs, time_runs=True, time_repeats=repeats)
    model = P.ServerModel()
    rows = []
    matrix = {s.name: s.as_dict() for s in specs}

    for spec, res in zip(specs, results):
        if oracle:
            S.verify_oracle(res)  # engine≡loop on this point's backend
        n_pipes = spec.pipes
        dt = res.wall_s
        pps = n_pkts / dt if dt else 0.0
        gain = res.gain
        cfg = spec.park_config()
        d = P.measured_digest(
            res.alive_offered, res.telemetry.wire_bytes,
            res.telemetry.to_server_bytes,
            res.counters["splits"] / max(res.alive_offered, 1))
        base_d = P.TrafficDigest(d.mean_wire_bytes, d.mean_wire_bytes, 0.0)
        op_park = P.scale_pipes(
            P.peak_goodput(model, d, res.nf_cycles,
                           table_capacity=cfg.capacity, max_exp=cfg.max_exp,
                           parking=True), n_pipes)
        op_base = P.scale_pipes(
            P.peak_goodput(model, base_d, res.nf_cycles), n_pipes)
        model_gain = op_park.goodput_gbps / op_base.goodput_gbps - 1.0
        rows.append((
            f"pipeline/{spec.name}/pps", round(pps),
            f"wall_s={dt:.4f};splits={res.counters['splits']};"
            f"merges={res.counters['merges']};"
            f"premature={res.counters['premature_evictions']};"
            f"overflow={res.steer_stats['overflow']};"
            f"backend={spec.backend}", spec.name))
        rows.append((
            f"pipeline/{spec.name}/goodput_gain",
            round(gain["goodput_gain"], 4),
            f"link_byte_saving={gain['link_byte_saving']:.4f};"
            f"gain_naive={gain['goodput_gain_naive']:.4f};"
            f"model_peak_gain={model_gain:.4f};"
            f"model_goodput_gbps={op_park.goodput_gbps:.2f};"
            f"bottleneck={op_park.bottleneck};"
            f"pcie_reduction="
            f"{pcie_reduction(HostModel().link, res.telemetry):.4f}",
            spec.name))

    if verify and 1 in pipes_list:
        for spec1 in [s for s in specs if s.pipes == 1]:
            pkts = S.make_packets(spec1)
            chain = S.build_chain(spec1, pkts)
            cfg = spec1.park_config()
            bk = spec1.backend_config()
            trace = to_time_major(pkts, chunk)
            eng = E.run_engine(cfg, chain, trace, window=window,
                               explicit_drops=explicit_drops, backend=bk,
                               collect_sent=True)

            def run_loop(cfg=cfg, chain=chain, pkts=pkts, bk=bk):
                return simulate_loop(cfg, chain, pkts, window=window,
                                     chunk=chunk,
                                     explicit_drops=explicit_drops,
                                     backend=bk)

            loop_res = run_loop()
            dt_loop = _time(run_loop, max(1, repeats // 2))
            dt_eng = _time(
                lambda cfg=cfg, chain=chain, trace=trace, bk=bk:
                jax.block_until_ready(
                    E.run_engine(cfg, chain, trace, window=window,
                                 explicit_drops=explicit_drops,
                                 backend=bk).merged.payload),
                repeats)
            got, gl = wire_bytes(
                jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]),
                             eng.merged))
            want, wl_ = wire_bytes(_cat(loop_res.merged))
            identical = (np.array_equal(np.asarray(got), np.asarray(want))
                         and np.array_equal(np.asarray(gl), np.asarray(wl_))
                         and eng.counters == loop_res.counters
                         and eng.telemetry == loop_res.telemetry)
            # legacy row name for a single-backend sweep so committed
            # baselines keep gating it; per-backend names when swept
            vname = ("pipeline/engine_vs_seed_loop/identical"
                     if len(backends) == 1 else
                     f"pipeline/engine_vs_seed_loop_{spec1.backend}"
                     f"/identical")
            rows.append((
                vname, int(identical),
                f"speedup={dt_loop / dt_eng:.2f}x;"
                f"loop_s={dt_loop:.4f};engine_s={dt_eng:.4f};"
                f"backend={spec1.backend}", spec1.name))
            if not identical:
                raise SystemExit(
                    f"engine output diverged from seed loop "
                    f"(backend={spec1.backend})")
    return rows, matrix


def bench_fabric(pipes_list, devices_list, n_pkts, chunk, window, capacity,
                 pmax, repeats, backends=("ref",), oracle: bool = False,
                 explicit_drops: bool = False):
    """Fabric scaling sweep (DESIGN.md §12): every pipes point re-run with
    its pipe axis sharded over each requested device count.

    Device count 1 is auto-included as the invariance reference even when
    not requested: shard-count invariance — bit-identical counters,
    telemetry, per-pipe peak occupancy and occupancy series across device
    counts — is the sweep's correctness claim, asserted here and emitted
    as exact-gated ``shard_invariance_identical`` rows.  Any divergence
    exits non-zero.  Timing rows (``fabric/.../pps``) record the scaling
    trajectory; ``devices_effective`` in the derived field exposes the
    guarded fallback (requested counts that didn't divide the pipe axis
    ran replicated on one device; counts beyond the visible devices raise).
    """
    from repro.switchsim import fabric
    devices_list = sorted(set(devices_list) | {1})
    specs = S.pipeline_grid(pipes_list, packets=n_pkts, chunk=chunk,
                            window=window, pmax=pmax, capacity=capacity,
                            explicit_drops=explicit_drops,
                            backends=backends, devices=devices_list)
    results = S.run_matrix(specs, time_runs=True, time_repeats=repeats)
    matrix = {s.name: s.as_dict() for s in specs}
    rows = []
    points: dict = {}  # (pipes, backend) -> [(spec, result)] in devices order
    for spec, res in zip(specs, results):
        if oracle:
            S.verify_oracle(res)  # engine≡loop per pipe, hence per shard
        eff = fabric.resolve_devices(spec.pipes, spec.devices)
        dt = res.wall_s
        rows.append((
            f"fabric/{spec.name}/pps", round(n_pkts / dt) if dt else 0,
            f"wall_s={dt:.4f};devices={spec.devices};"
            f"devices_effective={eff};pipes={spec.pipes};"
            f"backend={spec.backend}", spec.name))
        rows.append((
            f"fabric/{spec.name}/goodput_gain",
            round(res.gain["goodput_gain"], 4),
            f"link_byte_saving={res.gain['link_byte_saving']:.4f};"
            f"devices={spec.devices}", spec.name))
        points.setdefault((spec.pipes, spec.backend), []).append((spec, res))

    diverged = []
    for (pipes, _bk), group in sorted(points.items()):
        ref_spec, ref = group[0]  # devices=1 (devices_list is sorted)
        assert ref_spec.devices == 1
        label = ref_spec.name.rsplit("_dev", 1)[0]
        bad = []
        for spec, res in group[1:]:
            same = (
                res.counters == ref.counters
                and res.per_pipe_counters == ref.per_pipe_counters
                and res.telemetry == ref.telemetry
                and res.per_pipe_telemetry == ref.per_pipe_telemetry
                and res.nf_counters == ref.nf_counters
                and res.per_pipe_nf_counters == ref.per_pipe_nf_counters
                and res.per_pipe_peak_occupancy
                == ref.per_pipe_peak_occupancy
                and np.array_equal(np.asarray(res.per_pipe_occ_series),
                                   np.asarray(ref.per_pipe_occ_series)))
            if not same:
                bad.append(spec.name)
        rows.append((
            f"fabric/{label}/shard_invariance_identical", int(not bad),
            f"devices={'/'.join(str(s.devices) for s, _ in group)};"
            f"diverged={','.join(bad) or 'none'}", label))
        diverged.extend(bad)
    if diverged:
        raise SystemExit(
            f"shard-count invariance violated: {', '.join(diverged)} "
            f"diverged from the single-device reference")
    return rows, matrix


def bench_recirc(n_pkts, chunk, window, pmax, recirc_frac=0.25):
    """Paper §6.2.5 / Fig. 13 direction on the stateful engine: sweep table
    occupancy (capacity vs the in-flight window) and compare goodput gain
    with the recirculation lane off vs on.  At high occupancy the lane must
    win strictly — retries rescue occupied-slot skips and second passes park
    up to 352B — or the bench exits non-zero.  Every recirculation-on run is
    also checked against the host-loop oracle (counters + telemetry)."""
    specs = S.recirc_grid(packets=n_pkts, chunk=chunk, window=window,
                          pmax=pmax, recirc_frac=recirc_frac)
    results = {r.spec.name: r for r in S.run_matrix(specs)}
    model = P.ServerModel()
    matrix = {s.name: s.as_dict() for s in specs}
    rows = []
    gains = {}
    for label in ("low", "mid", "high"):
        off = results[f"occ_{label}_off"]
        on = results[f"occ_{label}_on"]
        capacity = off.spec.capacity
        S.verify_oracle(on)  # raises OracleMismatch on divergence
        gains[label] = {"off": off.gain["goodput_gain"],
                        "on": on.gain["goodput_gain"]}
        c_on = on.counters
        d = P.measured_digest(
            n_pkts, on.telemetry.wire_bytes, on.telemetry.to_server_bytes,
            c_on["splits"] / max(n_pkts, 1),
            recirc_per_pkt=c_on["recirculations"] / max(n_pkts, 1))
        op = P.evaluate(model, d, on.nf_cycles, send_gbps=10.0)
        rows.append((
            f"recirc/occ_{label}/gain_off",
            round(gains[label]["off"], 4),
            f"capacity={capacity};"
            f"peak_occ={off.peak_occupancy};"
            f"skip_occupied={off.counters['skip_occupied']}",
            off.spec.name))
        rows.append((
            f"recirc/occ_{label}/gain_on",
            round(gains[label]["on"], 4),
            f"capacity={capacity};peak_occ={on.peak_occupancy};"
            f"recirculations={c_on['recirculations']};"
            f"budget_drops={c_on['recirc_budget_drops']};"
            f"skip_occupied={c_on['skip_occupied']};"
            f"premature={c_on['premature_evictions']};"
            f"model_lat_us={op.latency_us:.2f}", on.spec.name))
        rows.append((
            f"recirc/occ_{label}/gain_delta",
            round(gains[label]["on"] - gains[label]["off"], 4),
            f"recirc_frac={recirc_frac}", None))
    if not gains["high"]["on"] > gains["high"]["off"]:
        raise SystemExit(
            f"recirculation gain not above baseline at high occupancy: "
            f"on={gains['high']['on']:.4f} off={gains['high']['off']:.4f}")
    return rows, matrix


def main() -> None:
    # shared flags (--tiny/--json/--no-verify/--oracle/--backend) come
    # from the common parent parser (benchmarks/common.py); this bench is
    # the one that sweeps multiple --backend values
    ap = make_parser(__doc__)
    ap.add_argument("--pipes", type=int, nargs="+", default=[1, 2, 4, 8])
    ap.add_argument("--packets", type=int, default=16384)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--window", type=int, default=2)
    ap.add_argument("--capacity", type=int, default=4096)
    ap.add_argument("--pmax", type=int, default=2048)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--devices", type=int, nargs="+", default=[1],
                    help="fabric scaling sweep (DESIGN.md §12): shard each "
                         "pipes point over these device counts (1 is "
                         "auto-included as the invariance reference); "
                         "emits fabric/* rows instead of pipeline/*")
    ap.add_argument("--host-devices", type=int, default=0, metavar="N",
                    help="force the CPU platform to expose N devices "
                         "(repro.distributed.force_host_devices; must run "
                         "before jax initializes, which this flag "
                         "guarantees by applying it first)")
    ap.add_argument("--recirc", action="store_true",
                    help="run the recirculation occupancy sweep "
                         "(paper §6.2.5) instead of the pipes sweep")
    ap.add_argument("--recirc-frac", type=float, default=0.25,
                    help="recirculation-port share of pipe capacity")
    ap.add_argument("--explicit-drops", action="store_true",
                    help="NF-dropped parked packets send OP=drop "
                         "notifications back to the switch (paper §6.2.4)")
    args = ap.parse_args()
    check_flags(ap, args)
    backends = args.backend or ["ref"]
    if args.host_devices:
        # before ANY jax device use — force_host_devices raises if too late
        from repro.distributed import force_host_devices
        force_host_devices(args.host_devices)
    fabric_sweep = args.devices != [1]
    if args.recirc:
        # the occupancy sweep owns these knobs; fail loudly rather than
        # silently ignoring an explicit flag
        ignored = [flag for flag, val, default in (
            ("--capacity", args.capacity, 4096),
            ("--repeats", args.repeats, 3),
            ("--no-verify", args.no_verify, False),
            ("--explicit-drops", args.explicit_drops, False),
            ("--backend", args.backend, None),
            ("--oracle", args.oracle, False),
            ("--devices", tuple(args.devices), (1,)),
        ) if val != default]
        if ignored:
            ap.error(f"--recirc does not take {', '.join(ignored)} "
                     f"(the sweep sets capacity per occupancy point and "
                     f"always verifies against the loop oracle)")
    if fabric_sweep:
        if len(backends) > 1:
            ap.error("--devices sweeps take a single --backend (the "
                     "invariance reference is per (pipes, backend) point)")
        if args.no_verify:
            ap.error("--no-verify only applies to the pipes sweep's "
                     "seed-loop check; the fabric sweep's invariance "
                     "check is not optional")
    if args.tiny:
        args.packets, args.chunk, args.capacity = 512, 64, 256
        args.pmax, args.repeats = 512, 1
    if args.packets % args.chunk:
        ap.error(f"--packets ({args.packets}) must be a multiple of "
                 f"--chunk ({args.chunk})")
    if args.recirc:
        rows, matrix = bench_recirc(args.packets, args.chunk, args.window,
                                    args.pmax, recirc_frac=args.recirc_frac)
    elif fabric_sweep:
        rows, matrix = bench_fabric(args.pipes, args.devices, args.packets,
                                    args.chunk, args.window, args.capacity,
                                    args.pmax, args.repeats,
                                    backends=backends,
                                    oracle=args.oracle,
                                    explicit_drops=args.explicit_drops)
    else:
        rows, matrix = bench(args.pipes, args.packets, args.chunk,
                             args.window, args.capacity, args.pmax,
                             args.repeats, verify=not args.no_verify,
                             explicit_drops=args.explicit_drops,
                             backends=backends, oracle=args.oracle)
    print_rows(rows)
    if args.json:
        # single-backend runs record their backend as artifact provenance
        # (compare.py uses it to match baselines per backend); resolved to
        # what actually ran, so "auto" can never mask a platform difference
        backend = None
        if not args.recirc and len(backends) == 1:
            from repro.backend import as_config
            backend = as_config(backends[0]).concrete().default
        family = ("recirc" if args.recirc
                  else "fabric" if fabric_sweep else "pipeline")
        write_bench_json(args.json, family, rows, matrix=matrix,
                         backend=backend)


if __name__ == "__main__":
    main()
