#!/usr/bin/env python3
"""Chip smoke run: the PayloadPark engine on a TPU at the size of one ToR.

The deployment is the paper's: one top-of-rack switch serving 8 NF servers
(§6.3.2) through the §7 Firewall -> NAT -> Maglev LB chain, on datacenter
traffic (Benson et al., IMC 2010), with recirculation on and 8192 parking
slots per pipe (``hostmodel.per_server_capacity(0.40, ...)``: the paper's
<40% of switch memory).  Traffic, tables and flow pools are generated from
``--seed``; nothing is read from outside the repository.

Default (one chip), all in this one process:

  A. materialized engine: the deployment through ``scenarios.run_matrix``
     on ``pallas`` and on ``ref``.  The two must agree bit for bit
     (counters, per-pipe telemetry, NF counters, occupancy series, merged
     packets), and ``verify_oracle`` (the host-loop reference
     ``simulate_loop``) must pass on the Pallas result.
  B. streaming engine: ``run_stream`` at ``bench_streaming``'s full
     geometry (1024 steps x 256 packets, segment 128) on ``pallas``,
     checked against the materialized engine by ``replay_oracle``.

``--chips 4`` runs only the fabric path: 4 ToRs x 8 pipes = 32 pipes
sharded over 4 chips (one ToR per chip), and the same 32 pipes on one
device.  Results must be identical bit for bit, and the sharded outputs
must span all 4 devices.

Lines before the last are progress and smoke readings, not benchmark
metrics.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``, printed only
when every phase passed.  Without a TPU, or on any failed check, the
script exits non-zero without it.

    python3 chip_smoke.py [--seed N]
    python3 chip_smoke.py --chips 4 [--seed N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
import traceback
import warnings
from pathlib import Path

REPO = Path(__file__).resolve().parent
ORACLE_BUDGET_S = 120.0   # host-loop reference: stop adding pipes after this
TOR_PIPES = 8
PACKETS_PER_PIPE = 16384


class SmokeFailure(Exception):
    """A check of the smoke run did not hold."""


def log(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def peak_bytes(device) -> object:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", "n/a")


def tor_spec(seed: int, backend: str, pipes: int = TOR_PIPES,
             devices: int = 1):
    """``pipes`` pipes of the 8-server ToR deployment (several ToRs when
    ``pipes`` is a multiple of 8: pipes share nothing)."""
    from repro.core.park import ParkConfig
    from repro.hostmodel.server import per_server_capacity
    from repro.scenarios import ScenarioSpec

    capacity = per_server_capacity(
        0.40, ParkConfig(pmax=2048, recirculation=True), TOR_PIPES)
    return ScenarioSpec(
        name=f"tor_{pipes}p_fw_nat_lb", workload=("datacenter",),
        chain=("fw", "nat", "lb"), pipes=pipes, recirc=True,
        capacity=capacity, max_exp=4, flows=1024, fw_rules=20,
        packets=pipes * PACKETS_PER_PIPE, chunk=256, window=2, pmax=2048,
        seed=seed, backend=backend, devices=devices)


def run_point(phase: str, spec):
    """One point through ``run_matrix``: a first call (traffic generation
    and compile included) and one warm re-run after block_until_ready."""
    import jax
    from repro.scenarios import run_matrix

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        res = run_matrix([spec], time_runs=True, time_repeats=1)[0]
        total = time.perf_counter() - t0
    fabric = [str(w.message) for w in caught if "fabric" in str(w.message)]
    check(not fabric, f"{spec.name}: fabric fell back: {fabric}")
    log(phase, point=spec.name, backend=spec.backend, devices=spec.devices,
        first_call_s=f"{total - res.group_wall_s:.3f}",
        warm_run_s=f"{res.group_wall_s:.3f}",
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
        goodput_gain=f"{res.gain['goodput_gain']:.6f}",
        splits=res.counters["splits"], merges=res.counters["merges"],
        recirculations=res.counters["recirculations"],
        peak_occupancy=res.peak_occupancy)
    return res


def differences(a, b) -> list[str]:
    """Every fact on which two results of the same traffic disagree."""
    import jax
    import numpy as np

    diffs = [name for name in (
        "counters", "per_pipe_counters", "telemetry", "per_pipe_telemetry",
        "nf_counters", "per_pipe_nf_counters", "per_pipe_peak_occupancy")
        if getattr(a, name) != getattr(b, name)]
    if not np.array_equal(np.asarray(a.per_pipe_occ_series),
                          np.asarray(b.per_pipe_occ_series)):
        diffs.append("per_pipe_occ_series")
    leaves_a = jax.tree_util.tree_leaves_with_path(a.merged)
    leaves_b = jax.tree.leaves(b.merged)
    for (path, x), y in zip(leaves_a, leaves_b):
        if not np.array_equal(np.asarray(x), np.asarray(y)):
            diffs.append("merged" + jax.tree_util.keystr(path))
    return diffs


def phase_materialized(seed: int) -> None:
    from repro.scenarios import verify_oracle

    pallas = run_point("A", tor_spec(seed, "pallas"))
    ref = run_point("A", tor_spec(seed, "ref"))
    diffs = differences(pallas, ref)
    check(not diffs, f"pallas and ref disagree on {diffs}")
    log("A", pallas_vs_ref="bit-identical",
        merged_rows=int(pallas.merged.alive.size),
        merged_payload_bytes=int(pallas.merged.payload.size))

    t0 = time.perf_counter()
    checked = []
    for pipe in range(pallas.spec.pipes):
        verify_oracle(pallas, pipes=[pipe])
        checked.append(pipe)
        if time.perf_counter() - t0 > ORACLE_BUDGET_S:
            break
    log("A", verify_oracle="pass", backend="pallas",
        pipes_checked=",".join(map(str, checked)),
        of_pipes=pallas.spec.pipes,
        oracle_s=f"{time.perf_counter() - t0:.3f}")


def phase_streaming(seed: int) -> None:
    import jax
    from benchmarks.bench_streaming import FULL, workload
    from repro.switchsim.stream import replay_oracle, run_stream

    cfg, chain, source = workload(FULL, seed=seed)
    kw = dict(window=FULL["window"], segment_len=FULL["segment_len"],
              backend="pallas")
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t0 = time.perf_counter()
        first = run_stream(cfg, chain, source, reservoir=FULL["reservoir"],
                           **kw)
        t1 = time.perf_counter()
        warm = run_stream(cfg, chain, source, reservoir=FULL["reservoir"],
                          **kw)
        t2 = time.perf_counter()
    donation = sum("donated buffers" in str(w.message) for w in caught)
    check(warm.counters == first.counters
          and warm.telemetry == first.telemetry,
          "two streaming runs of the same source disagree")
    packets = first.steps * FULL["chunk"]
    log("B", packets=packets, segments=first.segments,
        segment_len=first.segment_len, first_call_s=f"{t1 - t0:.3f}",
        warm_run_s=f"{t2 - t1:.3f}", donation_warnings=donation,
        peak_bytes_in_use=peak_bytes(jax.devices()[0]),
        p99_us=first.latency.get("p99_us"),
        peak_occupancy=first.peak_occupancy)
    t0 = time.perf_counter()
    rep = replay_oracle(cfg, chain, source, segments=4, **kw)
    log("B", replay_oracle="pass", steps=rep["steps"],
        segments=rep["segments"], oracle_s=f"{time.perf_counter() - t0:.3f}")


def phase_fabric(seed: int, chips: int) -> None:
    sharded_spec = tor_spec(seed, "pallas", pipes=chips * TOR_PIPES,
                            devices=chips)
    sharded = run_point("F", sharded_spec)
    single = run_point("F", dataclasses.replace(sharded_spec, devices=1))
    span = sharded.merged.payload.sharding.device_set
    check(len(span) == chips,
          f"sharded outputs span {len(span)} devices, not {chips}")
    diffs = differences(sharded, single)
    check(not diffs, f"devices={chips} and devices=1 disagree on {diffs}")
    log("F", pipes=sharded_spec.pipes, tors=chips, devices=chips,
        output_devices=len(span), shard_count_invariance="bit-identical")


def main() -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the generated traffic (default 0)")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the fabric path over four chips")
    args = ap.parse_args()

    if not (REPO / "src" / "repro").is_dir():
        print("chip_smoke: the repro package is not next to this script",
              file=sys.stderr)
        return 2
    sys.path[:0] = [str(REPO / "src"), str(REPO)]

    import jax
    from repro.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform})",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} "
              f"devices are visible", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    log("setup", platform=dev.platform, kind=repr(dev.device_kind),
        count=len(devices), seed=args.seed, compile_cache=cache)

    try:
        if args.chips > 1:
            phase_fabric(args.seed, args.chips)
        else:
            phase_materialized(args.seed)
            phase_streaming(args.seed)
    except Exception:  # noqa: BLE001 — any failure fails the smoke run
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
