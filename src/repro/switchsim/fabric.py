"""Fabric-scale sharded simulation: the pipe axis across devices.

One ToR switch is 8 per-port pipes on one device (``engine.run_pipes``,
DESIGN.md §3).  A datacenter fabric is dozens of such switches — hundreds
of pipes over 10⁷+ packets — and pipes share *nothing* (the hardware pipes
share nothing either), so the flat vmapped pipe axis the scenario runner
already batches on (DESIGN.md §8) is embarrassingly shardable.  This
module puts a ``jax.sharding`` mesh under it:

  * ``switch_mesh(devices)`` builds a 1-D mesh over the first ``devices``
    visible devices, axis name ``"switch"`` — each mesh slot simulates an
    equal slice of the fabric's pipes (one or more switches' worth);
  * ``shard_over_switch(run, devices)`` wraps the engine's vmapped
    single-pipe program in ``shard_map``: every input (traces, fault
    masks, drain flags) and every output (states, counters ys, telemetry
    ys) carries the pipe axis leading, so ONE ``PartitionSpec("switch")``
    is the whole contract — no collectives, no replicated outputs, no
    cross-shard traffic of any kind;
  * ``SWITCH``, ``PartitionSpec("switch")``, is the one contract the
    engine and the traffic program share: the pipe axis of every trace
    leaf leads and shards contiguously over the mesh.  A sharded point's
    traffic is drawn, steered and exchanged per shard by
    ``scenarios.spec.sharded_traffic``, one jitted program over the same
    mesh, so its traces arrive already sharded as the engine's
    ``shard_map`` takes them and no device holds the whole call's
    payload draw (one-device points keep the eager draw);
  * ``resolve_devices(pipes, devices)`` is the guarded
    fallback-to-replication (``distributed.sharding.divides_axis``, the
    same predicate the model-parallel rules use): when the pipe count
    does not divide the requested device count, the run warns and
    executes replicated on one device — never padded.  Fewer visible
    devices than requested is an error, never a silent single-device run.

**Shard-count invariance is the correctness contract**: the same
``ScenarioSpec`` run on 1, 2 or 8 devices yields bit-identical counters,
telemetry and occupancy, because sharding only re-tiles the pipe axis and
every per-pipe scan is reduction-free across pipes (cross-pipe aggregation
happens host-side in int64 after the program returns, exactly as in the
single-device path).  ``tests/test_fabric.py`` pins this on forced host
devices; the engine≡loop oracle holds per shard — ``verify_oracle``
re-runs the host loop on each device's pipe slice independently
(DESIGN.md §12).

CPU-only hosts (CI included) exercise real multi-device sharding via the
forced-host-device recipe: ``distributed.force_host_devices(8)`` before
jax initializes, or ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
in the environment — see ``benchmarks/bench_pipeline.py --host-devices``.

Entry points: ``engine.run_pipes(..., devices=N)`` (the engine owns result
assembly; it resolves the device count through this module and fetches the
shard_mapped program from its compile cache), ``ScenarioSpec(devices=N)``
(a first-class grid axis, part of the compile key), and
``bench_pipeline --devices`` (the scaling sweep, ``BENCH_fabric.json``).

Design notes: DESIGN.md §12 (fabric sharding).
"""
from __future__ import annotations

import warnings

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec

from repro.distributed.sharding import axis_size, divides_axis

SWITCH_AXIS = "switch"
SWITCH = PartitionSpec(SWITCH_AXIS)


def fabric_devices() -> int:
    """Devices visible to the fabric (forced host devices included).

    This is the call that initializes the jax backend — anything that
    needs ``distributed.force_host_devices`` must run before it."""
    return len(jax.devices())


def switch_mesh(devices: int) -> Mesh:
    """1-D ``("switch",)`` mesh over the first ``devices`` devices.

    The axis is ``Auto``-typed: the engine's host-side finalization indexes
    single pipes out of the sharded outputs (``a[p]``), a gather that an
    ``Explicit`` axis refuses to resolve without an ``out_sharding``."""
    return jax.make_mesh((devices,), (SWITCH_AXIS,),
                         axis_types=(AxisType.Auto,))


def resolve_devices(pipes: int, devices: int | None) -> int:
    """The device count a ``pipes``-wide run will actually shard over.

    Returns ``devices`` when it is usable (>1, visible, and dividing the
    pipe axis — ``distributed.sharding.divides_axis``, the same guard the
    model-parallel rules apply to weight dims).  A pipe count that does not
    divide the devices warns and returns 1, the replicated single-device
    path; shard-count invariance makes that safe (bit-identical results,
    only wall-clock changes).  Asking for more devices than are visible
    raises: running such a request on one device would hide the missing
    hardware behind a result that looks sharded.
    """
    if devices is None or devices <= 1:
        return 1
    avail = fabric_devices()
    if devices > avail:
        raise ValueError(
            f"fabric: {devices} devices requested but only {avail} "
            f"visible.  On CPU, force host devices before jax initializes "
            f"(repro.distributed.force_host_devices({devices}) or "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={devices}).")
    if not divides_axis(pipes, devices):
        warnings.warn(
            f"fabric: pipe axis of {pipes} does not divide over "
            f"{devices} devices — falling back to replication "
            f"(single device; results are bit-identical by the "
            f"shard-count-invariance contract).",
            stacklevel=2)
        return 1
    return devices


def shard_over_switch(run, devices: int):
    """Wrap the engine's vmapped pipe program in ``shard_map``.

    ``run`` is ``vmap(_build_scan(...))`` — signature
    ``(traces, server_up, lb_up, drain) -> (state, cstates, ys)`` with the
    pipe axis leading on every input and output leaf.  The whole sharding
    contract is therefore one spec: ``PartitionSpec("switch")`` on axis 0,
    trailing axes replicated.  Each device runs the identical scan over
    its contiguous pipe slice; outputs remain logically global arrays, so
    the engine's host-side finalization (int64 sums, per-pipe slicing, the
    scenario runner's per-scenario regrouping) gathers from the shards
    transparently and is byte-for-byte the single-device code path.

    The caller (``engine._compiled``) jits the returned function and
    caches it keyed on ``devices``, so re-runs never re-trace.
    """
    mesh = switch_mesh(devices)
    assert axis_size(mesh, SWITCH_AXIS) == devices
    # check_vma=False: the body is a pure per-pipe map with no collectives
    # and no replicated outputs, so the varying-axes checker has nothing to
    # prove and only adds tracing overhead on wide fabrics.
    return jax.shard_map(run, mesh=mesh, in_specs=(SWITCH,) * 4,
                         out_specs=SWITCH, check_vma=False)
