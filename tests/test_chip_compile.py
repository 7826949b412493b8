"""Compile the dataplane for a described TPU v5e (no chip attached).

Interpret mode runs the Pallas kernel bodies on the CPU, but it accepts
what the chip's compiler refuses (a 1-D data-dependent gather, a block not
aligned to the tiling, more VMEM than a kernel may use).  These tests hand
the TPU compiler the real shapes of the 8-pipe ToR deployment that
``chip_smoke.py`` runs: every dataplane primitive with ``interpret=False``
at chunk 256, and the whole FW -> NAT -> LB engine program on ``pallas``.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import this
file.  Where it cannot be described the tests skip from the fixture.
"""
from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.backend import BackendConfig, primitive
from repro.core.packet import dead_batch
from repro.scenarios.spec import ScenarioSpec, build_chain
from repro.switchsim import engine as E
from repro.traffic.generator import pipe_trace_steps

CHUNK = 256
CAPACITY = 8192  # per_server_capacity(0.40, ParkConfig(pmax=2048,
                 # recirculation=True), 8): the paper's <40% budget
ROW_BYTES = 352  # recirculation-mode row (core.park.PARK_BYTES_RECIRC)
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _i32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)


def _primitive_args(name: str, s):
    b = (CHUNK,)
    if name == "crc16_tag":
        return (_i32(b, s), _i32(b, s))
    if name == "acl_match":
        return (_i32(b, s), _i32((20,), s))
    if name == "maglev_select":
        return tuple(_i32(b, s) for _ in range(5)) + (
            _i32((251,), s), _i32((8,), s))
    table = jax.ShapeDtypeStruct((CAPACITY, ROW_BYTES), jnp.uint8,
                                 sharding=s)
    mask = jax.ShapeDtypeStruct(b, jnp.bool_, sharding=s)
    if name == "payload_store":
        rows = jax.ShapeDtypeStruct((CHUNK, ROW_BYTES), jnp.uint8,
                                    sharding=s)
        return (table, rows, _i32(b, s), mask)
    assert name == "payload_fetch"
    return (table, _i32(b, s), mask)


@pytest.mark.parametrize("name", ["crc16_tag", "acl_match", "maglev_select",
                                  "payload_store", "payload_fetch"])
def test_primitive_compiles_for_v5e(one_chip, name):
    fn = jax.jit(partial(primitive(name).pallas, interpret=False))
    compiled = fn.lower(*_primitive_args(name, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _tor_engine(sharding, tors: int = 1, devices: int = 1):
    """The engine program ``run_matrix`` builds for ``tors`` ToRs of the
    chip smoke's deployment (8 pipes each), uncached, with its argument
    shapes on ``sharding``."""
    spec = ScenarioSpec(
        name="tor", workload=("datacenter",), chain=("fw", "nat", "lb"),
        pipes=8 * tors, recirc=True, capacity=CAPACITY, max_exp=4,
        flows=1024, fw_rules=20, packets=8 * tors * 16384, chunk=CHUNK,
        window=2, pmax=2048, backend="pallas", devices=devices)
    cfg = spec.park_config()
    chain = build_chain(spec, None)  # flows > 0: rules come from the pool
    lane = E.recirc_slots(cfg, CHUNK)
    steps = pipe_trace_steps(spec.packets, spec.pipes, CHUNK) + \
        spec.window + 1
    lead = (spec.pipes, steps)
    template = jax.eval_shape(lambda: dead_batch(CHUNK, spec.pmax))
    traces = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(lead + a.shape, a.dtype,
                                       sharding=sharding), template)
    mask = jax.ShapeDtypeStruct(lead, jnp.bool_, sharding=sharding)
    drain = jax.ShapeDtypeStruct((spec.pipes,), jnp.bool_, sharding=sharding)
    fn = E._compiled.__wrapped__(cfg, chain, spec.window, False,
                                 BackendConfig("pallas"), False, pipes=True,
                                 recirc=lane, devices=devices)
    return fn.lower(traces, mask, mask, drain).compile()


def test_tor_engine_compiles_for_v5e(one_chip):
    """The whole 8-pipe FW -> NAT -> LB engine program with recirculation,
    as ``run_matrix`` builds it for the chip smoke's deployment."""
    compiled = _tor_engine(one_chip)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < \
        V5E_HBM_BYTES


def test_fabric_engine_compiles_for_v5e_2x2(topo, monkeypatch):
    """4 ToRs (32 pipes) shard_mapped over the four chips of a v5e 2x2,
    one ToR per chip: the ``chip_smoke.py --chips 4`` program.  The mesh
    comes from the described devices, which ``jax.devices()`` (the CPU
    here) cannot give ``fabric.switch_mesh``."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec

    from repro.switchsim import fabric

    mesh = Mesh(topo.devices[:4], (fabric.SWITCH_AXIS,),
                axis_types=(AxisType.Auto,))
    monkeypatch.setattr(fabric, "switch_mesh", lambda devices: mesh)
    sharded = NamedSharding(mesh, PartitionSpec(fabric.SWITCH_AXIS))
    compiled = _tor_engine(sharded, tors=4, devices=4)
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # pipes share nothing: no collective may appear in the program
    for op in ("all-gather", "all-reduce", "collective-permute",
               "all-to-all"):
        assert op not in text, op
