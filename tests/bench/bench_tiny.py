"""Tiny copies of the benchmark's cells, small enough for a CPU test run.

They keep every mechanism of the real cells (pipes, recirculation lane,
the whole chain, the flow pool, the synthetic source) at a few hundred
packets, and run on the ``ref`` backend.
"""
import copy
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


def _load(rel: str) -> dict:
    return json.loads((BENCH / rel).read_text())


def matrix_config() -> dict:
    cfg = copy.deepcopy(_load("configs/tor8_fw_nat_lb.json"))
    cfg.update(pipes=2, packets_per_pipe=1024, chunk=64, backend="ref")
    cfg["park"]["capacity"] = 256
    return cfg


def fabric_config() -> dict:
    """The ToR's pipes sharded over four devices: 8 pipes, 2 a device."""
    cfg = matrix_config()
    cfg.update(pipes=8, devices=4, packets_per_pipe=256)
    return cfg


def stream_config() -> dict:
    cfg = copy.deepcopy(_load("configs/nat1_stream.json"))
    cfg.update(steps_per_call=32, chunk=64, segment_len=16, reservoir=256,
               backend="ref")
    cfg["park"]["capacity"] = 256
    # a flow table smaller than the flows: the NAT drops, and dropped
    # packets leave their parked payloads in the table
    cfg["nat"]["capacity"] = 256
    return cfg


def traffic(name: str) -> dict:
    return copy.deepcopy(_load(f"traffic/{name}.json"))
