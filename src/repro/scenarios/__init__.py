"""Scenario matrix subsystem (DESIGN.md §8).

Declarative scenario specs (``spec``), a registry of named families
(``registry``, populated by ``matrix`` with the paper's evaluation grid),
and the vmapped sweep runner (``runner``) that executes trace-compatible
points as one compiled XLA program.
"""
from repro.scenarios.adversarial import (adversarial_family, bounds_for,
                                         degradation_block,
                                         degradation_metrics)
from repro.scenarios.matrix import pipeline_grid, recirc_grid
from repro.scenarios.registry import family, names, register
from repro.scenarios.runner import (OracleMismatch, ScenarioResult,
                                    default_rows, engine_programs,
                                    run_matrix, traffic_programs,
                                    verify_oracle)
from repro.scenarios.spec import (ScenarioSpec, build_chain, compile_key,
                                  draw_packets, grid, make_packets,
                                  resolve_workload, sharded_traffic, steer)

__all__ = [
    "family", "names", "register", "pipeline_grid", "recirc_grid",
    "OracleMismatch", "ScenarioResult", "default_rows", "engine_programs",
    "run_matrix", "traffic_programs", "verify_oracle",
    "ScenarioSpec", "build_chain", "compile_key", "draw_packets", "grid",
    "make_packets", "resolve_workload", "sharded_traffic", "steer",
    "adversarial_family", "bounds_for", "degradation_block",
    "degradation_metrics",
]
