"""A run whose timed path is broken underneath comes out not correct.

The harness's look for a chip is skipped; set-up, the window and the
check run as in a real run, at tiny sizes, on the CPU.  The cells run on
one chip, so there is no exchange between chips to leave out.
"""
import time

import pytest

import bench_tiny as T
import fault_cases as FC
from bench import drivers as D
from bench import harness


def _run(driver, config, traffic):
    cell = harness.Cell(workload={"name": "tiny", "chips": 1},
                        config=config, traffic=traffic,
                        manifest={"end_to_end": [], "per_layer": []})
    run, _ = harness.measure(cell, driver, 0.0, False, time.perf_counter(),
                             {}, log=lambda *_: None)
    assert run.calls
    return driver.check()


CASES = {
    "matrix": (D.MatrixDriver, T.matrix_config, "dc",
               ("state_unchanged", "half_the_batch", "merge_answer_altered")),
    "stream": (D.StreamDriver, T.stream_config, "enterprise",
               ("state_unchanged", "half_the_batch",
                "parked_answer_altered")),
}


@pytest.mark.parametrize("engine,fault", [
    (e, f) for e, case in CASES.items() for f in case[3]])
def test_a_broken_timed_path_is_not_correct(monkeypatch, engine, fault):
    drv, config, traffic, _ = CASES[engine]
    module, name, wrap = FC.FAULTS[fault]
    with FC.planted(monkeypatch, module, name, wrap):
        checks = _run(drv(config(), T.traffic(traffic), seed=4000000007),
                      config(), T.traffic(traffic))
    assert checks.compared > 0
    assert not checks.ok(), checks.values


@pytest.mark.parametrize("engine", sorted(CASES))
def test_the_same_run_unbroken_is_correct(engine):
    drv, config, traffic, _ = CASES[engine]
    checks = _run(drv(config(), T.traffic(traffic), seed=4000000007),
                  config(), T.traffic(traffic))
    assert checks.ok(), checks.values
