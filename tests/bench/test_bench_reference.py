"""The numpy reference agrees with the simulator, and notices a fault.

At tiny sizes on the CPU: every fact the benchmark compares (counters,
telemetry, occupancy, the lane, every merged packet field by field, the
streamed run's reservoir and final table) must be equal for all three
traffic mixes; one corrupted parked payload byte or one corrupted NAT
rewrite in the program's output must be counted.
"""
import copy

import numpy as np
import pytest

import bench_tiny as T
from bench import drivers as D

MIXES = ("dc", "min64", "enterprise")
# a mix may also vary its offered load over time, as a sine of the step
LOADED = {"period": 32, "base": 0.75, "amplitude": 0.25, "phase": 0.0}


def _stream_traffic(mix):
    tr = T.traffic("enterprise")
    if mix == "enterprise_loaded":
        return dict(tr, load=LOADED)
    base = T.traffic(mix)
    tr.update(workload=base["workload"], sizes=base["sizes"],
              probs=base["probs"])
    return tr


@pytest.fixture(scope="module", params=MIXES)
def matrix_run(request):
    d = D.MatrixDriver(T.matrix_config(), T.traffic(request.param),
                       seed=2**31 + 77)
    d.call(1, keep=True)
    return request.param, d


@pytest.fixture(scope="module", params=MIXES + ("enterprise_loaded",))
def stream_run(request):
    d = D.StreamDriver(T.stream_config(), _stream_traffic(request.param),
                       seed=2**32 + 5)
    d.call(1, keep=True)
    return request.param, d


def test_matrix_agrees(matrix_run):
    mix, d = matrix_run
    checks = d.check()
    assert checks.compared == d.check_pipes
    assert checks.values == dict.fromkeys(D.MERGED_CHECKS, 0), (mix, checks.values)
    pipes = d.kept[0]["pipes"]
    alive = sum(int(p["merged"]["alive"].sum()) for p in pipes.values())
    assert alive > 0
    splits = sum(p["counters"]["splits"] for p in pipes.values())
    assert (splits > 0) == (mix != "min64"), mix


def test_stream_agrees(stream_run):
    mix, d = stream_run
    checks = d.check()
    assert checks.compared == 1
    assert checks.values == dict.fromkeys(D.STREAM_CHECKS, 0), (mix, checks.values)
    assert d.kept[0]["telemetry"]["merged_pkts"] > 0


def _payload_row(pipe):
    """(step, row) of a merged packet with payload, a parked one where
    any parked (payloads of 160 B and more are parked)."""
    m = pipe["merged"]
    for least in (160, 18):
        rows = np.argwhere(m["alive"] & (m["payload_len"] >= least))
        if len(rows):
            return tuple(rows[len(rows) // 2])
    raise AssertionError("no merged packet carries payload")


def test_one_payload_byte_is_noticed(matrix_run):
    _, d = matrix_run
    saved = d.kept
    d.kept = copy.deepcopy(saved)
    try:
        pipe = next(iter(d.kept[0]["pipes"].values()))
        t, r = _payload_row(pipe)
        pipe["merged"]["payload"][t, r, 17] ^= 0x5A
        checks = d.check()
        assert checks.values["payload_byte_diffs"] == 1
        assert not checks.ok()
    finally:
        d.kept = saved


def test_one_nat_rewrite_is_noticed(matrix_run):
    _, d = matrix_run
    saved = d.kept
    d.kept = copy.deepcopy(saved)
    try:
        pipe = next(iter(d.kept[0]["pipes"].values()))
        m = pipe["merged"]
        t, r = np.argwhere(m["alive"])[0]
        m["src_port"][t, r] += 1
        checks = d.check()
        assert checks.values["nat_rewrite_diffs"] == 1
        assert not checks.ok()
    finally:
        d.kept = saved


def test_one_parked_byte_in_the_stream_table_is_noticed(stream_run):
    _, d = stream_run
    saved = d.kept
    d.kept = copy.deepcopy(saved)
    try:
        table = d.kept[0]["state"]["ptable"]
        table[3, 5] ^= 1
        checks = d.check()
        assert checks.values["payload_byte_diffs"] == 1
        assert not checks.ok()
    finally:
        d.kept = saved


def test_the_control_comes_out_wrong(matrix_run):
    """The control (the reference with the NAT probing 1 slot instead of
    the stated 8) in the program's place must fail the comparison."""
    _, d = matrix_run
    checks = d.check(control=True)
    assert not checks.ok()
    assert checks.values["fw_verdict_diffs"] + \
        checks.values["nat_rewrite_diffs"] > 0


def test_the_control_comes_out_wrong_in_the_stream(stream_run):
    _, d = stream_run
    checks = d.check(control=True)
    assert not checks.ok()
    assert checks.values["nat_count_diffs"] + \
        checks.values["telemetry_diffs"] > 0
