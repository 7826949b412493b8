"""Per-shard traffic of a sharded scenario point (DESIGN.md §12).

A point sharded over several devices draws, steers and exchanges its
traffic in one program on the ``("switch",)`` mesh
(``scenarios.spec.sharded_traffic``).  Its traces and steering stats
must equal the one-device eager draw (``make_packets`` + ``steer``) bit
for bit, and ``run_matrix`` must give the same results on four devices
as on one.  The tests run in subprocesses on four forced host devices:
the device count is fixed before JAX starts, and the pytest process
keeps one.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, timeout: int = 900) -> dict:
    """Run ``code`` on four host devices; the JSON of its last line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


PRELUDE = """
import dataclasses, json
import jax
import numpy as np
import repro.scenarios as S
from repro.scenarios.spec import make_packets, sharded_traffic, steer
from repro.traffic.generator import steer_stats
assert len(jax.devices()) == 4

def point(workload, pipes, packets, chunk=64, seed=2**31 - 77):
    return S.ScenarioSpec(
        name="fab", workload=workload, chain=("fw", "nat", "lb"),
        pipes=pipes, recirc=True, capacity=256, max_exp=4,
        packets=packets, chunk=chunk, window=2, pmax=256, flows=1000,
        fw_rules=20, seed=seed, backend="ref", devices=4)

def diffs(a, b):
    return {f.name: int(np.asarray(getattr(a, f.name)).dtype
                        != np.asarray(getattr(b, f.name)).dtype
                        or np.asarray(getattr(a, f.name)).shape
                        != np.asarray(getattr(b, f.name)).shape
                        or (np.asarray(getattr(a, f.name))
                            != np.asarray(getattr(b, f.name))).sum())
            for f in dataclasses.fields(a)}
"""


@pytest.mark.parametrize("workload,pipes,packets,chunk", [
    (("datacenter",), 8, 2048, 64),
    (("datacenter",), 16, 4096, 64),
    (("fixed", 64), 8, 2048, 64),
    (("fixed", 64), 16, 4096, 64),
    # 4 packets a pipe and a capacity of 5 (chunk 1): hash skew overflows
    # some pipes, whose late arrivals are lost
    (("datacenter",), 16, 64, 1),
])
def test_per_shard_traces_equal_the_eager_draw(workload, pipes, packets,
                                               chunk):
    """The per-shard program's traces and steering stats equal the
    one-device ``make_packets`` + ``steer`` bit for bit, and arrive
    sharded over the four devices, a quarter of the pipes on each."""
    out = run_sub(PRELUDE + f"""
spec = point({workload!r}, {pipes}, {packets}, {chunk})
fn, args = sharded_traffic([spec], 4)
traces, (counts,) = fn(*args)
one = dataclasses.replace(spec, devices=1)
want, stats = steer(one, make_packets(one))
print(json.dumps(dict(
    diffs=diffs(traces, want),
    stats_equal=steer_stats(counts, stats["pipe_capacity"]) == stats,
    overflow=stats["overflow"],
    shards=sorted({{(len(a.sharding.device_set),
                     a.addressable_shards[0].data.shape[0])
                    for a in jax.tree.leaves(traces)}}))))
""")
    assert not any(out["diffs"].values()), out["diffs"]
    assert out["stats_equal"]
    assert out["shards"] == [[4, pipes // 4]]
    assert (out["overflow"] > 0) == (chunk == 1)


def test_run_matrix_on_four_devices_equals_one_device():
    """``run_matrix`` on four devices gives the one-device counters,
    telemetry, NF counters, steering stats and merged packets, and the
    engine≡loop oracle holds on a pipe of each shard."""
    out = run_sub(PRELUDE + """
spec = point(("datacenter",), 8, 2048)
four = S.run_matrix([spec])[0]
one = S.run_matrix([dataclasses.replace(spec, devices=1)])[0]
S.verify_oracle(four, pipes=[0, 3, 4, 7])
print(json.dumps(dict(
    same=dict(counters=four.counters == one.counters,
              per_pipe=four.per_pipe_counters == one.per_pipe_counters,
              telemetry=four.per_pipe_telemetry == one.per_pipe_telemetry,
              nf=four.per_pipe_nf_counters == one.per_pipe_nf_counters,
              occ=four.per_pipe_peak_occupancy == one.per_pipe_peak_occupancy,
              steer=four.steer_stats == one.steer_stats),
    merged=diffs(four.merged, one.merged),
    drawn=four.prepared.pkts is None,
    spans=len(four.merged.payload.sharding.device_set))))
""")
    assert all(out["same"].values()), out["same"]
    assert not any(out["merged"].values()), out["merged"]
    assert out["drawn"] and out["spans"] == 4


def test_traffic_programs_are_the_programs_run_matrix_runs():
    """``traffic_programs`` gives the very program ``run_matrix`` calls,
    on equal arguments; ``engine_programs`` builds the engine's
    arguments through it; a one-device point has none."""
    out = run_sub(PRELUDE + """
from repro.scenarios import runner, spec as SP
called = []
build = SP.sharded_traffic

def spy(*a, **k):
    called.append(build(*a, **k))
    return called[-1]

runner.sharded_traffic = spy
spec = point(("datacenter",), 8, 2048)
S.run_matrix([spec])
(fn, args), = S.traffic_programs([spec])
n_run = len(called)
(efn, eargs), = S.engine_programs([spec])
print(json.dumps(dict(
    n_run=n_run, n_all=len(called), same_fn=called[0][0] is fn,
    same_args=[bool((jax.random.key_data(a) == jax.random.key_data(b)).all())
               for a, b in zip(called[0][1], args)],
    engine_sharded=len(eargs[0].payload.sharding.device_set),
    one_device=len(S.traffic_programs([dataclasses.replace(
        spec, devices=1)])))))
""")
    assert out["n_run"] == 2 and out["n_all"] == 3, out
    assert out["same_fn"] and out["same_args"] == [True]
    assert out["engine_sharded"] == 4
    assert out["one_device"] == 0


def test_no_device_holds_the_whole_payload():
    """No buffer of the compiled traffic program is as large as the
    whole call's payload: each device draws, steers and exchanges its
    own share (the per-device memory at the real size is checked on the
    described chip, ``tests/test_chip_compile.py``)."""
    out = run_sub(PRELUDE + """
import re
spec = point(("datacenter",), 8, 4096)
fn, args = sharded_traffic([spec], 4)
text = fn.lower(*args).compile().as_text()
sizes = [int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
         for dims in re.findall(r"[a-z0-9]+\\[([0-9,]*)\\]", text)]
print(json.dumps(dict(largest=max(sizes),
                      whole=spec.packets * spec.pmax)))
""")
    assert out["largest"] < out["whole"], out
