"""A ``run_matrix`` cell whose pipes are sharded over four devices.

The sharded tests run in subprocesses on four forced host devices: the
device count is fixed before JAX starts, and the pytest process keeps
one.  They use the tiny fabric (8 pipes, ``devices: 4``, 2 pipes a
device) on the ``ref`` backend.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import bench_tiny as T
from bench import drivers as D

TESTS = os.path.dirname(os.path.abspath(__file__))


def run_sub(code: str, tmp_path, timeout: int = 600) -> dict:
    """Run ``code`` on four host devices; the JSON of its last line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(T.ROOT / "src"), str(T.ROOT),
                                           TESTS]))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env, cwd=tmp_path)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return json.loads(r.stdout.strip().splitlines()[-1])


MAIN = """
    import argparse, contextlib, io, json, time
    import jax
    import bench_tiny as T
    from bench import drivers as D, harness
    assert len(jax.devices()) == 4

    cell = harness.Cell(
        workload={{"name": "tiny", "chips": {chips}}},
        config=T.{config}(), traffic=T.traffic("{traffic}"),
        manifest={{"end_to_end": [
            {{"name": "sim_pps", "unit": "packets/s"}},
            {{"name": "setup_s", "unit": "s"}}], "per_layer": []}})
    harness.load_cell = lambda name: cell
    spans, picks, in_window = [], [], []
    run_matrix, sample = D.run_matrix, D.MatrixDriver.sample
    measure = harness.measure

    def spy_run(specs):
        res = run_matrix(specs)
        spans.append(len(res[0].merged.payload.sharding.device_set))
        return res

    def spy_sample(self, rng):
        picks.append(sample(self, rng))
        return picks[-1]

    def spy_measure(*a, **k):
        run, compiled = measure(*a, **k)
        in_window.append(compiled)
        return run, compiled

    D.run_matrix, D.MatrixDriver.sample = spy_run, spy_sample
    harness.measure = spy_measure
    args = argparse.Namespace(workload="tiny", seed=2**32 + 9, seconds=0.0,
                              trace=0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = harness.main(args, time.perf_counter(), require_tpu=False)
    result = json.loads(buf.getvalue().strip().splitlines()[-1])
    print(json.dumps(dict(rc=rc, result=result, spans=spans, picks=picks,
                          in_window=in_window)))
"""


def test_a_sharded_run_is_correct_and_checks_every_shard(tmp_path):
    """``harness.main`` on the tiny fabric: the engine's merged arrays
    span the four devices, every call's check replays a pipe of each
    shard, nothing compiles in the window, and the run comes out
    correct."""
    out = run_sub(MAIN.format(config="fabric_config", traffic="dc",
                              chips=4), tmp_path)
    assert out["rc"] == 0
    res = out["result"]
    assert res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    assert out["in_window"] == [""]
    assert out["spans"] and set(out["spans"]) == {4}
    assert len(out["picks"]) == 1 + res["attempted"]    # warm-up too
    for pick in out["picks"]:
        assert {q // 2 for q in pick} == {0, 1, 2, 3}, pick
    assert res["checks"]["replayed"]["value"] == 4 * res["attempted"]


@pytest.mark.parametrize("config,traffic", [
    ("matrix_config", "dc"), ("stream_config", "enterprise")])
def test_a_one_chip_window_compiles_nothing(tmp_path, config, traffic):
    """After the warm-up, the window's calls (which keep what the check
    needs) run only programs the warm-up compiled: in a fresh process,
    with an empty compile cache."""
    out = run_sub(MAIN.format(config=config, traffic=traffic, chips=1),
                  tmp_path)
    assert out["rc"] == 0 and out["result"]["correct"] is True
    assert out["in_window"] == [""]


def test_the_sharded_draw_is_bit_identical(tmp_path):
    """The reference's traffic drawn sharded over four devices equals
    its eager draw and the program's own ``make_packets``, bit for bit,
    and stays sharded until it reaches the host."""
    out = run_sub("""
        import json
        import jax
        import numpy as np
        import bench_tiny as T
        from bench import drivers as D, generator as G
        from repro.scenarios.spec import make_packets

        cfg, tr = T.fabric_config(), T.traffic("dc")
        d = D.MatrixDriver(cfg, tr, seed=2**33 + 1)
        diffs, shards = {}, set()
        for i in (1, 2):
            spec = d.spec(i)
            draw = [G.call_packets(spec.seed, tr, d.packets, spec.pmax,
                                   cfg["flows"], cfg["flow_pool_seed"],
                                   devices) for devices in (1, 4)]
            prog = make_packets(spec)
            for f in G.FIELDS:
                want = np.asarray(getattr(prog, f))
                diffs[f"{i}.{f}"] = [
                    int(a[f].dtype != want.dtype or a[f].shape != want.shape
                        or (a[f] != want).sum()) for a in draw]
            fn = G._sharded_draw(4, tuple(tr["sizes"]), tuple(tr["probs"]),
                                 d.packets, spec.pmax, cfg["flows"],
                                 cfg["flow_pool_seed"])
            for v in fn(jax.random.key(spec.seed)).values():
                shards.add((len(v.sharding.device_set),
                            v.addressable_shards[0].data.shape[0]))
        print(json.dumps(dict(diffs=diffs, shards=sorted(shards),
                              packets=d.packets)))
    """, tmp_path)
    assert all(v == [0, 0] for v in out["diffs"].values()), out["diffs"]
    assert out["shards"] == [[4, out["packets"] // 4]]


def test_a_cell_not_held_to_its_chips_is_refused(tmp_path):
    """``hold_to_config`` refuses a pipe count that does not divide the
    devices (the program would run it on one), a sharded file in a
    one-chip cell, an unsharded one in a four-chip cell, and a streamed
    cell on four chips; it takes the tiny fabric on four chips."""
    out = run_sub("""
        import json, warnings
        import bench_tiny as T
        from bench import drivers as D, harness

        def hold(config, traffic, chips, engine=D.MatrixDriver):
            cell = harness.Cell(workload={"name": "tiny", "chips": chips},
                                config=config, traffic=traffic, manifest={})
            try:
                harness.hold_to_config(
                    cell, engine(config, traffic, 7).program_facts())
            except harness.CellError as e:
                return str(e)
            return None

        fab, dc = T.fabric_config(), T.traffic("dc")
        warnings.simplefilter("ignore")
        print(json.dumps(dict(
            held=hold(fab, dc, 4),
            six_pipes=hold(dict(fab, pipes=6), dc, 4),
            sharded_on_one_chip=hold(fab, dc, 1),
            unsharded_on_four=hold(T.matrix_config(), dc, 4),
            stream_on_four=hold(T.stream_config(), T.traffic("enterprise"),
                                4, D.StreamDriver))))
    """, tmp_path)
    assert out.pop("held") is None
    want = {"six_pipes": ("6 pipes on 1 device", "asks for 4", "for 4 chip"),
            "sharded_on_one_chip": ("on 4 device", "for 1 chip"),
            "unsharded_on_four": ("on 1 device", "asks for 1", "for 4 chip"),
            "stream_on_four": ("on 1 device", "for 4 chip")}
    for case, words in want.items():
        assert out[case] is not None, case
        for w in words:
            assert w in out[case], (case, out[case])


@pytest.mark.parametrize("seed", [0, 5, 2**31 + 3, 2**40 + 11])
def test_one_device_draws_the_pipes_as_it_always_has(seed):
    d = D.MatrixDriver(T.matrix_config() | {"pipes": 8}, T.traffic("dc"),
                       seed)
    rng = np.random.default_rng([seed, 1])
    want = sorted(int(q) for q in np.random.default_rng([seed, 1]).choice(
        8, 3, replace=False))
    assert d.sample(rng) == want


@pytest.mark.parametrize("check_pipes", [3, 4, 6])
@pytest.mark.parametrize("seed", [1, 2**32 + 77])
def test_a_sharded_check_draws_a_pipe_of_each_shard(seed, check_pipes):
    d = D.MatrixDriver(T.fabric_config(), T.traffic("dc"), seed,
                       check_pipes=check_pipes)
    picks = [d.sample(np.random.default_rng([seed, i])) for i in range(20)]
    for pick in picks:
        assert len(pick) == len(set(pick)) == max(check_pipes, 4)
        assert {q // 2 for q in pick} == {0, 1, 2, 3}
    assert len({tuple(p) for p in picks}) > 1
    assert d.sample(np.random.default_rng([seed, 0])) == picks[0]
