"""The traffic readers (``bench/traffic_stages.py``): on made-up traces
of four chips, and on the traffic program of a tiny copy of the fabric
cell run on four host devices."""
import copy
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

import bench_tiny as T
from bench import stages
from bench import tracefile as TF
from bench import traffic_stages as TS
from bench.harness import reader

METRICS = ("traffic_us_per_pipe_step", "exchange_us_per_pipe_step")
TESTS = os.path.dirname(os.path.abspath(__file__))
DEVICES = [f"/device:TPU:{i}" for i in range(4)]
# the traffic program's module: op -> (result shape, opcode, scope, ns a
# chip, the ops its body runs); the exchange's loop spans 50 ns a chip,
# 35 of them its body's
TRAFFIC = {
    "%fusion.1": ("u8[512,256]", "fusion", "traffic.draw", 40, ()),
    "%fusion.2": ("s32[512]", "fusion", "traffic.steer", 10, ()),
    "%while.3": ("(s32[], u8[640,256])", "while", "traffic.exchange", 50,
                 ("%all-to-all.4", "%fusion.5")),
    "%all-to-all.4": ("u8[4,136,256]", "all-to-all", "traffic.exchange",
                      20, ()),
    "%fusion.5": ("u8[640,256]", "fusion", "traffic.exchange", 15, ()),
    # the engine's module has an op of this name and these dimensions too
    "%fusion.6": ("s32[8]", "fusion", "traffic.steer", 7, ()),
    "%copy.7": ("u8[2,5,64,256]", "copy", None, 3, ()),
}
ENGINE = {"%fusion.6": ("s32[8]", "fusion", "engine.tally", 0, ()),
          "%fusion.1": ("u8[8,2]", "fusion", "engine.split", 0, ())}
PER_CHIP = dict(draw=40, steer=10, exchange=50)     # ns, each counted once


def _module(ops) -> str:
    """A compiled module's text: its entry computation holds every op but
    the loop's body, which is a computation of its own."""
    body = {c for *_, called in ops.values() for c in called}
    lines = ["HloModule jit_program", "ENTRY %main (p.1: u32[2]) -> s32[] {"]
    for part in ("entry", "body"):
        if part == "body":
            lines += ["}", "%body.1 (p.2: s32[]) -> s32[] {"]
        for name, (shape, opcode, scope, _, called) in ops.items():
            if (name in body) != (part == "body"):
                continue
            path = "jit(program)" + (f"/{scope}/op" if scope else "")
            calls = ", condition=%cond.1, body=%body.1" if called else ""
            lines.append(f"  {name} = {shape} {opcode}(%p.1){calls}, "
                         f"metadata={{op_name=\"{path}\"}}")
    lines.append("}")
    return "\n".join(lines)


def _op(name) -> str:
    shape, opcode = TRAFFIC[name][:2]
    return f"{name} = {shape} {opcode}(%p.1)"


def _trace(named=DEVICES, partly=()):
    """Four chips ran the program; the chips in ``named`` show its ops by
    their HLO text, the others as ``region.N``, and those in ``partly``
    show the draw's op by its text and the rest as ``region.N``."""
    ops, device_ops = {}, {}
    for d in DEVICES:
        mine = [_op(n) if d in named or (d in partly and i == 0)
                else f"region.{i}" for i, n in enumerate(TRAFFIC)]
        device_ops[d] = set(mine)
        for op, n in zip(mine, TRAFFIC):
            ops[op] = ops.get(op, 0) + TRAFFIC[n][3]
    return TF.Trace(busy={d: [(0, 1000)] for d in DEVICES}, op_ns=ops,
                    op_count=dict.fromkeys(ops, 1),
                    op_text={op: op for op in ops}, spans=[], host=[],
                    device_ops=device_ops)


def _run(trace, whole=True, complete=True, steps=8):
    call = types.SimpleNamespace(pipes=4, pipe_steps=steps,
                                 traced_steps=steps, traced_whole=whole)
    return types.SimpleNamespace(trace=trace, traced=call,
                                 trace_complete=complete,
                                 cell=types.SimpleNamespace(config={}))


@pytest.fixture(autouse=True)
def _modules(monkeypatch):
    monkeypatch.setattr(stages, "engine_names",
                        lambda cell: stages.op_names(_module(ENGINE)))
    monkeypatch.setattr(TS, "traffic_names",
                        lambda cell: stages.op_names(_module(TRAFFIC)))


@pytest.mark.parametrize("named", [DEVICES, DEVICES[1:], DEVICES[2:3]])
def test_the_readers_read_the_named_chips_over_their_own_steps(named):
    """Each scope's time over the named chips, each ns once, over those
    chips' share of the pipe-steps: one chip or four read alike."""
    run = _run(_trace(named))
    steps = 8 * len(named) / 4
    want = sum(PER_CHIP.values()) * len(named) / 1e3 / steps
    assert reader("traffic_us_per_pipe_step")(run) == pytest.approx(want)
    assert reader("exchange_us_per_pipe_step")(run) == pytest.approx(
        PER_CHIP["exchange"] * len(named) / 1e3 / steps)


def test_scopes_ops_of_both_programs_and_unscoped_ops():
    by_scope, named = TS.scope_ns(_trace(), TS.traffic_names(None),
                                  stages.engine_names(None))
    assert named == DEVICES
    assert by_scope == {k: 4.0 * v for k, v in PER_CHIP.items()} | {
        "other": 4.0 * 3}
    assert TS.scope_of("jit(program)/shard_map/traffic.exchange/gather") \
        == "exchange"
    assert TS.scope_of("jit(run)/engine.split/add") is None


@pytest.mark.parametrize("name", METRICS)
def test_nothing_is_read_where_no_chip_is_named(name):
    assert reader(name)(_run(_trace(named=()))) is None


@pytest.mark.parametrize("name", METRICS)
def test_nothing_is_read_where_a_chip_is_partly_named(name):
    """The trace sums an op's time over the chips: a chip that names some
    of the program's ops adds their time without its own steps, so no
    chip can be read apart from it."""
    trace = _trace(named=DEVICES[1:], partly=DEVICES[:1])
    assert TS.scope_ns(trace, TS.traffic_names(None),
                       stages.engine_names(None)) is None
    assert reader(name)(_run(trace)) is None


@pytest.mark.parametrize("name", METRICS)
def test_an_op_one_chip_alone_carries_counts_for_neither(name):
    """The first chip also runs the host's eager ops: one whose name and
    dimensions a traffic op has too is that chip's own, and the reading
    is the one without it."""
    trace = _trace()
    own = "%fusion.2 = s32[512] add(%q.1)"      # the steer op's name, dims
    trace.op_ns[own] = trace.op_count[own] = 5
    trace.op_text[own] = own
    trace.device_ops[DEVICES[0]].add(own)
    assert reader(name)(_run(trace)) == pytest.approx(
        reader(name)(_run(_trace())))
    by_scope, named = TS.scope_ns(trace, TS.traffic_names(None),
                                  stages.engine_names(None))
    assert named == DEVICES and by_scope["steer"] == 4.0 * PER_CHIP["steer"]
    # an unnamed chip that names such an op of its own is left out
    trace = _trace(named=DEVICES[1:])
    trace.op_ns[own] = trace.op_count[own] = 5
    trace.op_text[own] = own
    trace.device_ops[DEVICES[0]].add(own)
    assert reader(name)(_run(trace)) == pytest.approx(
        reader(name)(_run(_trace(named=DEVICES[1:]))))


@pytest.mark.parametrize("name", METRICS)
def test_nothing_is_read_from_an_incomplete_or_partial_slice(name):
    assert reader(name)(_run(_trace(), complete=False)) is None
    assert reader(name)(_run(_trace(), whole=False)) is None
    assert reader(name)(_run(None)) is None


@pytest.mark.parametrize("name", METRICS)
def test_a_cell_without_the_traffic_program_reads_nothing(monkeypatch,
                                                          name):
    monkeypatch.setattr(TS, "traffic_names", lambda cell: {})
    assert reader(name)(_run(_trace())) is None


def test_a_program_without_traffic_programs_has_no_text(monkeypatch):
    import repro.scenarios
    monkeypatch.delattr(repro.scenarios, "traffic_programs")
    assert TS._traffic_text(T.fabric_config(), T.traffic("dc")) == ""
    assert TS._traffic_text(T.stream_config(),
                            T.traffic("enterprise")) == ""


def _tiny_fabric() -> dict:
    """``configs/fabric_tor8x8.json`` at a few hundred packets a pipe:
    16 pipes, 4 a device, on the ``ref`` backend."""
    cfg = copy.deepcopy(json.loads(
        (T.BENCH / "configs" / "fabric_tor8x8.json").read_text()))
    cfg.update(pipes=16, packets_per_pipe=256, chunk=64, backend="ref")
    cfg["park"]["capacity"] = 256
    return cfg


def test_the_tiny_fabric_runs_correct_and_names_its_traffic(tmp_path):
    """``harness.main`` on a tiny copy of the fabric cell, on four host
    devices: correct, a pipe of every shard replayed in every call, and
    the traffic program's text names all three scopes."""
    code = f"""
        import argparse, contextlib, io, json, time
        import jax
        from bench import drivers as D, harness, traffic_stages as TS
        assert len(jax.devices()) == 4
        cell = harness.Cell(
            workload={{"name": "tiny", "chips": 4}},
            config=json.loads({json.dumps(json.dumps(_tiny_fabric()))}),
            traffic=json.loads({json.dumps(json.dumps(T.traffic("dc")))}),
            manifest={{"end_to_end": [
                {{"name": "sim_pps", "unit": "packets/s"}}],
                "per_layer": []}})
        harness.load_cell = lambda name: cell
        picks, sample = [], D.MatrixDriver.sample

        def spy(self, rng):
            picks.append(sample(self, rng))
            return picks[-1]

        D.MatrixDriver.sample = spy
        args = argparse.Namespace(workload="tiny", seed=2**34 + 5,
                                  seconds=0.0, trace=0)
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = harness.main(args, time.perf_counter(), require_tpu=False)
        scopes = sorted({{TS.scope_of(op_name) or "" for _, op_name, _ in
                          TS.traffic_names(cell).values()}})
        print(json.dumps(dict(rc=rc, picks=picks, scopes=scopes,
            result=json.loads(buf.getvalue().strip().splitlines()[-1]))))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               PYTHONPATH=os.pathsep.join([str(T.ROOT / "src"), str(T.ROOT),
                                           TESTS]))
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env,
                       cwd=tmp_path)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    out = json.loads(r.stdout.strip().splitlines()[-1])
    res = out["result"]
    assert out["rc"] == 0 and res["correct"] is True, res["checks"]
    assert res["device"]["count"] == 4
    for pick in out["picks"]:
        assert {q // 4 for q in pick} == {0, 1, 2, 3}, pick
    assert res["checks"]["replayed"]["value"] == 4 * res["attempted"]
    assert {"draw", "steer", "exchange"} <= set(out["scopes"])
