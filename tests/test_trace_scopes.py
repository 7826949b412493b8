"""The names the program gives its parts in a profile (DESIGN.md §14).

The benchmark reads them: the engine's stage scopes reach the compiled
program's op names, and the entry points' host phases are disjoint
profiler spans on the calling thread, in order.
"""
import gc
import re
import weakref

import jax
import numpy as np
import pytest

from bench import tracefile as TF
from repro.scenarios import ScenarioSpec, engine_programs, run_matrix
from repro.scenarios.runner import _prepare
from repro.switchsim import engine as E
from repro.switchsim.stream import run_stream, segment_program
from repro.traffic.stream import SyntheticSource

ENGINE_SCOPES = ("engine.lane", "engine.split", "engine.nf", "engine.ring",
                 "engine.merge", "engine.tally")
NF_SCOPES = ("nf.Firewall", "nf.Nat", "nf.MaglevLB")
PHASES = ("repro.prepare", "repro.dispatch", "repro.finalize",
          "repro.nf_cycles")
STREAM_PHASES = ("repro.stream.source", "repro.stream.dispatch",
                 "repro.stream.sync")

SPEC = ScenarioSpec(name="scopes", workload=("datacenter",),
                    chain=("fw", "nat", "lb"), pipes=2, recirc=True,
                    recirc_frac=0.25, capacity=64, max_exp=4, packets=256,
                    chunk=32, window=2, pmax=256, flows=32, fw_rules=4,
                    seed=5, backend="ref")


def _op_names(compiled_text: str) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def _scopes_in(names) -> set[str]:
    return {m for n in names for m in re.findall(r"(?:engine|nf)\.\w+", n)}


@pytest.fixture(scope="module")
def prepared():
    return _prepare(SPEC)


def test_engine_stage_and_nf_scopes_reach_the_compiled_program():
    (fn, args), = engine_programs([SPEC])
    text = fn.lower(*args).compile().as_text()
    found = _scopes_in(_op_names(text))
    assert set(ENGINE_SCOPES) <= found
    assert set(NF_SCOPES) <= found
    # the NF scopes sit inside the chain's stage
    for name in _op_names(text):
        if "/nf." in name:
            assert "engine.nf/" in name, name


def test_engine_programs_are_the_programs_run_matrix_runs(monkeypatch):
    """The program a trace reader compiles is the one the entry point
    calls, on equal arguments."""
    called = []
    build = E.pipes_program

    def spy(*args, **kw):
        called.append(build(*args, **kw))
        return called[-1]

    monkeypatch.setattr(E, "pipes_program", spy)
    run_matrix([SPEC])
    (fn, args), = engine_programs([SPEC])
    assert len(called) == 2 and called[0][0] is fn
    for a, b in zip(jax.tree.leaves(called[0][1]), jax.tree.leaves(args)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_stream_segment_program_carries_the_same_scopes(prepared):
    src = SyntheticSource(steps=8, chunk=SPEC.chunk, pmax=SPEC.pmax, seed=3,
                          flows=16)
    fn, args = segment_program(SPEC.park_config(), prepared.chain, src,
                               window=SPEC.window, segment_len=4,
                               backend=SPEC.backend, reservoir=16)
    text = fn.lower(*args).compile().as_text()
    found = _scopes_in(_op_names(text))
    assert set(ENGINE_SCOPES) <= found
    assert set(NF_SCOPES) <= found


def _profile(tmp_path, fn):
    """Run ``fn`` once under the profiler inside a ``bench.call`` span
    (``tracefile.load`` keeps the host events of that span's thread);
    the program's spans, in order of their start."""
    fn()        # compile outside the profile
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.call"):
        fn()
    jax.profiler.stop_trace()
    tr = TF.load(TF.find(str(tmp_path)))
    return sorted(((n, s, e) for n, s, e in tr.host
                   if n.startswith("repro.")), key=lambda x: x[1])


def _first_seen(spans) -> list[str]:
    order = []
    for n, _, _ in spans:
        if n not in order:
            order.append(n)
    return order


def _assert_disjoint(spans):
    for (n1, _, e1), (n2, s2, _) in zip(spans, spans[1:]):
        assert e1 <= s2, f"{n1} overlaps {n2}"


def test_run_matrix_phases_are_disjoint_spans_in_order(tmp_path):
    spans = _profile(tmp_path, lambda: run_matrix([SPEC]))
    assert _first_seen(spans) == list(PHASES)
    _assert_disjoint(spans)


def test_run_stream_phases_are_disjoint_spans_in_order(tmp_path, prepared):
    cfg = SPEC.park_config()
    src = SyntheticSource(steps=8, chunk=SPEC.chunk, pmax=SPEC.pmax, seed=3,
                          flows=16)
    spans = _profile(tmp_path, lambda: run_stream(
        cfg, prepared.chain, src, window=SPEC.window, segment_len=4,
        reservoir=16))
    assert _first_seen(spans) == list(STREAM_PHASES)
    _assert_disjoint(spans)
    # two segments and the drain: a draw for each segment, an enqueue and
    # a sync for all three
    names = [n for n, _, _ in spans]
    assert names.count("repro.stream.source") == 2
    assert names.count("repro.stream.dispatch") == 3
    assert names.count("repro.stream.sync") == 3


def test_run_stream_spans_keep_one_segment_live(monkeypatch, prepared):
    """The spans split a segment's draw from its enqueue; the draw of the
    next segment still waits until the last one is freed."""
    refs = []
    draw = SyntheticSource.segment

    def spy(self, start, count):
        gc.collect()
        assert all(r() is None for r in refs), "two segments live"
        seg = draw(self, start, count)
        if count > 1:       # not the one-step template of the first chunk
            refs.append(weakref.ref(seg.payload))
        return seg

    monkeypatch.setattr(SyntheticSource, "segment", spy)
    src = SyntheticSource(steps=16, chunk=SPEC.chunk, pmax=SPEC.pmax, seed=3,
                          flows=16)
    run_stream(SPEC.park_config(), prepared.chain, src, window=SPEC.window,
               segment_len=4, reservoir=16)
    assert len(refs) == 4
