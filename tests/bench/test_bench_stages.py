"""The stage and phase readers (``bench/stages.py``): on made-up traces,
and on the engine programs of the tiny cells."""
import types

import pytest

import bench_tiny
from bench import stages
from bench import tracefile as TF
from bench.harness import reader

STAGE_METRICS = ("split_us_per_pipe_step", "lane_us_per_pipe_step",
                 "nf_us_per_pipe_step", "ring_us_per_pipe_step",
                 "merge_us_per_pipe_step", "other_device_share")
PHASE_METRICS = ("prepare_idle_ms_per_call", "dispatch_idle_ms_per_call",
                 "finalize_idle_ms_per_call", "unspanned_idle_share")
PATH = "jit(run)/vmap()/while/body/closed_call/"
# the engine's module: computation -> its ops, each op -> (result shape,
# opcode, scope below the engine loop, device ns in the trace, the
# computations it runs)
MODULE = {
    "%main": {"%while.1": ("(s32[], u8[8,2,320,2048]{3,2,0,1:T(8,128)})",
                           "while", None, 500, ("%cond.1", "%body.1"))},
    "%cond.1": {"%compare.2": ("pred[]", "compare", None, 0, ())},
    "%body.1": {
        "%fusion.2": ("u8[8,256,2048]{2,0,1:T(8,128)}", "fusion",
                      "engine.split", 30, ()),
        "%closed_call.3": ("s32[8,128]{1,0}", "custom-call", "engine.split",
                           10, ()),
        "%call.12": ("s32[8,64]{1,0}", "call", "engine.lane", 110,
                     ("%lane.1",)),
        "%while.30": ("(s32[], s32[8,16384]{1,0})", "while",
                      "engine.nf/nf.Nat", 170, ("%cond.30", "%body.30")),
        "%fusion.6": ("pred[8,320]{1,0}", "fusion", "engine.nf/nf.Firewall",
                      30, ()),
        "%fusion.7": ("u8[8,2,320,2048]{3,2,0,1}", "fusion", "engine.ring",
                      60, ()),
        "%fusion.8": ("u8[5242880]{0:T(1024)}", "fusion", "engine.merge", 50,
                      ()),
        "%fusion.9": ("s32[8]{0}", "fusion", "engine.tally", 36, ()),
    },
    "%lane.1": {"%fusion.4": ("u8[8,64,2048]{2,1,0}", "fusion",
                              "engine.lane", 100, ())},
    "%cond.30": {"%compare.31": ("pred[]", "compare", "engine.nf/nf.Nat", 0,
                                 ())},
    "%body.30": {"%fusion.5": ("s32[8,16384]{1,0}", "fusion",
                               "engine.nf/nf.Nat", 150, ())},
}
OPS = {name: op for ops in MODULE.values() for name, op in ops.items()}
# ops of other programs: generation with a loop of its own, and an eager
# op whose name the engine's module has too, with other dimensions
OTHER = {"%while.20": ("(s32[], u32[4096]{0})", "while", 20),
         "%fusion.10": ("u32[4096]{0}", "fusion", 20),
         "%fusion.9": ("s64[8]{0}", "convert", 10)}
# device ns by stage of the trace below
WANT = dict(split=40, lane=110, nf=200, ring=60, merge=50,
            other=4 + 36 + 20 + 10)


def _text(name, shape, opcode):
    """An op as a TPU trace names it: its HLO text, without op name."""
    tail = ', custom_call_target="tpu_custom_call"' \
        if opcode == "custom-call" else ""
    return f"{name} = {shape} {opcode}(%p.1){tail}"


def _module():
    """The engine's compiled text: the same instructions, printed with
    other layouts, with their op names and the computations they run."""
    lines = ["HloModule jit_run"]
    for comp, ops in MODULE.items():
        head = "ENTRY " if comp == "%main" else ""
        lines.append(f"{head}{comp} (p.1: s32[]) -> s32[] {{")
        for name, (shape, opcode, scope, _, called) in ops.items():
            path = "jit(run)/vmap()/while" if scope is None \
                else f"{PATH}{scope}/add"
            calls = ""
            if opcode == "while":
                calls = f", condition={called[0]}, body={called[1]}"
            elif opcode == "call":
                calls = f", to_apply={called[0]}"
            lines.append(f"  {name} = {stages.LAYOUT.sub('{0}', shape)} "
                         f"{opcode}(%p.1){calls}, metadata={{op_name="
                         f"\"{path}\" stack_frame_id=3}}")
        lines.append("  ROOT %tuple.13 = (s32[]) tuple(%p.1)")
        lines.append("}")
    return "\n".join(lines)


@pytest.fixture(autouse=True)
def _engine_module(monkeypatch):
    monkeypatch.setattr(stages, "engine_names",
                        lambda cell: stages.op_names(_module()))


def _trace(drop=()):
    """One whole call [0, 1000) on one device.  The engine's loop op
    spans [300, 800), 500 ns, and its body ops 496 of them: split 40 (a
    Pallas kernel among them), a lane ``call`` of 110 around a lane op
    of 100, a NAT loop of 170 around its body op of 150, the firewall
    30, ring 60, merge 50, tally 36.  Generation takes 20 ns before it,
    an eager result op 10 ns after it.  Busy [100, 120), [300, 800),
    [900, 910): 530 ns; idle 470 ns.  Host phases: prepare [0, 290),
    dispatch [290, 310), finalize [310, 940), nf_cycles [940, 990)."""
    ops = {_text(n, shape, opcode): ns
           for n, (shape, opcode, _, ns, _) in OPS.items()
           if ns and n not in drop}
    ops.update({_text(n, shape, opcode): ns
                for n, (shape, opcode, ns) in OTHER.items()})
    pallas = _text("%closed_call.3", *OPS["%closed_call.3"][:2])
    return TF.Trace(
        busy={"/device:TPU:0": [(100, 120), (300, 800), (900, 910)]},
        op_ns=ops, op_count=dict.fromkeys(ops, 1),
        op_text={op: op for op in ops},
        spans=[("bench.call", 0, 1000), ("bench.traced", 0, 1000)],
        host=[("repro.prepare", 0, 290), ("PjitFunction(_randint)", 95, 125),
              ("repro.dispatch", 290, 310), ("repro.finalize", 310, 940),
              ("np.asarray(jax.Array)", 800, 900),
              ("repro.nf_cycles", 940, 990)],
        kernels={pallas: [(320, 330)]},
        device_ops={"/device:TPU:0": set(ops)})


def _run(trace, whole=True, complete=True, steps=10):
    call = types.SimpleNamespace(start=0.0, end=1.0, packets=100, pipes=1,
                                 pipe_steps=steps, traced_steps=steps,
                                 traced_whole=whole)
    return types.SimpleNamespace(trace=trace, calls=[call], traced=call,
                                 trace_complete=complete,
                                 cell=types.SimpleNamespace(
                                     workload={"name": "x"}, config={}))


def _names():
    return stages.op_names(_module())


def test_the_module_text_gives_each_instruction_its_op_name():
    names = _names()
    assert names["%fusion.8"] == ("u8[5242880]", PATH + "engine.merge/add",
                                  ())
    assert names["%closed_call.3"][1].endswith("engine.split/add")
    assert names["%tuple.13"][1] == ""      # printed without an op name
    # a control-flow op runs the instructions of its computations
    assert set(names["%while.1"][2]) == {"%compare.2", "%tuple.13"} | set(
        MODULE["%body.1"])
    assert set(names["%call.12"][2]) == {"%fusion.4", "%tuple.13"}
    assert names["%fusion.2"][2] == ()


def test_each_device_ns_counts_once():
    """A control op counts what its body ops leave of it: the engine
    loop's 4 ns of control (other), the lane call's 10 (lane), the NAT
    loop's 20 (nf); another program's loop counts through its body."""
    assert stages.is_control(_text("%while.1", *OPS["%while.1"][:2]))
    assert not stages.is_control(_text("%closed_call.3",
                                       *OPS["%closed_call.3"][:2]))
    tr = _trace()
    by = stages.stage_ns(tr, _names())
    assert by == WANT
    assert sum(by.values()) == \
        TF.busy_ns(tr, *TF.window(tr)) * len(tr.devices())


def test_a_loop_whose_body_the_trace_lacks_counts_whole():
    assert stages.stage_ns(_trace(drop=("%fusion.5",)), _names()) == WANT


def test_stage_metrics_per_pipe_step():
    run = _run(_trace())
    for stage in stages.STAGES:
        assert reader(f"{stage}_us_per_pipe_step")(run) == \
            pytest.approx(WANT[stage] / 1e3 / 10)
    # tally, loop control, generation and the eager result op are the rest
    assert reader("other_device_share")(run) == pytest.approx(
        100 * 70 / 530)


def _two_devices(tr):
    """The same call on two devices, as ``tracefile.load`` reduces it:
    each op's time summed over both, busy intervals kept per device.
    The second runs the same ops, and is busy for 500 of the 530 ns."""
    return TF.Trace(busy={"/device:TPU:0": tr.busy["/device:TPU:0"],
                          "/device:TPU:1": [(300, 800)]},
                    op_ns={op: 2 * ns for op, ns in tr.op_ns.items()},
                    op_count={op: 2 * n for op, n in tr.op_count.items()},
                    op_text=tr.op_text, spans=tr.spans, host=tr.host,
                    kernels={op: 2 * ivs for op, ivs in tr.kernels.items()},
                    device_ops={"/device:TPU:0": set(tr.op_ns),
                                "/device:TPU:1": set(tr.op_ns)})


@pytest.mark.parametrize("devices", [1, 2])
def test_per_pipe_step_time_counts_every_device(devices):
    """The pipe-steps are every device's pipes' steps, and the device
    time is summed over the devices too: two devices read twice the
    stage time of one over the same pipe-steps, and one reads what it
    always has.  The shares stay shares."""
    tr = _trace() if devices == 1 else _two_devices(_trace())
    run = _run(tr)
    assert stages.stage_ns(tr, _names()) == {
        k: devices * ns for k, ns in WANT.items()}
    for stage in stages.STAGES:
        assert reader(f"{stage}_us_per_pipe_step")(run) == pytest.approx(
            devices * WANT[stage] / 1e3 / 10)
    busy = 530 if devices == 1 else 530 + 500
    assert reader("device_us_per_pipe_step")(run) == pytest.approx(
        busy / 1e3 / 10)
    assert reader("other_device_share")(run) == pytest.approx(
        100 * 70 / 530)
    assert TF.named_on_every_device(tr)
    assert reader("kernel_device_share")(run) == pytest.approx(
        100 * devices * 10 / busy)


@pytest.mark.parametrize("named_kernel", [False, True])
def test_a_device_whose_ops_go_unnamed_reads_nothing(named_kernel):
    """A second device that runs the same call, but whose ops come as
    ``region.N`` without their HLO text: its loop, stages and payload
    kernels are not found, so every stage and kernel reader reads
    nothing rather than a share of the devices, in a trace that lost no
    launch.  So too where a kernel that keeps its own name (the CRC)
    shows on both devices, as on a four-chip TPU trace.  Busy time needs
    no names."""
    one = _trace()
    unnamed = {f"region.{i}": ns for i, ns in enumerate(one.op_ns.values())}
    crc = '%crc16_kernel.40 = s32[8,128] custom-call(%p.1), ' \
        'custom_call_target="tpu_custom_call"'
    both = {crc: 20} if named_kernel else {}
    kernels = dict(one.kernels, **{op: [(850, 860), (850, 860)]
                                   for op in both})
    tr = TF.Trace(busy={"/device:TPU:0": one.busy["/device:TPU:0"],
                        "/device:TPU:1": [(300, 800)]},
                  op_ns={**one.op_ns, **unnamed, **both},
                  op_count={**one.op_count, **dict.fromkeys(unnamed, 1),
                            **dict.fromkeys(both, 2)},
                  op_text={**one.op_text, **{op: op for op in unnamed},
                           **{op: op for op in both}},
                  spans=one.spans, host=one.host, kernels=kernels,
                  device_ops={"/device:TPU:0": set(one.op_ns) | set(both),
                              "/device:TPU:1": set(unnamed) | set(both)})
    assert TF.named_on_every_device(one)
    assert not TF.named_on_every_device(tr)
    assert TF.complete(tr, 1)      # no launch of the named device lost
    assert stages.stage_ns(tr, _names()) is None
    run = _run(tr)
    for name in STAGE_METRICS + ("kernel_device_share",
                                 "payload_kernel_roofline"):
        assert reader(name)(run) is None, name
    assert reader("device_us_per_pipe_step")(run) == pytest.approx(
        (530 + 500) / 1e3 / 10)


def test_ops_of_other_programs_and_unscoped_ops_land_in_other():
    """An op the engine's module lacks, or has under its name with other
    dimensions, is no stage's: the eager ``%fusion.9`` is not the
    engine's tally fusion of that name.  An engine op under no stage
    scope is other too."""
    names = _names()
    tr = _trace()
    for op in tr.op_ns:
        if op.startswith(("%fusion.10 ", "%fusion.9 = s64")):
            assert stages._own(op, names) is None
    names["%fusion.8"] = (names["%fusion.8"][0], "jit(run)/vmap()/add", ())
    by = stages.stage_ns(tr, names)
    assert by["merge"] == 0 and by["other"] == 70 + 50


def test_stage_of_reads_the_scope_and_not_the_nf():
    assert stages.stage_of(f"{PATH}engine.nf/nf.Nat/while") == "nf"
    assert stages.stage_of(f"{PATH}engine.tally/add") == "tally"
    assert stages.stage_of("jit(run)/engine.splitter/add") is None
    assert stages.stage_of("") is None


def test_idle_inside_each_phase_is_summed():
    run = _run(_trace())
    # idle: [0,100) [120,300) [800,900) [910,1000)
    assert reader("prepare_idle_ms_per_call")(run) == pytest.approx(
        (100 + 170) / 1e6)
    assert reader("dispatch_idle_ms_per_call")(run) == pytest.approx(
        10 / 1e6)
    # finalize [310, 940): 100 + 30; nf_cycles [940, 990): 50
    assert reader("finalize_idle_ms_per_call")(run) == pytest.approx(
        (100 + 30 + 50) / 1e6)
    # idle 470 ns; [990, 1000) lies in no span
    assert reader("unspanned_idle_share")(run) == pytest.approx(
        100 * 10 / 470)


def test_a_phase_is_the_union_of_its_spans():
    tr = _trace()
    tr.host += [("repro.dispatch", 295, 305), ("repro.dispatch", 0, 50)]
    run = _run(tr)
    # a second dispatch span inside the first counts once; one inside
    # prepare counts for both phases
    assert reader("dispatch_idle_ms_per_call")(run) == pytest.approx(
        (10 + 50) / 1e6)
    assert reader("prepare_idle_ms_per_call")(run) == pytest.approx(
        (100 + 170) / 1e6)


def test_a_missing_phase_reads_zero_and_its_idle_is_unspanned():
    tr = _trace()
    tr.host = [h for h in tr.host if h[0] != "repro.nf_cycles"]
    run = _run(tr)
    assert reader("finalize_idle_ms_per_call")(run) == pytest.approx(
        130 / 1e6)
    assert reader("unspanned_idle_share")(run) == pytest.approx(
        100 * 60 / 470)
    tr.host = [h for h in tr.host if h[0] != "repro.prepare"]
    assert reader("prepare_idle_ms_per_call")(run) == 0


def test_a_program_that_names_nothing_reads_nothing(monkeypatch):
    """The parent of the scopes and spans: an unnamed trace reads None,
    not 0."""
    monkeypatch.setattr(stages, "engine_names", lambda cell: stages.op_names(
        _module().replace("engine.", "")))
    tr = _trace()
    tr.host = [h for h in tr.host if not h[0].startswith("repro.")]
    run = _run(tr)
    for name in STAGE_METRICS + PHASE_METRICS:
        assert reader(name)(run) is None, name


def test_the_engine_loop_is_the_outer_control_op():
    assert stages.outer_loops(_names()) == {"%while.1"}


@pytest.mark.parametrize("other_module", [
    # a body op of the engine's loop the module lacks: its 30 ns stay
    # with the loop, 6.8% of its span
    lambda text: text.replace("%fusion.2 ", "%fusion.99 "),
    # a loop of other dimensions: the trace holds no loop of the module
    lambda text: text.replace("%while.1 = (s32[]", "%while.1 = (s64[]"),
], ids=["body_op_missing", "loop_missing"])
def test_a_module_other_than_the_one_that_ran_reads_nothing(monkeypatch,
                                                             other_module):
    """The stage time of ops the module does not know would otherwise
    move to ``other`` unseen."""
    monkeypatch.setattr(stages, "engine_names", lambda cell: stages.op_names(
        other_module(_module())))
    run = _run(_trace())
    for name in STAGE_METRICS:
        assert reader(name)(run) is None, name


def test_loop_control_within_the_limit_is_read():
    tr = _trace()
    tr.op_ns[_text("%while.1", *OPS["%while.1"][:2])] += 1
    assert stages.stage_ns(tr, _names())["other"] == WANT["other"] + 1


@pytest.mark.parametrize("name", STAGE_METRICS + PHASE_METRICS)
def test_readers_find_nothing_without_a_trace(name):
    assert reader(name)(_run(None)) is None


@pytest.mark.parametrize("name", STAGE_METRICS)
def test_stage_readers_skip_an_incomplete_trace(name):
    assert reader(name)(_run(_trace(), complete=False)) is None
    assert reader(name)(_run(_trace())) is not None


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_phase_readers_need_a_whole_call(name):
    assert reader(name)(_run(_trace(), whole=False)) is None
    assert reader(name)(_run(_trace())) is not None


def test_a_device_free_trace_reads_no_stage():
    tr = _trace()
    tr.busy = {}
    assert stages.stage_ns(tr, _names()) is None
    assert reader("split_us_per_pipe_step")(_run(tr)) is None
    # no device ran: every ns of a span is idle
    assert reader("dispatch_idle_ms_per_call")(_run(tr)) == pytest.approx(
        20 / 1e6)


@pytest.mark.parametrize("engine", ["run_matrix", "run_stream"])
def test_the_engine_text_of_a_tiny_cell_names_every_stage(engine):
    """The engine program is built by the program's own functions from
    the configuration, and its text gives op names in all six stage
    scopes."""
    if engine == "run_matrix":
        config, traffic = bench_tiny.matrix_config(), bench_tiny.traffic("dc")
    else:
        config = bench_tiny.stream_config()
        traffic = bench_tiny.traffic("enterprise")
    names = stages.op_names(stages._engine_text(config, traffic))
    found = {stages.stage_of(op_name) for _, op_name, _ in names.values()}
    assert set(stages.STAGES) | {"tally"} <= found


def test_a_program_without_its_own_builders_reads_nothing(monkeypatch):
    """The parent of the scopes lacks the functions that build its
    engine program: the stage readers read nothing there, and raise
    nothing."""
    import repro.scenarios
    monkeypatch.delattr(repro.scenarios, "engine_programs")
    text = stages._engine_text(bench_tiny.matrix_config(),
                               bench_tiny.traffic("dc"))
    assert text == ""
    monkeypatch.setattr(stages, "engine_names",
                        lambda cell: stages.op_names(text))
    for name in STAGE_METRICS:
        assert reader(name)(_run(_trace())) is None, name
