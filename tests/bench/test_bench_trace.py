"""The reduction from a profiler trace to the per-layer metrics."""
import types

import pytest

from bench import harness
from bench import tracefile as TF
from bench.harness import reader


def _trace():
    """Two calls on one device: call 1 [0, 100), call 2 [100, 260).
    Device ops: busy [10, 40) and [30, 60) overlap, [120, 200); the store
    kernel runs 10 ns in two launches, the CRC kernel 20 ns in one."""
    busy = TF.union([(10, 40), (30, 60), (120, 200)])
    return TF.Trace(
        busy={"/device:TPU:0": busy},
        op_ns={"fusion.1": 50, "custom-call.7": 10, "custom-call.9": 20,
               "while.3": 60},
        op_count={"fusion.1": 2, "custom-call.7": 1, "custom-call.9": 1,
                  "while.3": 1},
        op_text={"fusion.1": "fusion.1",
                 "custom-call.7": "%closed_call.7 = s32[8,128] custom-call("
                                  "), custom_call_target=\"tpu_custom_call\"",
                 "custom-call.9": "%crc16_kernel.9 = s32[8,128] custom-call("
                                  "), custom_call_target=\"tpu_custom_call\"",
                 "while.3": "while.3"},
        spans=[("bench.call", 0, 100), ("bench.call", 100, 260),
               ("bench.segment", 200, 250)],
        host=[("PjitFunction(run)", 60, 110), ("np.asarray", 205, 240)],
        kernels={"custom-call.7": [(20, 25), (30, 35)],
                 "custom-call.9": [(130, 150)]},
        device_ops={"/device:TPU:0": {"fusion.1", "custom-call.7",
                                      "custom-call.9", "while.3"}})


def test_union_overlap_gaps():
    merged = TF.union([(5, 9), (0, 3), (2, 4), (9, 12), (20, 20)])
    assert merged == [(0, 4), (5, 12)]
    assert TF.overlap(merged, 3, 10) == 1 + 5
    assert TF.gaps(merged, -2, 15) == [(-2, 0), (4, 5), (12, 15)]
    assert TF.gaps([], 0, 7) == [(0, 7)]


def test_window_busy_and_kernels():
    tr = _trace()
    assert TF.window(tr) == (0, 260)
    assert TF.busy_ns(tr, 0, 260) == 50 + 80
    assert TF.busy_ns(tr, 100, 260) == 80
    assert TF.kernel_ns(tr, 0, 260) == 30
    assert TF.kernel_ns(tr, 100, 260) == 20
    assert TF.kernel_ns(tr, 0, 260, ("crc16_kernel",)) == 10
    assert TF.kernel_ns(tr, 0, 260, ("crc16_kernel", "closed_call")) == 0
    assert TF.kernel_ns(tr, 0, 33) == 5 + 3


def test_gap_labels_and_breakdown():
    tr = _trace()
    assert TF.label((200, 250), tr.spans, tr.host) == \
        "bench.segment / np.asarray"
    assert TF.label((60, 120), tr.spans, tr.host) == \
        "bench.call / PjitFunction(run)"
    bd = TF.breakdown(tr)
    assert bd["device_ops"][0] == ["while.3", 60e-9]
    assert [g[1] for g in bd["idle_gaps"]] == [60e-9, 60e-9, 10e-9]
    assert bd["idle_gaps"][0][0] == "bench.call / PjitFunction(run)"


def _run(trace, whole=True, complete=True):
    calls = [types.SimpleNamespace(
        start=0.0, end=1.0, packets=1000, pipes=1, pipe_steps=20,
        store_rows=640, stored_rows=100, fetch_rows=640, fetched_rows=90,
        traced_steps=20, traced_whole=whole)]
    cell = types.SimpleNamespace(config={"park": {"row_bytes": 352}})
    return types.SimpleNamespace(trace=trace, calls=calls, cell=cell,
                                 peaks={"hbm_bytes_per_s": 819e9},
                                 traced=calls[0], trace_complete=complete)


def test_sim_pps_reads_the_calls_time_not_the_gaps_between_them():
    """Two 1-s calls with 3 s of the check's copies between them: 2000
    packets over the 2 s of the calls, where the window spans 5 s."""
    calls = [types.SimpleNamespace(start=0.0, end=1.0, packets=1000),
             types.SimpleNamespace(start=4.0, end=5.0, packets=1000)]
    run = harness.Run(cell=None, setup_s=0.0, calls=calls, peak_bytes=0,
                      peaks={})
    assert run.window_s == 5.0 and run.calls_s == 2.0
    assert reader("sim_pps")(run) == pytest.approx(1000.0)


def test_readers_on_a_made_up_trace():
    run = _run(_trace())
    # no bench.traced span: the window spans both calls, 260 ns, 130 busy
    assert reader("host_ms_per_call")(run) == pytest.approx(130 / 1e6)
    assert reader("device_idle_share")(run) == pytest.approx(100 * 130 / 260)
    assert reader("device_us_per_pipe_step")(run) == pytest.approx(
        130 / 1e3 / 20)
    assert reader("kernel_device_share")(run) == pytest.approx(100 * 30 / 130)
    need = (640 + 640) * 8 + 352 * (2 * 100 + 3 * 90)
    assert reader("payload_kernel_roofline")(run) == pytest.approx(
        100 * need / 819e9 / 10e-9)


def test_a_profiled_slice_and_a_dropped_tail():
    tr = _trace()
    tr.spans.append(("bench.traced", 100, 260))
    assert TF.window(tr) == (100, 260)
    tr.dropped = 180
    assert TF.window(tr) == (100, 180)
    run = _run(tr, whole=False)
    assert reader("device_idle_share")(run) == pytest.approx(100 * 20 / 80)
    assert reader("host_ms_per_call")(run) is None
    assert reader("payload_kernel_roofline")(run) is None
    assert TF.short("%while.697 = (s32[]) while(%t), body=%b") == "%while.697"


def test_readers_find_nothing_without_a_trace_or_kernels():
    run = _run(None)
    for name in ("host_ms_per_call", "device_idle_share",
                 "device_us_per_pipe_step", "kernel_device_share",
                 "payload_kernel_roofline"):
        assert reader(name)(run) is None
    tr = _trace()
    tr.kernels = {}
    assert not TF.complete(tr, 1)
    assert reader("payload_kernel_roofline")(_run(tr)) is None
    assert reader("kernel_device_share")(_run(tr)) == 0


def test_a_trace_that_lost_kernel_launches_is_not_read():
    """The payload kernels launch a whole number of times per step: two
    launches over one or two steps are whole, over three are not, nor is
    one that lost a launch.  The kernel readers then read nothing; the
    busy time, a union that the outer loop's op spans, is still read."""
    tr = _trace()
    assert TF.payload_launches(tr) == 2
    assert TF.complete(tr, 1) and TF.complete(tr, 2)
    assert not TF.complete(tr, 3)
    tr.kernels["custom-call.7"].pop()
    assert not TF.complete(tr, 2)
    run = _run(tr, complete=False)
    assert reader("kernel_device_share")(run) is None
    assert reader("payload_kernel_roofline")(run) is None
    assert reader("device_idle_share")(run) == pytest.approx(100 * 130 / 260)


class _Driver:
    """Calls that take no time; each traced one reads as complete or not
    in the order given."""

    def __init__(self, verdicts):
        self.verdicts, self.traced = list(verdicts), []

    def warm_up(self):
        pass

    def call(self, i, keep, tracer=None):
        return types.SimpleNamespace(start=float(i), end=i + 1.0, packets=1,
                                     pipes=1, traced_steps=1, i=i)


@pytest.mark.parametrize("verdicts,profiled", [
    ((True,), [1]), ((False, True), [1, 2]),
    ((False, False, False, True), [1, 2, 3])])
def test_a_traced_run_profiles_again_while_launches_are_lost(
        monkeypatch, verdicts, profiled):
    drv = _Driver(verdicts)

    def profile(driver, i, tracer, log):
        driver.traced.append(i)
        return driver.call(i, True), _trace(), driver.verdicts.pop(0)

    monkeypatch.setattr(harness, "_profile", profile)
    cell = harness.Cell(workload={"name": "x", "chips": 1}, config={},
                        traffic={}, manifest={})
    run, _ = harness.measure(cell, drv, 0.0, True, 0.0, {},
                             log=lambda *_: None)
    assert drv.traced == profiled
    assert run.traced.i == profiled[-1]
    assert run.trace_complete == (len(profiled) < 3 or verdicts[2])
    assert len(run.calls) == len(profiled)


def test_compile_count_leaves_out_programs_loaded_from_the_cache():
    import jax
    from jax._src.dispatch import BACKEND_COMPILE_EVENT

    compiles = harness.Compiles()
    for name in ("a", "b", "a"):
        jax.monitoring.record_event_duration_secs(BACKEND_COMPILE_EVENT, 0.5,
                                                  fun_name=name)
    jax.monitoring.record_event(harness.Compiles.HIT)
    jax.monitoring.record_event_duration_secs(BACKEND_COMPILE_EVENT, 0.25,
                                              fun_name="c")
    assert len(compiles) == 3 and compiles.loads == 1
    assert compiles.seconds == 1.75
    assert compiles.since(1) == "a x1, b x1"


def test_load_a_recorded_trace(tmp_path):
    """A real (CPU) trace: the bench spans come back in order; a CPU run
    has no device plane, so it reads no device time."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.call"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = TF.load(TF.find(str(tmp_path)))
    calls = tr.calls()
    assert len(calls) == 2 and calls[0][1] <= calls[1][0]
    assert tr.devices() == []
    lo, hi = TF.window(tr)
    assert TF.busy_ns(tr, lo, hi) == 0.0
    with pytest.raises(FileNotFoundError):
        TF.find(str(tmp_path / "empty"))
