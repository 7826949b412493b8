"""One run of one cell: set up, measure a window, check, report.

Everything a cell is made of is found by name: the manifest
(``BENCHMARK.json``) names the cell's configuration and traffic mix, the
configuration's file lies where the manifest says, the mix is
``traffic/<traffic>.json``, and each metric is read by
``metrics/<metric>.py``.  A later cell, mix or metric is a new file and
a new manifest entry; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class CellError(Exception):
    """The cell cannot be run as its files describe it."""


@dataclasses.dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    manifest: dict

    def metrics(self, kind: str) -> list[dict]:
        """The cell's metrics of one kind (``end_to_end`` or
        ``per_layer``): those without a ``workloads`` list, and those
        whose list names the cell."""
        name = self.workload["name"]
        return [m for m in self.manifest[kind]
                if name in m.get("workloads", [name])]


def load_cell(name: str, manifest_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    manifest = json.loads(manifest_path.read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in {manifest_path.name} "
                        f"(have {sorted(cells)})")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((ROOT / configs[cell["config"]]["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    return Cell(workload=cell, config=config, traffic=traffic,
                manifest=manifest)


def reader(metric: str):
    """The ``read(run)`` function of ``metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    if spec is None or not path.is_file():
        raise CellError(f"no reader for metric {metric!r} ({path})")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@dataclasses.dataclass
class Run:
    """What a reader may read: the cell, the timed calls, the device, the
    peaks of its kind, and in a traced run the reduced trace of the
    profiled call, and whether it holds every kernel launch."""

    cell: Cell
    setup_s: float
    calls: list
    peak_bytes: int
    peaks: dict
    trace: object = None
    traced: object = None           # the Call the trace profiled
    trace_complete: bool = False

    @property
    def window_s(self) -> float:
        return self.calls[-1].end - self.calls[0].start

    @property
    def calls_s(self) -> float:
        """The window's time inside its calls: the window less the
        benchmark's own copies between them."""
        return sum(c.end - c.start for c in self.calls)


def hold_to_config(cell: Cell, facts: dict) -> None:
    """Refuse a configuration file that says something else than what the
    program builds from it: the file is the reference's only source.
    The program's pipes must also run sharded over just as many devices
    as the file's ``devices`` (1 where it has none) and the cell's
    ``chips`` say: the program would run a pipe count that does not
    divide its devices on one device, and a cell on four chips that ran
    on one would measure one."""
    cfg, park, tr = cell.config, facts["park"], cell.traffic
    mine = cfg["park"]
    theirs = dict(capacity=park.capacity, max_exp=park.max_exp,
                  max_clk=park.max_clk, min_park_len=park.min_park_len,
                  recirculation=park.recirculation, pmax=park.pmax,
                  recirc_frac=park.recirc_frac, pass_bytes=park.pass_bytes,
                  row_bytes=park.park_bytes)
    wrong = [k for k, v in theirs.items() if mine.get(k) != v]
    nfs = facts["nfs"]
    if "Nat" in nfs:
        from repro.nf.nat import PROBE_DEPTH
        nat = nfs["Nat"]
        theirs = dict(capacity=nat.capacity, max_exp=nat.max_exp,
                      base_port=nat.base_port, nat_ip=nat.nat_ip,
                      probe_depth=PROBE_DEPTH)
        wrong += [f"nat.{k}" for k, v in theirs.items()
                  if cfg["nat"].get(k) != v]
    if "MaglevLB" in nfs:
        lb = nfs["MaglevLB"]
        if list(lb.backends) != cfg["lb"]["backends"] or \
                lb.table_size != cfg["lb"]["table_size"]:
            wrong.append("lb")
    wl = facts["workload"]
    if [int(s) for s in wl.sizes] != tr["sizes"] or \
            [float(p) for p in wl.probs] != tr["probs"]:
        wrong.append("traffic sizes/probs")
    if wrong:
        raise CellError(f"{cfg['name']}: the configuration files disagree "
                        f"with the program on {wrong}")
    chips, asked = cell.workload["chips"], cfg.get("devices", 1)
    if facts["devices"] != asked or facts["devices"] != chips:
        raise CellError(
            f"{cfg['name']}: the program runs {cfg.get('pipes', 1)} pipes "
            f"on {facts['devices']} device(s), where the configuration "
            f"asks for {asked} and the cell {cell.workload['name']!r} for "
            f"{chips} chip(s)")


class Compiles:
    """The programs XLA compiles from now on, by name, and the seconds
    spent compiling or loading programs.  JAX records a program loaded
    from the persistent cache as a compile too, after a cache hit: those
    are loads, not compiles."""

    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self.event, self.names, self.loads, self.seconds = \
            BACKEND_COMPILE_EVENT, [], 0, 0.0
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_done)

    def _on_event(self, event, **_):
        if event == self.HIT:
            self._hit = True

    def _on_done(self, event, duration, fun_name="?", **_):
        if event != self.event:
            return
        self.seconds += duration
        if self._hit:
            self.loads += 1
        else:
            self.names.append(fun_name)
        self._hit = False

    def __len__(self) -> int:
        return len(self.names)

    def since(self, n: int) -> str:
        """The programs compiled after the first ``n``, with counts."""
        names = self.names[n:]
        return ", ".join(f"{k} x{names.count(k)}" for k in sorted(set(names)))


TRACE_TRIES = 3     # profiled calls at most, until one holds every launch


def _profile(driver, i: int, tracer, log):
    """Call ``i`` under the profiler; its trace, reduced, and whether the
    trace holds every kernel launch of the profiled slice."""
    from bench import tracefile
    call = driver.call(i, keep=True, tracer=tracer)
    t = time.perf_counter()
    reduced = tracefile.load(tracefile.find(tracer.log_dir))
    shutil.rmtree(tracer.log_dir, ignore_errors=True)
    os.makedirs(tracer.log_dir)
    steps = call.traced_steps // call.pipes
    complete = tracefile.complete(reduced, steps)
    log(f"trace of call {i} read in {time.perf_counter() - t:.3f} s: "
        f"{sum(reduced.op_count.values())} device ops, "
        f"{len(reduced.spans)} spans, dropped from {reduced.dropped}, "
        f"{tracefile.payload_launches(reduced)} payload kernel launches "
        f"for {steps} steps per pipe: "
        f"{'complete' if complete else 'incomplete'}")
    return call, reduced, complete


def measure(cell: Cell, driver, seconds: float, trace: bool, t_start: float,
            peaks: dict, log=print):
    """Warm up, then run whole calls until ``seconds`` have passed.

    In a traced run the first call is profiled, and the next ones too
    while a profile has lost kernel launches, up to ``TRACE_TRIES``.
    Returns the ``Run`` and the programs compiled in the window."""
    import jax

    t = time.perf_counter()
    compiles = Compiles()
    driver.warm_up()            # every program the window runs compiles
    setup_s = time.perf_counter() - t_start
    before = len(compiles)
    log(f"setup done in {setup_s:.3f} s, {time.perf_counter() - t:.3f} s "
        f"of it warming up: {before} programs compiled, {compiles.loads} "
        f"loaded from the cache, {compiles.seconds:.3f} s in both")
    tracer = None
    if trace:
        from bench.drivers import Tracer
        tracer = Tracer(tempfile.mkdtemp(prefix="bench-trace-"))
    run = Run(cell=cell, setup_s=setup_s, calls=[], peak_bytes=0,
              peaks=peaks)
    tries, i, t0 = 0, 1, time.perf_counter()

    def profiling() -> bool:
        return tracer is not None and not run.trace_complete \
            and tries < TRACE_TRIES

    while (not run.calls or time.perf_counter() - t0 < seconds
           or profiling()):
        with jax.profiler.TraceAnnotation("bench.call"):
            if profiling():
                tries += 1
                call, run.trace, run.trace_complete = _profile(
                    driver, i, tracer, log)
                run.traced = call
            else:
                call = driver.call(i, keep=True)
        run.calls.append(call)
        i += 1
    if tracer:
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
    run.peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices())
    return run, compiles.since(before)


def read_metrics(run: Run, kind: str) -> dict:
    out = {}
    for m in run.cell.metrics(kind):
        value = reader(m["name"])(run)
        if value is not None:
            if not math.isfinite(value):
                raise CellError(f"metric {m['name']} read {value}")
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(args, t_start: float, require_tpu: bool = True) -> int:
    """Run ``args.workload`` once; print the result line; return the exit
    code.  ``require_tpu=False`` lets a test drive a run on the CPU."""
    def log(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    try:
        cell = load_cell(args.workload)
    except (CellError, OSError, KeyError, ValueError) as e:
        log(f"cannot load the cell: {e}")
        return 2
    import jax

    devices = jax.devices()
    dev = devices[0]
    chips = cell.workload["chips"]
    if require_tpu and dev.platform != "tpu":
        log(f"no TPU: JAX found {dev.platform}")
        return 1
    if len(devices) < chips:
        log(f"the cell needs {chips} chips, JAX sees {len(devices)}")
        return 1
    peaks_table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if require_tpu and dev.device_kind not in peaks_table:
        log(f"no peaks for device kind {dev.device_kind!r}")
        return 1
    peaks = peaks_table.get(dev.device_kind, {})

    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    # keep every program, however quick to compile: the entry points
    # retrace small programs on every call, and only the first run of a
    # cell in a checkout is to compile them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    from bench.drivers import DRIVERS, LIMIT
    driver = DRIVERS[cell.config["engine"]](cell.config, cell.traffic,
                                            args.seed)
    try:
        hold_to_config(cell, driver.program_facts())
    except CellError as e:
        log(f"cannot run the cell: {e}")
        return 2

    run, in_window = measure(cell, driver, args.seconds, bool(args.trace),
                             t_start, peaks, log)
    log(f"window: {len(run.calls)} calls in {run.window_s:.3f} s, "
        f"{run.calls_s:.3f} s of it in the calls "
        f"({', '.join(f'{c.end - c.start:.3f}' for c in run.calls)}), "
        f"programs compiled inside it: {in_window or 'none'}")
    kind = "per_layer" if args.trace else "end_to_end"
    metrics = read_metrics(run, kind)
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(devices), memory_peak_bytes=run.peak_bytes)
    breakdown = None
    if args.trace:
        from bench import tracefile
        lo, hi = tracefile.window(run.trace)
        device["busy_s"] = tracefile.busy_ns(run.trace, lo, hi) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        breakdown = tracefile.breakdown(run.trace)

    t = time.perf_counter()
    run.trace = None
    checks = driver.check()
    log(f"reference replayed {checks.compared} "
        f"{'pipes' if cell.config['engine'] == 'run_matrix' else 'calls'} "
        f"in {time.perf_counter() - t:.3f} s")
    correct = checks.ok()
    result = dict(correct=correct, attempted=len(run.calls),
                  failed=checks.failed,
                  metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": LIMIT}
                        for k, v in checks.values.items()}
    result["checks"]["replayed"] = {"value": checks.compared, "least": 1}
    for k, v in checks.values.items():
        print(f"check {k} = {v} (limit {LIMIT})", file=sys.stderr)
    print(f"check replayed = {checks.compared} (at least 1)",
          file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
