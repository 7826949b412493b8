"""The calls a cell's window makes, and the check of what they produced.

A driver is chosen by the configuration's ``engine`` key:

  * ``run_matrix``: one call is one whole ``scenarios.run_matrix([spec])``
    of the deployment, on traffic drawn from a fresh per-call seed, as a
    researcher's sweep makes them: traffic generation, steering, the
    engine and the host-side regrouping all belong to the call.
  * ``run_stream``: one call is one whole ``switchsim.stream.run_stream``
    over ``steps_per_call`` steps of the traffic mix's source timeline,
    from a place on it that the seed picks; call ``i`` streams the
    ``i``-th such slice from there, so consecutive calls see fresh
    traffic.  The timeline's own seed is the mix's ``timeline_seed``: the
    source compiles it into its generator, so one seed for every run
    keeps every program in the compile cache after a cell's first run.

``warm_up`` compiles every program the window's calls run, on call 0's
traffic, which no timed call repeats.

A call ends when every array it returned is ready on the device.  Then
it keeps what the check needs on the host: counters, telemetry,
occupancy, and for a sample of pipes drawn from the seed the merged
packets themselves.  Those copies are the benchmark's own work and lie
outside the call's time.  After the window, ``check`` replays those calls
through the numpy reference and counts every fact on which the two
disagree.
"""
from __future__ import annotations

import dataclasses
import math
import time

import jax
import numpy as np
from repro.core.park import ParkConfig
from repro.nf.chain import Chain
from repro.nf.nat import Nat
from repro.scenarios import ScenarioSpec, run_matrix
from repro.scenarios.spec import build_chain, resolve_workload
from repro.switchsim import fabric
from repro.switchsim.stream import run_stream
from repro.traffic.stream import DiurnalLoad, SyntheticSource, TraceSource

from bench import generator as G
from bench import reference as R
from bench.harness import CellError

MAX_SEED = 2**31 - 16       # the simulator's seeds must stay within int32


def derive_seed(seed: int, *words: int) -> int:
    """A seed for one call or source, from the run's ``--seed``."""
    ss = np.random.SeedSequence([seed % 2**64, *words])
    return int(ss.generate_state(1, np.uint64)[0] % MAX_SEED)


@dataclasses.dataclass
class Call:
    """What one timed call did, by the host clock."""

    start: float
    end: float
    packets: int        # alive offered packets simulated
    pipes: int          # pipes simulated side by side
    pipe_steps: int     # pipe-steps executed, drain steps included
    stored_rows: int    # payload rows written to table slots
    fetched_rows: int   # payload rows read back from table slots
    store_rows: int     # rows handed to payload_store, enabled or not
    fetch_rows: int     # rows handed to payload_fetch, enabled or not
    traced_steps: int = 0       # pipe-steps inside the profiled slice
    traced_whole: bool = False  # the profiled slice is this whole call


class Tracer:
    """The profiled slice of a traced run: ``start`` turns the profiler on
    and opens the ``bench.traced`` span, ``stop`` closes both.  A driver
    calls them around as much of one call as the profiler can hold (it
    keeps at most 2 GB of events, some 6 million device ops)."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self._span = None

    @property
    def active(self) -> bool:
        return self._span is not None

    def start(self) -> None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._span = jax.profiler.TraceAnnotation("bench.traced")
        self._span.__enter__()

    def stop(self) -> None:
        self._span.__exit__(None, None, None)
        self._span = None
        jax.profiler.stop_trace()


LIMIT = 0   # every comparison is exact: no fact may differ


class Checks:
    """Counts of facts on which program and reference disagree; each must
    stay at or under ``LIMIT``."""

    def __init__(self, names):
        self.values = dict.fromkeys(names, 0)
        self.compared = 0       # calls (or pipes) replayed
        self.failed = 0         # replayed calls with any difference

    def add(self, name: str, n) -> None:
        self.values[name] += int(n)

    def total(self) -> int:
        return sum(self.values.values())

    def ok(self) -> bool:
        return self.compared > 0 and all(
            v <= LIMIT for v in self.values.values())


def _dict_diffs(a: dict, b: dict) -> int:
    return sum(a.get(k) != b.get(k) for k in set(a) | set(b))


LANE_COUNTERS = ("recirculations", "recirc_budget_drops")
LANE_TELEMETRY = ("recirc_pkts", "recirc_bytes")


def _ref_config(config: dict, fw_rules_ips=()) -> dict:
    """The reference's view of a configuration file."""
    return dict(park=config["park"], window=config["window"],
                chain=config["chain"], fw_rules_ips=list(fw_rules_ips),
                nat=config.get("nat"), lb=config.get("lb"))


def _control(ref_cfg: dict) -> dict:
    """The control: the reference with one guarantee of the configuration
    broken.  The NAT probes 1 slot of its flow table instead of the stated
    ``probe_depth``, the bounded-work shortcut that would tempt a faster
    NAT; flows whose home slot is taken are then dropped or re-mapped."""
    return dict(ref_cfg, nat=dict(ref_cfg["nat"], probe_depth=1))


def _count_common(checks: Checks, got: dict, ref: dict) -> None:
    """Counters, lane, telemetry and NAT count of one pipe."""
    for k in R.COUNTERS:
        name = "lane_diffs" if k in LANE_COUNTERS else "park_counter_diffs"
        checks.add(name, got["counters"].get(k) != ref["counters"][k])
    for k in R.TELEMETRY:
        name = "lane_diffs" if k in LANE_TELEMETRY else "telemetry_diffs"
        checks.add(name, got["telemetry"].get(k) != ref["telemetry"][k])
    checks.add("nat_count_diffs", _dict_diffs(got["nf_counters"],
                                              ref["nf_counters"]))


# ---------------------------------------------------------------------------
# run_matrix: the materialized engine
# ---------------------------------------------------------------------------

MERGED_CHECKS = ("steering_diffs", "park_counter_diffs", "occupancy_diffs",
                 "lane_diffs", "telemetry_diffs", "nat_count_diffs",
                 "fw_verdict_diffs", "nat_rewrite_diffs", "lb_choice_diffs",
                 "header_diffs", "payload_byte_diffs")


class MatrixDriver:
    """Whole ``run_matrix`` calls of one deployment."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 check_pipes: int = 3):
        self.config, self.traffic, self.seed = config, traffic, seed
        park = config["park"]
        self.pipes = config["pipes"]
        self.devices = config.get("devices", 1)
        self.packets = self.pipes * config["packets_per_pipe"]
        self.spec_kw = dict(
            name=config["name"], workload=tuple(traffic["workload"]),
            chain=tuple(config["chain"]), pipes=self.pipes,
            recirc=park["recirculation"], recirc_frac=park["recirc_frac"],
            capacity=park["capacity"], max_exp=park["max_exp"],
            packets=self.packets, chunk=config["chunk"],
            window=config["window"], pmax=park["pmax"],
            flows=config["flows"], fw_rules=config["fw_rules"],
            nat_capacity=config["nat"]["capacity"],
            backend=config["backend"], devices=self.devices)
        # one pipe of each shard at least, so no chip goes unchecked
        self.check_pipes = min(max(check_pipes, self.devices), self.pipes)
        self.kept: list[dict] = []
        lane = (math.floor(park["recirc_frac"] * config["chunk"] + 1e-9)
                if park["recirculation"] else 0)
        self.lane = lane
        self.drain = config["window"] + (1 if lane else 0)

    def warm_up(self) -> None:
        """Call 0, kept as a timed call keeps its pipes (which compiles
        the slicing of them), then forgotten."""
        self.call(0, keep=True)
        self.kept.clear()

    def spec(self, i: int):
        return ScenarioSpec(seed=derive_seed(self.seed, i), **self.spec_kw)

    def program_facts(self) -> dict:
        """What the program builds for this configuration, in the
        configuration file's terms, to hold the file to it."""
        spec = self.spec(0)
        nfs = {type(nf).__name__: nf for nf in build_chain(spec, None).nfs}
        try:
            devices = fabric.resolve_devices(spec.pipes, spec.devices)
        except ValueError as e:     # more devices asked than JAX sees
            raise CellError(f"{spec.name}: {e}") from None
        return dict(park=spec.park_config(), nfs=nfs,
                    workload=resolve_workload(spec.workload),
                    devices=devices)

    def sample(self, rng) -> list[int]:
        """The pipes of one call that the check replays, drawn from
        ``rng``: on one device ``check_pipes`` of them; sharded, a pipe
        of each shard (the shards hold equal runs of pipes) and as many
        more as make ``check_pipes``."""
        if self.devices == 1:
            pick = rng.choice(self.pipes, self.check_pipes, replace=False)
        else:
            per = self.pipes // self.devices
            first = per * np.arange(self.devices) \
                + rng.integers(per, size=self.devices)
            rest = rng.choice(np.setdiff1d(np.arange(self.pipes), first),
                              self.check_pipes - self.devices, replace=False)
            pick = np.concatenate([first, rest])
        return sorted(int(q) for q in pick)

    def call(self, i: int, keep: bool, tracer: Tracer | None = None) -> Call:
        """One whole call; with a ``tracer``, all of it is profiled."""
        if tracer:
            tracer.start()
        t0 = time.perf_counter()
        res = run_matrix([self.spec(i)])[0]
        jax.block_until_ready(res.merged)
        t1 = time.perf_counter()
        if tracer:
            tracer.stop()
        steps = res.steer_stats["pipe_capacity"] // self.config["chunk"] \
            + self.drain
        ctr = res.counters
        rows = self.config["chunk"] + self.lane
        call = Call(start=t0, end=t1, packets=res.alive_offered,
                    pipes=self.pipes, pipe_steps=self.pipes * steps,
                    stored_rows=ctr["splits"],
                    fetched_rows=ctr["merges"] + ctr["explicit_drops"],
                    store_rows=self.pipes * steps * rows,
                    fetch_rows=self.pipes * steps * rows)
        if tracer:
            call.traced_steps, call.traced_whole = call.pipe_steps, True
        if keep:
            sample = self.sample(
                np.random.default_rng([self.seed % 2**64, i]))
            self.kept.append(dict(
                call=i, steer=dict(res.steer_stats), pipes={
                    q: dict(counters=dict(res.per_pipe_counters[q]),
                            telemetry=res.per_pipe_telemetry[q].as_dict(),
                            nf_counters=dict(res.per_pipe_nf_counters[q]),
                            peak=res.per_pipe_peak_occupancy[q],
                            occ=np.asarray(res.per_pipe_occ_series[q]),
                            merged={f: np.asarray(getattr(res.merged, f)[q])
                                    for f in G.FIELDS})
                    for q in sample}))
        return call

    def check(self, control: bool = False) -> Checks:
        """Replay every kept call through the reference and count the
        facts on which the program's output differs.  With ``control``
        the control stands in the program's place."""
        checks = Checks(MERGED_CHECKS)
        cfg, tr = self.config, self.traffic
        ips, _ = G.flow_pool(cfg["flows"], cfg["flow_pool_seed"])
        ref_cfg = _ref_config(cfg, np.asarray(ips[:cfg["fw_rules"]]).tolist())
        for kept in self.kept:
            before = checks.total()
            pkts = G.call_packets(derive_seed(self.seed, kept["call"]),
                                  tr, self.packets, cfg["park"]["pmax"],
                                  cfg["flows"], cfg["flow_pool_seed"],
                                  self.devices)
            traces, stats = G.steer(pkts, self.pipes, cfg["chunk"])
            checks.add("steering_diffs", _dict_diffs(kept["steer"], stats))
            for q, got in kept["pipes"].items():
                ref = R.run_pipe(ref_cfg, traces[q])
                if control:
                    out = R.run_pipe(_control(ref_cfg), traces[q])
                    got = dict(out, peak=int(out["occ"].max()))
                compare_pipe(checks, got, ref)
                checks.compared += 1
            checks.failed += checks.total() > before
        return checks


def compare_pipe(checks: Checks, got: dict, ref: dict) -> None:
    """Every fact of one pipe of one call: counters, occupancy, lane,
    telemetry and each merged packet, field by field."""
    _count_common(checks, got, ref)
    occ_got, occ_ref = got["occ"], ref["occ"]
    if occ_got.shape != occ_ref.shape:
        checks.add("occupancy_diffs", max(occ_got.size, occ_ref.size))
    else:
        checks.add("occupancy_diffs", (occ_got != occ_ref).sum())
    checks.add("park_counter_diffs", got["peak"] != int(occ_ref.max()))
    a, b = got["merged"], ref["merged"]
    if a["alive"].shape != b["alive"].shape:
        checks.add("header_diffs", max(a["alive"].size, b["alive"].size))
        return
    checks.add("fw_verdict_diffs", (a["alive"] != b["alive"]).sum())
    both = a["alive"] & b["alive"]
    for f in G.FIELDS:
        if f in ("alive", "payload"):
            continue
        n = (a[f][both] != b[f][both]).sum()
        name = {"src_ip": "nat_rewrite_diffs", "src_port": "nat_rewrite_diffs",
                "dst_ip": "lb_choice_diffs"}.get(f, "header_diffs")
        checks.add(name, n)
    checks.add("payload_byte_diffs",
               (a["payload"][both] != b["payload"][both]).sum())


# ---------------------------------------------------------------------------
# run_stream: the streaming engine
# ---------------------------------------------------------------------------

STREAM_CHECKS = ("park_counter_diffs", "occupancy_diffs", "lane_diffs",
                 "telemetry_diffs", "nat_count_diffs", "sojourn_diffs",
                 "table_diffs", "payload_byte_diffs")


class SliceSource(TraceSource):
    """Steps ``[offset, offset + steps)`` of a long source timeline, as a
    source of its own; every segment drawn is a span in the profiler's
    trace, and ``hooks`` run when the segment they are keyed by is
    requested."""

    def __init__(self, inner: TraceSource, offset: int, steps: int,
                 hooks: dict | None = None):
        self.inner, self.offset, self.hooks = inner, offset, hooks or {}
        self.chunk, self.pmax, self.steps = inner.chunk, inner.pmax, steps

    def segment(self, start: int, count: int):
        if not 0 <= start <= start + count <= self.steps:
            raise ValueError(f"segment [{start}, {start + count}) outside "
                             f"[0, {self.steps})")
        if start in self.hooks and count > 1:
            self.hooks[start]()
        with jax.profiler.TraceAnnotation("bench.segment"):
            return self.inner.segment(self.offset + start, count)


class StreamDriver:
    """Whole ``run_stream`` calls, ``steps_per_call`` steps each."""

    def __init__(self, config: dict, traffic: dict, seed: int,
                 check_calls: int = 2):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.check_calls = check_calls
        self.steps = config["steps_per_call"]
        self.source_seed = traffic["timeline_seed"]
        # where on the timeline the run's calls start, from the run's seed
        self.start = self.steps * (derive_seed(seed, 0)
                                   % (MAX_SEED // self.steps - 64))
        self.kept: list[dict] = []
        self._source = None
        park = config["park"]
        self.lane = (math.floor(park["recirc_frac"] * config["chunk"] + 1e-9)
                     if park["recirculation"] else 0)
        self.drain = config["window"] + (1 if self.lane else 0)

    def _build(self):
        cfg, tr, park = self.config, self.traffic, self.config["park"]
        self.park = ParkConfig(capacity=park["capacity"],
                               max_exp=park["max_exp"], pmax=park["pmax"],
                               recirculation=park["recirculation"],
                               recirc_frac=park["recirc_frac"])
        nat = cfg["nat"]
        nfs = {"nat": lambda: Nat(nat_ip=nat["nat_ip"],
                                  capacity=nat["capacity"],
                                  base_port=nat["base_port"],
                                  max_exp=nat["max_exp"])}
        self.chain = Chain(tuple(nfs[n]() for n in cfg["chain"]))
        self.workload = resolve_workload(tuple(tr["workload"]))
        load = tr.get("load")
        self._source = SyntheticSource(
            steps=2**31 - 1, chunk=cfg["chunk"], pmax=park["pmax"],
            seed=self.source_seed, workload=self.workload,
            flows=tr["flows"], load=DiurnalLoad(**load) if load else None)

    def program_facts(self) -> dict:
        if self._source is None:
            self._build()
        return dict(park=self.park,
                    nfs={type(nf).__name__: nf for nf in self.chain.nfs},
                    workload=self.workload, devices=1)

    def offset(self, i: int) -> int:
        """The timeline step at which call ``i`` starts (call 0 warms up)."""
        return self.start + i * self.steps

    def _run(self, src: TraceSource):
        cfg = self.config
        return run_stream(self.park, self.chain, src, window=cfg["window"],
                          segment_len=cfg["segment_len"],
                          backend=cfg["backend"],
                          reservoir=cfg["reservoir"],
                          reservoir_seed=cfg["reservoir_seed"])

    def warm_up(self) -> None:
        """One segment and the drain: the window's calls run the same two
        programs (the segment program and the drain pad) and the source's
        segment generator, all at the same shapes."""
        if self._source is None:
            self._build()
        self._run(SliceSource(self._source, self.offset(0),
                              self.config["segment_len"]))

    def call(self, i: int, keep: bool, tracer: Tracer | None = None) -> Call:
        """One whole call; with a ``tracer``, one segment of it is
        profiled: from the request for the second segment to the request
        for the third, which spans that segment's generation, its program
        and the host's hand-off (a whole call is more device ops than the
        profiler keeps)."""
        if self._source is None:
            self._build()
        cfg = self.config
        seg = cfg["segment_len"]
        hooks = {seg: tracer.start, 2 * seg: tracer.stop} if tracer else None
        src = SliceSource(self._source, self.offset(i), self.steps, hooks)
        t0 = time.perf_counter()
        res = self._run(src)
        jax.block_until_ready(res.state)
        t1 = time.perf_counter()
        steps = self.steps + self.drain
        rows = cfg["chunk"] + self.lane
        ctr = res.counters
        call = Call(start=t0, end=t1, packets=res.telemetry.wire_pkts,
                    pipes=1, pipe_steps=steps, stored_rows=ctr["splits"],
                    fetched_rows=ctr["merges"] + ctr["explicit_drops"],
                    store_rows=steps * rows, fetch_rows=steps * rows)
        if tracer:
            call.traced_steps = seg
            if tracer.active:       # a call of fewer than 3 segments
                tracer.stop()
                call.traced_steps = steps - seg
        if keep:
            st = res.state
            self.kept.append(dict(
                call=i, counters=dict(res.counters),
                telemetry=res.telemetry.as_dict(),
                nf_counters=dict(res.nf_counters),
                peak=res.peak_occupancy, occ_segments=res.occ_segments,
                latency=dict(res.latency),
                state={k: np.asarray(getattr(st, k)) for k in (
                    "tbl_idx", "clk", "meta_exp", "meta_clk", "meta_len",
                    "ptable", "counters")}))
        return call

    def check(self, control: bool = False) -> Checks:
        """Replay ``check_calls`` of the kept calls, drawn from the seed,
        through the reference and count the facts on which the program's
        output differs (the reference streams a call in about as long as
        a quarter of a window).  With ``control`` the control stands in
        the program's place."""
        checks = Checks(STREAM_CHECKS)
        rng = np.random.default_rng([self.seed % 2**64, 0xC4EC])
        pick = sorted(rng.choice(len(self.kept), min(self.check_calls,
                                                     len(self.kept)),
                                 replace=False))
        cfg, tr, park = self.config, self.traffic, self.config["park"]
        traffic = G.StreamTraffic(
            self.source_seed, tr, cfg["chunk"], park["pmax"], tr["flows"],
            self.source_seed + tr["flow_pool_seed_offset"], tr.get("load"))
        for kept in (self.kept[j] for j in pick):
            offset = self.offset(kept["call"])

            def replay(ref_cfg):
                return R.run_stream(
                    ref_cfg, lambda s, n: traffic.segment(offset + s, n),
                    self.steps, cfg["chunk"], cfg["segment_len"],
                    cfg["reservoir"], cfg["reservoir_seed"])

            ref = replay(_ref_config(cfg))
            got = kept
            if control:
                out = replay(_control(_ref_config(cfg)))
                got = dict(out, peak=out["peak_occupancy"])
            before = checks.total()
            compare_stream(checks, got, ref)
            checks.compared += 1
            checks.failed += checks.total() > before
        return checks


def compare_stream(checks: Checks, got: dict, ref: dict) -> None:
    """Every fact one streamed call reports, and its final table."""
    _count_common(checks, got, ref)
    checks.add("park_counter_diffs", got["peak"] != ref["peak_occupancy"])
    a, b = got["occ_segments"], ref["occ_segments"]
    checks.add("occupancy_diffs", sum(x != y for x, y in zip(a, b))
               + abs(len(a) - len(b)))
    checks.add("sojourn_diffs", _dict_diffs(got["latency"], ref["latency"]))
    for k, v in ref["state"].items():
        g = np.asarray(got["state"][k])
        v = np.asarray(v)
        name = "payload_byte_diffs" if k == "ptable" else "table_diffs"
        if g.shape != v.shape:
            checks.add(name, max(g.size, v.size))
        else:
            checks.add(name, (g != v).sum())


DRIVERS = {"run_matrix": MatrixDriver, "run_stream": StreamDriver}
