"""Declarative scenario specs and the grid expander (DESIGN.md §8).

A ``ScenarioSpec`` names one point of the evaluation matrix: workload x
NF chain x recirculation mode x pipes x table occupancy x trace geometry.
It is a frozen, hashable value — no arrays, no callables — so specs can be
grouped, deduplicated, serialized into BENCH_*.json artifacts, and used as
compile-cache keys.  Everything runnable (packets, chains, ParkConfigs) is
*derived* from the spec by pure functions in this module; the sweep runner
(repro.scenarios.runner) is the only place that executes anything.

Workloads are named tuples (``("fixed", 512)``, ``("enterprise",)``,
``("datacenter",)``) resolved via ``resolve_workload``.  Chains are tuples
of NF names (``("fw", "nat", "lb")``) resolved via ``build_chain``; the
firewall's blocked list is drawn from the deterministic flow pool when the
spec constrains flows (``flows > 0``), which makes the chain — and hence
the compiled engine — identical across workload axes.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

from repro.backend import BackendConfig, as_config
from repro.core.park import ParkConfig
from repro.core.packet import PacketBatch, to_time_major
from repro.nf.chain import Chain
from repro.nf.firewall import Firewall
from repro.nf.macswap import MacSwap
from repro.nf.maglev import MaglevLB
from repro.nf.nat import Nat
from repro.switchsim.faults import NO_FAULT, FaultSpec
from repro.traffic import generator as T

# ("fixed", size) | ("enterprise",) | ("datacenter",)
# | ("adversarial", base, attack_fraction, burst)   (DESIGN.md §10)
# | ("churn", pool, rotate)
WorkloadSpec = tuple
ChainSpec = tuple     # e.g. ("fw", "nat", "lb"); names below


@dataclasses.dataclass(frozen=True)
class ScenarioSpec:
    """One declarative point of the evaluation matrix.

    ``name`` is the point's identity inside its family; artifact rows are
    emitted as ``<family>/<name>/<metric>``.  ``flows`` > 0 constrains
    ``src_ip`` to a deterministic ``flows``-IP pool (flow structure for
    NAT/LB plus a workload-independent firewall rule set); 0 keeps the
    seed benches' behaviour (random IPs, rules drawn from the traffic).
    ``backend`` names the dataplane-backend the point runs on
    (``repro.backend``: "ref" | "pallas" | "pallas_interpret" | "auto") —
    a first-class grid axis, so ref-vs-Pallas sweeps ride the same runner
    as every other comparison (DESIGN.md §9).

    ``fault`` injects one fault event (``switchsim.faults.FaultSpec``,
    DESIGN.md §10); fault *timing* is data, so faulted and healthy points
    still share a compile group.  ``nat_capacity`` overrides the NAT
    flow-table size (0 = the NF's default) — the churn family shrinks it
    below the live flow window to sustain CLOCK aging.

    ``devices`` shards the point's flat pipe axis over that many devices
    (``switchsim.fabric``, DESIGN.md §12) — a first-class grid axis and
    part of the compile key, since a sharded program is a different XLA
    program even at equal shapes.  Results are device-count invariant
    (bit-identical counters/telemetry/occupancy), so scaling sweeps vary
    only wall-clock.
    """

    name: str
    workload: WorkloadSpec = ("enterprise",)
    chain: ChainSpec = ("fw", "nat")
    pipes: int = 1
    recirc: bool = False
    recirc_frac: float = 0.25
    capacity: int = 4096
    max_exp: int = 2
    packets: int = 16384
    chunk: int = 256
    window: int = 2
    pmax: int = 2048
    explicit_drops: bool = False
    seed: int = 0
    flows: int = 0
    fw_rules: int = 20
    backend: str = "auto"
    fault: FaultSpec = NO_FAULT
    nat_capacity: int = 0
    devices: int = 1

    def __post_init__(self):
        as_config(self.backend)  # validates the backend name eagerly
        if self.packets % self.chunk:
            raise ValueError(
                f"{self.name}: packets ({self.packets}) must be a multiple "
                f"of chunk ({self.chunk})")
        if self.pipes < 1:
            raise ValueError(f"{self.name}: pipes must be >= 1")
        if self.devices < 1:
            raise ValueError(f"{self.name}: devices must be >= 1")
        resolve_workload(self.workload)  # validates the name eagerly
        for nf in self.chain:
            if nf not in _NF_NAMES:
                raise ValueError(
                    f"{self.name}: unknown NF {nf!r} (have {_NF_NAMES})")
        if self.flows and "fw" in self.chain and self.fw_rules >= self.flows:
            raise ValueError(
                f"{self.name}: fw_rules ({self.fw_rules}) must be < flows "
                f"({self.flows}) — blocking the whole pool drops 100% of "
                f"the traffic")
        if self.flows and self.workload[0] in ("adversarial", "churn"):
            raise ValueError(
                f"{self.name}: workload {self.workload[0]!r} owns the "
                f"source identity (spoofed/churning flows); flows must be 0")
        if self.nat_capacity and "nat" not in self.chain:
            raise ValueError(
                f"{self.name}: nat_capacity set but no 'nat' in chain")
        f = self.fault
        if f.active:
            steps = T.pipe_trace_steps(self.packets, self.pipes, self.chunk)
            if f.end > steps:
                raise ValueError(
                    f"{self.name}: fault window [{f.start}, {f.end}) "
                    f"exceeds the {steps}-step per-pipe trace — faults "
                    f"must live within the offered traffic")
            if f.kind == "server" and f.pipe >= self.pipes:
                raise ValueError(
                    f"{self.name}: fault pipe {f.pipe} >= pipes "
                    f"({self.pipes})")
            if f.kind == "lb" and "lb" not in self.chain:
                raise ValueError(
                    f"{self.name}: lb fault but no 'lb' in chain")

    def park_config(self) -> ParkConfig:
        return ParkConfig(capacity=self.capacity, max_exp=self.max_exp,
                          pmax=self.pmax, recirculation=self.recirc,
                          recirc_frac=self.recirc_frac)

    def backend_config(self) -> BackendConfig:
        """Concrete (platform-resolved) backend selection: "auto" and its
        resolution share one compile group on any given host."""
        return as_config(self.backend).concrete()

    def as_dict(self) -> dict:
        """JSON-ready form for the schema-v2 artifact ``matrix`` block."""
        d = dataclasses.asdict(self)
        d["workload"] = list(self.workload)
        d["chain"] = list(self.chain)
        return d


def resolve_workload(ws: WorkloadSpec) -> T.Workload:
    """Workload-spec tuple -> traffic.generator.Workload."""
    kind = ws[0]
    if kind == "fixed":
        return T.fixed(int(ws[1]))
    if kind == "enterprise":
        return T.enterprise()
    if kind == "datacenter":
        return T.datacenter()
    if kind == "adversarial":
        return T.adversarial(base=ws[1], attack_fraction=float(ws[2]),
                             burst=int(ws[3]))
    if kind == "churn":
        return T.churn(pool=int(ws[1]), rotate=int(ws[2]))
    raise ValueError(f"unknown workload spec {ws!r}")


def draw_packets(spec: ScenarioSpec, key: jax.Array,
                 pool=None) -> PacketBatch:
    """The flat batch of one scenario point, drawn from ``key``: the
    workload's packets, their sources taken from the flow pool when the
    spec constrains flows (``pool``, ``traffic.generator.flow_pool``'s
    ``(ips, ports)``, is drawn here where not given).  Traceable once the
    pool is given, so ``sharded_traffic`` draws under jit with the same
    calls in the same order."""
    wl = resolve_workload(spec.workload)
    pkts = wl.make_batch(key, spec.packets, pmax=spec.pmax)
    if spec.flows:
        ips, ports = T.flow_pool(spec.flows) if pool is None else pool
        idx = jax.random.randint(jax.random.fold_in(key, 1),
                                 (spec.packets,), 0, spec.flows)
        # both halves of the NAT flow key (src_ip, src_port) come from the
        # pool, so repeat flows actually repeat at the NF chain
        pkts = pkts.replace(src_ip=ips[idx], src_port=ports[idx])
    return pkts


def make_packets(spec: ScenarioSpec) -> PacketBatch:
    """Deterministic traffic for one scenario point, drawn eagerly on the
    default device (the whole batch at once).

    The PRNG key folds in only ``seed`` — two specs with equal
    (workload, packets, pmax, flows, seed) produce bit-identical traffic
    no matter how the rest of the grid differs, so recirc-on/off pairs
    compare the same packets.  A point sharded over several devices does
    not come here: ``sharded_traffic`` draws its batch per shard, bit for
    bit this one (DESIGN.md §12).
    """
    return draw_packets(spec, jax.random.key(spec.seed))


def firewall_rules(spec: ScenarioSpec,
                   pkts: PacketBatch | None) -> tuple[int, ...]:
    """Blocked-IP list: from the flow pool when flows are constrained
    (workload-independent -> chains shareable across workload axes),
    otherwise the seed benches' rule source (first N unique src IPs).
    Without ``pkts`` (a point drawn per shard) the source IPs are drawn
    alone: the batch's header draw, jitted, whose payload XLA leaves
    out."""
    if spec.flows:
        ips, _ = T.flow_pool(spec.flows)
        return tuple(int(ip) for ip in
                     np.asarray(ips[:spec.fw_rules]).tolist())
    src_ip = (pkts.src_ip if pkts is not None else
              _src_ips(_traffic_shape(spec))(jax.random.key(spec.seed)))
    return tuple(int(ip) for ip in
                 np.unique(np.asarray(src_ip))[:spec.fw_rules].tolist())


@functools.lru_cache(maxsize=None)
def _src_ips(shape: ScenarioSpec):
    return jax.jit(lambda key: draw_packets(shape, key).src_ip)


_NF_NAMES = ("fw", "nat", "lb", "macswap")


def build_chain(spec: ScenarioSpec, pkts: PacketBatch) -> Chain:
    """Chain-spec tuple -> runnable (and hashable) nf.chain.Chain."""
    nfs = []
    for nf in spec.chain:
        if nf == "fw":
            nfs.append(Firewall(rules=firewall_rules(spec, pkts)))
        elif nf == "nat":
            nfs.append(Nat(capacity=spec.nat_capacity) if spec.nat_capacity
                       else Nat())
        elif nf == "lb":
            nfs.append(MaglevLB(fault_target=spec.fault.backend
                                if spec.fault.kind == "lb" else -1))
        elif nf == "macswap":
            nfs.append(MacSwap())
    return Chain(tuple(nfs))


def steer(spec: ScenarioSpec, pkts: PacketBatch):
    """Shard a scenario's traffic into its (P, T, chunk, ...) traces, on
    the device that holds ``pkts`` (``make_packets``' eager draw).

    Single-pipe scenarios skip hashing entirely (identity + tail padding
    via ``to_time_major``); multi-pipe scenarios go through the §6.3.2
    flow steering.  Returns ``(traces, steer_stats)``.  A point sharded
    over several devices is steered per shard instead, inside
    ``sharded_traffic``'s program, to the same traces and stats.
    """
    if spec.pipes == 1:
        trace = to_time_major(pkts, spec.chunk)
        traces = jax.tree.map(lambda a: a[None], trace)
        stats = dict(per_pipe_arrivals=[spec.packets], overflow=0,
                     pipe_capacity=spec.packets)
        return traces, stats
    shards, stats = T.steer_pipes(pkts, spec.pipes, chunk=spec.chunk)
    traces = jax.tree.map(
        lambda a: a.reshape((spec.pipes, a.shape[1] // spec.chunk,
                             spec.chunk) + a.shape[2:]), shards)
    return traces, stats


def _traffic_shape(spec: ScenarioSpec) -> ScenarioSpec:
    """The spec with the fields that do not shape its traffic program
    (name, seed) at fixed values: the program's cache key."""
    return dataclasses.replace(spec, name="traffic", seed=0)


def sharded_traffic(specs, devices: int):
    """The per-shard traffic program of one compile group and its
    arguments: ``(fn, args)``, where ``fn(*args)`` returns
    ``(traces, counts)``.  ``traces`` are the members' (P, T, chunk, ...)
    traces stacked on one pipe axis, sharded over ``devices`` devices as
    the engine's ``shard_map`` takes them (``fabric.SWITCH``); ``counts``
    holds each member's per-pipe arrivals, whose stats
    (``traffic.generator.steer_stats``) are ``steer``'s.

    One jitted program on the ``("switch",)`` mesh (DESIGN.md §12):

      * ``traffic.draw``: each device draws its contiguous share of each
        member's flat batch along the packet axis, by ``draw_packets``'
        calls (partitionable threefry gives the bits of the whole draw);
      * ``traffic.steer``: each device steers its own packets
        (``traffic.generator.steer_shard``); only per-pipe counts cross
        devices;
      * ``traffic.exchange``: every packet moves to the device that holds
        its pipe, at its slot (``exchange_rows``).

    The traces equal ``make_packets`` + ``steer`` of each member bit for
    bit.  Each member's packets must divide over the devices, and the
    members' pipes together too.
    """
    fn = _traffic_program(tuple(_traffic_shape(s) for s in specs), devices)
    return fn, tuple(jax.random.key(s.seed) for s in specs)


@functools.lru_cache(maxsize=None)
def _traffic_program(shapes: tuple, devices: int):
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.switchsim import fabric

    mesh = fabric.switch_mesh(devices)
    pools = [T.flow_pool(s.flows) if s.flows else None for s in shapes]
    steps = T.pipe_trace_steps(shapes[0].packets, shapes[0].pipes,
                               shapes[0].chunk)
    cap = steps * shapes[0].chunk            # every member's pipe capacity
    per_device = sum(s.pipes for s in shapes) // devices
    slots = per_device * cap

    def per_shard(*batches):
        dest, slot, counts, offset = [], [], [], 0
        with jax.named_scope("traffic.steer"):
            for s, pkts in zip(shapes, batches):
                pipe, pos, n = T.steer_shard(pkts, s.pipes, fabric.SWITCH_AXIS)
                at = (offset + pipe) * cap + pos
                dest.append(jnp.where(pos < cap, at // slots, devices))
                slot.append(at % slots)
                counts.append(n)
                offset += s.pipes
        with jax.named_scope("traffic.exchange"):
            rows = T.exchange_rows(
                jax.tree.map(lambda *a: jnp.concatenate(a),
                             *[T.pack_rows(b) for b in batches]),
                jnp.concatenate(dest), jnp.concatenate(slot), slots,
                fabric.SWITCH_AXIS, devices)
            traces = jax.tree.map(
                lambda a: a.reshape((per_device, steps, shapes[0].chunk)
                                    + a.shape[1:]),
                T.unpack_rows(rows))
        return traces, tuple(counts)

    def program(*keys):
        batches = []
        with jax.named_scope("traffic.draw"):
            for s, key, pool in zip(shapes, keys, pools):
                batches.append(jax.lax.with_sharding_constraint(
                    draw_packets(s, key, pool),
                    NamedSharding(mesh, fabric.SWITCH)))
        return jax.shard_map(
            per_shard, mesh=mesh, in_specs=(fabric.SWITCH,) * len(shapes),
            out_specs=(fabric.SWITCH, PartitionSpec()),
            check_vma=False)(*batches)

    return jax.jit(program)


def grid(base: ScenarioSpec, name_fmt: str, **axes) -> list[ScenarioSpec]:
    """Expand a cartesian grid of spec fields around ``base``.

    ``axes`` maps field names to value lists; ``name_fmt`` is formatted
    with each point's axis values (e.g. ``grid(base, "occ_{capacity}",
    capacity=[256, 512])``).  Axis order follows keyword order, so row
    ordering in artifacts is stable.
    """
    for field in axes:
        if field not in {f.name for f in dataclasses.fields(ScenarioSpec)}:
            raise ValueError(f"unknown grid axis {field!r}")
    specs = []
    names = list(axes.keys())
    for values in itertools.product(*axes.values()):
        kw = dict(zip(names, values))
        specs.append(dataclasses.replace(
            base, name=name_fmt.format(**kw), **kw))
    if len({s.name for s in specs}) != len(specs):
        raise ValueError(f"name_fmt {name_fmt!r} does not separate the grid")
    return specs


def compile_key(spec: ScenarioSpec, chain: Chain, steps: int):
    """Trace-compatibility signature (DESIGN.md §8).

    Two scenario points sharing this key run the *same* XLA program on
    stacked pipe traces: equal ParkConfig (capacity/max_exp/recirc mode and
    fraction -> equal state shapes and lane width), equal chain constants,
    equal trace geometry (``steps`` is taken from the point's actual
    steered traces, so per-pipe capacity rounding is reflected exactly),
    and the same concrete backend selection (a ref point and a Pallas
    point are different XLA programs even at equal shapes).  ``devices``
    is part of the key for the same reason: a shard_mapped program is a
    different XLA program, and a compile group spanning devices must stay
    ONE program whose concatenated pipe axis shards as a whole.  Points
    that differ only in workload, seed or flow structure batch together;
    shape-changing axes (occupancy/capacity, recirc_frac, chunk, window)
    fall back to the engine's lru_cache-keyed per-point loop.
    """
    from repro.switchsim import engine as E
    cfg = spec.park_config()
    lane = E.recirc_slots(cfg, spec.chunk)
    return (cfg, chain, spec.window, spec.chunk, steps, spec.pmax,
            spec.explicit_drops, lane, spec.backend_config(), spec.devices)
