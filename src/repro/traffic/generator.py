"""Workload generation mirroring the paper's PktGen setup (§6.1, Fig. 6).

Three workload families:
  * ``fixed(size)`` — fixed-size UDP packets (256..1492 B sweeps, Figs. 8/9/15/16)
  * ``enterprise()`` — bimodal packet-size distribution reproducing Benson et
    al. [IMC'10] enterprise-datacenter traffic as digitized from the paper's
    Fig. 6: ~30 % of packets carry payloads under 160 B (not splittable) and
    the mean packet size is ~882 B.
  * ``datacenter()`` — the DC-side distribution from the same Benson et al.
    study (the paper §7's "datacenter-characteristic traffic"): strongly
    bimodal at the two extremes — ~45 % of packets are small control/ACK
    traffic under 203 B total (not splittable) and ~45 % ride near the MTU,
    mean ~700 B.  Distinct from ``enterprise()``, whose mass sits in the
    mid sizes; this is the workload the §7 FW->NAT->LB chain headline
    (13 % goodput gain, 28 % with recirculation) is evaluated on.

Packet sizes are total on-wire bytes including the 42-byte header.

``steer_pipes`` is the ingress steering stage for the multi-pipe engine
(DESIGN.md §3): it shards a flat batch across N per-port pipes by a hash of
the flow 5-tuple, the software analogue of the ToR switch mapping each
server-facing port to its own pipeline (§6.3.2).  Flow affinity is exact:
every packet of a 5-tuple lands in the same pipe, so per-pipe NAT/LB state
behaves as it would behind a real port.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packet import (HDR_BYTES, PacketBatch, dead_batch,
                               gather_rows, make_udp_batch)

# Digitized bimodal enterprise distribution (paper Fig. 6).  30 % of packets
# are below 202 B total (payload < 160 B -> ENB=0), mean ~= 882 B.
ENTERPRISE_SIZES = np.array([64, 128, 190, 512, 1024, 1492], np.int32)
ENTERPRISE_PROBS = np.array([0.10, 0.12, 0.08, 0.12, 0.18, 0.40])
ENTERPRISE_MEAN = float((ENTERPRISE_SIZES * ENTERPRISE_PROBS).sum())  # ~879.5

# Benson et al. DC-side distribution (paper §7): mass at the two extremes —
# small control/ACK packets (64..128 B, not splittable) and near-MTU data
# packets; the thin middle is what distinguishes it from the enterprise mix.
DATACENTER_SIZES = np.array([64, 128, 256, 595, 1024, 1492], np.int32)
DATACENTER_PROBS = np.array([0.35, 0.10, 0.05, 0.05, 0.10, 0.35])
DATACENTER_MEAN = float((DATACENTER_SIZES * DATACENTER_PROBS).sum())  # ~702


@dataclasses.dataclass(frozen=True)
class Workload:
    name: str
    sizes: np.ndarray   # candidate total packet sizes (bytes)
    probs: np.ndarray   # selection probabilities

    @property
    def mean_pkt_bytes(self) -> float:
        return float((self.sizes * self.probs).sum())

    def splittable_share(self, min_park_len: int = 160,
                         park_bytes: int = 160) -> float:
        """Fraction of offered wire bytes Split can park: expected parked
        bytes / expected packet bytes.  The PCIe-load reduction on the NF
        server is monotone in this share (DESIGN.md §7) — it is the
        workload-side knob the host-model benchmark sweeps."""
        parked = sum(
            p * min(s - HDR_BYTES, park_bytes)
            for s, p in zip(self.sizes, self.probs)
            if s - HDR_BYTES >= min_park_len)
        return float(parked) / self.mean_pkt_bytes

    def sample_sizes(self, key: jax.Array, n: int) -> jax.Array:
        idx = jax.random.choice(
            key, self.sizes.shape[0], (n,), p=jnp.asarray(self.probs))
        return jnp.asarray(self.sizes)[idx]

    def make_batch(self, key: jax.Array, n: int, pmax: int = 2048,
                   **field_overrides) -> PacketBatch:
        k1, k2 = jax.random.split(key)
        sizes = self.sample_sizes(k1, n)
        return make_udp_batch(k2, n, sizes, pmax=pmax, **field_overrides)


def fixed(size: int) -> Workload:
    assert size >= HDR_BYTES
    return Workload(f"fixed{size}", np.array([size], np.int32),
                    np.array([1.0]))


# --------------------------------------------------------------------------
# Adversarial & churn workloads (DESIGN.md §10)
# --------------------------------------------------------------------------

# Attack packets spoof the source but converge on one victim service —
# classic SYN-flood shape, sized just past the parking threshold so every
# attack packet CLAIMS a table slot while parking almost no useful bytes.
VICTIM_IP = 0x0A00FFFE
VICTIM_PORT = 80
ATTACK_SIZE = 208  # 166 B payload: minimally splittable (>= 160 + HDR 42)


@dataclasses.dataclass(frozen=True)
class AdversarialWorkload(Workload):
    """Base traffic with a burst-structured small-packet storm overlaid.

    ``attack_fraction`` of the per-batch *burst slots* (contiguous
    ``burst``-packet runs) are replaced by attack packets: spoofed random
    sources, one victim destination, ``attack_size`` bytes total — just
    splittable, so each one claims a parking slot for a 160-byte payload
    and evicts legitimate large-packet state under pressure.

    Attack-slot placement is COUPLED across fractions: each burst slot
    draws one permutation rank from the key, and a slot attacks iff its
    rank falls below ``attack_fraction``'s cut.  Raising the fraction only
    *adds* attack slots (never moves them), which is what makes drop rate
    provably monotone in attack load for the property tests, and
    ``attack_fraction=0`` is bit-identical to the base workload.
    """

    base: Workload = None
    attack_fraction: float = 0.0
    burst: int = 32
    attack_size: int = ATTACK_SIZE

    def make_batch(self, key: jax.Array, n: int, pmax: int = 2048,
                   **field_overrides) -> PacketBatch:
        k1, k2 = jax.random.split(key)
        sizes = self.base.sample_sizes(k1, n)
        km, kip, kport = jax.random.split(
            jax.random.fold_in(key, 0x5ADF), 3)
        n_slots = -(-n // self.burst)
        rank = jax.random.permutation(km, n_slots)
        n_attack = int(round(self.attack_fraction * n_slots))
        mask = rank[jnp.arange(n) // self.burst] < n_attack
        sizes = jnp.where(mask, self.attack_size, sizes)
        pkts = make_udp_batch(k2, n, sizes, pmax=pmax, **field_overrides)
        spoof_ip = jax.random.randint(kip, (n,), 1 << 28, (1 << 31) - 1,
                                      dtype=jnp.int32)
        spoof_port = jax.random.randint(kport, (n,), 1024, 65536,
                                        dtype=jnp.int32)
        return pkts.replace(
            src_ip=jnp.where(mask, spoof_ip, pkts.src_ip),
            src_port=jnp.where(mask, spoof_port, pkts.src_port),
            dst_ip=jnp.where(mask, jnp.int32(VICTIM_IP), pkts.dst_ip),
            dst_port=jnp.where(mask, jnp.int32(VICTIM_PORT), pkts.dst_port),
        )


def adversarial(base: str | Workload = "enterprise",
                attack_fraction: float = 0.5, burst: int = 32,
                attack_size: int = ATTACK_SIZE) -> AdversarialWorkload:
    """Small-packet-storm workload (attack-fraction x burst axes)."""
    if isinstance(base, str):
        base = {"enterprise": enterprise, "datacenter": datacenter}[base]()
    frac = float(attack_fraction)
    if not 0.0 <= frac <= 1.0:
        raise ValueError(f"attack_fraction must be in [0, 1], got {frac}")
    if burst < 1:
        raise ValueError(f"burst must be >= 1, got {burst}")
    if attack_size - HDR_BYTES < 160:
        raise ValueError(
            f"attack_size {attack_size} is not splittable (payload < 160)")
    # mixture view for the analytic helpers (mean bytes, splittable share)
    sizes = np.append(base.sizes, np.int32(attack_size))
    probs = np.append(base.probs * (1.0 - frac), frac)
    return AdversarialWorkload(
        name=f"adv_{base.name}_f{int(round(frac * 100)):02d}_b{burst}",
        sizes=sizes, probs=probs, base=base, attack_fraction=frac,
        burst=int(burst), attack_size=int(attack_size))


def _flow_identity(flow: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Deterministic flow index -> (src_ip, src_port), murmur-style mix."""
    h = flow.astype(jnp.int32) * jnp.int32(-2048144789)
    h = h ^ (h >> 13)
    h = h * jnp.int32(-1028477379)
    h = h ^ (h >> 16)
    ip = (h & jnp.int32(0x7FFFFFFF)) | jnp.int32(1)
    port = jnp.int32(1024) + ((h >> 7) & jnp.int32(0x7FFF))
    return ip, port


@dataclasses.dataclass(frozen=True)
class ChurnWorkload(Workload):
    """Base traffic whose flow population slides over time.

    Packets draw flows uniformly from a ``pool``-wide window that advances
    by ``pool // 2`` every ``rotate`` packets (half-overlapping windows):
    every flow stays active across two windows and then never returns.
    With a NAT table smaller than the live window this sustains CLOCK
    aging — mappings age out *while their flows are still sending*, which
    is exactly the stale-mapping edge case ``nat_stale_hits`` counts.
    """

    base: Workload = None
    pool: int = 256
    rotate: int = 1024

    def make_batch(self, key: jax.Array, n: int, pmax: int = 2048,
                   **field_overrides) -> PacketBatch:
        k1, k2 = jax.random.split(key)
        sizes = self.base.sample_sizes(k1, n)
        pkts = make_udp_batch(k2, n, sizes, pmax=pmax, **field_overrides)
        ku = jax.random.fold_in(key, 0xC4)
        u = jax.random.randint(ku, (n,), 0, self.pool, dtype=jnp.int32)
        win = (jnp.arange(n, dtype=jnp.int32) // self.rotate)
        flow = win * (self.pool // 2) + u
        ip, port = _flow_identity(flow)
        return pkts.replace(src_ip=ip, src_port=port)


def churn(pool: int = 256, rotate: int = 1024,
          base: str | Workload = "enterprise") -> ChurnWorkload:
    """Sustained flow-churn workload (NAT CLOCK-aging pressure)."""
    if isinstance(base, str):
        base = {"enterprise": enterprise, "datacenter": datacenter}[base]()
    if pool < 2 or rotate < 1:
        raise ValueError(f"need pool >= 2 and rotate >= 1, got "
                         f"({pool}, {rotate})")
    return ChurnWorkload(
        name=f"churn_{base.name}_p{pool}_r{rotate}", sizes=base.sizes,
        probs=base.probs, base=base, pool=int(pool), rotate=int(rotate))


def enterprise() -> Workload:
    return Workload("enterprise", ENTERPRISE_SIZES, ENTERPRISE_PROBS)


def datacenter() -> Workload:
    return Workload("datacenter", DATACENTER_SIZES, DATACENTER_PROBS)


def flow_pool(n_flows: int, seed: int = 7) -> tuple[jax.Array, jax.Array]:
    """Deterministic pool of ``n_flows`` distinct (src_ip, src_port) flows.

    Constraining a workload's source identity to a fixed pool (instead of
    the full 2^31 x 64k space) gives scenarios a flow structure: firewall
    rules drawn from the pool IPs drop a controlled traffic share, the NAT
    flow table (keyed on src_ip + src_port) sees repeat flows instead of a
    fresh mapping per packet, and — because the pool depends only on
    ``seed`` — the resulting NF chain is *identical across workloads*,
    which is what lets the scenario runner share one compiled engine
    across workload axes (DESIGN.md §8).

    Returns ``(ips, ports)``, both (n_flows,) int32.
    """
    assert n_flows >= 1
    kip, kport = jax.random.split(jax.random.key(seed))
    ips = jax.random.randint(kip, (n_flows,),
                             1, (1 << 31) - 1, dtype=jnp.int32)
    ports = jax.random.randint(kport, (n_flows,), 1024, 65536,
                               dtype=jnp.int32)
    # IP collisions are astronomically unlikely but would silently merge
    # flows (port collisions across distinct IPs are fine)
    assert int(jnp.unique(ips).shape[0]) == n_flows
    return ips, ports


# --------------------------------------------------------------------------
# Multi-pipe ingress steering (DESIGN.md §3)
# --------------------------------------------------------------------------

def flow_hash(pkts: PacketBatch) -> jax.Array:
    """Avalanche hash of the flow 5-tuple, (B,) non-negative int32.

    Built from the same murmur3-finalizer constants as the NAT flow-table
    hash (but over the full 5-tuple, with its own mixing sequence — the two
    are not bit-compatible); a switch would compute this with its hash
    engine over the same header fields.
    """
    h = pkts.src_ip ^ jnp.int32(-1640531527)
    h = (h * jnp.int32(-2048144789)) ^ pkts.dst_ip
    h = h ^ (h >> 13)
    h = (h * jnp.int32(-1028477379)) ^ (pkts.src_port << 16) ^ pkts.dst_port
    h = h ^ (h >> 16)
    h = (h * jnp.int32(-2048144789)) ^ pkts.proto
    h = h ^ (h >> 13)
    return h & jnp.int32(0x7FFFFFFF)


def pipe_trace_steps(packets: int, pipes: int, chunk: int) -> int:
    """Per-pipe engine steps after §6.3.2 steering — mirrors
    ``steer_pipes``'s default pipe-capacity rounding (~1.25x fair share,
    rounded up to ``chunk``).  Fault windows (``switchsim.faults``) are
    indexed in these per-pipe steps; ``ScenarioSpec`` validates fault
    timing against this."""
    if pipes == 1:
        return packets // chunk
    fair = -(-packets // pipes)
    slack = (fair * 5) // 4
    return -(-slack // chunk)


def steer_pipes(
    pkts: PacketBatch,
    num_pipes: int,
    pipe_capacity: int | None = None,
    chunk: int = 256,
) -> tuple[PacketBatch, dict]:
    """Shard a flat batch into per-pipe batches by flow hash.

    Returns ``(shards, stats)`` where ``shards`` leaves have shape
    (num_pipes, pipe_capacity, ...).  Slots beyond a pipe's arrival count
    are dead packets; arrivals beyond ``pipe_capacity`` (hash skew) are
    dropped and counted in ``stats['overflow']`` — the analogue of an
    ingress-port queue overrunning.  ``pipe_capacity`` defaults to ~1.25x
    the fair share, rounded up to a multiple of ``chunk`` so the result
    feeds ``core.packet.to_time_major`` directly.

    Packet order within a pipe preserves arrival order, so single-pipe
    steering (num_pipes=1) is the identity modulo tail padding.
    """
    b = pkts.batch_size
    pipe = flow_hash(pkts) % num_pipes                      # (B,)
    if pipe_capacity is None:
        fair = -(-b // num_pipes)                           # ceil
        slack = fair if num_pipes == 1 else (fair * 5) // 4
        pipe_capacity = -(-slack // chunk) * chunk          # round to chunk
    onehot = pipe[:, None] == jnp.arange(num_pipes)[None, :]  # (B, P)
    pos = jnp.cumsum(onehot, axis=0) - 1                    # arrival index
    pos = jnp.take_along_axis(pos, pipe[:, None], axis=1)[:, 0]
    ok = pos < pipe_capacity
    dest = jnp.where(ok, pipe * pipe_capacity + pos,
                     num_pipes * pipe_capacity)
    # Invert the permutation: src_of[dest] = packet row; empty slots -> B,
    # which gather_rows maps to a dead packet.
    src_of = jnp.full((num_pipes * pipe_capacity,), b, jnp.int32)
    src_of = src_of.at[dest].set(jnp.arange(b, dtype=jnp.int32), mode="drop")
    shards = gather_rows(pkts, src_of)
    shards = jax.tree.map(
        lambda a: a.reshape((num_pipes, pipe_capacity) + a.shape[1:]), shards)
    return shards, steer_stats(jnp.sum(onehot, axis=0), pipe_capacity)


# --------------------------------------------------------------------------
# Per-shard steering and exchange (DESIGN.md §12)
# --------------------------------------------------------------------------

def steer_stats(counts, pipe_capacity: int) -> dict:
    """``steer_pipes``'s stats from every pipe's arrivals: arrivals beyond
    ``pipe_capacity`` are the overflow."""
    counts = [int(c) for c in np.asarray(counts)]
    return dict(per_pipe_arrivals=counts,
                overflow=sum(max(c - pipe_capacity, 0) for c in counts),
                pipe_capacity=pipe_capacity)


def steer_shard(pkts: PacketBatch, num_pipes: int, axis: str):
    """``steer_pipes``'s decision for this device's shard of a flat batch
    sharded contiguously along its packet axis over mesh axis ``axis``;
    called inside ``shard_map``.

    Returns ``(pipe, pos, counts)``: each local packet's pipe (its flow
    hash modulo ``num_pipes``; 0 for one pipe, as ``to_time_major``
    keeps arrival order) and its arrival index inside that pipe over the
    whole batch, and every pipe's arrivals over the whole batch (the same
    on every device).  Only the per-pipe counts cross devices."""
    b = pkts.batch_size
    pipe = (flow_hash(pkts) % num_pipes if num_pipes > 1
            else jnp.zeros((b,), jnp.int32))
    onehot = pipe[:, None] == jnp.arange(num_pipes)[None, :]   # (b, P)
    every = jax.lax.all_gather(jnp.sum(onehot, axis=0, dtype=jnp.int32),
                               axis)                           # (D, P)
    earlier = jnp.arange(every.shape[0]) < jax.lax.axis_index(axis)
    before = jnp.sum(jnp.where(earlier[:, None], every, 0), axis=0)
    rank = jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1
    pos = before[pipe] + jnp.take_along_axis(rank, pipe[:, None], axis=1)[:, 0]
    return pipe, pos, jnp.sum(every, axis=0)


def pack_rows(p: PacketBatch) -> tuple[jax.Array, jax.Array]:
    """A flat batch as rows of two arrays: the header fields as one
    (B, 15) int32 row per packet, and the (B, pmax) payload bytes."""
    heads = [getattr(p, f.name).astype(jnp.int32)
             for f in dataclasses.fields(PacketBatch) if f.name != "payload"]
    return jnp.stack(heads, axis=1), p.payload


def unpack_rows(rows: tuple[jax.Array, jax.Array]) -> PacketBatch:
    """Inverse of ``pack_rows``; an all-zero row is a dead packet."""
    heads, payload = rows
    names = [f.name for f in dataclasses.fields(PacketBatch)
             if f.name != "payload"]
    like = dead_batch(1, 1)
    return PacketBatch(payload=payload, **{
        n: heads[:, i].astype(getattr(like, n).dtype)
        for i, n in enumerate(names)})


def exchange_rows(rows, dest: jax.Array, slot: jax.Array, slots: int,
                  axis: str, devices: int):
    """Move each of this device's rows to slot ``slot`` of device
    ``dest`` along mesh axis ``axis`` of ``devices`` devices (a ``dest``
    of ``devices`` moves the row nowhere); called inside ``shard_map``.
    ``rows`` is a pytree of arrays whose leading axis is the row.
    Returns the ``slots`` rows this device received, zero where none
    arrived.

    Rows travel in rounds of one ``all_to_all`` per leaf, at most ``m``
    rows from each device to each other, in the order they are held; the
    rounds go on until every row has arrived.  ``m`` is a sixteenth above
    half the even share, so hash-balanced traffic takes two rounds, and a
    round's buffers hold a little over half this device's rows; skewed
    traffic takes more rounds."""
    n = dest.shape[0]
    m = -(-(n + n // 16) // (2 * devices))
    m = -(-m // 8) * 8
    onehot = dest[:, None] == jnp.arange(devices)[None, :]      # (n, D)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0, dtype=jnp.int32) - 1,
        jnp.minimum(dest, devices - 1)[:, None], axis=1)[:, 0]
    most = jax.lax.pmax(jnp.max(jnp.sum(onehot, axis=0, dtype=jnp.int32)),
                        axis)

    def send(a, src, fill=0):
        # row ``src``, or ``fill`` for a src past the rows
        return jax.lax.all_to_all(a.at[src].get(mode="fill", fill_value=fill),
                                  axis, 0, 0, tiled=True)

    def one_round(k, out):
        lo = k * m
        go = (dest < devices) & (rank >= lo) & (rank < lo + m)
        at = jnp.where(go, dest * m + rank - lo, devices * m)
        src = jnp.full((devices * m,), n, jnp.int32).at[at].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
        into = jnp.full((slots,), devices * m, jnp.int32).at[
            send(slot, src, slots)].set(
                jnp.arange(devices * m, dtype=jnp.int32), mode="drop")
        came = into < devices * m
        return jax.tree.map(
            lambda a, o: jnp.where(
                came.reshape((slots,) + (1,) * (o.ndim - 1)),
                send(a, src).at[into].get(mode="fill", fill_value=0), o),
            rows, out)

    out = jax.tree.map(lambda a: jnp.zeros((slots,) + a.shape[1:], a.dtype),
                       rows)
    return jax.lax.fori_loop(0, (most + m - 1) // m, one_round, out)
