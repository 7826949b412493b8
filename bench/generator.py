"""The yardstick's copy of the traffic the simulator is asked to run.

The timed calls generate their own traffic inside the simulator's entry
points (``scenarios.run_matrix`` builds packets from the spec's seed,
``switchsim.stream.run_stream`` pulls them from a ``SyntheticSource``).
The reference must not take those packets from the program, so this
module draws the same packets again from the same seed: the same
``jax.random`` calls, in the same order, which give the same bits.  A
deployment sharded over several chips is drawn by those calls jitted,
their outputs sharded over the chips along the packet axis, so that no
chip holds the whole batch; JAX's partitionable threefry gives a
sharded draw the bits of the unsharded one.  Everything after the random
draws (flow identities, steering onto pipes) is plain numpy.

A packet batch here is a dict of numpy arrays keyed by ``FIELDS``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

HDR_BYTES = 42
FIELDS = ("dst_mac", "src_mac", "src_ip", "dst_ip", "proto", "src_port",
          "dst_port", "payload_len", "payload", "alive", "pp_valid",
          "pp_enb", "pp_op", "pp_ti", "pp_clk", "pp_crc")
_BOOL = ("alive", "pp_valid")


def dead(rows: int, pmax: int) -> dict:
    """``rows`` packets that are not there: every field zero."""
    out = {f: np.zeros((rows,), np.int32) for f in FIELDS}
    for f in _BOOL:
        out[f] = np.zeros((rows,), bool)
    out["payload"] = np.zeros((rows, pmax), np.uint8)
    return out


def _udp_batch(key, n: int, sizes, pmax: int) -> dict:
    """UDP packets of total lengths ``sizes`` with random header fields and
    payload bytes; the payload beyond each packet's length is zero."""
    ks = jax.random.split(key, 6)
    pkt_len = jnp.broadcast_to(jnp.asarray(sizes, jnp.int32), (n,))
    payload_len = jnp.maximum(pkt_len - HDR_BYTES, 0)
    payload = jax.random.randint(ks[0], (n, pmax), 0, 256, dtype=jnp.int32)
    mask = jnp.arange(pmax)[None, :] < payload_len[:, None]
    payload = jnp.where(mask, payload, 0).astype(jnp.uint8)

    def draw(k, lo, hi):
        return jax.random.randint(k, (n,), lo, hi, dtype=jnp.int32)

    port = draw(ks[5], 1024, 65536)   # source and destination share a key
    z = jnp.zeros((n,), jnp.int32)
    return dict(
        dst_mac=draw(ks[1], 0, (1 << 31) - 1),
        src_mac=draw(ks[2], 0, (1 << 31) - 1),
        src_ip=draw(ks[3], 0, (1 << 31) - 1),
        dst_ip=draw(ks[4], 0, (1 << 31) - 1),
        proto=jnp.full((n,), 17, jnp.int32),
        src_port=port, dst_port=port,
        payload_len=payload_len, payload=payload,
        alive=jnp.ones((n,), bool), pp_valid=jnp.zeros((n,), bool),
        pp_enb=z, pp_op=z, pp_ti=z, pp_clk=z, pp_crc=z)


def _mix_batch(key, n: int, mix: dict, pmax: int) -> dict:
    """A batch whose sizes are drawn from the mix's size distribution."""
    k1, k2 = jax.random.split(key)
    sizes = np.asarray(mix["sizes"], np.int32)
    probs = np.asarray(mix["probs"], np.float64)
    idx = jax.random.choice(k1, sizes.shape[0], (n,), p=jnp.asarray(probs))
    return _udp_batch(k2, n, jnp.asarray(sizes)[idx], pmax)


def flow_pool(n_flows: int, seed: int):
    """The materialized engine's pool of (src_ip, src_port) flows."""
    kip, kport = jax.random.split(jax.random.key(seed))
    ips = jax.random.randint(kip, (n_flows,), 1, (1 << 31) - 1,
                             dtype=jnp.int32)
    ports = jax.random.randint(kport, (n_flows,), 1024, 65536,
                               dtype=jnp.int32)
    return ips, ports


def _call_batch(key, mix: dict, packets: int, pmax: int, flows: int,
                pool_seed: int) -> dict:
    """The flat batch one materialized call offers, as jax arrays."""
    pkts = _mix_batch(key, packets, mix, pmax)
    if flows:
        ips, ports = flow_pool(flows, pool_seed)
        idx = jax.random.randint(jax.random.fold_in(key, 1), (packets,), 0,
                                 flows)
        pkts = dict(pkts, src_ip=ips[idx], src_port=ports[idx])
    return pkts


@functools.lru_cache(maxsize=None)
def _sharded_draw(devices: int, sizes: tuple, probs: tuple, packets: int,
                  pmax: int, flows: int, pool_seed: int):
    """``_call_batch`` jitted, every output sharded along its packet axis
    over the first ``devices`` devices."""
    mesh = Mesh(np.asarray(jax.devices()[:devices]), ("packets",))
    mix = dict(sizes=sizes, probs=probs)
    return jax.jit(
        lambda key: _call_batch(key, mix, packets, pmax, flows, pool_seed),
        out_shardings=NamedSharding(mesh, PartitionSpec("packets")))


def call_packets(seed: int, mix: dict, packets: int, pmax: int,
                 flows: int, pool_seed: int, devices: int = 1) -> dict:
    """The flat batch one materialized call offers, as numpy arrays;
    drawn sharded over ``devices`` devices where that is above 1."""
    key = jax.random.key(seed)
    if devices == 1:
        pkts = _call_batch(key, mix, packets, pmax, flows, pool_seed)
    else:
        pkts = _sharded_draw(devices, tuple(mix["sizes"]),
                             tuple(mix["probs"]), packets, pmax, flows,
                             pool_seed)(key)
    return {f: np.asarray(v) for f, v in pkts.items()}


def _i32(a):
    return np.asarray(a, np.int64).astype(np.int32)


def flow_hash(p: dict) -> np.ndarray:
    """Avalanche hash of the 5-tuple that steers a packet to its pipe
    (int32 arithmetic that wraps)."""
    with np.errstate(over="ignore"):
        h = p["src_ip"] ^ np.int32(-1640531527)
        h = (h * np.int32(-2048144789)) ^ p["dst_ip"]
        h = h ^ (h >> 13)
        h = (h * np.int32(-1028477379)) ^ (p["src_port"] << 16) \
            ^ p["dst_port"]
        h = h ^ (h >> 16)
        h = (h * np.int32(-2048144789)) ^ p["proto"]
        h = h ^ (h >> 13)
    return h & np.int32(0x7FFFFFFF)


def steer(p: dict, pipes: int, chunk: int):
    """Flow-affine steering onto ``pipes`` pipes (paper §6.3.2): arrival
    order is kept inside a pipe, a pipe holds about 1.25x its fair share
    rounded up to whole chunks, and arrivals beyond that are lost.

    Returns ``(traces, stats)``: ``traces`` is a list with one dict of
    (steps, chunk, ...) arrays per pipe.
    """
    b = p["alive"].shape[0]
    pmax = p["payload"].shape[1]
    if pipes == 1:
        cap = b
        pipe = np.zeros((b,), np.int64)
    else:
        fair = -(-b // pipes)
        cap = -(-((fair * 5) // 4) // chunk) * chunk
        pipe = flow_hash(p).astype(np.int64) % pipes
    traces, arrivals, overflow = [], [], 0
    for q in range(pipes):
        rows = np.flatnonzero(pipe == q)
        arrivals.append(int(rows.size))
        overflow += max(int(rows.size) - cap, 0)
        rows = rows[:cap]
        t = dead(cap, pmax)
        for f in FIELDS:
            t[f][:rows.size] = p[f][rows]
        traces.append({f: v.reshape((cap // chunk, chunk) + v.shape[1:])
                       for f, v in t.items()})
    return traces, dict(per_pipe_arrivals=arrivals, overflow=overflow,
                        pipe_capacity=cap)


def splitmix32(x):
    """Counter-based 32-bit mix (uint32 in, uint32 out)."""
    z = np.asarray(x, np.uint64) + np.uint64(0x9E3779B9)
    z &= np.uint64(0xFFFFFFFF)
    z = ((z ^ (z >> np.uint64(16))) * np.uint64(0x85EBCA6B)) \
        & np.uint64(0xFFFFFFFF)
    z = ((z ^ (z >> np.uint64(13))) * np.uint64(0xC2B2AE35)) \
        & np.uint64(0xFFFFFFFF)
    return (z ^ (z >> np.uint64(16))).astype(np.uint32)


def pool_identity(flow, pool_seed: int):
    """The streaming source's (src_ip, src_port) of a flow index."""
    h = splitmix32(np.asarray(flow, np.uint32) ^
                   splitmix32(np.uint32(pool_seed)))
    h2 = splitmix32(h)
    ip = (h.astype(np.int32) & np.int32(0x7FFFFFFF)) | np.int32(1)
    port = np.int32(1024) + (h2.astype(np.int32) & np.int32(0x7FFF))
    return ip, port


class StreamTraffic:
    """Chunk ``t`` of the streaming source: a function of (seed, t) alone.

    ``segment(start, count)`` returns numpy (count, chunk, ...) arrays.
    The random draws and the load curve run in one jitted, vmapped
    program, as the source under test draws them, so that float rounding
    of the load curve matches too.
    """

    def __init__(self, seed: int, mix: dict, chunk: int, pmax: int,
                 n_flows: int, pool_seed: int, load: dict | None):
        self.chunk, self.n_flows, self.pool_seed = chunk, n_flows, pool_seed
        self._seed, self._mix, self._pmax, self._load = seed, mix, pmax, load
        self._jit = jax.jit(self._segment, static_argnames="count")

    def _one_step(self, t):
        key = jax.random.fold_in(jax.random.key(self._seed), t)
        p = _mix_batch(key, self.chunk, self._mix, self._pmax)
        fidx = jnp.zeros((self.chunk,), jnp.int32)
        if self.n_flows:
            kf = jax.random.fold_in(key, 0xF10)
            fidx = jax.random.randint(kf, (self.chunk,), 0, self.n_flows,
                                      dtype=jnp.int32)
        offered = jnp.int32(self.chunk)
        if self._load is not None:
            ld = self._load
            ang = 2.0 * jnp.pi * (t.astype(jnp.float32) / ld["period"]) \
                + ld["phase"]
            level = ld["base"] + ld["amplitude"] * jnp.sin(ang)
            offered = jnp.round(level * self.chunk).astype(jnp.int32)
        return p, fidx, offered

    def _segment(self, start, count: int):
        ts = start + jnp.arange(count, dtype=jnp.int32)
        return jax.vmap(self._one_step)(ts)

    def segment(self, start: int, count: int) -> dict:
        p, fidx, offered = self._jit(jnp.int32(start), count)
        p = {f: np.asarray(v) for f, v in p.items()}
        if self.n_flows:
            p["src_ip"], p["src_port"] = pool_identity(np.asarray(fidx),
                                                       self.pool_seed)
        alive = np.arange(self.chunk)[None, :] < np.asarray(offered)[:, None]
        for f, v in p.items():
            keep = alive.reshape(alive.shape + (1,) * (v.ndim - 2))
            p[f] = np.where(keep, v, np.zeros((), v.dtype))
        return p
