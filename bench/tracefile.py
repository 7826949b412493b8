"""Reduction of a JAX profiler trace to the intervals the metrics read.

``jax.profiler`` writes an ``.xplane.pb`` file: planes (one per device and
one for the host), their lines (threads, or op streams on a device) and
events with a start and a duration in nanoseconds on one clock.  This
module keeps only

  * each device's busy time, as the union of the intervals in which an
    XLA operation ran on it (the device plane's ``XLA Ops`` line);
  * the total device time of every operation name, with the text by
    which a kernel is found (its name and its string statistics), the
    interval of every launch of a Pallas kernel, and the names each
    device ran;
  * the benchmark's own spans (host events named ``bench.*``) and the
    other host events of the thread that made them, which say what the
    host was doing while the device sat idle.

The interval arithmetic is separate from the file reading so that it can
be checked on made-up intervals.
"""
from __future__ import annotations

import dataclasses
import glob
import os

SPAN_PREFIX = "bench."
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
DROPPED = "Trace Buffers Dropped"   # the profiler's mark where it gave up
# how a Pallas (Mosaic) kernel shows in an op's HLO text; the op is named
# after the jitted function around the kernel, or ``closed_call``
PALLAS = 'custom_call_target="tpu_custom_call"'
# the Pallas kernels that keep their own name in the trace (ACL match, CRC,
# Maglev select); the payload store and fetch kernels are the others
NAMED_KERNELS = ("acl_match_kernel", "crc16_kernel", "maglev_kernel")


@dataclasses.dataclass
class Trace:
    busy: dict            # device plane -> merged busy intervals (ns)
    op_ns: dict           # op name -> device ns summed over all devices
    op_count: dict        # op name -> number of executions
    op_text: dict         # op name -> its name and string stats
    spans: list           # (name, start, end) of bench spans
    host: list            # (name, start, end) other events of that thread
    dropped: int | None = None   # device events lost from here on
    kernels: dict = dataclasses.field(default_factory=dict)
    # Pallas op name -> (start, end) of each of its launches
    device_ops: dict = dataclasses.field(default_factory=dict)
    # device plane -> the set of op names it ran

    def devices(self) -> list[str]:
        return sorted(d for d, iv in self.busy.items() if iv)

    def calls(self) -> list[tuple[int, int]]:
        return [(s, e) for n, s, e in self.spans if n == "bench.call"]


def union(intervals) -> list[tuple[int, int]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged, lo: int, hi: int) -> int:
    """Length of ``[lo, hi)`` covered by disjoint sorted intervals."""
    return sum(max(0, min(e, hi) - max(s, lo)) for s, e in merged
               if e > lo and s < hi)


def gaps(merged, lo: int, hi: int) -> list[tuple[int, int]]:
    """The idle intervals of ``[lo, hi)`` between disjoint busy ones."""
    out, cur = [], lo
    for s, e in merged:
        if e <= lo or s >= hi:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        out.append((cur, hi))
    return out


def label(gap, spans, host) -> str:
    """What the host was doing in a gap: the innermost bench span that
    holds its middle, and the host event that overlaps it most."""
    s, e = gap
    mid = (s + e) // 2
    inside = [(se - ss, n) for n, ss, se in spans if ss <= mid < se]
    where = min(inside)[1] if inside else "outside calls"
    best, what = 0, ""
    for n, hs, he in host:
        ov = min(he, e) - max(hs, s)
        if ov > best:
            best, what = ov, n
    return f"{where} / {what}" if what else where


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _text(event) -> str:
    parts = [event.name]
    for _, v in event.stats:
        if isinstance(v, str):
            parts.append(v)
    return " ".join(parts)


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` file into a ``Trace``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy, op_ns, op_count, op_text, kernels = {}, {}, {}, {}, {}
    device_ops: dict = {}
    spans, host_lines, dropped = [], [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            intervals, ran = [], device_ops.setdefault(plane.name, set())
            for line in plane.lines:
                if line.name != OPS_LINE:
                    dropped += [int(ev.start_ns) for ev in line.events
                                if ev.name == DROPPED]
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    d = int(ev.duration_ns)
                    intervals.append((s, s + d))
                    name = ev.name
                    if name not in op_ns:
                        op_ns[name] = op_count[name] = 0
                        op_text[name] = _text(ev)
                    op_ns[name] += d
                    op_count[name] += 1
                    ran.add(name)
                    if PALLAS in op_text[name]:
                        kernels.setdefault(name, []).append((s, s + d))
            busy[plane.name] = union(intervals)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.name, int(ev.start_ns),
                           int(ev.start_ns + ev.duration_ns))
                          for ev in line.events if ev.duration_ns > 0]
                mine = [ev for ev in events if ev[0].startswith(SPAN_PREFIX)]
                if mine:
                    spans.extend(mine)
                    host_lines.extend(ev for ev in events
                                      if not ev[0].startswith(SPAN_PREFIX))
    return Trace(busy=busy, op_ns=op_ns, op_count=op_count, op_text=op_text,
                 spans=sorted(spans, key=lambda x: x[1]), host=host_lines,
                 dropped=min(dropped) if dropped else None, kernels=kernels,
                 device_ops=device_ops)


def window(trace: Trace) -> tuple[int, int]:
    """The traced window: the ``bench.traced`` span (else the first
    call's start to the last call's end), cut where the profiler began
    to drop device events."""
    marked = [(s, e) for n, s, e in trace.spans if n == "bench.traced"]
    spans = marked or trace.calls()
    if not spans:
        raise ValueError("the trace holds no bench.traced or bench.call span")
    lo, hi = spans[0][0], spans[-1][1]
    if trace.dropped is not None:
        hi = min(hi, trace.dropped)
    return lo, hi


def short(op: str) -> str:
    """An HLO op's name without its text: ``%while.697 = (...) while(...)``
    reads ``%while.697``."""
    return op.split(" = ", 1)[0]


def busy_ns(trace: Trace, lo: int, hi: int) -> float:
    """Device busy time in ``[lo, hi)``, averaged over the devices used."""
    devs = trace.devices()
    if not devs:
        return 0.0
    return sum(overlap(trace.busy[d], lo, hi) for d in devs) / len(devs)


def launches(trace: Trace, lo: int, hi: int, besides=()) -> list:
    """(start, end) of the Pallas kernel launches that start in
    ``[lo, hi)``, leaving out kernels whose op text names one of
    ``besides``."""
    return [(s, e) for op, evs in trace.kernels.items()
            if not any(n in trace.op_text[op] for n in besides)
            for s, e in evs if lo <= s < hi]


def kernel_ns(trace: Trace, lo: int, hi: int, besides=()) -> int:
    """Device time of the Pallas kernels in ``[lo, hi)``, leaving out
    those whose op text names one of ``besides``."""
    return sum(min(e, hi) - s for s, e in launches(trace, lo, hi, besides))


def payload_launches(trace: Trace) -> int:
    """Launches of the payload store and fetch kernels in the window."""
    return len(launches(trace, *window(trace), NAMED_KERNELS))


def named_on_every_device(trace: Trace) -> bool:
    """Whether every device shows its payload kernels, where any device
    does: the devices of a sharded program run the same ones.  A device
    whose ops come without their HLO text (on four chips, the first
    chip's are at times named ``region.N``, but for the kernels that
    keep their own name) hides its payload kernels and its stages."""
    payload = {op for op in trace.kernels
               if not any(n in trace.op_text[op] for n in NAMED_KERNELS)}
    found = [bool(trace.device_ops.get(d, set()) & payload)
             for d in trace.devices()]
    return all(found) or not any(found)


def complete(trace: Trace, steps: int) -> bool:
    """Whether the window holds every kernel launch it ran.

    The payload kernels run a fixed number of times in every step of every
    pipe, so their launches in a window of ``steps`` steps per pipe are a
    whole multiple of ``steps``.  The profiler can lose device events
    without a drop mark; a window that lost some of them almost never
    keeps that multiple, and its kernel times are then not read.
    """
    n = payload_launches(trace)
    return steps > 0 and n > 0 and n % steps == 0


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device operations that took most time, and the longest idle
    gaps of the window labelled by what the host was doing."""
    lo, hi = window(trace)
    ops = sorted(trace.op_ns.items(), key=lambda kv: -kv[1])[:top]
    devs = trace.devices()
    idle = gaps(trace.busy[devs[0]], lo, hi) if devs else [(lo, hi)]
    idle = sorted(idle, key=lambda g: g[0] - g[1])[:top]
    return dict(
        device_ops=[[short(name), ns / 1e9] for name, ns in ops],
        idle_gaps=[[label(g, trace.spans, trace.host), (g[1] - g[0]) / 1e9]
                   for g in idle])
