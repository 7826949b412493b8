"""Plain numpy reference of one PayloadPark pipe, written from the paper.

One switch pipe in front of one NF server, advanced one step (one chunk
of arriving packets) at a time, every register access in packet order as
P4 guarantees it (paper §5):

  1. recirculation pass for the packets that entered the recirculation
     lane at the previous step (§6.2.5): a packet that parked part of its
     payload appends more into its own row; a packet that found its slot
     occupied tries Split again;
  2. Split of the arriving chunk (Algorithm 1): the tagger hands the next
     table index to each packet with at least ``min_park_len`` payload
     bytes, a slot whose expiry count drops to 0 is (re)claimed, up to
     ``pass_bytes`` of payload are parked and the packet leaves with the
     7-byte PayloadPark header and a CRC-16 tag;
  3. packets that want another pass (payload left and row width left, or
     an occupied slot) enter the lane, up to its width, in arrival order;
  4. the NF server runs the chain (firewall ACL, NAT, Maglev LB) on the
     lane's packets followed by the chunk's;
  5. the server's output returns ``window`` steps later and is merged
     (Algorithm 2): header checked, generation compared, payload put back,
     slot freed.

Per-link telemetry and the monitoring counters are counted where the
switch would count them.  Nothing here imports the simulator.
"""
from __future__ import annotations

import math

import numpy as np

from bench.generator import FIELDS, HDR_BYTES, dead

PP_HDR_BYTES = 7
OP_DROP = 1
COUNTERS = ("splits", "merges", "explicit_drops", "disabled_returns",
            "evictions", "premature_evictions", "skip_small_payload",
            "skip_occupied", "crc_failures", "recirculations",
            "recirc_budget_drops", "fault_drops")
TELEMETRY = ("wire_pkts", "wire_bytes", "to_server_pkts", "to_server_bytes",
             "from_server_pkts", "from_server_bytes", "recirc_pkts",
             "recirc_bytes", "merged_pkts", "merged_bytes")


# --------------------------------------------------------------------------
# CRC-16/CCITT-FALSE over the 4 tag bytes (table index, generation)
# --------------------------------------------------------------------------

def _crc_table() -> np.ndarray:
    table = np.zeros(256, np.int64)
    for b in range(256):
        c = b << 8
        for _ in range(8):
            c = ((c << 1) ^ 0x1021) if c & 0x8000 else (c << 1)
        table[b] = c & 0xFFFF
    return table


_CRC = _crc_table()


def crc16_tag(ti, clk) -> np.ndarray:
    ti = np.asarray(ti, np.int64)
    clk = np.asarray(clk, np.int64)
    crc = np.full(ti.shape, 0xFFFF, np.int64)
    for byte in (ti & 0xFF, (ti >> 8) & 0xFF, clk & 0xFF, (clk >> 8) & 0xFF):
        crc = ((crc << 8) & 0xFFFF) ^ _CRC[((crc >> 8) ^ byte) & 0xFF]
    return crc.astype(np.int32)


# --------------------------------------------------------------------------
# Maglev lookup table (Eisenbud et al., NSDI'16) and the 5-tuple hash
# --------------------------------------------------------------------------

_M64 = (1 << 64) - 1


def _mix64(salt: int, b: int) -> int:
    x = (b * 0x9E3779B97F4A7C15 + salt * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _M64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _M64
    return x ^ (x >> 31)


def maglev_table(backends, size: int) -> np.ndarray:
    """Maglev population: backends take turns filling their next
    preferred free slot, preference = offset + j * skip (mod size)."""
    n = len(backends)
    offset = [_mix64(1, b) % size for b in backends]
    skip = [_mix64(2, b) % (size - 1) + 1 for b in backends]
    entry = [-1] * size
    nxt = [0] * n
    filled = 0
    while filled < size:
        for i in range(n):
            c = (offset[i] + nxt[i] * skip[i]) % size
            while entry[c] >= 0:
                nxt[i] += 1
                c = (offset[i] + nxt[i] * skip[i]) % size
            entry[c] = i
            nxt[i] += 1
            filled += 1
            if filled == size:
                break
    return np.asarray(entry, np.int64)


def _wrap32(x: int) -> int:
    x &= 0xFFFFFFFF
    return x - (1 << 32) if x & 0x80000000 else x


def maglev_hash5(p: dict, rows) -> list[int]:
    out = []
    for i in rows:
        h = int(p["src_ip"][i])
        for f in ("dst_ip", "src_port", "dst_port", "proto"):
            h = _wrap32(h * 1000003) ^ int(p[f][i])
        out.append(h & 0x7FFFFFFF)
    return out


def nat_hash(ip: int, port: int, capacity: int) -> int:
    h = ip ^ -1640531527
    h = _wrap32(h * -2048144789) ^ port
    h = h ^ (h >> 13)
    h = _wrap32(h * -1028477379)
    return (h & 0x7FFFFFFF) % capacity


# --------------------------------------------------------------------------
# Packet helpers
# --------------------------------------------------------------------------

def _copy(p: dict) -> dict:
    return {f: v.copy() for f, v in p.items()}


def _cat(a: dict, b: dict) -> dict:
    return {f: np.concatenate([a[f], b[f]]) for f in FIELDS}


def _wire_len(p: dict) -> np.ndarray:
    return HDR_BYTES + np.where(p["pp_valid"], PP_HDR_BYTES, 0) \
        + p["payload_len"].astype(np.int64)


def _tally(p: dict) -> tuple[int, int]:
    alive = p["alive"]
    return int(alive.sum()), int(_wire_len(p)[alive].sum())


def _drop_front(p: dict, i: int, n: int) -> None:
    """Remove the first ``n`` payload bytes of packet ``i``."""
    length = int(p["payload_len"][i])
    row = p["payload"][i]
    row[:length - n] = row[n:length].copy()
    row[length - n:] = 0
    p["payload_len"][i] = length - n


class Pipe:
    """One switch pipe and its NF server; ``step`` advances one chunk."""

    def __init__(self, config: dict, chunk_rows: int):
        park = config["park"]
        self.m = park["capacity"]
        self.max_exp = park["max_exp"]
        self.max_clk = park["max_clk"]
        self.min_park = park["min_park_len"]
        self.pass_bytes = park["pass_bytes"]
        self.row_bytes = park["row_bytes"]
        self.pmax = park["pmax"]
        self.lane_w = (math.floor(park["recirc_frac"] * chunk_rows + 1e-9)
                       if park["recirculation"] else 0)
        self.window = config["window"]
        self.chain = tuple(config["chain"])
        self.fw_rules = set(config.get("fw_rules_ips", ()))
        nat = config.get("nat")
        if nat:
            self.nat_cfg = dict(nat)
            cap = nat["capacity"]
            self.nat_ip_tab = [-1] * cap
            self.nat_port_tab = [-1] * cap
            self.nat_exp = [0] * cap
        lb = config.get("lb")
        if lb:
            self.lb_table = maglev_table(lb["backends"], lb["table_size"])
            self.lb_ips = np.asarray(lb["backends"], np.int64)
        self.ti = 0
        self.clk = 0
        self.meta_exp = np.zeros(self.m, np.int64)
        self.meta_clk = np.zeros(self.m, np.int64)
        self.meta_len = np.zeros(self.m, np.int64)
        self.ptable = np.zeros((self.m, self.row_bytes), np.uint8)
        self.counters = dict.fromkeys(COUNTERS, 0)
        self.nat_stale_hits = 0
        nf_rows = chunk_rows + self.lane_w
        self.ring = [dead(nf_rows, self.pmax) for _ in range(max(self.window, 1))]
        self.lane = dead(self.lane_w, self.pmax)
        self.t = 0

    # -- Split (Algorithm 1) ---------------------------------------------
    def split(self, p: dict) -> dict:
        p = _copy(p)
        b = p["alive"].shape[0]
        enb = np.zeros(b, bool)
        ti_out = np.zeros(b, np.int64)
        clk_out = np.zeros(b, np.int64)
        small = p["alive"] & (p["payload_len"] < self.min_park)
        self.counters["skip_small_payload"] += int(small.sum())
        for i in np.flatnonzero(p["alive"] & (p["payload_len"]
                                              >= self.min_park)):
            self.ti = (self.ti + 1) % self.m
            self.clk += 1
            if self.clk >= self.max_clk:
                self.clk = 1          # generation 0 marks a free slot
            ti = self.ti
            exp = int(self.meta_exp[ti])
            if exp >= 1:
                exp -= 1
                if exp == 0:
                    self.counters["evictions"] += 1
            if exp != 0:
                self.meta_exp[ti] = exp
                self.counters["skip_occupied"] += 1
                continue
            park = min(int(p["payload_len"][i]), self.pass_bytes)
            self.meta_exp[ti] = self.max_exp
            self.meta_clk[ti] = self.clk
            self.meta_len[ti] = park
            self.ptable[ti] = 0
            self.ptable[ti, :park] = p["payload"][i, :park]
            self.counters["splits"] += 1
            _drop_front(p, i, park)
            enb[i] = True
            ti_out[i], clk_out[i] = ti, self.clk
        alive = p["alive"]
        p["pp_valid"] = alive.copy()
        p["pp_enb"] = enb.astype(np.int32)
        p["pp_op"] = np.zeros(b, np.int32)
        p["pp_ti"] = ti_out.astype(np.int32)
        p["pp_clk"] = clk_out.astype(np.int32)
        p["pp_crc"] = np.where(enb, crc16_tag(ti_out, clk_out), 0).astype(
            np.int32)
        return p

    # -- recirculation pass (§6.2.5) ---------------------------------------
    def recirculate(self, lane: dict) -> dict:
        p = _copy(lane)
        live = p["alive"] & p["pp_valid"]
        self.counters["recirculations"] += int(live.sum())
        for i in np.flatnonzero(live & (p["pp_enb"] == 1)):
            ti = min(max(int(p["pp_ti"][i]), 0), self.m - 1)
            if self.meta_clk[ti] != p["pp_clk"][i]:
                continue                  # evicted meanwhile: no append
            cur = int(self.meta_len[ti])
            extra = min(int(p["payload_len"][i]), max(self.row_bytes - cur, 0))
            if extra <= 0:
                continue
            self.ptable[ti, cur:cur + extra] = p["payload"][i, :extra]
            self.meta_len[ti] = cur + extra
            _drop_front(p, i, extra)
        retry = live & (p["pp_enb"] == 0)
        if retry.any():
            again = self.split(dict(p, alive=retry))
            for f in FIELDS:
                keep = retry.reshape((-1,) + (1,) * (p[f].ndim - 1))
                p[f] = np.where(keep, again[f], p[f])
        return p

    def select_lane(self, out: dict):
        """Admit second-pass candidates into the lane, in arrival order."""
        alive, valid = out["alive"], out["pp_valid"]
        cont = alive & valid & (out["pp_enb"] == 1) & (out["payload_len"] > 0)
        retry = alive & valid & (out["pp_enb"] == 0) & \
            (out["payload_len"] >= self.min_park)
        cand = np.flatnonzero(cont | retry)
        admitted = cand[:self.lane_w]
        self.counters["recirc_budget_drops"] += int(cand.size - admitted.size)
        lane = dead(self.lane_w, self.pmax)
        for f in FIELDS:
            lane[f][:admitted.size] = out[f][admitted]
        out = dict(out, alive=alive.copy())
        out["alive"][admitted] = False
        return out, lane

    # -- the NF server -----------------------------------------------------
    def firewall(self, p: dict) -> None:
        blocked = np.isin(p["src_ip"], list(self.fw_rules)) if self.fw_rules \
            else np.zeros_like(p["alive"])
        p["alive"] = p["alive"] & ~blocked

    def nat(self, p: dict) -> None:
        """MazuNAT-style source NAT: a flow keeps the port of the slot it
        holds; idle slots expire; a flow whose slot aged out is dropped
        once and re-binds on its next packet."""
        cfg = self.nat_cfg
        cap, depth, max_exp = cfg["capacity"], cfg["probe_depth"], \
            cfg["max_exp"]
        key_ip, key_port, exp = self.nat_ip_tab, self.nat_port_tab, \
            self.nat_exp
        rows = np.flatnonzero(p["alive"])
        mapped = {}
        for i in rows:
            ip, port = int(p["src_ip"][i]), int(p["src_port"][i])
            h = nat_hash(ip, port, cap)
            slot = stale = free = -1
            for j in range(depth):
                idx = (h + j) % cap
                live = exp[idx] > 0
                match = key_ip[idx] == ip and key_port[idx] == port
                if slot < 0 and live and match:
                    slot = idx
                if stale < 0 and not live and match:
                    stale = idx
                if free < 0 and not live:
                    free = idx
            if slot >= 0:
                exp[slot] = max_exp
                mapped[i] = cfg["base_port"] + slot
            elif stale >= 0:
                key_ip[stale] = key_port[stale] = -1
                self.nat_stale_hits += 1
            elif free >= 0:
                key_ip[free], key_port[free] = ip, port
                exp[free] = max_exp
                mapped[i] = cfg["base_port"] + free
            else:
                for j in range(depth):
                    idx = (h + j) % cap
                    exp[idx] = max(exp[idx] - 1, 0)
        for i in rows:
            if i in mapped:
                p["src_ip"][i] = cfg["nat_ip"]
                p["src_port"][i] = mapped[i]
            else:
                p["alive"][i] = False

    def load_balance(self, p: dict) -> None:
        rows = np.flatnonzero(p["alive"])
        h = np.asarray(maglev_hash5(p, rows), np.int64)
        p["dst_ip"][rows] = self.lb_ips[self.lb_table[h % len(self.lb_table)]]

    def server(self, p: dict) -> dict:
        p = _copy(p)
        for nf in self.chain:
            {"fw": self.firewall, "nat": self.nat,
             "lb": self.load_balance}[nf](p)
        return p

    # -- Merge (Algorithm 2) -----------------------------------------------
    def merge(self, p: dict) -> dict:
        p = _copy(p)
        alive, valid = p["alive"], p["pp_valid"]
        crc_ok = crc16_tag(p["pp_ti"], p["pp_clk"]) == p["pp_crc"]
        disabled = alive & valid & (p["pp_enb"] == 0)
        self.counters["disabled_returns"] += int(disabled.sum())
        done = disabled.copy()          # header removed, packet forwarded
        dropped = np.zeros_like(alive)
        for i in np.flatnonzero(alive & valid & (p["pp_enb"] == 1)):
            if not crc_ok[i]:
                self.counters["crc_failures"] += 1
                dropped[i] = True
                continue
            ti, clk = int(p["pp_ti"][i]), int(p["pp_clk"][i])
            if self.meta_clk[ti] != clk:
                self.counters["premature_evictions"] += 1
                dropped[i] = True
                continue
            n = int(self.meta_len[ti])
            row = self.ptable[ti].copy()
            self.meta_exp[ti] = self.meta_clk[ti] = self.meta_len[ti] = 0
            self.ptable[ti] = 0
            if p["pp_op"][i] == OP_DROP:
                self.counters["explicit_drops"] += 1
                dropped[i] = True
                continue
            length = int(p["payload_len"][i])
            rest = p["payload"][i, :length].copy()
            p["payload"][i] = 0
            p["payload"][i, :n] = row[:n]
            p["payload"][i, n:n + length] = rest
            p["payload_len"][i] = n + length
            self.counters["merges"] += 1
            done[i] = True
        p["alive"] = alive & ~dropped
        p["pp_valid"] = valid & ~done & ~dropped
        for f in ("pp_enb", "pp_op", "pp_ti", "pp_clk", "pp_crc"):
            p[f] = np.where(done | dropped, 0, p[f]).astype(np.int32)
        return p

    # -- one step ----------------------------------------------------------
    def step(self, chunk: dict) -> tuple[dict, dict]:
        tel = {}
        tel["wire_pkts"], tel["wire_bytes"] = _tally(chunk)
        if self.lane_w:
            rout = self.recirculate(self.lane)
        out = self.split(chunk)
        if self.lane_w:
            out, self.lane = self.select_lane(out)
            tel["recirc_pkts"], tel["recirc_bytes"] = _tally(self.lane)
            to_server = _cat(rout, out)
        else:
            tel["recirc_pkts"] = tel["recirc_bytes"] = 0
            to_server = out
        tel["to_server_pkts"], tel["to_server_bytes"] = _tally(to_server)
        back = self.server(to_server)
        if self.window:
            slot = self.t % self.window
            back, self.ring[slot] = self.ring[slot], back
        tel["from_server_pkts"], tel["from_server_bytes"] = _tally(back)
        merged = self.merge(back)
        tel["merged_pkts"], tel["merged_bytes"] = _tally(merged)
        self.t += 1
        return merged, tel

    def drain_steps(self) -> int:
        return self.window + (1 if self.lane_w else 0)

    def occupancy(self) -> int:
        return int((self.meta_exp > 0).sum())


def run_pipe(config: dict, trace: dict) -> dict:
    """One pipe over a (steps, chunk, ...) trace, drained.

    Returns the merged packets of every step from ``window`` on (as the
    engine returns them), the occupancy after every step, the counters,
    the NAT's stale-mapping count and the per-link telemetry.
    """
    steps, chunk = trace["alive"].shape[:2]
    pipe = Pipe(config, chunk)
    pmax = trace["payload"].shape[2]
    merged, occ = [], []
    tel = dict.fromkeys(TELEMETRY, 0)
    for t in range(steps + pipe.drain_steps()):
        chunk_t = ({f: v[t] for f, v in trace.items()} if t < steps
                   else dead(chunk, pmax))
        m, step_tel = pipe.step(chunk_t)
        for k, v in step_tel.items():
            tel[k] += v
        merged.append(m)
        occ.append(pipe.occupancy())
    merged = merged[pipe.window:]
    return dict(
        merged={f: np.stack([m[f] for m in merged]) for f in FIELDS},
        occ=np.asarray(occ, np.int64), counters=dict(pipe.counters),
        nf_counters=({"nat_stale_hits": pipe.nat_stale_hits}
                     if "nat" in pipe.chain else {}),
        telemetry=tel, pipe=pipe)


# --------------------------------------------------------------------------
# The streamed run: one pipe over consecutive segments, with the sojourn
# reservoir and per-segment occupancy the streaming engine keeps
# --------------------------------------------------------------------------

SPLIT_MERGE_NS = 30_000   # paper §4: split -> merge dwell of ~30 us


def _splitmix32(x: int) -> int:
    z = (x + 0x9E3779B9) & 0xFFFFFFFF
    z = ((z ^ (z >> 16)) * 0x85EBCA6B) & 0xFFFFFFFF
    z = ((z ^ (z >> 13)) * 0xC2B2AE35) & 0xFFFFFFFF
    return z ^ (z >> 16)


class Reservoir:
    """Algorithm R over every merged packet's sojourn: sample ``m`` fills
    slot ``m`` while the reservoir fills, later lands in slot
    ``splitmix32(seed ^ m * phi) % (m + 1)`` when that is a slot."""

    def __init__(self, k: int, seed: int):
        self.vals = np.zeros(k, np.int64)
        self.k, self.seed, self.n = k, seed, 0

    def add(self, sample: int) -> None:
        m = self.n
        if m < self.k:
            j = m
        else:
            h = _splitmix32(self.seed ^ ((m * 0x9E3779B9) & 0xFFFFFFFF))
            j = h % (m + 1)
        if j < self.k:
            self.vals[j] = sample
        self.n += 1

    def quantiles_us(self) -> dict:
        out = dict(samples=self.n, reservoir=self.k)
        valid = np.sort(self.vals[:min(self.n, self.k)])
        if valid.size:
            for name, q in (("p50_us", 0.50), ("p99_us", 0.99),
                            ("p999_us", 0.999)):
                out[name] = float(np.quantile(valid, q,
                                              method="nearest")) / 1e3
        return out


def _occ_summary(start: int, occ: list[int]) -> dict:
    a = np.asarray(occ, np.int64)
    return dict(start=start, steps=int(a.size), min=int(a.min()),
                mean=float(a.mean()), max=int(a.max()), last=int(a[-1]))


def run_stream(config: dict, segment, steps: int, chunk: int,
               segment_len: int, reservoir: int, reservoir_seed: int) -> dict:
    """One pipe over ``steps`` chunks drawn ``segment_len`` at a time from
    ``segment(start, count)``, then drained.  Returns what a streamed run
    reports: counters, telemetry, the NAT count, peak occupancy, the
    occupancy of each segment, the sojourn quantiles and the final
    table."""
    pipe = Pipe(config, chunk)
    step_ns = max(1, round(SPLIT_MERGE_NS / max(pipe.window, 1)))
    res = Reservoir(reservoir, reservoir_seed)
    tel = dict.fromkeys(TELEMETRY, 0)
    lane_rows = np.arange(chunk + pipe.lane_w) < pipe.lane_w
    occ_segments, peak = [], 0
    spans = [(s, min(segment_len, steps - s))
             for s in range(0, steps, segment_len)]
    spans.append((steps, pipe.drain_steps()))
    for start, count in spans:
        seg = segment(start, count) if start < steps else None
        occ = []
        for t in range(count):
            chunk_t = ({f: v[t] for f, v in seg.items()} if seg is not None
                       else dead(chunk, pipe.pmax))
            merged, step_tel = pipe.step(chunk_t)
            for k, v in step_tel.items():
                tel[k] += v
            dwell = (pipe.window + lane_rows.astype(np.int64)) * step_ns
            sojourn = dwell + (_wire_len(merged) * 4) // 5
            for i in np.flatnonzero(merged["alive"]):
                res.add(int(sojourn[i]))
            occ.append(pipe.occupancy())
        occ_segments.append(_occ_summary(start, occ))
        peak = max(peak, max(occ))
    state = dict(tbl_idx=pipe.ti, clk=pipe.clk, meta_exp=pipe.meta_exp,
                 meta_clk=pipe.meta_clk, meta_len=pipe.meta_len,
                 ptable=pipe.ptable,
                 counters=np.asarray([pipe.counters[c] for c in COUNTERS]))
    return dict(counters=dict(pipe.counters), telemetry=tel,
                nf_counters=({"nat_stale_hits": pipe.nat_stale_hits}
                             if "nat" in pipe.chain else {}),
                peak_occupancy=peak, occ_segments=occ_segments,
                latency=res.quantiles_us(), state=state)
