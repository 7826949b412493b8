"""PayloadPark lookup table: Split / Merge / Evict / Explicit-Drop / Recirculate.

Faithful implementation of the paper's Algorithms 1 and 2 on a JAX state
machine.  P4 guarantees *atomic, per-packet sequential* register semantics
("Thanks to the atomic nature of action execution in P4, subsequent packets in
the match-action pipeline are guaranteed to get different indexes", §5); we
reproduce that with a ``lax.scan`` over packets in arrival (FIFO) order for
the control plane (tagger + metadata table), while the bulk payload movement
(the paper's stage 3..N striping across MAT-local register arrays, Fig. 4)
and the per-packet tag CRCs route through the dataplane-backend registry
(``repro.backend``, DESIGN.md §9): a frozen ``BackendConfig`` selects the
jnp reference or the Pallas TPU kernels per primitive.

Design mapping (see DESIGN.md §2):
  P4 MAT columns holding payload blocks  ->  lane-striped rows of ``ptable``
  one stateful register access per MAT   ->  one dynamic-slice store per row
  per-port pipes                         ->  one ParkState per ingress shard
  recirculation through a second pipe    ->  ``recirculation=True`` widens the
                                             row from 160 B to 352 B (§6.2.5);
                                             one traversal still parks at most
                                             ``pass_bytes`` (160 B), and
                                             ``recirc_fn`` is the second pass
                                             that fills the upper lanes (and
                                             retries occupied-slot skips).
                                             Lane scheduling/budget live in
                                             ``switchsim.engine`` (DESIGN.md §6).

Deviations from the paper, recorded per DESIGN.md:
  * the generation clock skips 0 so that ``meta_clk == 0`` unambiguously means
    "free"; the paper's Alg. 2 compares clocks only, which is identical given
    tags never carry clk=0.
  * parked length is ``min(payload_len, park_bytes)`` recorded in a per-slot
    ``meta_len`` word.  The baseline configuration (park_bytes=160, eligibility
    payload>=160) makes this exactly the paper's fixed 160-byte parking; the
    generalization implements the paper's §7 "decoupling boundary" discussion
    and is exercised by the recirculation mode.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.backend import coerce_backend, dispatch
from repro.core import counters as C
from repro.core.header import crc16_tag, tag_valid
from repro.core.packet import OP_DROP, PacketBatch

BLOCK_BYTES = 16  # single MAT-cell width (paper Fig. 4: payload blocks P0..PL)
PARK_BYTES_BASE = 160  # paper §1: "store 160 bytes from each packet's payload"
PARK_BYTES_RECIRC = 352  # paper §6.2.5: recirculation raises 160 -> 352


@dataclasses.dataclass(frozen=True)
class ParkConfig:
    capacity: int = 4096          # M, lookup table entries
    max_exp: int = 1              # Expiry threshold (paper EXP; §6.2.4 sweeps 1/2/10)
    max_clk: int = 1 << 16        # clock rollover (2-byte register, §5)
    min_park_len: int = PARK_BYTES_BASE  # eligibility threshold (§5, §6.3.3)
    recirculation: bool = False   # §6.2.5: second pass through the pipeline
    pmax: int = 2048              # payload buffer capacity of PacketBatch
    recirc_frac: float = 0.25     # recirculation-port share of pipe capacity

    def __post_init__(self):
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {self.capacity}")
        if self.pmax < 1:
            raise ValueError(f"pmax must be >= 1, got {self.pmax}")
        if self.max_exp < 1:
            raise ValueError(f"max_exp must be >= 1, got {self.max_exp}")
        if self.max_clk < 2:
            raise ValueError(f"max_clk must be >= 2, got {self.max_clk}")
        if self.min_park_len < 1:
            raise ValueError(
                f"min_park_len must be >= 1, got {self.min_park_len}")
        if not 0.0 <= self.recirc_frac <= 1.0:
            raise ValueError(
                f"recirc_frac must be in [0, 1], got {self.recirc_frac}")

    @property
    def park_bytes(self) -> int:
        """Full lookup-table row width (accumulated across passes)."""
        return PARK_BYTES_RECIRC if self.recirculation else PARK_BYTES_BASE

    @property
    def pass_bytes(self) -> int:
        """Bytes one pipeline traversal can park (the stage budget of Fig. 4).

        The recirculation pass (``recirc_fn``) fills the remaining
        ``park_bytes - pass_bytes`` lanes; with recirculation off the two
        widths coincide and Split parks the whole row in one pass.
        """
        return min(PARK_BYTES_BASE, self.park_bytes)

    @property
    def banks(self) -> int:
        return self.park_bytes // BLOCK_BYTES


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ParkState:
    """Registers + tables of one PayloadPark pipe (paper Fig. 4)."""

    tbl_idx: jax.Array   # () int32 — TI register
    clk: jax.Array       # () int32 — CLK register
    meta_exp: jax.Array  # (M,) int32 — Expiry threshold per slot
    meta_clk: jax.Array  # (M,) int32 — generation per slot (0 = free)
    meta_len: jax.Array  # (M,) int32 — parked byte count per slot
    ptable: jax.Array    # (M, park_bytes) uint8 — lane-striped payload banks
    counters: jax.Array  # (C.NUM,) int64


def init_state(cfg: ParkConfig) -> ParkState:
    m = cfg.capacity
    return ParkState(
        tbl_idx=jnp.zeros((), jnp.int32),
        clk=jnp.zeros((), jnp.int32),
        meta_exp=jnp.zeros((m,), jnp.int32),
        meta_clk=jnp.zeros((m,), jnp.int32),
        meta_len=jnp.zeros((m,), jnp.int32),
        ptable=jnp.zeros((m, cfg.park_bytes), jnp.uint8),
        counters=C.zeros(),
    )


def occupancy(state: ParkState) -> jax.Array:
    """Number of live (parked) slots."""
    return jnp.sum(state.meta_exp > 0)


# --------------------------------------------------------------------------
# Per-row payload shift
# --------------------------------------------------------------------------

def _shift_rows(x: jax.Array, shift: jax.Array, bound: int,
                left: bool) -> jax.Array:
    """Shift each row of ``x`` by its own ``shift`` along the last axis.

    ``x`` is ``(rows, width)`` and ``shift`` an int32 ``(rows,)`` in
    ``[0, bound]``; vacated bytes read 0, and a shift at or beyond the
    width gives an all-zero row.  ``bound.bit_length()`` stages: stage k
    moves the rows whose bit k is set by ``2**k``, a slice of the
    zero-padded rows and a select, with no gather (a per-element
    ``take_along_axis`` moves the same bytes at about 90 MB/s on a TPU
    v5e, DESIGN.md §2).  The stages run as a loop, in int32: unrolled, the
    stages' own code made each engine program on the chip over 1 MB
    larger.
    """
    width = x.shape[1]
    if bound >= width:
        shift, bound = jnp.minimum(shift, width), width
    stages = bound.bit_length()
    pad = 1 << (stages - 1)  # the last stage's shift

    def stage(k, y):
        z = jnp.zeros((y.shape[0], pad), y.dtype)
        if left:
            moved = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([y, z], axis=1), 1 << k, width, axis=1)
        else:
            moved = jax.lax.dynamic_slice_in_dim(
                jnp.concatenate([z, y], axis=1), pad - (1 << k), width, axis=1)
        return jnp.where(((shift >> k) & 1)[:, None] == 1, moved, y)

    return jax.lax.fori_loop(0, stages, stage,
                             x.astype(jnp.int32)).astype(x.dtype)


def _row_prefix(x: jax.Array, width: int) -> jax.Array:
    """The first ``width`` bytes of each row of ``x``, zero-padded where
    ``x`` is narrower (pmax < park_bytes is legal; a parked row is then
    partly unreachable)."""
    head = x[:, :width]
    return jnp.pad(head, ((0, 0), (0, width - head.shape[1])))


# --------------------------------------------------------------------------
# Split (paper Algorithm 1)
# --------------------------------------------------------------------------

def _split_control(cfg: ParkConfig, state: ParkState, pkts: PacketBatch):
    """Sequential tagger + metadata-table pass.  Returns per-packet decisions."""
    m = cfg.capacity

    def step(carry, x):
        ti, clk, meta_exp, meta_clk, meta_len = carry
        alive, plen = x
        eligible = alive & (plen >= cfg.min_park_len)

        # -- stage 1: packet tagger (Alg. 1 lines 4-7) ----------------------
        ti_n = jnp.where(eligible, (ti + 1) % m, ti)
        clk_n = jnp.where(eligible, clk + 1, clk)
        # generation clock skips 0 (see module docstring)
        clk_n = jnp.where(clk_n >= cfg.max_clk, 1, clk_n)

        # -- stage 2: metadata probe (Alg. 1 lines 10-25) -------------------
        exp_pre = meta_exp[ti_n]
        exp_dec = jnp.where(exp_pre >= 1, exp_pre - 1, exp_pre)  # lines 11-13
        evicted = eligible & (exp_pre >= 1) & (exp_dec == 0)
        available = exp_dec == 0                                  # line 14
        claim = eligible & available

        new_exp = jnp.where(claim, cfg.max_exp, exp_dec)
        meta_exp = jnp.where(eligible, meta_exp.at[ti_n].set(new_exp), meta_exp)
        meta_clk = jnp.where(
            claim, meta_clk.at[ti_n].set(clk_n),
            jnp.where(evicted, meta_clk.at[ti_n].set(0), meta_clk),
        )
        park_len = jnp.minimum(plen, cfg.pass_bytes)
        meta_len = jnp.where(claim, meta_len.at[ti_n].set(park_len), meta_len)

        out = dict(
            enb=claim, ti=ti_n, clk=clk_n, evicted=evicted,
            skip_occupied=eligible & ~available,
            skip_small=alive & (plen < cfg.min_park_len),
            park_len=jnp.where(claim, park_len, 0),
        )
        return (ti_n, clk_n, meta_exp, meta_clk, meta_len), out

    carry0 = (state.tbl_idx, state.clk, state.meta_exp, state.meta_clk,
              state.meta_len)
    (ti, clk, meta_exp, meta_clk, meta_len), outs = jax.lax.scan(
        step, carry0, (pkts.alive, pkts.payload_len)
    )
    return (ti, clk, meta_exp, meta_clk, meta_len), outs


def split_fn(cfg: ParkConfig, state: ParkState, pkts: PacketBatch,
             backend=None) -> tuple[ParkState, PacketBatch]:
    """Split operation: park payload prefixes, emit header-only packets.

    Returns (new_state, packets-as-sent-to-the-NF-server).  Every alive packet
    leaves with a PayloadPark header (ENB=1 if parked, else 0 — §6.1).

    ``backend`` selects the payload_store / crc16_tag implementations
    (``repro.backend``).

    This is the un-jitted body, composable inside ``lax.scan`` (the
    multi-pipe engine, DESIGN.md §3); ``split`` is the jitted entry point.
    """
    backend = coerce_backend(backend)
    (ti, clk, meta_exp, meta_clk, meta_len), d = _split_control(cfg, state, pkts)

    # -- stage 3..N: stripe payload blocks into the payload table -----------
    # Claiming zeroes the full row (incl. lanes above pass_bytes), so a later
    # recirculation pass appends into zeros.
    park = _row_prefix(pkts.payload, cfg.park_bytes)
    lane = jnp.arange(cfg.park_bytes)[None, :]
    park = jnp.where(lane < d["park_len"][:, None], park, 0)
    ptable = dispatch("payload_store", backend)(
        state.ptable, park, d["ti"], d["enb"])

    counters = state.counters
    counters = C.bump(counters, "splits", jnp.sum(d["enb"]))
    counters = C.bump(counters, "evictions", jnp.sum(d["evicted"]))
    counters = C.bump(counters, "skip_occupied", jnp.sum(d["skip_occupied"]))
    counters = C.bump(counters, "skip_small_payload", jnp.sum(d["skip_small"]))

    new_state = ParkState(ti, clk, meta_exp, meta_clk, meta_len, ptable, counters)

    # -- packet transformation: drop the parked prefix, add the PP header ---
    shift = d["park_len"]
    remainder = _shift_rows(pkts.payload, shift, cfg.pass_bytes, left=True)
    new_len = pkts.payload_len - shift
    keep = jnp.arange(cfg.pmax)[None, :] < new_len[:, None]
    remainder = jnp.where(keep, remainder, 0)

    enb32 = d["enb"].astype(jnp.int32)
    out = pkts.replace(
        payload=jnp.where(pkts.alive[:, None], remainder, pkts.payload),
        payload_len=jnp.where(pkts.alive, new_len, pkts.payload_len),
        pp_valid=pkts.alive,
        pp_enb=jnp.where(pkts.alive, enb32, 0),
        pp_op=jnp.zeros_like(pkts.pp_op),
        pp_ti=jnp.where(d["enb"], d["ti"], 0),
        pp_clk=jnp.where(d["enb"], d["clk"], 0),
        pp_crc=jnp.where(d["enb"],
                         crc16_tag(d["ti"], d["clk"], backend=backend), 0),
    )
    return new_state, out


split = partial(jax.jit, static_argnames=("cfg", "backend"))(split_fn)


# --------------------------------------------------------------------------
# Recirculation pass (paper §6.2.5)
# --------------------------------------------------------------------------

def _select_rows(mask, a, b):
    """Per-row select between two identically-shaped PacketBatches."""
    return jax.tree.map(
        lambda x, y: jnp.where(
            mask.reshape(mask.shape + (1,) * (x.ndim - mask.ndim)), x, y),
        a, b)


def recirc_fn(cfg: ParkConfig, state: ParkState, pkts: PacketBatch,
              backend=None) -> tuple[ParkState, PacketBatch]:
    """One recirculation pass for packets re-injected through the
    recirculation port (paper §6.2.5).  Two cases, handled in order:

      * **continuation** (ENB=1 with payload remaining): append up to
        ``park_bytes - meta_len[TI]`` more payload bytes into the packet's
        existing row — the second traversal reaches the stages holding the
        upper lanes of the 352-byte row.  The tag (TI, CLK, CRC) is
        unchanged; the write is skipped if the slot was evicted in between
        (the stale tag then surfaces as a premature eviction at Merge,
        exactly as it would without recirculation).
      * **retry** (ENB=0 after an occupied-slot skip): a fresh Split
        attempt — the tagger hands out the next index, which may have been
        freed or expired since the first pass.  A retry that fails again
        counts another ``skip_occupied`` (counters are per attempt).

    Packets come out NF-bound; lane scheduling and the recirculation-port
    budget live in ``switchsim.engine`` (DESIGN.md §6).  The partial-row
    append stays on the plain-JAX path (the Pallas store kernel writes
    whole rows — a recorded deviation, DESIGN.md §9); retry Splits honour
    ``backend``.
    """
    backend = coerce_backend(backend)
    counters = C.bump(state.counters, "recirculations",
                      jnp.sum(pkts.alive & pkts.pp_valid))

    # -- continuation: append into the owned row ----------------------------
    ext = pkts.alive & pkts.pp_valid & (pkts.pp_enb == 1)
    ti = jnp.clip(pkts.pp_ti, 0, cfg.capacity - 1)
    own = ext & (state.meta_clk[ti] == pkts.pp_clk)
    cur = jnp.where(own, state.meta_len[ti], 0)
    extra = jnp.where(
        own,
        jnp.minimum(pkts.payload_len, jnp.maximum(cfg.park_bytes - cur, 0)),
        0)
    do_ext = own & (extra > 0)

    ins = _shift_rows(_row_prefix(pkts.payload, cfg.park_bytes), cur,
                      cfg.park_bytes, left=False)
    col = jnp.arange(cfg.park_bytes)[None, :]
    src = col - cur[:, None]
    region = (src >= 0) & (src < extra[:, None])
    new_row = jnp.where(region, ins, state.ptable[ti])
    rows = jnp.where(do_ext, ti, cfg.capacity)  # OOB rows dropped
    ptable = state.ptable.at[rows].set(new_row, mode="drop")
    meta_len = state.meta_len.at[rows].set(cur + extra, mode="drop")

    remainder = _shift_rows(pkts.payload, extra, cfg.park_bytes, left=True)
    new_len = pkts.payload_len - extra
    keep = jnp.arange(cfg.pmax)[None, :] < new_len[:, None]
    remainder = jnp.where(keep, remainder, 0)
    ext_out = pkts.replace(
        payload=jnp.where(do_ext[:, None], remainder, pkts.payload),
        payload_len=jnp.where(do_ext, new_len, pkts.payload_len),
    )
    mid = ParkState(state.tbl_idx, state.clk, state.meta_exp, state.meta_clk,
                    meta_len, ptable, counters)

    # -- retry: a second Split attempt for ENB=0 packets --------------------
    retry = pkts.alive & pkts.pp_valid & (pkts.pp_enb == 0)
    retry_in = ext_out.replace(alive=retry)
    new_state, retry_out = split_fn(cfg, mid, retry_in, backend=backend)
    # split_fn rewrites header fields of its whole batch; keep its result
    # only for the retry rows, the extension result for everything else.
    return new_state, _select_rows(retry, retry_out, ext_out)


recirc = partial(jax.jit, static_argnames=("cfg", "backend"))(recirc_fn)


# --------------------------------------------------------------------------
# Merge + Explicit Drop (paper Algorithm 2, §6.2.4)
# --------------------------------------------------------------------------

def _merge_control(cfg: ParkConfig, state: ParkState, pkts: PacketBatch,
                   backend=None):
    """Sequential metadata validation/free pass (Alg. 2 stages 1-2).

    The tag CRC check is pure per-packet math (independent of the table
    carry), so it runs batched through the backend dispatch BEFORE the
    sequential scan — on ``backend="pallas"`` the whole header validation
    is one kernel call instead of a per-packet bit loop.
    """
    crc_ok_all = tag_valid(pkts.pp_ti, pkts.pp_clk, pkts.pp_crc,
                           backend=backend)

    def step(carry, x):
        meta_exp, meta_clk, meta_len = carry
        alive, valid, enb, op, ti, clk, crc_ok = x
        is_pp = alive & valid & (enb == 1)
        checked = is_pp & crc_ok
        gen_ok = meta_clk[ti] == clk
        matched = checked & gen_ok                       # Alg. 2 line 11
        # free the slot (Alg. 2 line 13)
        meta_exp = jnp.where(matched, meta_exp.at[ti].set(0), meta_exp)
        meta_clk = jnp.where(matched, meta_clk.at[ti].set(0), meta_clk)
        plen = jnp.where(matched, meta_len[ti], 0)
        meta_len = jnp.where(matched, meta_len.at[ti].set(0), meta_len)
        out = dict(
            matched=matched,
            premature=checked & ~gen_ok,
            crc_fail=is_pp & ~crc_ok,
            disabled=alive & valid & (enb == 0),
            is_drop_op=matched & (op == OP_DROP),
            park_len=plen,
        )
        return (meta_exp, meta_clk, meta_len), out

    xs = (pkts.alive, pkts.pp_valid, pkts.pp_enb, pkts.pp_op,
          pkts.pp_ti, pkts.pp_clk, crc_ok_all)
    carry0 = (state.meta_exp, state.meta_clk, state.meta_len)
    (meta_exp, meta_clk, meta_len), outs = jax.lax.scan(step, carry0, xs)
    return (meta_exp, meta_clk, meta_len), outs


def merge_fn(cfg: ParkConfig, state: ParkState, pkts: PacketBatch,
             backend=None) -> tuple[ParkState, PacketBatch]:
    """Merge (and Explicit Drop) for packets returning from the NF server.

    Outcomes per packet:
      * ENB=0: PayloadPark header removed, packet forwarded (Alg. 2 stage 1).
      * ENB=1, OP=merge, tag valid: payload re-attached, slot freed.
      * ENB=1, OP=drop, tag valid: slot freed, packet consumed (§6.2.4).
      * CRC or generation mismatch: packet dropped, counted.

    ``backend`` selects the payload_fetch / crc16_tag implementations
    (``repro.backend``).

    Un-jitted body for ``lax.scan`` composition (DESIGN.md §3); ``merge`` is
    the jitted entry point.
    """
    backend = coerce_backend(backend)
    (meta_exp, meta_clk, meta_len), d = _merge_control(cfg, state, pkts,
                                                       backend=backend)

    # -- stage 3..N: gather payload blocks, then clear the rows --------------
    fetch = d["matched"] & ~d["is_drop_op"]
    parked, ptable = dispatch("payload_fetch", backend)(
        state.ptable, pkts.pp_ti, d["matched"])

    counters = state.counters
    counters = C.bump(counters, "merges", jnp.sum(fetch))
    counters = C.bump(counters, "explicit_drops", jnp.sum(d["is_drop_op"]))
    counters = C.bump(counters, "disabled_returns", jnp.sum(d["disabled"]))
    counters = C.bump(counters, "premature_evictions", jnp.sum(d["premature"]))
    counters = C.bump(counters, "crc_failures", jnp.sum(d["crc_fail"]))

    new_state = ParkState(state.tbl_idx, state.clk, meta_exp, meta_clk,
                          meta_len, ptable, counters)

    # -- packet transformation: payload := parked ++ carried remainder ------
    shift = jnp.where(fetch, d["park_len"], 0)
    col = jnp.arange(cfg.pmax)[None, :]
    carried = _shift_rows(pkts.payload, shift, cfg.park_bytes, left=False)
    # Clamp for pmax < park_bytes (parked length never exceeds the payload
    # that fit in pmax, so truncating the row loses nothing).
    parked_full = _row_prefix(parked, cfg.pmax)
    new_payload = jnp.where(col < shift[:, None], parked_full, carried)
    new_len = pkts.payload_len + shift
    keep = col < new_len[:, None]
    new_payload = jnp.where(keep, new_payload, 0)

    forwarded = d["disabled"] | fetch
    dropped = d["premature"] | d["crc_fail"] | d["is_drop_op"]
    out = pkts.replace(
        payload=jnp.where(forwarded[:, None], new_payload, pkts.payload),
        payload_len=jnp.where(forwarded, new_len, pkts.payload_len),
        alive=pkts.alive & ~dropped,
        pp_valid=pkts.pp_valid & ~forwarded & ~dropped,
        pp_enb=jnp.where(forwarded | dropped, 0, pkts.pp_enb),
        pp_op=jnp.where(forwarded | dropped, 0, pkts.pp_op),
        pp_ti=jnp.where(forwarded | dropped, 0, pkts.pp_ti),
        pp_clk=jnp.where(forwarded | dropped, 0, pkts.pp_clk),
        pp_crc=jnp.where(forwarded | dropped, 0, pkts.pp_crc),
    )
    return new_state, out


merge = partial(jax.jit, static_argnames=("cfg", "backend"))(merge_fn)


def stats(state: ParkState) -> dict[str, Any]:
    d = C.as_dict(state.counters)
    d["occupancy"] = int(occupancy(state))
    return d
