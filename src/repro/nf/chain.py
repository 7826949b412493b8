"""NF chain composition (paper §1: "NFs are often connected together in an NF
chain, such as FW-NAT") and the Explicit-Drop integration point (§6.2.4).

A chain is an ordered list of NFs; each NF is a pure function
``(state, pkts) -> (state, pkts, drop_mask, cycles)`` touching only headers.
``run`` threads the states, ORs the drop masks and sums the per-packet cycle
costs (used by the analytic performance model, switchsim.perfmodel).

``to_explicit_drops`` models the paper's 50-line OpenNetVM change: packets the
chain dropped, whose payload is parked (ENB=1), are turned into truncated
OP=drop notifications sent back to the switch so Merge can free the slot
immediately instead of waiting for expiry-based eviction.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.packet import OP_DROP, PacketBatch


@dataclasses.dataclass(frozen=True)
class Chain:
    nfs: tuple  # sequence of NF dataclasses (Firewall, Nat, MaglevLB, MacSwap)

    def init_state(self) -> tuple:
        return tuple(nf.init_state() for nf in self.nfs)

    def run(self, states: tuple, pkts: PacketBatch, backend=None, ctx=None):
        """Returns (new_states, pkts_out, dropped_by_chain, total_cycles).

        ``backend`` (``repro.backend.BackendConfig`` / name / None) selects
        each NF's hot-path primitive implementation and is threaded to every
        NF uniformly.  ``ctx`` is the per-step environment dict from the
        fault-injection layer (DESIGN.md §10) — currently ``{"lb_up": bool
        scalar}`` — threaded to every NF the same way; None means healthy."""
        dropped = jnp.zeros_like(pkts.alive)
        total_cycles = 0.0
        new_states = []
        for nf, st in zip(self.nfs, states):
            # each NF's ops carry ``nf.<class>`` in their op names, so a
            # device trace tells the NFs apart (DESIGN.md §14)
            with jax.named_scope(f"nf.{type(nf).__name__}"):
                st, pkts, drop, cycles = nf(st, pkts, backend=backend,
                                            ctx=ctx)
            dropped = dropped | drop
            total_cycles += cycles
            new_states.append(st)
        return tuple(new_states), pkts, dropped, total_cycles

    def state_counters(self, states: tuple) -> dict:
        """Aggregate the NF-private counters carried in chain state (e.g.
        NAT's ``nat_stale_hits``), as a flat name->scalar dict.  NFs opt in
        by defining ``state_counters(state)``; names must be unique across
        the chain (each NF prefixes its own)."""
        out: dict = {}
        for nf, st in zip(self.nfs, states):
            fn = getattr(nf, "state_counters", None)
            if fn is None:
                continue
            for name, val in fn(st).items():
                if name in out:
                    raise ValueError(f"duplicate NF counter {name!r}")
                out[name] = val
        return out

    def cycle_costs(self, backend=None) -> tuple[float, ...]:
        """Per-NF CPU cycle costs, in chain order, for the analytic model
        (perfmodel wants the slowest single NF — OpenNetVM pins each NF to
        its own core, §6.1).  Probed by running each NF on one dead packet
        through the SAME backend dispatch the simulation uses — a
        Pallas-backed NF is probed on the Pallas path, so the analytic
        model can never silently mix backends; every NF reports its cycle
        cost as a per-call Python float."""
        from repro.core.packet import dead_batch
        probe = dead_batch(1, 16)
        costs = []
        for nf in self.nfs:
            _, _, _, cycles = nf(nf.init_state(), probe, backend=backend)
            costs.append(float(cycles))
        return tuple(costs)


def to_explicit_drops(pkts: PacketBatch, dropped) -> PacketBatch:
    """Convert chain-dropped, parked packets into OP=drop notifications.

    Mirrors the paper §6.2.4: "The NF framework marks an incoming packet as
    dropped by changing the opcode, truncating the packet payload, and sending
    the resulting packet back to the switch."
    """
    notify = dropped & pkts.pp_valid & (pkts.pp_enb == 1)
    return pkts.replace(
        alive=pkts.alive | notify,           # resurrect as a notification
        payload_len=jnp.where(notify, 0, pkts.payload_len),
        payload=jnp.where(notify[:, None], 0, pkts.payload),
        pp_op=jnp.where(notify, OP_DROP, pkts.pp_op),
    )
