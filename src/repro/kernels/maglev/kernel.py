"""Pallas TPU kernel: Maglev L4-LB backend selection (paper §6.1).

Per-packet hot path of the load balancer: hash the 5-tuple, index the Maglev
lookup table, emit the backend VIP.  The (prime-sized) lookup table and the
backend IP list ride in SMEM (scalar prefetch) for the whole grid while
packet tiles stream through VMEM.  The double lookup (table -> backend id ->
backend ip) is the TPU analogue of the paper's two chained MAT lookups.

Mosaic lowers no data-dependent 1-D vector gather, so the lookup is a
compare-select sweep over the table instead: for each table slot ``t`` the
kernel reads ``bips[table[t]]`` as a scalar from SMEM and selects it into
every lane whose index equals ``t``.  That is ``T`` compare/select passes
over one (BT, 128) tile — a few hundred VPU ops per 1024 packets for the
251-slot table — and gives exactly ``bips[table[idx]]``.

The hash matches repro.backend.ref.maglev_hash5 bit-exactly (int32 wrap
semantics).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128


def _maglev_kernel(table_ref, bips_ref, sip_ref, dip_ref, sp_ref, dp_ref,
                   proto_ref, out_ref, *, table_size: int):
    h = sip_ref[...]
    for ref in (dip_ref, sp_ref, dp_ref, proto_ref):
        h = h * jnp.int32(1000003) ^ ref[...]
    h = h & jnp.int32(0x7FFFFFFF)
    idx = h % table_size                      # (BT, LANES)

    def body(t, acc):
        return jnp.where(idx == t, bips_ref[table_ref[t]], acc)

    out_ref[...] = jax.lax.fori_loop(0, table_size, body,
                                     jnp.zeros_like(idx))


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def maglev_kernel(sip, dip, sp, dp, proto, table, bips, *, bt: int = 8,
                  interpret: bool):
    """Packet fields: (N, LANES) int32; table: (T,) int32 backend ids;
    bips: (NB,) int32 backend VIPs -> (N, LANES) int32 VIP per packet."""
    n, lanes = sip.shape
    assert lanes == LANES and n % bt == 0
    t = table.shape[0]
    pkt_spec = pl.BlockSpec((bt, LANES), lambda i, *_: (i, 0))
    return pl.pallas_call(
        functools.partial(_maglev_kernel, table_size=t),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # Maglev table, backend IPs (SMEM)
            grid=(n // bt,),
            in_specs=[pkt_spec] * 5,
            out_specs=pkt_spec,
        ),
        out_shape=jax.ShapeDtypeStruct((n, LANES), jnp.int32),
        interpret=interpret,
    )(table, bips, sip, dip, sp, dp, proto)
