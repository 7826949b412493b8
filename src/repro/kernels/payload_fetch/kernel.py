"""Pallas TPU kernel: payload gather + slot clear (paper Alg. 2 stage 3..N).

Merge's data plane: for each returning packet whose tag validated, read the
parked payload row at ``idx`` and zero it ("hdr.pload_block[idx] =
pload_tbl[meta.tbl_idx]; pload_tbl[meta.tbl_idx] = 0", Alg. 2 lines 21-23).
Per packet this is exactly two stateful accesses to the same row — read then
clear — honouring the Tofino one-access-per-stage budget by splitting across
two logical stages; in the TPU kernel both touch the same resident VMEM block
so the clear is free of extra HBM traffic.

Unmatched packets (premature eviction / ENB=0) produce zero rows and leave
the table untouched (predicated, branch-free — P4-style).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 8


def _fetch_kernel(idx_ref, mask_ref, table_in_ref, out_ref, table_ref, *,
                  bt: int):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _():
        table_ref[...] = table_in_ref[...]

    for i in range(bt):
        b = t * bt + i
        row = idx_ref[b]
        live = mask_ref[b] != 0
        # gather (predicated to zero for unmatched packets)
        val = table_ref[pl.ds(row, 1), :]
        out_ref[pl.ds(i, 1), :] = jnp.where(live, val, 0)

        # clear the slot
        @pl.when(live)
        def _():
            table_ref[pl.ds(row, 1), :] = jnp.zeros_like(val)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def payload_fetch_kernel(table, idx, mask, *, bt: int = DEFAULT_BT,
                         interpret: bool):
    """table: (M, W) int32; idx/mask: (B,).  Returns (gathered, new_table)."""
    m, w = table.shape
    b = idx.shape[0]
    assert b % bt == 0, (b, bt)
    grid = (b // bt,)
    return pl.pallas_call(
        functools.partial(_fetch_kernel, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # idx, mask
            grid=grid,
            in_specs=[
                pl.BlockSpec((m, w), lambda t, *_: (0, 0)),  # table (resident)
            ],
            out_specs=[
                pl.BlockSpec((bt, w), lambda t, *_: (t, 0)),  # gathered tile
                pl.BlockSpec((m, w), lambda t, *_: (0, 0)),   # table out
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((b, w), table.dtype),
            jax.ShapeDtypeStruct((m, w), table.dtype),
        ],
        input_output_aliases={2: 1},  # table -> table out
        interpret=interpret,
    )(idx, mask.astype(idx.dtype), table)
