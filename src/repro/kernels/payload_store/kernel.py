"""Pallas TPU kernel: striped payload scatter (paper Alg. 1 stage 3..N).

TPU adaptation of the paper's MAT-column striping (Fig. 4): a parked payload
row is a lane vector of ``W`` int32 words (the paper's P0..PL 16-byte blocks
become contiguous lane groups); the payload table lives in HBM/VMEM as a
(M, W) register file.  One grid step processes a tile of ``BT`` packets and
performs at most one predicated dynamic-slice store per packet — the same
"single stateful access per stage per packet" discipline the Tofino imposes
(§2), which is also what keeps the kernel a pure streaming scatter with no
read-modify-write hazards (tags are unique by construction, §5).

BlockSpecs: the table is one resident VMEM block (index_map pins it for every
grid step; ``input_output_aliases`` makes the update in-place); payload tiles
are (BT, W) VMEM blocks; indices/enables ride in scalar-prefetch (SMEM), the
TPU analogue of PHV metadata fields.  ``W`` is padded to a multiple of 128
lanes by ops.py so every store is lane-aligned.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 8


def _store_kernel(idx_ref, enb_ref, payload_ref, table_in_ref, table_ref, *,
                  bt: int):
    t = pl.program_id(0)

    # Materialize the resident table block once; subsequent grid steps revisit
    # the same block, so VMEM contents persist (standard accumulation pattern).
    @pl.when(t == 0)
    def _():
        table_ref[...] = table_in_ref[...]

    for i in range(bt):  # unrolled: BT predicated stores per grid step
        b = t * bt + i
        row = idx_ref[b]

        @pl.when(enb_ref[b] != 0)
        def _():
            table_ref[pl.ds(row, 1), :] = payload_ref[pl.ds(i, 1), :]


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def payload_store_kernel(table, payload, idx, enb, *, bt: int = DEFAULT_BT,
                         interpret: bool):
    """table: (M, W) int32, payload: (B, W) int32, idx: (B,), enb: (B,)."""
    m, w = table.shape
    b, _ = payload.shape
    assert b % bt == 0, (b, bt)
    grid = (b // bt,)
    return pl.pallas_call(
        functools.partial(_store_kernel, bt=bt),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # idx, enb
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, w), lambda t, *_: (t, 0)),   # payload tile
                pl.BlockSpec((m, w), lambda t, *_: (0, 0)),    # table (resident)
            ],
            out_specs=pl.BlockSpec((m, w), lambda t, *_: (0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, w), table.dtype),
        input_output_aliases={3: 0},  # table_in -> table_out, in-place
        interpret=interpret,
    )(idx, enb.astype(idx.dtype), payload, table)
