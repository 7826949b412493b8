"""Pallas TPU kernel: CRC-16/CCITT-FALSE over PayloadPark tags.

The tag CRC (paper §3.2) is computed on Split (header construction) and
checked on Merge (header validation) — per-packet, on the hot path.  The
kernel is a fully-unrolled 4-byte x 8-bit branch-free bit loop over an int32
lane vector: TPU VPU-friendly (no data-dependent control flow; predication by
``jnp.where``, the vector analogue of P4 match predication).

Block layout: (BT, 128) tiles — the batch is reshaped to lane-major so each
grid step CRCs BT*128 tags at once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.backend.ref import CRC_INIT, CRC_POLY

LANES = 128


def _crc_kernel(ti_ref, clk_ref, out_ref):
    ti = ti_ref[...]
    clk = clk_ref[...]
    crc = jnp.full_like(ti, CRC_INIT)
    # bytes: ti&0xFF, ti>>8, clk&0xFF, clk>>8 (little-endian tag layout)
    for byte in (ti & 0xFF, (ti >> 8) & 0xFF, clk & 0xFF, (clk >> 8) & 0xFF):
        crc = crc ^ (byte << 8)
        for _ in range(8):
            hi = (crc >> 15) & 1
            crc = (crc << 1) & 0xFFFF
            crc = jnp.where(hi == 1, crc ^ CRC_POLY, crc)
    out_ref[...] = crc


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def crc16_kernel(ti, clk, *, bt: int = 8, interpret: bool):
    """ti, clk: (N, LANES) int32 -> (N, LANES) int32 CRCs."""
    n, lanes = ti.shape
    assert lanes == LANES and n % bt == 0, (ti.shape, bt)
    return pl.pallas_call(
        _crc_kernel,
        grid=(n // bt,),
        in_specs=[
            pl.BlockSpec((bt, LANES), lambda t: (t, 0)),
            pl.BlockSpec((bt, LANES), lambda t: (t, 0)),
        ],
        out_specs=pl.BlockSpec((bt, LANES), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n, LANES), jnp.int32),
        interpret=interpret,
    )(ti, clk)
