"""jit'd public wrapper for payload_store: byte-view plumbing + lane padding.

Converts the core's (M, park_bytes) uint8 table and (B, park_bytes) payload
rows to int32 word lanes, pads the lane count to a multiple of 128 (MXU/VPU
alignment), runs the Pallas kernel, and converts back.  In production the
table would be kept permanently in the padded int32 layout; the per-call
conversion here keeps the faithful byte-level core decoupled from the kernel
layout (and costs nothing under interpret-mode validation on CPU).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.payload_store.kernel import payload_store_kernel

LANES = 128


def _to_words(x):  # (..., 4k) uint8 -> (..., k) int32
    return jax.lax.bitcast_convert_type(
        x.reshape(*x.shape[:-1], x.shape[-1] // 4, 4), jnp.int32)


def _to_bytes(x, nbytes):  # (..., k) int32 -> (..., 4k) uint8
    b = jax.lax.bitcast_convert_type(x, jnp.uint8)
    return b.reshape(*x.shape[:-1], x.shape[-1] * 4)[..., :nbytes]


def _pad_lanes(x):
    w = x.shape[-1]
    pad = (-w) % LANES
    if pad:
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    return x


@partial(jax.jit, static_argnames=("interpret",))
def payload_store(table_u8, payload_u8, idx, enb, *, interpret: bool):
    """Scatter parked payload rows: table[idx[b]] = payload[b] where enb[b]."""
    m, nbytes = table_u8.shape
    assert nbytes % 4 == 0, nbytes
    b = payload_u8.shape[0]
    tw = _pad_lanes(_to_words(table_u8))
    pw = _pad_lanes(_to_words(payload_u8))
    bt = 8 if b % 8 == 0 else 1
    out = payload_store_kernel(tw, pw, idx.astype(jnp.int32),
                               enb, bt=bt, interpret=interpret)
    return _to_bytes(out[:, : nbytes // 4], nbytes)
