"""jit'd public wrapper for payload_fetch (see payload_store.ops for the
byte/word layout rationale)."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.payload_fetch.kernel import payload_fetch_kernel
from repro.kernels.payload_store.ops import _pad_lanes, _to_bytes, _to_words


@partial(jax.jit, static_argnames=("interpret",))
def payload_fetch(table_u8, idx, mask, *, interpret: bool):
    """Gather+clear parked rows.  Returns (parked (B, bytes) u8, new table)."""
    m, nbytes = table_u8.shape
    assert nbytes % 4 == 0, nbytes
    b = idx.shape[0]
    tw = _pad_lanes(_to_words(table_u8))
    bt = 8 if b % 8 == 0 else 1
    gathered, new_table = payload_fetch_kernel(
        tw, idx.astype(jnp.int32), mask, bt=bt, interpret=interpret)
    return (
        _to_bytes(gathered[:, : nbytes // 4], nbytes),
        _to_bytes(new_table[:, : nbytes // 4], nbytes),
    )
