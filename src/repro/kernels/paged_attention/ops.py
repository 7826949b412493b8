"""jit'd wrapper for paged decode attention."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_decode_attention_kernel


@partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pages, v_pages, page_table, lengths,
                           *, interpret: bool):
    """q: (B, K, G, E); k_pages/v_pages: (P, page, K, E);
    page_table: (B, MP) int32 with -1 padding; lengths: (B,) int32."""
    return paged_decode_attention_kernel(
        q, k_pages, v_pages, page_table.astype(jnp.int32),
        lengths.astype(jnp.int32), interpret=interpret)
