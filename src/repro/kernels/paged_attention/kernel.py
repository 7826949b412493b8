"""Pallas TPU kernel: paged decode attention over the parked-KV pool.

The serving-side Merge: parked payload pages are gathered *by tag* straight
from the pool while attention runs — the page table (the request header's
tag list) rides in scalar prefetch and drives the BlockSpec index_map, so
each grid step DMAs exactly one (page_tokens, K, E) KV page from the pool
into VMEM.  This is the canonical TPU paged-attention structure:

  grid = (B, MAX_PAGES);  k/v page blocks indexed by page_table[b, p];
  flash running (m, l, acc) in VMEM scratch, persisted across the page axis;
  the output block for request b is written on its last page step.

Pool pages never move in HBM (they are "parked"); only the 8-byte-per-page
header crossed the network to get here — the paper's goodput argument,
realized as a collective-bytes reduction (see benchmarks/bench_parking.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, out_ref,
                  m_ref, l_ref, acc_ref, *, page: int, max_pages: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (K, G, E)
    k = k_ref[0]                       # (page, K, E)
    v = v_ref[0]
    e = q.shape[-1]

    s = jnp.einsum("kge,tke->kgt", q, k,
                   preferred_element_type=jnp.float32) * (e ** -0.5)
    tok = p * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
    live = (tok < len_ref[b]) & (pt_ref[b, p] >= 0)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_ref[...]                # (K, G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=-1, keepdims=True)
    pv = jnp.einsum("kgt,tke->kge", pexp.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_new

    @pl.when(p == max_pages - 1)
    def _():
        out_ref[0] = (acc_ref[...]
                      / jnp.maximum(l_ref[...], 1e-30)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_kernel(q, k_pages, v_pages, page_table, lengths,
                                  *, interpret: bool):
    """q: (B,K,G,E); k_pages/v_pages: (P, page, K, E);
    page_table: (B, MP) int32 (-1 pad); lengths: (B,) int32."""
    b, kh, g, e = q.shape
    npages, page, _, _ = k_pages.shape
    mp = page_table.shape[1]

    kv_spec = pl.BlockSpec(
        (1, page, kh, e), lambda b_, p_, pt_, ln_: (pt_[b_, p_], 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page, max_pages=mp),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # page_table (orig, with -1), lengths
            grid=(b, mp),
            in_specs=[
                pl.BlockSpec((1, kh, g, e), lambda b_, p_, pt_, ln_: (b_, 0, 0, 0)),
                kv_spec,
                kv_spec,
            ],
            out_specs=pl.BlockSpec(
                (1, kh, g, e), lambda b_, p_, pt_, ln_: (b_, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((kh, g, 1), jnp.float32),
                pltpu.VMEM((kh, g, 1), jnp.float32),
                pltpu.VMEM((kh, g, e), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, e), q.dtype),
        interpret=interpret,
    )(page_table, lengths, q, k_pages, v_pages)
