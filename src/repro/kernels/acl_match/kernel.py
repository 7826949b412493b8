"""Pallas TPU kernel: firewall ACL linear probe (paper §6.1).

"The firewall linearly probes through a list of blocked IP addresses" — the
per-packet hot loop of the chain's first NF.  The kernel holds the (small)
rule list resident in VMEM and streams (BT, 128) packet tiles through a
broadcast-compare-reduce: every packet is checked against every rule in one
VPU pass (the literal linear probe, vectorized across lanes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _acl_kernel(ip_ref, rules_ref, out_ref):
    ip = ip_ref[...]          # (BT, LANES)
    rules = rules_ref[...]    # (1, R)
    hit = (ip[:, :, None] == rules[None, :, :]).any(axis=-1)
    out_ref[...] = hit.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def acl_match_kernel(ip, rules, *, bt: int = 8, interpret: bool):
    """ip: (N, LANES) int32; rules: (1, R) int32 -> (N, LANES) int32 0/1."""
    n, lanes = ip.shape
    assert lanes == LANES and n % bt == 0
    r = rules.shape[1]
    return pl.pallas_call(
        _acl_kernel,
        grid=(n // bt,),
        in_specs=[
            pl.BlockSpec((bt, LANES), lambda t: (t, 0)),
            pl.BlockSpec((1, r), lambda t: (0, 0)),  # rules resident
        ],
        out_specs=pl.BlockSpec((bt, LANES), lambda t: (t, 0)),
        out_shape=jax.ShapeDtypeStruct((n, LANES), jnp.int32),
        interpret=interpret,
    )(ip, rules)
