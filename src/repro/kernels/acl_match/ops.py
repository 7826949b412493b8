"""jit'd wrapper for the ACL match kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.acl_match.kernel import LANES, acl_match_kernel


@partial(jax.jit, static_argnames=("interpret",))
def acl_match(src_ip, rules, *, interpret: bool):
    """src_ip: (B,) int32; rules: (R,) int32 -> (B,) bool."""
    b = src_ip.shape[0]
    tile = LANES * 8
    pad = (-b) % tile
    # Pad with a sentinel that can never match a rule.
    ipp = jnp.pad(src_ip.astype(jnp.int32), (0, pad),
                  constant_values=-1).reshape(-1, LANES)
    out = acl_match_kernel(ipp, rules.astype(jnp.int32)[None, :],
                           interpret=interpret)
    return out.reshape(-1)[:b].astype(bool)
