"""jit'd wrapper for the Maglev selection kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.maglev.kernel import LANES, maglev_kernel


@partial(jax.jit, static_argnames=("interpret",))
def maglev_select(src_ip, dst_ip, src_port, dst_port, proto, table,
                  backend_ips, *, interpret: bool):
    """Per-packet backend VIP selection; all inputs (B,) int32."""
    b = src_ip.shape[0]
    tile = LANES * 8
    pad = (-b) % tile

    def prep(x):
        return jnp.pad(x.astype(jnp.int32), (0, pad)).reshape(-1, LANES)

    out = maglev_kernel(
        prep(src_ip), prep(dst_ip), prep(src_port), prep(dst_port),
        prep(proto), table.astype(jnp.int32),
        backend_ips.astype(jnp.int32), interpret=interpret)
    return out.reshape(-1)[:b]
