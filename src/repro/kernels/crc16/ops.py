"""jit'd wrapper: flat (B,) tag vectors -> lane-tiled kernel -> (B,) CRCs."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.crc16.kernel import LANES, crc16_kernel


@partial(jax.jit, static_argnames=("interpret",))
def crc16_tag_kernel_op(ti, clk, *, interpret: bool):
    b = ti.shape[0]
    tile = LANES * 8
    pad = (-b) % tile
    tip = jnp.pad(ti.astype(jnp.int32), (0, pad)).reshape(-1, LANES)
    clkp = jnp.pad(clk.astype(jnp.int32), (0, pad)).reshape(-1, LANES)
    out = crc16_kernel(tip, clkp, interpret=interpret)
    return out.reshape(-1)[:b]
