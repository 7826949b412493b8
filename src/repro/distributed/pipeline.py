"""GPipe-style pipeline parallelism over a mesh axis (default: "pod").

Microbatches stream through stages placed on successive mesh-axis slices;
activations move stage-to-stage with ``jax.lax.ppermute`` inside a
``shard_map``.  The static schedule runs ``num_micro + S - 1`` ticks; each
tick every stage computes one microbatch and forwards it, so the ppermute
overlaps with the next tick's compute (XLA schedules the send/recv around the
stage body — the compute/communication overlap the brief asks for).

Offered as an optional distribution mode: the production dry-run meshes use
("pod","data","model") with pod folded into data parallelism by default;
``pipeline_apply`` reuses the pod axis as the stage axis instead (bubble
fraction (S-1)/(T+S-1)).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def sequential_apply(stage_fn, stage_params, x):
    """Reference: run every stage in order over each microbatch.
    stage_params: (S, ...); x: (num_micro, mb, d)."""
    s = stage_params.shape[0] if hasattr(stage_params, "shape") else \
        jax.tree.leaves(stage_params)[0].shape[0]

    def body(xm):
        for i in range(s):
            xm = stage_fn(jax.tree.map(lambda a: a[i], stage_params), xm)
        return xm

    return jax.vmap(body)(x)


def pipeline_apply(stage_fn, stage_params, x, mesh, stage_axis: str = "pod"):
    """x: (num_micro, mb, d) replicated; stage_params sharded over
    ``stage_axis`` (one stage per slice).  Returns (num_micro, mb, d)."""
    s = mesh.shape[stage_axis]
    num_micro = x.shape[0]
    nstages = jax.tree.leaves(stage_params)[0].shape[0]
    assert nstages == s, (nstages, s)
    perm = [(i, (i + 1) % s) for i in range(s)]

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    xspec = P(*([None] * x.ndim))

    @partial(jax.shard_map, mesh=mesh, in_specs=(pspec, xspec),
             out_specs=xspec, check_vma=False)
    def run(params_local, x_all):
        stage_id = jax.lax.axis_index(stage_axis)
        is_first = stage_id == 0
        is_last = stage_id == s - 1
        p_local = jax.tree.map(lambda a: a[0], params_local)
        state = jnp.zeros_like(x_all[0])
        outputs = jnp.zeros_like(x_all)
        for t in range(num_micro + s - 1):
            feed = x_all[min(t, num_micro - 1)]
            x_in = jnp.where(is_first & (t < num_micro), feed, state)
            y = stage_fn(p_local, x_in)
            mb = t - (s - 1)
            if mb >= 0:
                outputs = outputs.at[mb].set(
                    jnp.where(is_last, y, outputs[mb]))
            state = jax.lax.ppermute(y, stage_axis, perm)
        # only the last stage wrote outputs; broadcast over the stage axis
        return jax.lax.psum(outputs, stage_axis)

    return run(stage_params, x)
