"""Distributed correctness on a forced-host 8-device mesh.

Each test runs in a SUBPROCESS because the device count must be fixed before
jax initializes (the main pytest process keeps 1 device for the smoke tests).
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro import configs
from repro.configs.reduced import reduced
from repro.distributed.sharding import Rules
from repro.models.lm import LM
from repro.training.train_step import TrainConfig, init_train_state, train_step
from repro.training.optimizer import AdamWConfig
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
cfg = reduced(configs.get("minitron-8b"))
lm = LM(cfg)
tcfg = TrainConfig(adamw=AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10))
state = init_train_state(lm, jax.random.key(0))
batch = {
  "tokens": jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size, dtype=jnp.int32),
  "labels": jax.random.randint(jax.random.key(2), (4, 32), 0, cfg.vocab_size, dtype=jnp.int32),
}
"""


def test_sharded_train_step_matches_single_device():
    run_sub(PRELUDE + """
# single-device reference
ref_state, ref_metrics = train_step(lm, tcfg, state, batch)

with mesh:
    rules = Rules(cfg, mesh)
    sspec = rules.to_shardings(rules.state_spec(state))
    bspec = rules.to_shardings(rules.batch_spec(batch))
    st = jax.device_put(state, sspec)
    bt = jax.device_put(batch, bspec)
    fn = jax.jit(lambda s, b: train_step(lm, tcfg, s, b,
                                         shard=rules.act_shard()),
                 in_shardings=(sspec, bspec), out_shardings=(sspec, None))
    new_state, metrics = fn(st, bt)

assert abs(float(metrics["loss"]) - float(ref_metrics["loss"])) < 2e-2, (
    float(metrics["loss"]), float(ref_metrics["loss"]))
for a, b in zip(jax.tree.leaves(ref_state["params"]),
                jax.tree.leaves(new_state["params"])):
    d = np.max(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)))
    assert d < 0.05, d
print("sharded == single OK")
""")


def test_sharded_decode_matches_single_device():
    run_sub(PRELUDE + """
params = state["params"]
toks = batch["tokens"]
logits_ref, cache_ref = lm.prefill(params, {"tokens": toks}, cache_len=40)
out_ref, _ = lm.decode_step(params, cache_ref,
                            jnp.argmax(logits_ref, -1).astype(jnp.int32),
                            jnp.full((4,), 32, jnp.int32))
with mesh:
    rules = Rules(cfg, mesh)
    pspec = rules.to_shardings(rules.param_specs(params))
    pt = jax.device_put(params, pspec)
    logits_s, cache_s = jax.jit(
        lambda p, b: lm.prefill(p, b, cache_len=40,
                                shard=rules.act_shard()))(pt, {"tokens": toks})
    out_s, _ = jax.jit(
        lambda p, c, t, i: lm.decode_step(p, c, t, i,
                                          shard=rules.act_shard()))(
        pt, cache_s, jnp.argmax(logits_s, -1).astype(jnp.int32),
        jnp.full((4,), 32, jnp.int32))
d = np.max(np.abs(np.asarray(out_ref, np.float32)
                  - np.asarray(out_s, np.float32)))
assert d < 0.06, d
print("decode sharded OK", d)
""")


def test_checkpoint_reshard_elastic():
    """Save under a (2,4) mesh, restore under (4,2) — elastic rescale."""
    run_sub(PRELUDE + """
import tempfile, os
from repro.training import checkpoint as ckpt
with mesh:
    rules = Rules(cfg, mesh)
    sspec = rules.to_shardings(rules.state_spec(state))
    st = jax.device_put(state, sspec)
d = tempfile.mkdtemp()
ckpt.save(d, 1, st)

mesh2 = jax.make_mesh((4, 2), ("data", "model"),
                      axis_types=(AxisType.Auto,) * 2)
with mesh2:
    rules2 = Rules(cfg, mesh2)
    template = jax.eval_shape(lambda: init_train_state(lm, jax.random.key(0)))
    sspec2 = rules2.to_shardings(rules2.state_spec(template))
    restored = ckpt.restore(d, 1, template, sspec2)
for a, b in zip(jax.tree.leaves(state["params"]),
                jax.tree.leaves(restored["params"])):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
print("elastic reshard OK")
""")


def test_quantized_psum_shard_map():
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from functools import partial
from jax.sharding import AxisType, PartitionSpec as P
from repro.training.compression import quantized_psum
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.key(0), (8, 64))

@partial(jax.shard_map, mesh=mesh, in_specs=P("data", None),
         out_specs=P("data", None))
def f(xs):
    return quantized_psum(xs, "data")[None] * jnp.ones((xs.shape[0], 1))

got = f(x)[0]
want = x.sum(0)
err = np.max(np.abs(np.asarray(got) - np.asarray(want)))
scale = np.max(np.abs(np.asarray(x))) / 127 * 8
assert err <= scale + 1e-5, (err, scale)
print("quantized psum OK", err)
""")


def test_pipeline_parallel_matches_sequential():
    run_sub("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.distributed.pipeline import pipeline_apply, sequential_apply
mesh = jax.make_mesh((4, 2), ("pod", "model"),
                     axis_types=(AxisType.Auto,) * 2)
# toy 4-stage MLP pipeline
k = jax.random.key(0)
ws = jax.random.normal(k, (4, 16, 16)) * 0.3
x = jax.random.normal(jax.random.key(1), (8, 4, 16))  # (microbatches, mb, d)

def stage(w, x):
    return jnp.tanh(x @ w)

want = sequential_apply(stage, ws, x)
with mesh:
    got = pipeline_apply(stage, ws, x, mesh, stage_axis="pod")
np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                           rtol=2e-5, atol=2e-5)
print("pipeline OK")
""")
