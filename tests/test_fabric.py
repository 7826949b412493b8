"""Fabric sharding correctness (switchsim.fabric, DESIGN.md §12).

The headline contract is shard-count invariance: the same scenario run
with its pipe axis sharded over 1, 2 or 8 devices yields bit-identical
counters, telemetry and occupancy.  The multi-device tests run in
SUBPROCESSES because the device count must be fixed before jax
initializes (the main pytest process keeps 1 device for everything else);
each subprocess forces 8 host devices via XLA_FLAGS — the same recipe
``repro.distributed.force_host_devices`` applies programmatically, whose
own guard semantics are tested in-process below.
"""
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 8, timeout: int = 900,
            force_env: bool = True):
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    if force_env:
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


PRELUDE = """
import warnings
import numpy as np
import repro.scenarios as S

def point(pipes, devices, packets=512, **kw):
    return S.pipeline_grid([pipes], packets=packets, chunk=64, window=2,
                           pmax=512, capacity=256, devices=(devices,),
                           **kw)[0]

def same(a, b):
    return (a.counters == b.counters
            and a.per_pipe_counters == b.per_pipe_counters
            and a.telemetry == b.telemetry
            and a.per_pipe_telemetry == b.per_pipe_telemetry
            and a.nf_counters == b.nf_counters
            and a.per_pipe_nf_counters == b.per_pipe_nf_counters
            and a.per_pipe_peak_occupancy == b.per_pipe_peak_occupancy
            and np.array_equal(np.asarray(a.per_pipe_occ_series),
                               np.asarray(b.per_pipe_occ_series)))
"""


def test_shard_count_invariance_1_2_8():
    """Bit-identical counters/telemetry/occupancy on 1, 2 and 8 devices,
    with the engine≡loop oracle green per shard on every device count."""
    run_sub(PRELUDE + """
import jax
assert len(jax.devices()) == 8, jax.devices()
res = {d: S.run_matrix([point(8, d)])[0] for d in (1, 2, 8)}
for d in (2, 8):
    assert same(res[d], res[1]), f"devices={d} diverged from devices=1"
    S.verify_oracle(res[d])   # per-pipe == per-shard (DESIGN.md §12)
print("invariance OK")
""")


def test_per_shard_oracle_recirc_modes_and_backends():
    """verify_oracle on sharded runs in both recirc modes x both backends."""
    run_sub(PRELUDE + """
import dataclasses
for backend in ("ref", "pallas_interpret"):
    for recirc in (False, True):
        spec = dataclasses.replace(
            point(4, 2, backends=(backend,)),
            name=f"fab_{backend}_{int(recirc)}", recirc=recirc)
        res = S.run_matrix([spec])[0]
        S.verify_oracle(res)
        ref = S.run_matrix([dataclasses.replace(spec, devices=1)])[0]
        assert same(res, ref), (backend, recirc)
print("oracle OK")
""")


def test_non_dividing_pipe_count_falls_back():
    """pipes=3 over 2 devices warns and equals the single-device run."""
    run_sub(PRELUDE + """
with warnings.catch_warnings(record=True) as w:
    warnings.simplefilter("always")
    r3 = S.run_matrix([point(3, 2, packets=384)])[0]
assert any("does not divide" in str(x.message) for x in w), \
    [str(x.message) for x in w]
ref = S.run_matrix([point(3, 1, packets=384)])[0]
assert same(r3, ref)
print("fallback OK")
""")


def test_run_matrix_group_spans_devices():
    """Two same-compile-key specs at devices=2 batch into ONE sharded
    program (their concatenated pipe axis spans the devices) and match
    their solo runs bit-for-bit."""
    run_sub(PRELUDE + """
import dataclasses
# flows>0 draws firewall rules from the deterministic pool instead of the
# traffic, so the two seeds share one chain and hence one compile key
a = dataclasses.replace(point(2, 2), name="a", seed=0, flows=256)
b = dataclasses.replace(point(2, 2), name="b", seed=7, flows=256)
together = S.run_matrix([a, b])
assert together[0].group_size == 2, "specs did not share a compile group"
solo = [S.run_matrix([s])[0] for s in (a, b)]
for got, want in zip(together, solo):
    assert same(got, want), got.spec.name
print("group OK")
""")


def test_more_devices_than_visible_falls_back():
    """Requesting more devices than visible raises: no silent run on
    fewer devices than asked for."""
    run_sub(PRELUDE + """
try:
    S.run_matrix([point(2, 4)])
except ValueError as e:
    assert "only 2 visible" in str(e), e
else:
    raise SystemExit("oversubscribed fabric run did not raise")
print("visibility error OK")
""", devices=2)


def test_force_host_devices_sets_flag_and_device_count():
    """force_host_devices before jax init yields that many devices, and
    replaces (not duplicates) a pre-existing force flag."""
    run_sub("""
import os
os.environ["XLA_FLAGS"] = \\
    "--xla_force_host_platform_device_count=3 --xla_dump_to=/dev/null"
from repro.distributed import force_host_devices
force_host_devices(5)
flags = os.environ["XLA_FLAGS"].split()
assert flags.count("--xla_force_host_platform_device_count=5") == 1, flags
assert not any(f.startswith("--xla_force_host_platform_device_count=3")
               for f in flags), flags
assert "--xla_dump_to=/dev/null" in flags, flags
import jax
assert len(jax.devices()) == 5, jax.devices()
print("force OK")
""", force_env=False)


def test_force_host_devices_raises_after_jax_init():
    run_sub("""
import jax, jax.numpy as jnp
jnp.zeros(2).block_until_ready()   # initializes the backend
from repro.distributed import force_host_devices
try:
    force_host_devices(8)
except RuntimeError as e:
    assert "already" in str(e) or "initialized" in str(e), e
else:
    raise SystemExit("force_host_devices did not raise after init")
print("guard OK")
""", force_env=False)


def test_force_host_devices_rejects_bad_count():
    from repro.distributed import force_host_devices
    with pytest.raises(ValueError):
        force_host_devices(0)


def test_spec_devices_validation_and_compile_key():
    """devices is validated and separates compile groups (a sharded
    program is a different XLA program)."""
    import dataclasses

    import repro.scenarios as S
    from repro.scenarios.spec import compile_key

    base = S.pipeline_grid([2], packets=128, chunk=64, window=2, pmax=512,
                           capacity=256)[0]
    with pytest.raises(ValueError, match="devices"):
        dataclasses.replace(base, devices=0)
    pkts = S.make_packets(base)
    chain = S.build_chain(base, pkts)
    k1 = compile_key(base, chain, steps=2)
    k2 = compile_key(dataclasses.replace(base, devices=2), chain, steps=2)
    assert k1 != k2
    assert k1 == compile_key(dataclasses.replace(base, seed=5), chain,
                             steps=2)


def test_resolve_devices_guards():
    """resolve_devices: trivial counts short-circuit without touching jax;
    oversubscribed requests raise."""
    from repro.switchsim import fabric

    assert fabric.resolve_devices(8, None) == 1
    assert fabric.resolve_devices(8, 1) == 1
    assert fabric.resolve_devices(8, 0) == 1
    # the single in-process device: 2 > visible -> error
    with pytest.raises(ValueError, match="visible"):
        fabric.resolve_devices(8, 2)
