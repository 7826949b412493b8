"""Distribution layer: sharding rules, pipeline parallelism, collectives.

Two consumers sit on top of this package (see ``sharding.py``):

  * the serving/training side — ``launch/dryrun.py`` builds production
    meshes and resolves ``Rules`` PartitionSpecs for the LM step functions;
  * the dataplane side — ``switchsim/fabric.py`` shard_maps the engine's
    flat pipe axis over a 1-D ``("switch",)`` mesh (DESIGN.md §12).

``force_host_devices`` is the ONE sanctioned way to get multi-device CPU
runs (the SNIPPETS.md ``--xla_force_host_platform_device_count`` recipe):
it must run before jax initializes a backend, and it *raises* when called
too late instead of silently mutating an env var jax has already read —
the bug the seed-era ``launch/dryrun.py`` header carried.
"""
from __future__ import annotations

import os
import sys

_FORCE_FLAG = "--xla_force_host_platform_device_count"


def jax_backend_initialized() -> bool:
    """True once jax has initialized any backend — the point at which the
    platform device count is locked and XLA_FLAGS edits stop working.

    Importing jax does NOT initialize a backend; the first operation that
    touches devices (``jax.devices()``, any traced computation) does.
    Kept dependency-light: never imports jax itself, only inspects an
    already-imported module, so calling this cannot trigger the very
    initialization it checks for.
    """
    jax_mod = sys.modules.get("jax")
    if jax_mod is None:
        return False
    return bool(jax_mod._src.xla_bridge._backends)


def force_host_devices(n: int) -> None:
    """Force the CPU platform to expose ``n`` devices (XLA_FLAGS recipe).

    Prepends ``--xla_force_host_platform_device_count=n`` to ``XLA_FLAGS``
    (replacing any previous occurrence) so CPU-only hosts — CI included —
    exercise *real* multi-device sharding: ``switchsim/fabric.py`` meshes,
    the dry-run's 512-chip mesh, the forced-host distributed tests.

    Raises ``RuntimeError`` if jax has already initialized a backend: the
    device count is locked at first backend init, so a late call would be
    a silent no-op — exactly the hazard this helper exists to remove
    (``launch/dryrun.py`` used to mutate the env var inline and hope it
    ran first).  Call it before anything touches jax devices: entry-point
    tops, subprocess preludes, benchmark ``--host-devices`` flags.
    """
    n = int(n)
    if n < 1:
        raise ValueError(f"device count must be >= 1, got {n}")
    if jax_backend_initialized():
        raise RuntimeError(
            "force_host_devices called after jax initialized a backend — "
            "the host device count is locked at first init and XLA_FLAGS "
            "is no longer read.  Call it before any jax device use "
            "(or launch a fresh process / set XLA_FLAGS="
            f"{_FORCE_FLAG}={n} in the environment).")
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if not f.startswith(_FORCE_FLAG)]
    os.environ["XLA_FLAGS"] = " ".join([f"{_FORCE_FLAG}={n}"] + kept).strip()
