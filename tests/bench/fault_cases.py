"""Faults planted in the simulator's timed path, for the fault tests.

Each fault wraps a function the engines look up when they build their
compiled step, so the compiled-program caches are cleared around it.
"""
import contextlib

import jax.numpy as jnp

from repro.core import park
from repro.switchsim import engine, stream


def _clear():
    engine._compiled.cache_clear()
    stream._segment_program.cache_clear()


@contextlib.contextmanager
def planted(monkeypatch, module, name, wrap):
    orig = getattr(module, name)
    _clear()
    with monkeypatch.context() as m:
        m.setattr(module, name, wrap(orig))
        if module is engine and name == "scan_step":
            m.setattr(stream, "scan_step", getattr(module, name))
        try:
            yield
        finally:
            _clear()


def frozen_state(step_factory):
    """A step that returns the switch state it was given."""
    def factory(*a, **k):
        step = step_factory(*a, **k)

        def run(carry, xs, drain):
            new, ys = step(carry, xs, drain)
            return (carry[0],) + tuple(new[1:]), ys
        return run
    return factory


def half_the_batch(step_factory):
    """A step that leaves the second half of every arriving chunk out."""
    def factory(*a, **k):
        step = step_factory(*a, **k)

        def run(carry, xs, drain):
            cin, s_up, l_up = xs
            half = jnp.arange(cin.alive.shape[0]) < cin.alive.shape[0] // 2
            return step(carry, (cin.replace(alive=cin.alive & half), s_up,
                                l_up), drain)
        return run
    return factory


def altered_merge(merge_fn):
    """Merge hands back one payload byte altered in each merged chunk."""
    def run(cfg, state, pkts, backend=None):
        state, out = merge_fn(cfg, state, pkts, backend=backend)
        first = jnp.argmax(out.alive & (out.payload_len > 0))
        flip = jnp.zeros_like(out.payload).at[first, 0].set(1)
        return state, out.replace(payload=out.payload ^ flip)
    return run


def altered_store(dispatch):
    """Split parks every payload with its first byte altered."""
    def run(name, backend=None):
        fn = dispatch(name, backend)
        if name != "payload_store":
            return fn

        def store(table, payload, idx, enb):
            return fn(table, payload.at[:, 0].add(1), idx, enb)
        return store
    return run


FAULTS = {
    "state_unchanged": (engine, "scan_step", frozen_state),
    "half_the_batch": (engine, "scan_step", half_the_batch),
    "merge_answer_altered": (engine, "merge_fn", altered_merge),
    "parked_answer_altered": (park, "dispatch", altered_store),
}
