"""Core PayloadPark: unit tests + hypothesis property tests (paper Alg. 1/2)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (pip install .[test])")
import hypothesis.strategies as st  # noqa: E402
from hypothesis import given, settings  # noqa: E402

from repro.core import counters as C
from repro.core.header import crc16_tag
from repro.core.packet import (HDR_BYTES, OP_DROP, PP_HDR_BYTES,
                               make_udp_batch, wire_bytes)
from repro.core.park import (PARK_BYTES_BASE, PARK_BYTES_RECIRC, ParkConfig,
                             _shift_rows, init_state, merge, merge_fn,
                             occupancy, recirc, recirc_fn, split, split_fn)

CFG = ParkConfig(capacity=64, max_exp=2, pmax=1024)


def mk(key, n, size, **kw):
    return make_udp_batch(jax.random.key(key), n, size, pmax=1024, **kw)


class TestSplit:
    def test_parks_large_payloads(self):
        st_ = init_state(CFG)
        pkts = mk(0, 8, 300)
        st2, out = split(CFG, st_, pkts)
        assert int(jnp.sum(out.pp_enb)) == 8
        # payload truncated by exactly 160B; +7B PP header on the wire
        assert jnp.all(out.payload_len == pkts.payload_len - PARK_BYTES_BASE)
        assert jnp.all(out.pkt_len() == pkts.pkt_len() - PARK_BYTES_BASE
                       + PP_HDR_BYTES)
        assert C.as_dict(st2.counters)["splits"] == 8
        assert int(occupancy(st2)) == 8

    def test_small_payloads_skip_with_header(self):
        """<160B payloads still get the PP header, ENB=0 (paper §6.1)."""
        st_ = init_state(CFG)
        pkts = mk(0, 8, 150)  # payload 108 < 160
        st2, out = split(CFG, st_, pkts)
        assert int(jnp.sum(out.pp_enb)) == 0
        assert bool(jnp.all(out.pp_valid))
        assert C.as_dict(st2.counters)["skip_small_payload"] == 8
        assert int(occupancy(st2)) == 0

    def test_exactly_160_parks(self):
        st_ = init_state(CFG)
        pkts = mk(0, 4, HDR_BYTES + 160)
        _, out = split(CFG, st_, pkts)
        assert int(jnp.sum(out.pp_enb)) == 4
        assert jnp.all(out.payload_len == 0)

    def test_crc_on_header(self):
        st_ = init_state(CFG)
        _, out = split(CFG, st_, mk(0, 4, 300))
        assert jnp.all(out.pp_crc == crc16_tag(out.pp_ti, out.pp_clk))

    def test_table_full_disables_split(self):
        cfg = ParkConfig(capacity=4, max_exp=10, pmax=1024)
        st_ = init_state(cfg)
        st_, out1 = split(cfg, st_, mk(0, 4, 300))
        assert int(jnp.sum(out1.pp_enb)) == 4
        # table now full; EXP=10 means nothing evicts on one more pass
        st_, out2 = split(cfg, st_, mk(1, 4, 300))
        assert int(jnp.sum(out2.pp_enb)) == 0
        assert C.as_dict(st_.counters)["skip_occupied"] == 4

    def test_eviction_after_exp_wraps(self):
        """EXP=1: one full wrap evicts abandoned payloads (paper §4)."""
        cfg = ParkConfig(capacity=4, max_exp=1, pmax=1024)
        st_ = init_state(cfg)
        st_, _ = split(cfg, st_, mk(0, 4, 300))   # fill, never merged
        st_, out = split(cfg, st_, mk(1, 4, 300))  # wrap: evict + reclaim
        assert int(jnp.sum(out.pp_enb)) == 4
        assert C.as_dict(st_.counters)["evictions"] == 4


class TestMerge:
    def test_roundtrip_wire_identical(self):
        st_ = init_state(CFG)
        pkts = mk(0, 16, 300)
        want_w, want_l = wire_bytes(pkts)
        st_, sent = split(CFG, st_, pkts)
        st_, merged = merge(CFG, st_, sent)
        got_w, got_l = wire_bytes(merged)
        assert jnp.all(got_w == want_w) and jnp.all(got_l == want_l)
        assert int(occupancy(st_)) == 0
        d = C.as_dict(st_.counters)
        assert d["merges"] == 16 and d["premature_evictions"] == 0

    def test_enb0_forwarded_header_removed(self):
        st_ = init_state(CFG)
        st_, sent = split(CFG, st_, mk(0, 8, 150))
        st_, out = merge(CFG, st_, sent)
        assert not bool(jnp.any(out.pp_valid))
        assert bool(jnp.all(out.alive))
        assert C.as_dict(st_.counters)["disabled_returns"] == 8

    def test_premature_eviction_detected_and_dropped(self):
        cfg = ParkConfig(capacity=4, max_exp=1, pmax=1024)
        st_ = init_state(cfg)
        st_, sent1 = split(cfg, st_, mk(0, 4, 300))
        st_, _ = split(cfg, st_, mk(1, 4, 300))   # evicts batch 1's payloads
        st_, out = merge(cfg, st_, sent1)         # stale generations
        assert not bool(jnp.any(out.alive))
        assert C.as_dict(st_.counters)["premature_evictions"] == 4

    def test_crc_corruption_dropped(self):
        st_ = init_state(CFG)
        st_, sent = split(CFG, st_, mk(0, 4, 300))
        bad = sent.replace(pp_crc=sent.pp_crc ^ 1)
        st_, out = merge(CFG, st_, bad)
        assert not bool(jnp.any(out.alive))
        assert C.as_dict(st_.counters)["crc_failures"] == 4

    def test_explicit_drop_frees_slot(self):
        st_ = init_state(CFG)
        st_, sent = split(CFG, st_, mk(0, 4, 300))
        dropped = sent.replace(pp_op=jnp.full_like(sent.pp_op, OP_DROP),
                               payload_len=jnp.zeros_like(sent.payload_len))
        st_, out = merge(CFG, st_, dropped)
        assert int(occupancy(st_)) == 0
        assert C.as_dict(st_.counters)["explicit_drops"] == 4
        assert not bool(jnp.any(out.alive))  # notifications are consumed

    def test_double_merge_is_premature(self):
        st_ = init_state(CFG)
        st_, sent = split(CFG, st_, mk(0, 4, 300))
        st_, _ = merge(CFG, st_, sent)
        st_, out = merge(CFG, st_, sent)  # replay
        assert not bool(jnp.any(out.alive))
        assert C.as_dict(st_.counters)["premature_evictions"] == 4


class TestRecirculation:
    """Pass-based recirculation (paper §6.2.5, DESIGN.md §6): Split parks
    one pass width (160B); ``recirc`` is the second traversal that fills
    the 352B row.  The full lane/budget suite is tests/test_recirc.py."""

    def test_recirc_parks_352_over_two_passes(self):
        cfg = ParkConfig(capacity=64, max_exp=2, pmax=1024,
                         recirculation=True)
        assert cfg.park_bytes == PARK_BYTES_RECIRC == 352
        assert cfg.pass_bytes == PARK_BYTES_BASE == 160
        st_ = init_state(cfg)
        pkts = mk(0, 8, 500)   # payload 458 >= 160
        st_, sent = split(cfg, st_, pkts)
        assert jnp.all(sent.payload_len == pkts.payload_len - 160)
        st_, sent = recirc(cfg, st_, sent)
        assert jnp.all(sent.payload_len == pkts.payload_len - 352)
        st_, out = merge(cfg, st_, sent)
        w0, _ = wire_bytes(pkts)
        w1, _ = wire_bytes(out)
        assert jnp.all(w0 == w1)

    def test_recirc_partial_park(self):
        """Payload in [160, 352): the whole payload parks after the second
        pass (variable length, DESIGN.md deviation note)."""
        cfg = ParkConfig(capacity=64, max_exp=2, pmax=1024,
                         recirculation=True)
        st_ = init_state(cfg)
        pkts = mk(0, 8, HDR_BYTES + 200)
        st_, sent = split(cfg, st_, pkts)
        assert jnp.all(sent.payload_len == 200 - 160)
        st_, sent = recirc(cfg, st_, sent)
        assert jnp.all(sent.payload_len == 0)
        st_, out = merge(cfg, st_, sent)
        w0, _ = wire_bytes(pkts)
        w1, _ = wire_bytes(out)
        assert jnp.all(w0 == w1)


@settings(max_examples=15, deadline=None)
@given(
    sizes=st.lists(st.integers(HDR_BYTES, 900), min_size=1, max_size=40),
    capacity=st.integers(4, 64),
    max_exp=st.integers(1, 3),
)
def test_property_fifo_roundtrip(sizes, capacity, max_exp):
    """For any packet stream and table geometry, FIFO split->merge with the
    table large enough (in-flight = one batch <= capacity) is byte-exact and
    counter-consistent: splits == merges, occupancy returns to 0."""
    cfg = ParkConfig(capacity=capacity, max_exp=max_exp, pmax=1024)
    st_ = init_state(cfg)
    n = len(sizes)
    pkts = make_udp_batch(jax.random.key(7), n, jnp.asarray(sizes), pmax=1024)
    w0, l0 = wire_bytes(pkts)
    st_, sent = split(cfg, st_, pkts)
    st_, out = merge(cfg, st_, sent)
    d = C.as_dict(st_.counters)
    if n <= capacity:
        # no same-batch wrap: every parked payload must merge back
        assert d["premature_evictions"] == 0
        got_w, got_l = wire_bytes(out)
        assert jnp.all(got_w == w0) and jnp.all(got_l == l0)
        assert int(occupancy(st_)) == 0
    # conservation: every split was merged, evicted, or is still parked
    assert d["splits"] == d["merges"] + d["evictions"] + int(occupancy(st_))
    assert d["premature_evictions"] <= d["evictions"]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_unique_live_tags(seed):
    """All live (parked) slots hold distinct tags; tags never use clk=0."""
    cfg = ParkConfig(capacity=16, max_exp=2, pmax=1024)
    st_ = init_state(cfg)
    pkts = make_udp_batch(jax.random.key(seed), 12, 400, pmax=1024)
    st_, sent = split(cfg, st_, pkts)
    live = st_.meta_exp > 0
    clks = st_.meta_clk[live]
    assert jnp.all(clks > 0)
    assert len(set(map(int, clks))) == int(live.sum())


def test_backend_paths_match():
    """ref vs pallas_interpret through split/merge (the retired kernel
    toggle's TypeError contract is covered by tests/test_backend.py)."""
    st0 = init_state(CFG)
    pkts = mk(3, 16, 400)
    st_a, sent_a = split(CFG, st0, pkts, backend="ref")
    st_b, sent_b = split(CFG, st0, pkts, backend="pallas_interpret")
    assert jnp.all(st_a.ptable == st_b.ptable)
    assert jnp.all(sent_a.payload == sent_b.payload)
    st_a2, out_a = merge(CFG, st_a, sent_a, backend="ref")
    st_b2, out_b = merge(CFG, st_b, sent_b, backend="pallas_interpret")
    assert jnp.all(out_a.payload == out_b.payload)
    assert jnp.all(st_a2.ptable == st_b2.ptable)


# --------------------------------------------------------------------------
# Per-row payload shift (the log-step shifter behind Split, Merge, recirc)
# --------------------------------------------------------------------------

def _gather_shift(x, shift, left):
    """The formulation the shifter replaced: a clipped per-element gather."""
    col = jnp.arange(x.shape[1])[None, :]
    idx = col + shift[:, None] if left else col - shift[:, None]
    return jnp.take_along_axis(x, jnp.clip(idx, 0, x.shape[1] - 1), axis=1)


def _caller_mask(width, shift, length, left):
    """The bytes a caller keeps of the shifted rows: Split's and recirc's
    ``keep`` (left), Merge's ``col >= shift`` and recirc's ``region``
    (right), each over a row of ``length`` live bytes."""
    col = jnp.arange(width)[None, :]
    if left:
        return col < (length - shift)[:, None]
    return (col >= shift[:, None]) & (col < (length + shift)[:, None])


# (width, bound): Split's pmax-wide rows shifted by up to pass_bytes, the
# lane's and Merge's by up to park_bytes, and a pmax below park_bytes.
_GEOMETRY = {"split": (512, PARK_BYTES_BASE), "merge": (512, PARK_BYTES_RECIRC),
             "pmax_below_park": (128, PARK_BYTES_RECIRC)}


@pytest.mark.parametrize("pattern", ["zero", "one", "bound", "random", "dead"])
@pytest.mark.parametrize("geometry", sorted(_GEOMETRY))
@pytest.mark.parametrize("left", [True, False], ids=["left", "right"])
def test_shift_rows_matches_clipped_gather(left, geometry, pattern):
    """On every byte the callers' masks keep, the shifter equals the clipped
    ``take_along_axis``; the bytes it vacates read 0."""
    width, bound = _GEOMETRY[geometry]
    rows = 24
    rng = np.random.default_rng([int(left), width, bound, len(pattern)])
    length = rng.integers(0, width + 1, rows).astype(np.int32)
    x = rng.integers(0, 256, (rows, width)).astype(np.uint8)
    shift = {"zero": np.zeros(rows), "one": np.ones(rows),
             "bound": np.full(rows, bound),
             "random": rng.integers(0, bound + 1, rows),
             "dead": rng.integers(0, bound + 1, rows)}[pattern]
    alive = (np.zeros(rows, bool) if pattern == "dead"
             else rng.random(rows) < 0.8 if pattern == "random"
             else np.ones(rows, bool))
    x, length, alive = jnp.asarray(x), jnp.asarray(length), jnp.asarray(alive)
    shift = jnp.where(alive, jnp.asarray(shift, jnp.int32), 0)  # dead: 0

    got = _shift_rows(x, shift, bound, left)
    assert got.dtype == jnp.uint8 and got.shape == x.shape
    keep = _caller_mask(width, shift, length, left)

    def caller(shifted):
        return jnp.where(alive[:, None], jnp.where(keep, shifted, 0), x)

    np.testing.assert_array_equal(caller(got),
                                  caller(_gather_shift(x, shift, left)))
    col = jnp.arange(width)[None, :]
    vacated = (col >= width - shift[:, None]) if left else (
        col < shift[:, None])
    assert not bool(jnp.any(jnp.where(vacated, got, 0)))


@pytest.mark.parametrize("recirculation", [False, True], ids=["off", "on"])
@pytest.mark.parametrize("fn", [split_fn, merge_fn, recirc_fn],
                         ids=["split", "merge", "recirc"])
def test_no_per_element_payload_gather(fn, recirculation):
    """Split, Merge and the recirculation pass move payload bytes without a
    gather over the payload buffer, as uint8 or widened (a per-element
    gather there runs at ~90 MB/s on a TPU v5e, DESIGN.md §2)."""
    pmax, batch = 384, 12  # shapes no other operand of these functions has
    cfg = ParkConfig(capacity=32, max_exp=2, pmax=pmax,
                     recirculation=recirculation)
    pkts = make_udp_batch(jax.random.key(0), batch, 300, pmax=pmax)
    text = jax.jit(fn, static_argnums=0).lower(
        cfg, init_state(cfg), pkts).as_text()
    payload_gather = re.compile(
        rf"gather.*\(tensor<{batch}x{pmax}x\w+>")
    assert not [ln for ln in text.splitlines() if payload_gather.search(ln)]
