"""Unified dataplane-backend layer (repro.backend, DESIGN.md §9).

Covers the registry contract (one ref + one Pallas impl per primitive),
BackendConfig resolution, cross-layer parity (ref ≡ pallas_interpret
bit-exact per primitive AND through the full engine), golden vectors
captured from the pre-refactor jnp math (Firewall / MaglevLB / tag CRC must
be unchanged), the removal of the retired ``use_kernel`` kwarg (now a
``TypeError`` everywhere), and the scenario runner's ``backend`` grid axis
with the engine≡loop oracle in both recirculation modes.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.scenarios as S
from repro.backend import (BACKENDS, PRIMITIVES, BackendConfig, as_config,
                           coerce_backend, dispatch, primitive)
from repro.core.header import crc16_tag, tag_valid
from repro.core.packet import make_udp_batch, to_time_major, wire_bytes
from repro.core.park import (ParkConfig, init_state, merge_fn, recirc_fn,
                             split_fn)
from repro.nf.chain import Chain
from repro.nf.firewall import Firewall
from repro.nf.maglev import MaglevLB
from repro.nf.nat import Nat
from repro.switchsim import engine as E
from repro.switchsim.simulate import simulate, simulate_loop


class TestBackendConfig:
    def test_backend_names_validated(self):
        with pytest.raises(ValueError, match="unknown backend"):
            BackendConfig("cuda")
        with pytest.raises(ValueError, match="unknown backend"):
            BackendConfig("ref", (("crc16_tag", "cuda"),))
        with pytest.raises(ValueError, match="unknown primitive"):
            BackendConfig("ref", (("bogus", "ref"),))

    def test_auto_resolves_per_platform(self):
        cfg = BackendConfig("auto")
        want = "pallas" if jax.default_backend() == "tpu" else "ref"
        assert cfg.resolve("crc16_tag") == want
        assert cfg.concrete() == BackendConfig(want)

    def test_overrides_dict_normalized_and_ordered(self):
        a = BackendConfig("ref", {"maglev_select": "pallas_interpret",
                                  "crc16_tag": "pallas_interpret"})
        b = BackendConfig("ref", (("crc16_tag", "pallas_interpret"),
                                  ("maglev_select", "pallas_interpret")))
        # hash() here deliberately exercises BackendConfig's hashability
        # (the jit-static-arg contract); exempt from RPL003 via the
        # replint baseline — nothing persistent is built from the value
        assert a == b and hash(a) == hash(b)
        assert a.resolve("maglev_select") == "pallas_interpret"
        assert a.resolve("payload_store") == "ref"

    def test_concrete_drops_redundant_overrides(self):
        cfg = BackendConfig("pallas_interpret",
                            {"crc16_tag": "pallas_interpret",
                             "acl_match": "ref"})
        assert cfg.concrete() == BackendConfig(
            "pallas_interpret", (("acl_match", "ref"),))

    def test_as_config_spellings(self):
        assert as_config(None) == BackendConfig()
        assert as_config("ref") == BackendConfig("ref")
        cfg = BackendConfig("pallas_interpret")
        assert as_config(cfg) is cfg
        with pytest.raises(TypeError, match="backend must be"):
            as_config(42)

    def test_coerce_is_pure_backend_validation(self):
        assert coerce_backend() == BackendConfig().concrete()
        assert coerce_backend("ref") == BackendConfig("ref")
        assert coerce_backend("auto") == coerce_backend(None)
        with pytest.raises(ValueError, match="unknown backend"):
            coerce_backend("cuda")

    def test_coerce_rejects_retired_use_kernel(self):
        with pytest.raises(TypeError):
            coerce_backend(use_kernel=True)

    def test_registry_matches_the_declared_primitive_set(self):
        assert set(PRIMITIVES) == {"crc16_tag", "acl_match", "maglev_select",
                                   "payload_store", "payload_fetch"}
        for name in PRIMITIVES:
            p = primitive(name)
            assert callable(p.ref) and callable(p.pallas)
        with pytest.raises(KeyError, match="unknown primitive"):
            dispatch("bogus")
        assert "auto" in BACKENDS

    def test_dispatch_ref_returns_the_registry_ref(self):
        assert dispatch("acl_match", "ref") is primitive("acl_match").ref


def _pkts(key=4, n=300, size=300, pmax=512):
    return make_udp_batch(jax.random.key(key), n, size, pmax=pmax)


class TestPrimitiveParity:
    """Every registry primitive: ref ≡ pallas_interpret bit-exact on
    randomized batches (the cross-layer parity satellite)."""

    @pytest.mark.parametrize("n", [1, 5, 300, 1024])
    def test_crc16_tag(self, n):
        ks = jax.random.split(jax.random.key(0), 2)
        ti = jax.random.randint(ks[0], (n,), 0, 1 << 16, dtype=jnp.int32)
        clk = jax.random.randint(ks[1], (n,), 1, 1 << 16, dtype=jnp.int32)
        np.testing.assert_array_equal(
            np.asarray(dispatch("crc16_tag", "ref")(ti, clk)),
            np.asarray(dispatch("crc16_tag", "pallas_interpret")(ti, clk)))

    @pytest.mark.parametrize("b,r", [(7, 1), (500, 20)])
    def test_acl_match(self, b, r):
        ks = jax.random.split(jax.random.key(1), 2)
        ips = jax.random.randint(ks[0], (b,), 0, 60, dtype=jnp.int32)
        rules = jax.random.randint(ks[1], (r,), 0, 60, dtype=jnp.int32)
        got_r = dispatch("acl_match", "ref")(ips, rules)
        got_p = dispatch("acl_match", "pallas_interpret")(ips, rules)
        assert got_r.dtype == got_p.dtype == jnp.bool_
        np.testing.assert_array_equal(np.asarray(got_r), np.asarray(got_p))

    @pytest.mark.parametrize("b", [3, 300])
    def test_maglev_select(self, b):
        lb = MaglevLB()
        st = lb.init_state()
        p = _pkts(n=b)
        args = (p.src_ip, p.dst_ip, p.src_port, p.dst_port, p.proto,
                st["table"], st["backend_ips"])
        np.testing.assert_array_equal(
            np.asarray(dispatch("maglev_select", "ref")(*args)),
            np.asarray(dispatch("maglev_select", "pallas_interpret")(*args)))

    @pytest.mark.parametrize("m,w,b", [(16, 160, 8), (64, 352, 24)])
    def test_payload_store_fetch(self, m, w, b):
        ks = jax.random.split(jax.random.key(2), 4)
        table = jax.random.randint(ks[0], (m, w), 0, 256,
                                   dtype=jnp.int32).astype(jnp.uint8)
        payload = jax.random.randint(ks[1], (b, w), 0, 256,
                                     dtype=jnp.int32).astype(jnp.uint8)
        # unique rows: Split's sequential tagger never hands out the same
        # slot twice in one batch (duplicate-scatter order is unspecified)
        idx = jax.random.permutation(ks[2], m)[:b].astype(jnp.int32)
        enb = jax.random.bernoulli(ks[3], 0.7, (b,))
        t_r = dispatch("payload_store", "ref")(table, payload, idx, enb)
        t_p = dispatch("payload_store", "pallas_interpret")(
            table, payload, idx, enb)
        np.testing.assert_array_equal(np.asarray(t_r), np.asarray(t_p))
        g_r, c_r = dispatch("payload_fetch", "ref")(t_r, idx, enb)
        g_p, c_p = dispatch("payload_fetch", "pallas_interpret")(
            t_p, idx, enb)
        np.testing.assert_array_equal(np.asarray(g_r), np.asarray(g_p))
        np.testing.assert_array_equal(np.asarray(c_r), np.asarray(c_p))


class TestGoldenVectors:
    """Pre-refactor outputs captured from main: the registry's ref impls
    must reproduce the old in-module jnp math bit-for-bit."""

    # crc16_tag(ti = arange(16)*37 % 4096, clk = (arange(16)*101 + 1) % 65536)
    CRC_GOLDEN = [47089, 44615, 18521, 7240, 32657, 27213, 45146, 54014,
                  36192, 60446, 27164, 58320, 9670, 29071, 8083, 50827]
    # make_udp_batch(key(42), 24, 300, pmax=512): Firewall(rules=src_ip[:5])
    # drop mask and MaglevLB() dst_ip rewrites
    FW_GOLDEN = [1, 1, 1, 1, 1] + [0] * 19
    # Re-captured under JAX's current default jax_threefry_partitionable=True.
    # The previous vector (167772420, 167772421, 167772416, ...) is still
    # reproduced exactly with that flag set to False: only the random input
    # batch moved when JAX changed the default, not the Maglev hash or table.
    LB_GOLDEN = [167772419, 167772422, 167772417, 167772421, 167772419,
                 167772416, 167772421, 167772423, 167772420, 167772421,
                 167772420, 167772423, 167772420, 167772420, 167772418,
                 167772423, 167772417, 167772421, 167772417, 167772417,
                 167772419, 167772417, 167772417, 167772419]

    @pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
    def test_crc16_tag_unchanged(self, backend):
        ti = jnp.arange(16, dtype=jnp.int32) * 37 % 4096
        clk = (jnp.arange(16, dtype=jnp.int32) * 101 + 1) % 65536
        got = crc16_tag(ti, clk, backend=backend)
        assert np.asarray(got).tolist() == self.CRC_GOLDEN
        assert bool(jnp.all(tag_valid(ti, clk, got, backend=backend)))

    @pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
    def test_firewall_unchanged(self, backend):
        p = make_udp_batch(jax.random.key(42), 24, 300, pmax=512)
        fw = Firewall(rules=tuple(int(x) for x in
                                  np.asarray(p.src_ip[:5]).tolist()))
        _, out, drop, cycles = fw(fw.init_state(), p, backend=backend)
        assert np.asarray(drop).astype(int).tolist() == self.FW_GOLDEN
        assert cycles == 70.0
        np.testing.assert_array_equal(
            np.asarray(out.alive), ~np.asarray(drop))

    @pytest.mark.parametrize("backend", ["ref", "pallas_interpret"])
    def test_maglev_unchanged(self, backend):
        p = make_udp_batch(jax.random.key(42), 24, 300, pmax=512)
        lb = MaglevLB()
        _, out, _, _ = lb(lb.init_state(), p, backend=backend)
        assert np.asarray(out.dst_ip).tolist() == self.LB_GOLDEN


CFG = ParkConfig(capacity=64, max_exp=2, pmax=1024)


class TestRetiredUseKernel:
    """The ``use_kernel`` kwarg got its one deprecation cycle in PR 5 and
    is now gone end-to-end: every former acceptor raises ``TypeError``."""

    def test_split_merge_recirc_reject_use_kernel(self):
        st0 = init_state(CFG)
        pkts = make_udp_batch(jax.random.key(3), 16, 400, pmax=1024)
        with pytest.raises(TypeError, match="use_kernel"):
            split_fn(CFG, st0, pkts, use_kernel=True)
        st, sent = split_fn(CFG, st0, pkts, backend="pallas_interpret")
        with pytest.raises(TypeError, match="use_kernel"):
            merge_fn(CFG, st, sent, use_kernel=True)
        rc = ParkConfig(capacity=64, max_exp=2, pmax=1024,
                        recirculation=True)
        st_r, sent_r = split_fn(rc, init_state(rc), pkts)
        with pytest.raises(TypeError, match="use_kernel"):
            recirc_fn(rc, st_r, sent_r, use_kernel=False)

    def test_simulate_and_engine_reject_use_kernel(self):
        pkts = make_udp_batch(jax.random.key(5), 64, 300, pmax=512)
        cfg = ParkConfig(capacity=64, max_exp=2, pmax=512)
        chain = Chain((Nat(),))
        with pytest.raises(TypeError, match="use_kernel"):
            simulate(cfg, chain, pkts, window=1, chunk=32, use_kernel=True)
        with pytest.raises(TypeError, match="use_kernel"):
            simulate_loop(cfg, chain, pkts, window=1, chunk=32,
                          use_kernel=False)
        traces = jax.tree.map(lambda a: a[None], to_time_major(pkts, 32))
        with pytest.raises(TypeError, match="use_kernel"):
            E.run_pipes(cfg, chain, traces, window=1, use_kernel=False)
        with pytest.raises(TypeError, match="use_kernel"):
            E.run_engine(cfg, chain, to_time_major(pkts, 32), window=1,
                         use_kernel=True)

    def test_backend_spelling_still_works_everywhere(self):
        pkts = make_udp_batch(jax.random.key(5), 64, 300, pmax=512)
        cfg = ParkConfig(capacity=64, max_exp=2, pmax=512)
        chain = Chain((Nat(),))
        a = simulate(cfg, chain, pkts, window=1, chunk=32,
                     backend="pallas_interpret")
        b = simulate(cfg, chain, pkts, window=1, chunk=32, backend="ref")
        assert a.counters == b.counters
        assert a.telemetry == b.telemetry


class TestEngineBackends:
    def _setup(self, recirc=False):
        pkts = make_udp_batch(jax.random.key(0), 128, 300, pmax=512)
        chain = Chain((Firewall(rules=(int(pkts.src_ip[0]),)), Nat(),
                       MaglevLB()))
        cfg = ParkConfig(capacity=64, max_exp=4, pmax=512,
                         recirculation=recirc)
        return cfg, chain, pkts

    @pytest.mark.parametrize("recirc", [False, True])
    def test_engine_bit_exact_across_backends(self, recirc):
        cfg, chain, pkts = self._setup(recirc)
        tr = to_time_major(pkts, 32)
        res = {b: E.run_engine(cfg, chain, tr, window=1, backend=b)
               for b in ("ref", "pallas_interpret")}
        a, b = res["ref"], res["pallas_interpret"]
        assert a.counters == b.counters
        assert a.telemetry == b.telemetry
        wa = wire_bytes(jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), a.merged))
        wb = wire_bytes(jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), b.merged))
        np.testing.assert_array_equal(np.asarray(wa[0]), np.asarray(wb[0]))
        np.testing.assert_array_equal(np.asarray(wa[1]), np.asarray(wb[1]))

    def test_cycle_costs_probe_through_the_dispatch(self, monkeypatch):
        cfg, chain, pkts = self._setup()
        assert chain.cycle_costs(backend="pallas_interpret") == \
            chain.cycle_costs(backend="ref") == chain.cycle_costs()
        seen = []
        import repro.nf.firewall as fw_mod
        real = fw_mod.dispatch
        monkeypatch.setattr(fw_mod, "dispatch",
                            lambda name, backend=None:
                            (seen.append((name, backend)),
                             real(name, backend))[1])
        chain.cycle_costs(backend="pallas_interpret")
        assert ("acl_match", "pallas_interpret") in seen


class TestScenarioBackendAxis:
    def _grid(self, recirc_vals=(False,)):
        base = S.ScenarioSpec(
            name="", workload=("fixed", 512), chain=("fw", "nat", "lb"),
            capacity=64, packets=128, chunk=32, window=1, pmax=512,
            flows=32, fw_rules=4)
        return S.grid(base, "b_{backend}_r{recirc}",
                      backend=["ref", "pallas_interpret"],
                      recirc=list(recirc_vals))

    def test_backend_is_a_compile_key_axis(self):
        specs = self._grid()
        pkts = S.make_packets(specs[0])
        chain = S.build_chain(specs[0], pkts)
        keys = {S.compile_key(s, chain, 4) for s in specs}
        assert len(keys) == len(specs)  # one compiled program per backend

    def test_batched_equals_solo_with_backend_axis(self):
        """The batched≡solo bit-exactness invariant with ``backend`` as a
        grid axis: every point must equal its solo run_engine on the same
        backend, and the two backends must agree with each other."""
        specs = self._grid()
        results = S.run_matrix(specs)
        from repro.core.packet import to_time_major as ttm
        for spec, res in zip(specs, results):
            pkts = S.make_packets(spec)
            chain = S.build_chain(spec, pkts)
            solo = E.run_engine(spec.park_config(), chain,
                                ttm(pkts, spec.chunk), window=spec.window,
                                backend=spec.backend_config())
            assert res.counters == solo.counters
            assert res.telemetry == solo.telemetry
            assert res.gain == E.goodput_gain(solo)
        a, b = results
        assert a.counters == b.counters and a.telemetry == b.telemetry

    @pytest.mark.parametrize("recirc", [False, True])
    def test_verify_oracle_per_backend_both_recirc_modes(self, recirc):
        for res in S.run_matrix(self._grid(recirc_vals=(recirc,))):
            S.verify_oracle(res)  # raises OracleMismatch on divergence

    def test_backend_recorded_in_spec_provenance(self):
        spec = self._grid()[1]
        assert spec.backend == "pallas_interpret"
        assert spec.as_dict()["backend"] == "pallas_interpret"
        with pytest.raises(ValueError, match="unknown backend"):
            S.ScenarioSpec(name="x", backend="cuda")
