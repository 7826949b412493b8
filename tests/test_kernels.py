"""Per-kernel allclose vs the pure-jnp oracle, with shape/dtype sweeps
(interpret=True, passed explicitly, executes the Pallas kernel bodies on
CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.acl_match.ops import acl_match
from repro.kernels.acl_match.ref import acl_match_ref
from repro.kernels.crc16.ops import crc16_tag_kernel_op
from repro.kernels.crc16.ref import crc16_tag_ref
from repro.kernels.maglev.ops import maglev_select
from repro.kernels.maglev.ref import maglev_select_ref
from repro.kernels.paged_attention.ops import paged_decode_attention
from repro.kernels.paged_attention.ref import paged_decode_attention_ref
from repro.kernels.payload_fetch.ops import payload_fetch
from repro.kernels.payload_fetch.ref import payload_fetch_ref
from repro.kernels.payload_store.ops import payload_store
from repro.kernels.payload_store.ref import payload_store_ref
from repro.kernels.payload_store.ops import _to_words, _to_bytes


def rand_table(key, m, nbytes):
    return jax.random.randint(key, (m, nbytes), 0, 256,
                              dtype=jnp.int32).astype(jnp.uint8)


@pytest.mark.parametrize("m,nbytes,b", [(16, 160, 8), (64, 352, 24),
                                        (128, 160, 128), (32, 32, 5)])
def test_payload_store_sweep(m, nbytes, b):
    ks = jax.random.split(jax.random.key(0), 4)
    table = rand_table(ks[0], m, nbytes)
    payload = rand_table(ks[1], b, nbytes)
    idx = jax.random.permutation(ks[2], m)[:b] if b <= m else \
        jnp.arange(b) % m
    enb = jax.random.bernoulli(ks[3], 0.7, (b,))
    got = payload_store(table, payload, idx, enb, interpret=True)
    want_w = payload_store_ref(_to_words(table), _to_words(payload), idx, enb)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(_to_bytes(want_w, nbytes)))


@pytest.mark.parametrize("m,nbytes,b", [(16, 160, 8), (64, 352, 24),
                                        (128, 160, 128)])
def test_payload_fetch_sweep(m, nbytes, b):
    ks = jax.random.split(jax.random.key(1), 3)
    table = rand_table(ks[0], m, nbytes)
    idx = jax.random.permutation(ks[1], m)[:b] if b <= m else \
        jnp.arange(b) % m
    mask = jax.random.bernoulli(ks[2], 0.6, (b,))
    got_rows, got_table = payload_fetch(table, idx, mask, interpret=True)
    want_rows_w, want_table_w = payload_fetch_ref(_to_words(table), idx, mask)
    np.testing.assert_array_equal(
        np.asarray(got_rows), np.asarray(_to_bytes(want_rows_w, nbytes)))
    np.testing.assert_array_equal(
        np.asarray(got_table), np.asarray(_to_bytes(want_table_w, nbytes)))


@pytest.mark.parametrize("n", [1, 7, 1000, 1024])
def test_crc16_sweep(n):
    ks = jax.random.split(jax.random.key(2), 2)
    ti = jax.random.randint(ks[0], (n,), 0, 1 << 16, dtype=jnp.int32)
    clk = jax.random.randint(ks[1], (n,), 1, 1 << 16, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(crc16_tag_kernel_op(ti, clk, interpret=True)),
        np.asarray(crc16_tag_ref(ti, clk)))


def test_crc16_known_vector():
    # CRC-16/CCITT-FALSE("123456789") = 0x29B1; check our byte routine
    from repro.core.header import crc16_bytes
    data = jnp.asarray([ord(c) for c in "123456789"], jnp.int32)
    assert int(crc16_bytes(data)) == 0x29B1


@pytest.mark.parametrize("b,r", [(5, 1), (500, 20), (1024, 4)])
def test_acl_match_sweep(b, r):
    ks = jax.random.split(jax.random.key(3), 2)
    ips = jax.random.randint(ks[0], (b,), 0, 50, dtype=jnp.int32)
    rules = jax.random.randint(ks[1], (r,), 0, 50, dtype=jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(acl_match(ips, rules, interpret=True)),
        np.asarray(acl_match_ref(ips, rules)))


@pytest.mark.parametrize("b", [3, 300])
def test_maglev_sweep(b):
    from repro.nf.maglev import MaglevLB
    from repro.core.packet import make_udp_batch
    lb = MaglevLB()
    st = lb.init_state()
    p = make_udp_batch(jax.random.key(4), b, 300, pmax=512)
    got = maglev_select(p.src_ip, p.dst_ip, p.src_port, p.dst_port, p.proto,
                        st["table"], st["backend_ips"], interpret=True)
    want = maglev_select_ref(p.src_ip, p.dst_ip, p.src_port, p.dst_port,
                             p.proto, st["table"], st["backend_ips"])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("b,k,g,e,page,mp", [
    (4, 2, 4, 64, 16, 6),
    (2, 1, 8, 128, 128, 4),
    (8, 4, 1, 32, 8, 3),
])
def test_paged_attention_sweep(b, k, g, e, page, mp):
    npages = mp * b + 2
    ks = jax.random.split(jax.random.key(5), 5)
    q = jax.random.normal(ks[0], (b, k, g, e)).astype(jnp.bfloat16)
    kp = jax.random.normal(ks[1], (npages, page, k, e)).astype(jnp.bfloat16)
    vp = jax.random.normal(ks[2], (npages, page, k, e)).astype(jnp.bfloat16)
    rng = np.random.default_rng(0)
    pt = np.full((b, mp), -1, np.int32)
    lengths = np.zeros((b,), np.int32)
    for i in range(b):
        n = rng.integers(1, mp + 1)
        pt[i, :n] = rng.choice(npages, n, replace=False)
        lengths[i] = rng.integers(1, n * page + 1)
    got = paged_decode_attention(q, kp, vp, jnp.asarray(pt),
                                 jnp.asarray(lengths), interpret=True)
    want = paged_decode_attention_ref(q, kp, vp, jnp.asarray(pt),
                                      jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=0.02, rtol=0.05)
