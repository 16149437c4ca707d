"""Property tests: the 1-bit composition arithmetic is EXACT (paper §3)."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import bitops

settings.register_profile("ci", max_examples=25, deadline=None)
settings.load_profile("ci")


@st.composite
def qmat_pair(draw):
    s = draw(st.integers(1, 8))
    t = draw(st.integers(1, 8))
    m = draw(st.integers(1, 24))
    k = draw(st.integers(1, 96))
    n = draw(st.integers(1, 24))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << s, (m, k)).astype(np.int32)
    b = rng.integers(0, 1 << t, (k, n)).astype(np.int32)
    return s, t, a, b


@given(qmat_pair())
def test_bitserial_dot_exact(pair):
    s, t, a, b = pair
    want = a.astype(np.int64) @ b.astype(np.int64)
    got = bitops.bitserial_matmul_planes(jnp.asarray(a), jnp.asarray(b), s, t)
    np.testing.assert_array_equal(np.asarray(got), want)


@given(qmat_pair())
def test_bitserial_popcount_exact(pair):
    s, t, a, b = pair
    want = a.astype(np.int64) @ b.astype(np.int64)
    got = bitops.bitserial_matmul_packed(
        bitops.pack_a(jnp.asarray(a), s), bitops.pack_b(jnp.asarray(b), t))
    np.testing.assert_array_equal(np.asarray(got)[: a.shape[0], : b.shape[1]],
                                  want)


@given(st.integers(1, 8), st.integers(1, 40), st.integers(1, 130),
       st.integers(0, 2**31 - 1))
def test_pack_unpack_roundtrip(nbits, m, k, seed):
    rng = np.random.default_rng(seed)
    q = rng.integers(0, 1 << nbits, (m, k)).astype(np.int32)
    planes = bitops.bit_decompose(jnp.asarray(q), nbits)
    packed = bitops.pack_along_axis(planes, axis=-1)
    unpacked = bitops.unpack_along_axis(packed, axis=-1, size=k)
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(planes))
    np.testing.assert_array_equal(
        np.asarray(bitops.bit_compose(unpacked)), q)


@given(st.integers(1, 8), st.integers(1, 20), st.integers(1, 70),
       st.integers(0, 2**31 - 1))
def test_pack_a_pack_b_consistent(nbits, m, k, seed):
    """Column-wise A packing and row-wise B packing meet in the GEMM."""
    rng = np.random.default_rng(seed)
    a = rng.integers(0, 1 << nbits, (m, k)).astype(np.int32)
    b = rng.integers(0, 1 << nbits, (k, m)).astype(np.int32)
    got = bitops.bitserial_matmul_packed(
        bitops.pack_a(jnp.asarray(a), nbits), bitops.pack_b(jnp.asarray(b), nbits))
    np.testing.assert_array_equal(np.asarray(got), a.astype(np.int64) @ b)


def test_popcount_matmul_matches_binary_dot():
    rng = np.random.default_rng(0)
    a = (rng.random((37, 300)) < 0.3).astype(np.int32)
    b = (rng.random((300, 41)) < 0.6).astype(np.int32)
    ap = bitops.pack_a(jnp.asarray(a), 1)[0]
    bp = bitops.pack_b(jnp.asarray(b), 1)[0]
    got = bitops.popcount_matmul_packed(ap, bp)
    np.testing.assert_array_equal(np.asarray(got), a @ b)


def test_np_pack_words_matches_jax():
    rng = np.random.default_rng(3)
    bits = (rng.random((5, 77)) < 0.5).astype(np.int32)
    np_packed = bitops.np_pack_words(bits)
    jx_packed = bitops.pack_along_axis(jnp.asarray(bits), axis=-1)
    np.testing.assert_array_equal(np_packed, np.asarray(jx_packed))


@pytest.mark.parametrize("nbits", [3, 8, 11])
def test_np_pack_planes_matches_pack_a(nbits):
    """The host plane packer equals the JAX column-wise packer (D=77)."""
    rng = np.random.default_rng(nbits)
    q = rng.integers(0, 1 << nbits, (9, 77))
    dtype = np.uint8 if nbits <= 8 else np.uint16
    got = bitops.np_pack_planes(q.astype(dtype), nbits)
    want = bitops.pack_a(jnp.asarray(q, jnp.int32), nbits)
    assert got.shape == (nbits, 9, 3)
    np.testing.assert_array_equal(got, np.asarray(want))
