"""Property tests for core/quantize.py (hypothesis).

The quantizer is the foundation both training paths stand on, so these
pin its contract rather than example values: round-trip error bounded by
one step, clipping at the q-bit range, degenerate tensors (constant /
single-element) staying finite, bounded fake-quant drift, the STE gate,
and stochastic rounding staying within one level of deterministic
rounding while killing its systematic bias.
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, strategies as st

from repro.core import quantize as Q


def _arr(seed, n, lo=-4.0, hi=4.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.uniform(lo, hi, n).astype(np.float32))


@given(st.integers(2, 8), st.integers(0, 2**31 - 1), st.integers(2, 64))
def test_roundtrip_error_bounded_by_one_step(nbits, seed, n):
    x = _arr(seed, n)
    qp = Q.calibrate(x, nbits)
    q = Q.quantize(x, qp)
    assert q.dtype == jnp.int32
    assert 0 <= int(q.min()) and int(q.max()) <= qp.qmax
    deq = Q.dequantize(q, qp)
    err = jnp.abs(deq - x)
    # one step, plus float32 rounding of x - zero and q * scale + zero at
    # the magnitudes involved (a few ulps of the largest operand)
    mag = float(jnp.maximum(jnp.abs(x), jnp.abs(deq)).max()
                + jnp.abs(qp.zero))
    assert float(err.max()) <= float(qp.scale) + 4 * np.finfo(np.float32).eps * mag


@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_out_of_range_inputs_clip_to_qbit_range(nbits, seed):
    x = _arr(seed, 32)
    qp = Q.calibrate(x, nbits)
    far = jnp.concatenate([x - 100.0, x, x + 100.0])
    q = Q.quantize(far, qp)
    assert 0 <= int(q.min()) and int(q.max()) <= qp.qmax
    assert int(Q.quantize(jnp.max(x) + 100.0, qp)) == qp.qmax
    assert int(Q.quantize(jnp.min(x) - 100.0, qp)) == 0


@given(st.integers(2, 8), st.integers(-8, 8))
def test_constant_tensor_has_finite_scale_and_exact_roundtrip(nbits, value):
    x = jnp.full((5,), float(value), jnp.float32)
    qp = Q.calibrate(x, nbits)
    assert np.isfinite(float(qp.scale)) and float(qp.scale) > 0
    q = Q.quantize(x, qp)
    assert int(q.min()) == int(q.max()) == 0
    assert float(jnp.abs(Q.dequantize(q, qp) - x).max()) <= 1e-6


@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_single_element_tensor(nbits, seed):
    x = _arr(seed, 1)
    qp = Q.calibrate(x, nbits)
    assert np.isfinite(float(qp.scale))
    y = Q.fake_quant(x, nbits, qp)
    assert float(jnp.abs(y - x).max()) <= float(qp.scale) * (1 + 1e-5)


@given(st.integers(2, 8), st.integers(0, 2**31 - 1), st.booleans())
def test_fake_quant_drift_bounded_by_one_step(nbits, seed, recalibrate):
    # exact idempotence does not survive float rounding (floor((q*s)/s) can
    # land on q-1), but the second pass may move at most one step — and
    # with re-calibration the step only shrinks
    x = _arr(seed, 64)
    qp = None if recalibrate else Q.calibrate(x, nbits)
    y1 = Q.fake_quant(x, nbits, qp)
    y2 = Q.fake_quant(y1, nbits, qp)
    step = float(Q.calibrate(x, nbits).scale)
    assert float(jnp.abs(y2 - y1).max()) <= step * (1 + 1e-5)


@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_ste_gradient_is_indicator_of_clip_range(nbits, seed):
    x = _arr(seed, 64)
    qp = Q.calibrate(x[:32], nbits)  # half-range calibration => real clipping
    g = jax.grad(lambda v: jnp.sum(Q.fake_quant(v, nbits, qp)))(x)
    lo = float(qp.zero)
    hi = float(qp.zero + qp.scale * (qp.qmax + 1))  # STRICT upper bound
    inside = (np.asarray(x) >= lo) & (np.asarray(x) < hi)
    np.testing.assert_array_equal(np.asarray(g), inside.astype(np.float32))


@given(st.integers(2, 8), st.integers(0, 2**31 - 1), st.integers(0, 7))
def test_stochastic_rounding_within_one_level_and_deterministic_per_key(
        nbits, seed, key_seed):
    x = _arr(seed, 128)
    qp = Q.calibrate(x, nbits)
    key = jax.random.PRNGKey(key_seed)
    qs = Q.quantize_stochastic(x, qp, key)
    qd = Q.quantize(x, qp)
    assert qs.dtype == jnp.int32
    assert 0 <= int(qs.min()) and int(qs.max()) <= qp.qmax
    assert int(jnp.abs(qs - qd).max()) <= 1  # floor vs floor(+u): one level
    assert bool(jnp.all(qs == Q.quantize_stochastic(x, qp, key)))


def test_stochastic_rounding_is_unbiased_where_floor_is_not():
    # fixed grid, interior points (clipping would re-introduce bias at the
    # extremes): the SR mean converges to x, deterministic floor does not
    qp = Q.QuantParams(nbits=4, scale=jnp.float32(0.125),
                       zero=jnp.float32(-1.0))
    x = jnp.linspace(-0.9, 0.7, 41).astype(jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(0), 2048)
    deq = jax.vmap(
        lambda k: Q.dequantize(Q.quantize_stochastic(x, qp, k), qp))(keys)
    sr_bias = float(jnp.abs(deq.mean(0) - x).max())
    det_bias = float(jnp.abs(Q.dequantize(Q.quantize(x, qp), qp) - x).max())
    assert sr_bias < 0.02
    assert sr_bias < det_bias / 3


@given(st.integers(2, 8), st.integers(0, 2**31 - 1))
def test_affine_correction_recovers_dequantized_matmul(nbits, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.uniform(-2, 2, (8, 16)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-2, 2, (16, 4)).astype(np.float32))
    qa, qb = Q.calibrate(a, nbits), Q.calibrate(b, nbits)
    aq, bq = Q.quantize(a, qa), Q.quantize(b, qb)
    got = Q.affine_matmul_correction(aq, bq, qa, qb, aq @ bq)
    want = Q.dequantize(aq, qa) @ Q.dequantize(bq, qb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-3)
