"""Gradient-parity oracle + convergence regression for the int training path.

The contract the int path stakes its accuracy claim on: with
``grad_bits=0`` and stochastic rounding OFF, the integer forward's
gradients are the fake-quant path's gradients (float backward over the
same quantized operands, same STE gates). The oracle checks it layer by
layer at 2–8 bits across all backends; a seeded ≤30-step training run then
pins end-to-end convergence of both paths.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import nn as qnn
from repro.core import quantize as Q
from repro.graph import datasets, partition
from repro.models import gnn
from repro.train import intpath, trainer

BACKENDS = ("xla_dot", "popcount", "pallas")


@pytest.fixture(scope="module")
def setup():
    data = datasets.load("proteins", scale=0.05, seed=0)
    parts = partition.partition(data.csr, 8)
    batches = trainer.prepare_batches(data, parts, batch_size=4)
    bp, rp = intpath.batch_caps(batches)
    art = intpath.build_artifacts(batches[0], 4, block_pad=bp, rem_pad=rp)
    return data, parts, batches, art


def _fake_linear(h, w, b, x_bits, w_bits):
    return Q.fake_quant(h, x_bits) @ Q.fake_quant(w, w_bits) + b


def _fake_conv(u, adj, inv_deg, x_bits):
    uq = Q.fake_quant(u, x_bits)
    return (adj @ uq + uq) * inv_deg


def _dense_adj(batch):
    e = np.asarray(batch.edges)
    live = e[0] >= 0
    adj = np.zeros((batch.n_nodes, batch.n_nodes), np.float32)
    adj[e[0][live], e[1][live]] = 1.0
    return jnp.asarray(adj)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("backend", BACKENDS)
def test_qlinear_grad_parity_with_fake_quant(bits, backend):
    rng = np.random.default_rng(bits)
    h = jnp.asarray(rng.uniform(-2, 2, (48, 24)).astype(np.float32))
    w = jnp.asarray(rng.uniform(-1, 1, (24, 12)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-1, 1, 12).astype(np.float32))
    r = jnp.asarray(rng.uniform(-1, 1, (48, 12)).astype(np.float32))

    def loss_int(h, w, b):
        return jnp.sum(qnn.qlinear_train(h, w, b, x_bits=bits, w_bits=bits,
                                         backend=backend) * r)

    def loss_fake(h, w, b):
        return jnp.sum(_fake_linear(h, w, b, bits, bits) * r)

    vi, gi = jax.value_and_grad(loss_int, argnums=(0, 1, 2))(h, w, b)
    vf, gf = jax.value_and_grad(loss_fake, argnums=(0, 1, 2))(h, w, b)
    np.testing.assert_allclose(float(vi), float(vf), rtol=1e-4, atol=1e-3)
    for got, want in zip(gi, gf):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-3, atol=2e-3)


@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("backend", BACKENDS)
def test_qgraph_conv_grad_parity_with_fake_quant(setup, bits, backend):
    _, _, batches, _ = setup
    batch = batches[0]
    art = intpath.build_artifacts(batch, bits)
    adj = _dense_adj(batch)
    rng = np.random.default_rng(bits)
    u = jnp.asarray(rng.uniform(-2, 2, (batch.n_nodes, 8)).astype(np.float32))
    r = jnp.asarray(rng.uniform(-1, 1, u.shape).astype(np.float32))

    def loss_int(u):
        return jnp.sum(qnn.qgraph_conv_train(u, art, x_bits=bits,
                                             backend=backend) * r)

    def loss_fake(u):
        return jnp.sum(_fake_conv(u, adj, art.inv_deg, bits) * r)

    vi, gi = jax.value_and_grad(loss_int)(u)
    vf, gf = jax.value_and_grad(loss_fake)(u)
    np.testing.assert_allclose(float(vi), float(vf), rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(gi), np.asarray(gf),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_backends_bit_exact_with_sr_off(setup, bits):
    # the integer products are exact, so with deterministic rounding every
    # backend must produce IDENTICAL floats (same epilogue over same int32s)
    _, _, batches, _ = setup
    batch = batches[0]
    art = intpath.build_artifacts(batch, bits)
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.uniform(-2, 2, (32, 16)).astype(np.float32))
    w = jnp.asarray(rng.uniform(-1, 1, (16, 8)).astype(np.float32))
    u = jnp.asarray(rng.uniform(-2, 2, (batch.n_nodes, 8)).astype(np.float32))
    lin = {be: np.asarray(qnn.qlinear_train(h, w, x_bits=bits, w_bits=bits,
                                            backend=be)) for be in BACKENDS}
    conv = {be: np.asarray(qnn.qgraph_conv_train(u, art, x_bits=bits,
                                                 backend=be))
            for be in BACKENDS}
    for be in BACKENDS[1:]:
        np.testing.assert_array_equal(lin[be], lin[BACKENDS[0]])
        np.testing.assert_array_equal(conv[be], conv[BACKENDS[0]])


@pytest.mark.parametrize("model", ["gcn", "gin"])
def test_model_grad_parity(setup, model):
    # whole-model oracle: forward_int with grad_bits=0 vs the fake path on
    # the SAME pre-quantized layer-0 input, gradients within float-assoc.
    # The same tolerances hold for GIN's three requantizations a layer: the
    # two paths sum the same dequantized values in another order, so a
    # value on a floor boundary of the grid can land one step apart, and
    # GIN's gradients here differ by up to 4.0e-3 (eps's included), under
    # atol 5e-3. eps's gradient alone is pinned exactly at the layer, in
    # tests/test_gin_support.py.
    data, _, batches, art = setup
    batch = batches[0]
    make = (gnn.GNNConfig.paper_gcn if model == "gcn"
            else gnn.GNNConfig.paper_gin)
    cfg = make(data.features.shape[1], data.n_classes, x_bits=4, w_bits=4)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    if model == "gin":
        # a non-zero self weight per layer, so eps's term and gradient count
        params = {k: dict(v, eps=jnp.float32(0.1 * (i + 1)))
                  for i, (k, v) in enumerate(params.items())}
    adj = _dense_adj(batch)
    # raw features: fake_quant(x) calibrates the same grid build_artifacts
    # did, so layer 0 sees identical quantized values on both paths
    x = jnp.asarray(batch.features)
    y = jnp.asarray(batch.labels)
    mask = jnp.asarray(batch.train_mask)

    def loss(p, path):
        if path == "int":
            logits = gnn.forward_int(p, art, cfg)
        else:
            logits = gnn.forward(p, adj, x, art.inv_deg, cfg,
                                 path="fp32_dense", fake_bits=True)
        valid = (y >= 0) & mask
        lp = jax.nn.log_softmax(logits, -1)
        ll = jnp.take_along_axis(lp, jnp.clip(y, 0)[:, None], -1)[:, 0]
        return -jnp.sum(jnp.where(valid, ll, 0.0)) / jnp.maximum(
            jnp.sum(valid), 1)

    with jax.default_matmul_precision("highest"):
        vi, gi = jax.value_and_grad(lambda p: loss(p, "int"))(params)
        vf, gf = jax.value_and_grad(lambda p: loss(p, "fake"))(params)
    np.testing.assert_allclose(float(vi), float(vf), rtol=1e-3, atol=1e-3)
    flat_i = jax.tree_util.tree_leaves(gi)
    flat_f = jax.tree_util.tree_leaves(gf)
    assert len(flat_i) == len(flat_f) == len(jax.tree_util.tree_leaves(params))
    for a, b in zip(flat_i, flat_f):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_stochastic_requires_key_and_is_deterministic_per_key():
    rng = np.random.default_rng(0)
    h = jnp.asarray(rng.uniform(-2, 2, (16, 8)).astype(np.float32))
    w = jnp.asarray(rng.uniform(-1, 1, (8, 4)).astype(np.float32))
    with pytest.raises(ValueError, match="key"):
        qnn.qlinear_train(h, w, stochastic=True)
    k = jax.random.PRNGKey(3)
    a = qnn.qlinear_train(h, w, stochastic=True, key=k)
    b = qnn.qlinear_train(h, w, stochastic=True, key=k)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_convergence_regression_both_paths(setup):
    # seeded 30-step CPU regression: both paths must converge to matched
    # train loss / test accuracy — the accuracy half of the int-path claim
    data, parts, _, _ = setup
    cfg = gnn.GNNConfig.paper_gcn(data.features.shape[1], data.n_classes,
                                  x_bits=4, w_bits=4)
    acc, hist = {}, {}
    for arm, tcfg in {
        "fake": trainer.TrainConfig(steps=30, log_every=29, seed=0),
        "int": trainer.TrainConfig(steps=30, log_every=29, seed=0,
                                   path="int_bitserial"),
    }.items():
        params, _, h = trainer.train(data, parts, cfg, tcfg, batch_size=4)
        hist[arm] = h
        acc[arm] = trainer.evaluate(
            params, data, parts, cfg, qat=True,
            path="int_bitserial" if arm == "int" else "fp32_dense")
    for arm in ("fake", "int"):
        assert np.isfinite(hist[arm][-1]["loss"])
        assert hist[arm][-1]["loss"] < hist[arm][0]["loss"] * 0.6, arm
    assert acc["int"] >= acc["fake"] - 0.05
