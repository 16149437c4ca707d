"""The paper's GIN through every entry point: integer training and serving.

GIN aggregates first, ``a = Â h + eps·h``, then runs a two-GEMM MLP. On the
integer training path the ``eps·h`` self term is a float epilogue of
``api.nn.qgraph_conv_train`` over the same quantized ``h`` the aggregation
reads, so ``eps`` gets the gradient it gets on the fake-quant path. The
whole-model parity with the fake path is ``test_model_grad_parity[gin]``
in tests/test_int_train.py; here: the layer's exact parity, bit-exact
backends, stochastic rounding's keys, and the two launchers.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import nn as qnn
from repro.core import quantize as Q
from repro.graph import datasets, partition
from repro.launch import serve as serve_launch
from repro.launch import train as train_launch
from repro.models import gnn
from repro.train import intpath, trainer

BACKENDS = ("xla_dot", "popcount", "pallas")


@pytest.fixture(scope="module")
def setup():
    data = datasets.load("proteins", scale=0.05, seed=0)
    parts = partition.partition(data.csr, 8)
    batches = trainer.prepare_batches(data, parts, batch_size=4)
    return data, batches


def _dense_adj(batch):
    e = np.asarray(batch.edges)
    live = e[0] >= 0
    adj = np.zeros((batch.n_nodes, batch.n_nodes), np.float32)
    adj[e[0][live], e[1][live]] = 1.0
    return jnp.asarray(adj)


@pytest.mark.parametrize("bits", [2, 4, 8])
def test_gin_conv_eps_grad_parity_with_fake_quant(setup, bits):
    # a = (A+I) u_dq * inv_deg + eps * u_dq on both paths, over the same
    # quantized u: they differ only in summation order (exact integer
    # neighbour counts against a float matmul), so float32 rounding bounds
    # the gap, as in test_qgraph_conv_grad_parity
    _, batches = setup
    batch = batches[0]
    art = intpath.build_artifacts(batch, bits)
    adj = _dense_adj(batch)
    rng = np.random.default_rng(bits)
    u = jnp.asarray(rng.uniform(-2, 2, (batch.n_nodes, 8)).astype(np.float32))
    r = jnp.asarray(rng.uniform(-1, 1, u.shape).astype(np.float32))
    eps = jnp.float32(0.3)

    def loss_int(u, eps):
        return jnp.sum(qnn.qgraph_conv_train(u, art, eps=eps,
                                             x_bits=bits) * r)

    def loss_fake(u, eps):
        uq = Q.fake_quant(u, bits)
        return jnp.sum(((adj @ uq + uq) * art.inv_deg + eps * uq) * r)

    with jax.default_matmul_precision("highest"):
        vi, (gu_i, ge_i) = jax.value_and_grad(loss_int, (0, 1))(u, eps)
        vf, (gu_f, ge_f) = jax.value_and_grad(loss_fake, (0, 1))(u, eps)
    np.testing.assert_allclose(float(vi), float(vf), rtol=1e-4, atol=1e-2)
    np.testing.assert_allclose(np.asarray(gu_i), np.asarray(gu_f),
                               rtol=1e-3, atol=1e-3)
    # eps's gradient is sum(g * u_dq) over the same u_dq on both paths
    np.testing.assert_allclose(float(ge_i), float(ge_f), rtol=1e-5)


def test_gin_conv_pre_quantized_input_matches_float_input(setup):
    # layer 0 aggregates the batch features as art holds them, (xq, qpx):
    # the same integers as quantizing the float features in the layer
    _, batches = setup
    batch = batches[0]
    art = intpath.build_artifacts(batch, 8)
    x = jnp.asarray(batch.features)
    eps = jnp.float32(0.25)
    got = qnn.qgraph_conv_train((art.xq, art.qpx), art, eps=eps, x_bits=8)
    want = qnn.qgraph_conv_train(x, art, eps=eps, x_bits=8)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # no gradient reaches pre-quantized features; eps still gets its own
    g_eps = jax.grad(lambda e: jnp.sum(qnn.qgraph_conv_train(
        (art.xq, art.qpx), art, eps=e, x_bits=8)))(eps)
    assert np.isfinite(float(g_eps)) and float(g_eps) != 0.0


def _gin(data, bits=8):
    cfg = gnn.GNNConfig.paper_gin(data.features.shape[1], data.n_classes,
                                  x_bits=bits, w_bits=bits)
    params = gnn.init_params(jax.random.PRNGKey(1), cfg)
    params = {k: dict(v, eps=jnp.float32(0.2)) for k, v in params.items()}
    return cfg, params


def _loss(params, art, y, cfg, **kw):
    logits = gnn.forward_int(params, art, cfg, **kw)
    lp = jax.nn.log_softmax(logits, -1)
    ll = jnp.take_along_axis(lp, jnp.clip(y, 0)[:, None], -1)[:, 0]
    return -jnp.mean(jnp.where(y >= 0, ll, 0.0)), logits


@pytest.mark.parametrize("grad_bits", [0, 8])
def test_gin_forward_int_backends_bit_exact_with_sr_off(setup, grad_bits):
    # the integer products are exact, so with deterministic rounding every
    # backend gives identical logits and gradients (grad_bits=8 runs the
    # backward GEMMs as integer products too)
    data, batches = setup
    batch = batches[0]
    cfg, params = _gin(data)
    art = intpath.build_artifacts(batch, cfg.x_bits)
    y = jnp.asarray(batch.labels)
    out = {}
    for be in BACKENDS:
        (_, logits), grads = jax.value_and_grad(_loss, has_aux=True)(
            params, art, y, cfg, grad_bits=grad_bits, backend=be)
        out[be] = [np.asarray(a) for a in
                   jax.tree_util.tree_leaves((logits, grads))]
    for be in BACKENDS[1:]:
        for a, b in zip(out[be], out[BACKENDS[0]]):
            np.testing.assert_array_equal(a, b)


def test_gin_stochastic_needs_a_key_and_is_deterministic_per_key(setup):
    data, batches = setup
    cfg, params = _gin(data)
    art = intpath.build_artifacts(batches[0], cfg.x_bits)
    with pytest.raises(ValueError, match="key"):
        gnn.forward_int(params, art, cfg, stochastic=True)
    k = jax.random.PRNGKey(7)
    a = gnn.forward_int(params, art, cfg, stochastic=True, key=k)
    b = gnn.forward_int(params, art, cfg, stochastic=True, key=k)
    c = gnn.forward_int(params, art, cfg, stochastic=True,
                        key=jax.random.PRNGKey(8))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))


def test_train_launcher_int_path_trains_gin(capsys):
    out = train_launch.main(["--arch", "qgtc-gin", "--smoke", "--int-path",
                             "--steps", "6", "--log-every", "1",
                             "--grad-bits", "8"])
    losses = [h["loss"] for h in out["history"]]
    assert len(losses) == 6 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("arch,model", [(None, "gcn"), ("qgtc-gin", "gin")])
def test_serve_launcher_serves_the_gnn_named_by_arch(capsys, arch, model):
    argv = ["--gnn", "ogbn-arxiv", "--scale", "0.004", "--parts", "4",
            "--rounds", "2", "--smoke"]
    summary = serve_launch.main(argv + (["--arch", arch] if arch else []))
    assert summary["model"] == model
    assert summary["requests"] == 2 * 4  # every request of both rounds
    assert summary["requests_shed"] == 0


@pytest.mark.parametrize("argv", [
    ["--arch", "qgtc-gin"],                        # a GNN needs --gnn
    ["--gnn", "ogbn-arxiv", "--arch", "rwkv6-1.6b"],  # an LM is not a GNN
    [],
])
def test_serve_launcher_rejects_a_mismatched_arch(capsys, argv):
    with pytest.raises(SystemExit) as e:
        serve_launch.main(argv)
    assert e.value.code == 2
