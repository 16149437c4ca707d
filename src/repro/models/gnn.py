"""GNN models: Cluster-GCN and Batched GIN (paper §6.1 benchmarks).

Each model has three execution paths sharing one parameter pytree:

  fp32_dense — dense-adjacency fp32 matmuls (the "DGL dense" baseline)
  fp32_csr   — gather/segment-sum aggregation over the edge list (the
               DGL/PyG scatter-kernel analogue)
  qgtc       — the paper's path: binary adjacency, any-bit quantized
               activations/weights, integer bit-serial GEMMs with float
               rescale epilogues (Algorithm 1 + §4.5). Hidden layers
               requantize; only the final layer emits full precision.
  int_bitserial — the TRAINING twin of qgtc, for GCN and GIN: same
               integer forward, but differentiable (api.nn.qlinear_train /
               qgraph_conv_train custom_vjps with STE backward, optional
               quantized gradients + stochastic rounding) and fed by
               per-batch cached IntBatchArtifacts (repro.train.intpath)
               instead of a dense adjacency rebuilt every step.

The qgtc path is built from the functional layers in ``repro.api.nn``
(``qlinear`` / ``qgraph_conv``), which dispatch through the repro.api
backend registry: pick the execution engine with
``with repro.api.use("pallas", policy=...)`` or pass ``backend=``/
``policy=`` to ``forward_qgtc``. (GNNConfig used to carry an ``impl``
string; execution strategy now lives in the api layer, not the model
config.)

QAT (fake-quant, STE) runs on the fp32 graph; the integer path consumes the
same weights post-quantization, and tests assert the two agree within
accumulated rounding.

Model settings follow the paper: Cluster-GCN updates-then-aggregates
(X' = Â (X W), 3 layers, 16 hidden); GIN aggregates-then-updates with a
2-layer MLP (3 layers, 64 hidden).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.api import nn as qnn
from repro.core.quantize import QuantParams, calibrate, fake_quant, quantize
from repro.models.layers import constrain  # no-op outside repro.dist shard_ctx

__all__ = ["GNNConfig", "init_params", "forward", "forward_int",
           "forward_qgtc", "quantize_params"]


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    model: str = "gcn"  # gcn | gin
    in_dim: int = 128
    hidden: int = 16
    n_classes: int = 40
    layers: int = 3
    x_bits: int = 8  # activation bits (paper's s)
    w_bits: int = 8  # weight bits (paper's t)
    gin_eps: float = 0.0

    @staticmethod
    def paper_gcn(in_dim: int, n_classes: int, x_bits=8, w_bits=8) -> "GNNConfig":
        return GNNConfig("gcn", in_dim, 16, n_classes, 3, x_bits, w_bits)

    @staticmethod
    def paper_gin(in_dim: int, n_classes: int, x_bits=8, w_bits=8) -> "GNNConfig":
        return GNNConfig("gin", in_dim, 64, n_classes, 3, x_bits, w_bits)


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    s = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, jnp.float32) * s


def init_params(key: jax.Array, cfg: GNNConfig) -> dict:
    dims = [cfg.in_dim] + [cfg.hidden] * (cfg.layers - 1) + [cfg.n_classes]
    params = {}
    keys = jax.random.split(key, cfg.layers * 2)
    for l in range(cfg.layers):
        d_in, d_out = dims[l], dims[l + 1]
        if cfg.model == "gin":
            params[f"layer{l}"] = {
                "w1": _glorot(keys[2 * l], (d_in, max(d_out, cfg.hidden))),
                "b1": jnp.zeros((max(d_out, cfg.hidden),), jnp.float32),
                "w2": _glorot(keys[2 * l + 1], (max(d_out, cfg.hidden), d_out)),
                "b2": jnp.zeros((d_out,), jnp.float32),
                "eps": jnp.asarray(cfg.gin_eps, jnp.float32),
            }
        else:
            params[f"layer{l}"] = {
                "w": _glorot(keys[2 * l], (d_in, d_out)),
                "b": jnp.zeros((d_out,), jnp.float32),
            }
    return params


# ---------------------------------------------------------------- fp32 paths

def _aggregate_dense(adj_bin: jax.Array, h: jax.Array, inv_deg: jax.Array) -> jax.Array:
    """Â h with Â = (D+I)^-1 (A+I); adj_bin excludes self loops."""
    return (adj_bin.astype(h.dtype) @ h + h) * inv_deg


def _aggregate_csr(edges: jax.Array, h: jax.Array, inv_deg: jax.Array) -> jax.Array:
    src, dst = edges[0], edges[1]
    valid = (src >= 0)[:, None]
    msgs = jnp.where(valid, h[jnp.clip(src, 0)], 0.0)
    agg = jnp.zeros_like(h).at[jnp.clip(dst, 0)].add(msgs)
    return (agg + h) * inv_deg


def forward(
    params: dict,
    adj_or_edges: jax.Array,
    x: jax.Array,
    inv_deg: jax.Array,
    cfg: GNNConfig,
    path: str = "fp32_dense",
    fake_bits: bool = False,
    **int_kw,
) -> jax.Array:
    """fp32 forward (optionally QAT-fake-quantized). inv_deg: (N, 1).

    ``path="int_bitserial"`` dispatches to :func:`forward_int`:
    ``adj_or_edges`` must then be a ``repro.train.intpath.IntBatchArtifacts``
    (``x``/``inv_deg`` are ignored — features and degrees live in the
    artifacts) and ``int_kw`` forwards grad_bits/stochastic/key/backend/
    policy. The fake-quant path quantizes exactly where the integer paths
    do — including the pre-aggregation requant of ``u`` — so the two
    compute the same function up to GEMM rounding, which is what the
    gradient-parity oracle in tests/test_int_train.py pins down.
    """
    if path == "int_bitserial":
        return forward_int(params, adj_or_edges, cfg, **int_kw)
    agg = _aggregate_dense if path == "fp32_dense" else _aggregate_csr
    h = x
    for l in range(cfg.layers):
        p = params[f"layer{l}"]
        last = l == cfg.layers - 1
        if fake_bits:
            h = fake_quant(h, cfg.x_bits)
        if cfg.model == "gin":
            w1 = fake_quant(p["w1"], cfg.w_bits) if fake_bits else p["w1"]
            w2 = fake_quant(p["w2"], cfg.w_bits) if fake_bits else p["w2"]
            a = agg(adj_or_edges, h, inv_deg) + p["eps"] * h
            if fake_bits:
                a = fake_quant(a, cfg.x_bits)
            h = jax.nn.relu(a @ w1 + p["b1"])
            if fake_bits:
                h = fake_quant(h, cfg.x_bits)
            h = h @ w2 + p["b2"]
        else:  # cluster-GCN: update THEN aggregate (paper §6.2)
            w = fake_quant(p["w"], cfg.w_bits) if fake_bits else p["w"]
            u = h @ w + p["b"]
            if fake_bits:
                # the integer paths aggregate QUANTIZED u (forward_qgtc
                # requants before qgraph_conv; qgraph_conv_train quantizes
                # in-trace) — fake-quant here too so QAT trains the same
                # function the integer paths execute
                u = fake_quant(u, cfg.x_bits)
            h = agg(adj_or_edges, u, inv_deg)
        if not last:
            h = jax.nn.relu(h)
    return h


# ----------------------------------------------------------- training int path

def forward_int(
    params: dict,
    art,
    cfg: GNNConfig,
    *,
    grad_bits: int = 0,
    stochastic: bool = False,
    key: jax.Array | None = None,
    backend=None,
    policy=None,
) -> jax.Array:
    """Differentiable integer forward over cached batch artifacts.

    The float-parameter twin of :func:`forward_qgtc`, for both models:
    weights are quantized in-trace by the custom_vjp layers (so
    ``jax.grad`` reaches them through STE), activations flow quantized
    through the same bitserial GEMMs, and the aggregation runs blocked over
    ``art``'s per-partition diagonal blocks + cross-block edge remainder.
    Layer 0 consumes the batch features pre-quantized once in ``art``
    (``xq, qpx``) — no per-step feature requant. GIN's ``eps·h`` self term
    is a float epilogue of the aggregation over the same quantized ``h``,
    so ``eps`` gets its gradient as on the fake-quant path. ``grad_bits >
    0`` quantizes the backward GEMMs too; ``stochastic`` enables
    stochastic rounding (requires ``key``, split per quantizing layer so
    no two quantizers share randomness).
    """
    mm = dict(backend=backend, policy=policy)
    q = dict(x_bits=cfg.x_bits, grad_bits=grad_bits, stochastic=stochastic)
    per_layer = 3 if cfg.model == "gin" else 2
    keys = (jax.random.split(key, cfg.layers * per_layer)
            if key is not None else [None] * (cfg.layers * per_layer))
    h = (art.xq, art.qpx)
    for l in range(cfg.layers):
        p = params[f"layer{l}"]
        k = keys[per_layer * l:per_layer * (l + 1)]
        if cfg.model == "gin":
            a = qnn.qgraph_conv_train(h, art, eps=p["eps"], key=k[0], **q,
                                      **mm)
            a = constrain(a, "gnn_nodes", None)
            u = jax.nn.relu(qnn.qlinear_train(
                a, p["w1"], p["b1"], w_bits=cfg.w_bits, key=k[1], **q, **mm))
            h = qnn.qlinear_train(u, p["w2"], p["b2"], w_bits=cfg.w_bits,
                                  key=k[2], **q, **mm)
        else:
            u = qnn.qlinear_train(h, p["w"], p["b"], w_bits=cfg.w_bits,
                                  key=k[0], **q, **mm)
            u = constrain(u, "gnn_nodes", None)
            h = qnn.qgraph_conv_train(u, art, key=k[1], **q, **mm)
        if l != cfg.layers - 1:
            h = jax.nn.relu(h)
    return h


# ---------------------------------------------------------------- QGTC path

def quantize_params(params: dict, cfg: GNNConfig) -> dict:
    """Post-QAT weight quantization: int values + QuantParams per matrix."""
    out = {}
    for name, p in params.items():
        q = {}
        for k, v in p.items():
            if k.startswith("w"):
                qp = calibrate(v, cfg.w_bits)
                q[k] = (quantize(v, qp), qp)
            else:
                q[k] = v
        out[name] = q
    return out


def _requant(h: jax.Array, bits: int):
    qp = calibrate(h, bits)
    return quantize(h, qp), qp


def forward_qgtc(
    qparams: dict,
    adj_bin: jax.Array,
    x,
    inv_deg: jax.Array,
    cfg: GNNConfig,
    *,
    backend=None,
    policy=None,
    tiles=None,
) -> jax.Array:
    """Integer-domain forward (serving path). adj_bin: (N,N) 0/1 int32.

    ``x`` is either a float feature matrix (requantized here, the training
    parity path) or a pre-quantized ``(xq, QuantParams)`` pair — the §4.6
    fast path where the compound transfer feeds packed integer features
    straight into the first integer GEMM with no dequantize -> requantize
    roundtrip. ``backend``/``policy`` override the active repro.api context
    for every integer GEMM in the stack. ``tiles`` are precomputed zero-tile
    compact artifacts for ``adj_bin`` (see ``api.nn.qgraph_conv``); they
    reach only the aggregation GEMMs — the feature/weight GEMMs have a
    different, dense A operand.
    """
    mm = dict(backend=backend, policy=policy)
    hq, qph = qnn.as_quantized(x, cfg.x_bits)
    for l in range(cfg.layers):
        p = qparams[f"layer{l}"]
        last = l == cfg.layers - 1
        if cfg.model == "gin":
            a = qnn.qgraph_conv(adj_bin, hq, qph, inv_deg, tiles=tiles, **mm)
            hf = hq.astype(jnp.float32) * qph.scale + qph.zero
            a = a + p["eps"] * hf
            aq, qpa = _requant(a, cfg.x_bits)
            w1, qpw1 = p["w1"]
            u = qnn.qlinear(aq, qpa, w1, qpw1, bias=p["b1"], relu=True, **mm)
            uq, qpu = _requant(u, cfg.x_bits)
            w2, qpw2 = p["w2"]
            h = qnn.qlinear(uq, qpu, w2, qpw2, bias=p["b2"], **mm)
        else:
            w, qpw = p["w"]
            u = qnn.qlinear(hq, qph, w, qpw, bias=p["b"], **mm)
            uq, qpu = _requant(u, cfg.x_bits)
            h = qnn.qgraph_conv(adj_bin, uq, qpu, inv_deg, tiles=tiles, **mm)
        if not last:
            h = jax.nn.relu(h)
            hq, qph = _requant(h, cfg.x_bits)  # §4.5: requantize between layers
    return h
