"""Functional quantized layers shared by the GNN and LM stacks.

One implementation of the quantize -> pack -> integer-MM -> rescale
pipeline, so models/gnn.py, serve/engine.py and the LM serving path stop
duplicating it. Everything dispatches through the repro.api registry, so
``with repro.api.use("pallas"): ...`` switches the whole model.

  qlinear       — s-bit activations x t-bit weights -> float (affine
                  epilogue recovers x @ w), optional bias/relu
  qgraph_conv   — Â h aggregation via 1-bit adjacency x s-bit features
                  integer GEMM + dequant epilogue (Algorithm 1)
  wq_linear     — weight-only quantized projection (LM decode path)
  quantize_lm_params — walk an LM param pytree, weight-quantize every
                  large 2-D projection, report HBM savings
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro import api
from repro.core.quantize import (QuantParams, affine_matmul_correction,
                                 calibrate, dequantize, quantize,
                                 quantize_stochastic)

__all__ = ["as_quantized", "qlinear", "qgraph_conv", "qlinear_train",
           "qgraph_conv_train", "blocked_agg_full", "wq_linear",
           "quantize_lm_params"]


def as_quantized(x, nbits: int) -> tuple[jax.Array, QuantParams]:
    """Normalize a layer input to the quantized domain.

    Accepts either a float tensor (calibrate + quantize, the default
    training-parity path) or an already-quantized ``(xq, QuantParams)``
    pair — the §4.6 fast path, where the compound transfer delivers packed
    integer features and requantizing a dequantized roundtrip would only
    add noise and work. The fast path applies only when the pair's
    bit-width already matches ``nbits``; a mismatched pair (e.g. 8-bit
    transfer feeding a 4-bit model) is rescaled through float so the layer
    always computes at its configured precision.
    """
    if isinstance(x, tuple):
        xq, qp = x
        if not isinstance(qp, QuantParams):
            raise TypeError(
                f"pre-quantized input must be (xq, QuantParams), got "
                f"(..., {type(qp).__name__})")
        if qp.nbits == nbits:
            return xq, qp
        x = dequantize(xq, qp)
    qp = calibrate(x, nbits)
    return quantize(x, qp), qp


def qlinear(xq, qpx: QuantParams, wq, qpw: QuantParams, *, bias=None,
            relu: bool = False, backend=None, policy=None):
    """Integer GEMM of quantized activations x weights -> float x @ w.

    xq (M, K) unsigned qpx.nbits ints; wq (K, N) unsigned qpw.nbits ints.
    The exact int32 product is corrected by the rank-1 affine epilogue
    (quantize.affine_matmul_correction), then bias/relu are applied.
    """
    prod = api.bitserial_mm(xq, wq, qpx.nbits, qpw.nbits,
                            backend=backend, policy=policy)
    out = affine_matmul_correction(xq, wq, qpx, qpw, prod)
    if bias is not None:
        out = out + bias
    if relu:
        out = jax.nn.relu(out)
    return out


def qgraph_conv(adj_bin, hq, qph: QuantParams, inv_deg, *, backend=None,
                policy=None, tiles=None):
    """Â h with Â = (D+I)^-1 (A+I) over quantized features (Algorithm 1).

    adj_bin (N, N) 0/1 int32 (no self loops); hq (N, D) unsigned
    qph.nbits ints; inv_deg (N, 1). The 1-bit x s-bit integer GEMM computes
    exact neighbor sums of hq; the epilogue dequantizes, adds self, scales.

    ``tiles=(idx, counts, s_max)`` are precomputed zero-tile compact
    artifacts for the adjacency (repro.core.zerotile over the packed,
    tile-padded bit-plane — the serve cache holds exactly these); a
    jump-capable backend then skips zero adjacency tiles without any
    per-call occupancy analysis.
    """
    cnt = api.bitserial_mm(adj_bin, hq, 1, qph.nbits,
                           backend=backend, policy=policy, tiles=tiles)
    deg = jnp.sum(adj_bin, axis=1, keepdims=True).astype(jnp.float32)
    # dequant: sum_j h_j = scale * sum_j hq_j + deg * zero
    hf = hq.astype(jnp.float32) * qph.scale + qph.zero
    agg = cnt.astype(jnp.float32) * qph.scale + deg * qph.zero
    return (agg + hf) * inv_deg


def _in_range(x, qp: QuantParams):
    # STE gate, same convention as quantize.fake_quant: gradient passes iff
    # quantize() does not clip; the upper bound is strict.
    return (x >= qp.zero) & (x < qp.zero + qp.scale * (qp.qmax + 1))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _qlinear_train(x_bits, w_bits, grad_bits, sr, backend, policy,
                   h, hq, qph, w, b, key):
    out, _ = _qlt_fwd(x_bits, w_bits, grad_bits, sr, backend, policy,
                      h, hq, qph, w, b, key)
    return out


def _qlt_fwd(x_bits, w_bits, grad_bits, sr, backend, policy,
             h, hq, qph, w, b, key):
    kh = kg = None
    if sr and key is not None:
        kh, kg = jax.random.split(key)
    if hq is None:
        qph = calibrate(h, x_bits)
        hq = (quantize_stochastic(h, qph, kh) if sr and kh is not None
              else quantize(h, qph))
    qpw = calibrate(w, w_bits)
    # weights stay deterministically rounded: SR exists to de-bias the
    # per-step activation/gradient noise, not the (stable) weight grid
    wq = quantize(w, qpw)
    prod = api.bitserial_mm(hq, wq, x_bits, w_bits, backend=backend,
                            policy=policy)
    out = affine_matmul_correction(hq, wq, qph, qpw, prod) + b
    res = (hq, qph, wq, qpw, _in_range(h, qph), _in_range(w, qpw), kg)
    return out, res


def _qlt_bwd(x_bits, w_bits, grad_bits, sr, backend, policy, res, g):
    hq, qph, wq, qpw, h_mask, w_mask, kg = res
    if grad_bits:
        # Tango-style quantized backward: the incoming cotangent is itself
        # quantized (stochastically when sr) and both backward GEMMs run as
        # integer bitserial products with the same affine epilogue as the
        # forward. Error from this approximation is zero-mean under SR.
        qpg = calibrate(g, grad_bits)
        gq = (quantize_stochastic(g, qpg, kg) if sr and kg is not None
              else quantize(g, qpg))
        gh = affine_matmul_correction(
            gq, wq.T, qpg, qpw,
            api.bitserial_mm(gq, wq.T, grad_bits, w_bits, backend=backend,
                             policy=policy))
        gw = affine_matmul_correction(
            hq.T, gq, qph, qpg,
            api.bitserial_mm(hq.T, gq, x_bits, grad_bits, backend=backend,
                             policy=policy))
    else:
        # float backward over the QUANTIZED operands — exactly the fake-
        # quant path's gradients, which is what the parity oracle asserts
        gh = g @ dequantize(wq, qpw).T
        gw = dequantize(hq, qph).T @ g
    gh = jnp.where(h_mask, gh, 0.0)
    gw = jnp.where(w_mask, gw, 0.0)
    return (gh, None, None, gw, jnp.sum(g, axis=0), None)


_qlinear_train.defvjp(_qlt_fwd, _qlt_bwd)


def qlinear_train(h, w, bias=None, *, x_bits=8, w_bits=8, grad_bits=0,
                  stochastic=False, key=None, backend=None, policy=None):
    """Trainable integer linear: quantize -> bitserial GEMM -> STE backward.

    The forward is the same integer pipeline as :func:`qlinear` but wrapped
    in a custom_vjp so ``jax.grad`` works: activations and weights are
    quantized in-trace (Eq. 2 calibration per call, stochastic rounding of
    activations when ``stochastic``), multiplied through
    ``api.bitserial_mm`` and affine-corrected back to float. The backward
    applies straight-through estimators gated on the forward clip ranges;
    with ``grad_bits > 0`` both backward GEMMs also run as integer
    bitserial products over the quantized cotangent (fully quantized
    training à la Tango), otherwise they are float GEMMs over the
    quantized operands — bit-for-bit the fake-quant path's gradients.

    ``h`` may be a float tensor or a pre-quantized ``(hq, QuantParams)``
    pair (the layer-0 input: features are quantized once per batch and the
    cached integers reused every step; no gradient flows to them anyway).
    ``stochastic=True`` requires ``key``.
    """
    if stochastic and key is None:
        raise ValueError("stochastic=True requires a PRNG key")
    b = jnp.zeros((w.shape[-1],), jnp.float32) if bias is None else bias
    if isinstance(h, tuple):
        hq, qph = as_quantized(h, x_bits)
        hf = dequantize(hq, qph)
        return _qlinear_train(x_bits, w_bits, grad_bits, bool(stochastic),
                              backend, policy, hf, hq, qph, w, b, key)
    return _qlinear_train(x_bits, w_bits, grad_bits, bool(stochastic),
                          backend, policy, h, None, None, w, b, key)


def _blocked_agg(adjb, row_idx, v, s, backend, policy, tiles, s_maxes):
    """Exact A @ v over the stacked diagonal blocks of a batch adjacency.

    ``adjb`` (B, P, P) holds the per-partition 0/1 diagonal blocks, each
    zero-padded to the shared block size P; ``row_idx`` (B, P) maps block
    rows to batch node ids (-1 padding). All shapes are uniform across
    batches, so one jit trace of the training step serves every batch —
    block structure rides in as data, not as static slicing offsets.
    Cross-block edges are NOT here; callers add the edge_scatter_sum
    remainder. ``s == 0`` selects the float path (backward over an
    unquantized cotangent); otherwise the per-block GEMMs run through
    ``api.bitserial_mm`` (1-bit x s-bit), with optional per-block zero-tile
    compact artifacts ``tiles[b] = (idx, counts)`` + static ``s_maxes[b]``.
    """
    n, d = v.shape
    bcount = adjb.shape[0]
    valid = row_idx >= 0
    safe = jnp.clip(row_idx, 0)
    vb = jnp.where(valid[..., None], v[safe], 0)  # (B, P, D) gather
    out = jnp.zeros((n, d), v.dtype)
    for b in range(bcount):
        if s == 0:
            cnt = adjb[b].astype(v.dtype) @ vb[b]
        else:
            t = ((tiles[b][0], tiles[b][1], s_maxes[b])
                 if tiles is not None else None)
            cnt = api.bitserial_mm(adjb[b], vb[b], 1, s, backend=backend,
                                   policy=policy, tiles=t)
        # block node sets are disjoint; clipped -1 rows are masked to zero
        out = out.at[safe[b]].add(jnp.where(valid[b][:, None], cnt, 0))
    return out


def blocked_agg_full(adjb, row_idx, rsrc, rdst, v, s, *, backend=None,
                     policy=None, tiles=None, s_maxes=None):
    """Exact ``A @ v`` for a decomposed batch adjacency: blocks + remainder.

    The diagonal blocks run through :func:`_blocked_agg` (integer bitserial
    when ``s > 0``); the -1-padded cross-block edge list adds the rest via
    the dispatch layer's ``edge_scatter_sum``. This is the one sanctioned
    entry point for code outside the api layer (e.g.
    ``repro.train.intpath.blocked_aggregate``) — it keeps kernel imports
    behind the dispatch seam.
    """
    from repro.kernels import ops as kops

    cnt = _blocked_agg(adjb, row_idx, v, s, backend, policy, tiles, s_maxes)
    return cnt + kops.edge_scatter_sum(v, rsrc, rdst, v.shape[0])


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _qgraph_conv_train(x_bits, grad_bits, sr, backend, policy, s_maxes,
                       u, uq, qpu, eps, adjb, row_idx, rsrc, rdst, inv_deg,
                       deg, deg_in, tiles, key):
    out, _ = _qgc_fwd(x_bits, grad_bits, sr, backend, policy, s_maxes,
                      u, uq, qpu, eps, adjb, row_idx, rsrc, rdst, inv_deg,
                      deg, deg_in, tiles, key)
    return out


def _qgc_fwd(x_bits, grad_bits, sr, backend, policy, s_maxes,
             u, uq, qpu, eps, adjb, row_idx, rsrc, rdst, inv_deg, deg,
             deg_in, tiles, key):
    from repro.kernels import ops as kops

    ku = kg = None
    if sr and key is not None:
        ku, kg = jax.random.split(key)
    if uq is None:
        qpu = calibrate(u, x_bits)
        uq = (quantize_stochastic(u, qpu, ku) if sr and ku is not None
              else quantize(u, qpu))
    cnt = _blocked_agg(adjb, row_idx, uq, x_bits, backend, policy,
                       tiles, s_maxes)
    cnt = cnt + kops.edge_scatter_sum(uq, rsrc, rdst, u.shape[0])
    # dequant epilogue: sum_j u_dq[j] = scale*cnt + deg*zero; + self; scale
    u_dq = dequantize(uq, qpu)
    out = (cnt.astype(jnp.float32) * qpu.scale + deg * qpu.zero
           + u_dq) * inv_deg
    if eps is not None:
        # GIN's self term, a float epilogue over the same quantized u
        out = out + eps * u_dq
    res = (_in_range(u, qpu), adjb, row_idx, rsrc, rdst, inv_deg, deg_in,
           eps, None if eps is None else u_dq, kg)
    return out, res


def _qgc_bwd(x_bits, grad_bits, sr, backend, policy, s_maxes, res, g):
    from repro.kernels import ops as kops

    (u_mask, adjb, row_idx, rsrc, rdst, inv_deg, deg_in, eps, u_dq,
     kg) = res
    gp = g * inv_deg
    n = gp.shape[0]
    # out = (A+I) @ u_dq * inv_deg  =>  du = (A^T+I) @ (g*inv_deg), STE-masked.
    # Transposing each diagonal block IS the block decomposition of A^T (the
    # blocks are principal submatrices), so the backward reuses the forward
    # artifacts; the remainder transpose is just the src/dst swap. For the
    # symmetric graphs Cluster-GCN produces this is a no-op, but the
    # transpose keeps the gradient exact for any edge direction.
    adjt = jnp.swapaxes(adjb, 1, 2)
    if grad_bits:
        qpg = calibrate(gp, grad_bits)
        gq = (quantize_stochastic(gp, qpg, kg) if sr and kg is not None
              else quantize(gp, qpg))
        cnt = _blocked_agg(adjt, row_idx, gq, grad_bits, backend, policy,
                           None, None)
        cnt = cnt + kops.edge_scatter_sum(gq, rdst, rsrc, n)
        # self term stays the float gp — it is free and exact
        gu = (cnt.astype(jnp.float32) * qpg.scale + deg_in * qpg.zero) + gp
    else:
        cnt = _blocked_agg(adjt, row_idx, gp, 0, backend, policy, None, None)
        gu = cnt + kops.edge_scatter_sum(gp, rdst, rsrc, n) + gp
    geps = None
    if eps is not None:
        gu = gu + eps * g
        geps = jnp.sum(g * u_dq)
    gu = jnp.where(u_mask, gu, 0.0)
    return (gu, None, None, geps, None, None, None, None, None, None, None,
            None, None)


_qgraph_conv_train.defvjp(_qgc_fwd, _qgc_bwd)


def qgraph_conv_train(u, art, *, eps=None, x_bits=8, grad_bits=0,
                      stochastic=False, key=None, backend=None, policy=None):
    """Trainable Â u aggregation over cached integer batch artifacts.

    ``art`` is a ``repro.train.intpath.IntBatchArtifacts``: the batch
    adjacency decomposed once per Cluster-GCN batch into per-partition
    diagonal blocks (dense 1-bit GEMMs through ``api.bitserial_mm``, with
    optional zero-tile compact artifacts threaded per block) plus the
    sparse cross-partition remainder as an edge list (integer
    gather/scatter via ``kernels.ops.edge_scatter_sum``). The sum is
    bit-exact equal to the dense ``adj @ uq`` — tests/test_intpath.py
    asserts it — while doing ~batch_size x fewer GEMM flops than the dense
    batch adjacency, which is most of the int path's per-step win.

    Forward quantizes ``u`` in-trace (stochastic rounding when
    ``stochastic``); backward is ``(A^T + I) @ (g * inv_deg)`` with the STE
    mask from the forward calibration, run as an integer aggregation of the
    quantized cotangent when ``grad_bits > 0``.

    ``u`` may be a float tensor or a pre-quantized ``(uq, QuantParams)``
    pair, as in :func:`qlinear_train` (GIN's layer 0 aggregates the batch
    features ``art.xq, art.qpx``; no gradient flows to them). ``eps``
    (GIN's self weight, a float scalar) adds ``eps * dequant(uq)`` after
    the epilogue, on the same quantized ``u``; its gradient is
    ``sum(g * dequant(uq))``, and ``eps * g`` joins ``u``'s.
    """
    if stochastic and key is None:
        raise ValueError("stochastic=True requires a PRNG key")
    uq = qpu = None
    if isinstance(u, tuple):
        uq, qpu = as_quantized(u, x_bits)
        u = dequantize(uq, qpu)
    return _qgraph_conv_train(x_bits, grad_bits, bool(stochastic), backend,
                              policy, art.s_maxes, u, uq, qpu, eps, art.adjb,
                              art.row_idx, art.rem_src, art.rem_dst,
                              art.inv_deg, art.deg, art.deg_in, art.tiles,
                              key)


def wq_linear(x, wq, *, bias=None, out_dtype=jnp.bfloat16, backend=None,
              policy=None):
    """x (..., K) float @ weight-only-quantized W (K, N) + optional bias."""
    out = api.wq_mm(x, wq, out_dtype=out_dtype, backend=backend,
                    policy=policy)
    if bias is not None:
        out = (out + bias).astype(out_dtype)
    return out


def quantize_lm_params(params, nbits: int = 4, min_size: int = 4096,
                       skip: tuple = ("embed",)):
    """Weight-only-quantize every large 2-D projection in an LM pytree.

    Returns ``(params_q, stats)`` where params_q has each eligible leaf
    replaced by its quantize->dequantize roundtrip (the W-nbits serving
    effect on a stock forward pass) and stats reports the packed HBM
    footprint: {"n_quantized", "bytes_fp16", "bytes_packed", "ratio"}.
    """
    from repro.core.qgemm import weight_dequantize, weight_quantize

    stats = {"n_quantized": 0, "bytes_fp16": 0, "bytes_packed": 0}

    def visit(path, leaf):
        key = jax.tree_util.keystr(path)
        if (leaf.ndim != 2 or leaf.size <= min_size
                or any(s in key for s in skip)):
            return leaf
        wq = weight_quantize(leaf.astype(jnp.float32), nbits)
        stats["n_quantized"] += 1
        stats["bytes_fp16"] += leaf.size * 2
        stats["bytes_packed"] += leaf.size * nbits // 8 + wq.scale.size * 4
        return weight_dequantize(wq).astype(leaf.dtype)

    params_q = jax.tree_util.tree_map_with_path(visit, params)
    stats["ratio"] = stats["bytes_fp16"] / max(stats["bytes_packed"], 1)
    return params_q, stats
