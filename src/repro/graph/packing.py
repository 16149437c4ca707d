"""Bandwidth-optimized subgraph packing (paper §4.6).

Three host->device transfer strategies, mirroring Fig. 9b:
  I   — transfer the dense adjacency and dense features separately
  II  — transfer the sparse edge list and features separately, densify on
        device
  III — QGTC: pack (header | edge list | quantized-packed features) into ONE
        contiguous compound buffer, single transfer, then unpack + densify
        on device

On TPU the PCIe economics become host->HBM infeed; the trade is identical:
one large contiguous DMA beats several small ones, and shipping the sparse
form trades cheap on-device compute for scarce link bandwidth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitops import np_pack_words
from repro.graph.batching import SubgraphBatch
from repro.graph.sparse import sparse_to_dense
from repro.perf import spans

__all__ = ["pack_compound", "unpack_compound", "pack_feats", "unpack_feats",
           "transfer_dense", "transfer_sparse", "transfer_packed",
           "transfer_packed_feats", "compound_nbytes"]

_HDR = 8  # header words: n_nodes, n_valid, n_edges, dim, nbits, e_cap, wpf, reserved


def _quantize_feats(features: np.ndarray, nbits: int):
    fmin, fmax = float(features.min()), float(features.max())
    scale = max((fmax - fmin) / (1 << nbits), 1e-8)
    q = np.clip(np.floor((features - fmin) / scale), 0, (1 << nbits) - 1)
    return q.astype(np.uint32), scale, fmin


def _pack_body(batch: SubgraphBatch, nbits: int, e_cap: int):
    """Shared compound-layout core: quantize + bit-plane-pack + header."""
    q, scale, zero = _quantize_feats(batch.features, nbits)
    n, d = q.shape
    planes = np.stack([(q >> i) & 1 for i in range(nbits)])  # (nbits, N, D)
    packed = np_pack_words(planes)  # (nbits, N, ceil(D/32))
    wpf = packed.shape[-1]
    header = np.array([batch.n_nodes, batch.n_valid, batch.n_edges, d, nbits,
                       e_cap, wpf, 0], dtype=np.uint32)
    meta = {"scale": scale, "zero": zero, "n": n, "d": d, "nbits": nbits,
            "e_cap": e_cap, "wpf": wpf}
    return header, packed, meta


def pack_compound(batch: SubgraphBatch, nbits: int = 8) -> tuple[np.ndarray, dict]:
    """Pack one subgraph batch into a single uint32 buffer (strategy III).

    Features are quantized to ``nbits`` and bit-packed 32/word along the
    feature dim — the same 3D-stacked compression as the compute path, so
    the transfer cost scales with nbits (the paper's bit-level saving
    extends to the link, not just HBM).
    """
    with spans.span("pack"):
        header, packed, meta = _pack_body(batch, nbits,
                                          batch.edges.shape[1])
        buf = np.concatenate([
            header,
            batch.edges.astype(np.int32).view(np.uint32).ravel(),
            packed.ravel(),
        ])
    return buf, meta


def pack_feats(batch: SubgraphBatch, nbits: int = 8) -> tuple[np.ndarray, dict]:
    """Features-only compound buffer (header | packed quantized features).

    The serving tile cache (§4.4 extended across requests) keeps the
    adjacency artifacts — dense form, packed bit-planes, occupancy — on
    device; a repeat subgraph then only needs its (fresh) features shipped.
    Same header/bit-plane layout as :func:`pack_compound`, minus the edges
    (header e_cap = 0).
    """
    with spans.span("pack"):
        header, packed, meta = _pack_body(batch, nbits, e_cap=0)
        buf = np.concatenate([header, packed.ravel()])
    return buf, meta


@functools.partial(jax.jit, static_argnames=("n", "nbits", "wpf"))
def unpack_feats(buf: jax.Array, *, n: int, nbits: int, wpf: int):
    """Device-side unpack of a features-only compound buffer."""
    return buf[_HDR:_HDR + nbits * n * wpf].reshape(nbits, n, wpf)


@functools.partial(jax.jit, static_argnames=("n", "d", "nbits", "e_cap", "wpf"))
def unpack_compound(buf: jax.Array, *, n: int, d: int, nbits: int, e_cap: int,
                    wpf: int):
    """Device-side unpack: compound buffer -> (dense adjacency, packed feats)."""
    off = _HDR
    edges = buf[off:off + 2 * e_cap].view(jnp.int32).reshape(2, e_cap)
    off += 2 * e_cap
    packed = buf[off:off + nbits * n * wpf].reshape(nbits, n, wpf)
    adj = sparse_to_dense(edges, n)
    return adj, packed


def transfer_dense(batch: SubgraphBatch, device=None):
    """Strategy I: dense adjacency + dense features, two transfers."""
    n = batch.n_nodes
    adj = np.zeros((n, n), np.int32)
    e = batch.edges
    valid = e[0] >= 0
    adj[e[0, valid], e[1, valid]] = 1
    a = jax.device_put(adj, device)
    f = jax.device_put(batch.features, device)
    return a, f


def transfer_sparse(batch: SubgraphBatch, device=None):
    """Strategy II: edge list + dense features, two transfers + device scatter."""
    e = jax.device_put(batch.edges, device)
    f = jax.device_put(batch.features, device)
    adj = sparse_to_dense(e, batch.n_nodes)
    return adj, f


def transfer_packed(batch: SubgraphBatch, nbits: int = 8, device=None):
    """Strategy III (QGTC): one compound transfer + device unpack."""
    buf, meta = pack_compound(batch, nbits)
    with spans.span("transfer"):
        dbuf = jax.device_put(buf, device)
        adj, packed = unpack_compound(dbuf, n=meta["n"], d=meta["d"],
                                      nbits=meta["nbits"],
                                      e_cap=meta["e_cap"], wpf=meta["wpf"])
    return adj, packed, meta


def transfer_packed_feats(batch: SubgraphBatch, nbits: int = 8, device=None):
    """Strategy III on a tile-cache hit: features-only compound transfer."""
    buf, meta = pack_feats(batch, nbits)
    with spans.span("transfer"):
        dbuf = jax.device_put(buf, device)
        packed = unpack_feats(dbuf, n=meta["n"], nbits=meta["nbits"],
                              wpf=meta["wpf"])
    return packed, meta


def compound_nbytes(batch: SubgraphBatch, nbits: int = 8) -> dict:
    """Bytes moved under each strategy (the Fig. 9b 'derived' columns)."""
    n, d = batch.features.shape
    e_cap = batch.edges.shape[1]
    wpf = (d + 31) // 32
    return {
        "I_dense": n * n * 4 + n * d * 4,
        "II_sparse": 2 * e_cap * 4 + n * d * 4,
        "III_packed": (_HDR + 2 * e_cap + nbits * n * wpf) * 4,
        # tile-cache hit: adjacency artifacts already on device, only the
        # features-only compound buffer moves (see pack_feats)
        "III_feats": (_HDR + nbits * n * wpf) * 4,
    }
