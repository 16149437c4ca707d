"""Operations and bytes of the quantized GNN's integer GEMMs.

Counts the logical work of a call, whatever implements it: jump mode,
backend, tile grid and padding never change a count. Inputs are each
member request's real node count, the layer widths in the order
``models/gnn.py`` applies them, and the bitwidths.

  aggregation   one dense GEMM per member subgraph, ``2 * n_i**2 * d``
                (the paper's formulation; zero blocks that batching adds
                between members are not counted)
  weight GEMM   ``2 * n * d_in * d_out`` over the batch's real nodes
  bytes         the packed operands (adjacency at 1 bit, activations at
                ``x_bits``, weights at ``w_bits``) plus the int32 output
"""
from __future__ import annotations

__all__ = ["gemms", "batch_cost", "request_ops", "train_step_cost"]


def gemms(cfg: dict) -> list:
    """[("weight", d_in, d_out) | ("agg", d)] in forward order."""
    dims = ([cfg["feature_dim"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [cfg["num_classes"]])
    out = []
    for d_in, d_out in zip(dims[:-1], dims[1:]):
        if cfg["model"] == "gin":
            mid = max(d_out, cfg["hidden"])
            out += [("agg", d_in), ("weight", d_in, mid),
                    ("weight", mid, d_out)]
        else:
            out += [("weight", d_in, d_out), ("agg", d_out)]
    return out


def batch_cost(cfg: dict, sizes) -> tuple[int, int]:
    """(operations, bytes) of one forward over a batch of member requests
    with ``sizes`` real nodes each."""
    xb, wb = cfg["x_bits"], cfg["w_bits"]
    sizes = [int(s) for s in sizes]
    n = sum(sizes)
    ops = nbytes = 0
    for g in gemms(cfg):
        if g[0] == "agg":
            d = g[1]
            ops += sum(2 * s * s * d for s in sizes)
            nbytes += (sum(s * s for s in sizes) // 8 + n * d * xb // 8
                       + n * d * 4)
        else:
            _, d_in, d_out = g
            ops += 2 * n * d_in * d_out
            nbytes += (n * d_in * xb // 8 + d_in * d_out * wb // 8
                       + n * d_out * 4)
    return ops, nbytes


def request_ops(cfg: dict, n: int) -> int:
    """Model operations of one request of ``n`` nodes."""
    return batch_cost(cfg, [n])[0]


def train_step_cost(cfg: dict, sizes, cross_edges: int) -> dict:
    """One integer training step of a Cluster-GCN batch (GCN only).

    ``sizes`` are the batch's parts (its diagonal blocks), ``cross_edges``
    the directed edges between them. Returns

      model_ops      forward operations times 3 (forward and backward),
                     the aggregation counted per part block plus 2*d per
                     cross edge
      bitserial_ops  what the bit-serial GEMMs compute: the forward weight
                     and block-aggregation GEMMs, the backward aggregation
                     over the transposed blocks, the weight-gradient GEMM
                     of every layer and the input-gradient GEMM of every
                     layer but the first (whose input needs none)
      bitserial_bytes  their packed operands and int32 outputs
    """
    if cfg["model"] != "gcn":
        raise NotImplementedError("the integer training path runs GCN")
    xb, wb, gb = cfg["x_bits"], cfg["w_bits"], cfg["grad_bits"]
    sizes = [int(s) for s in sizes]
    n = sum(sizes)
    sq = sum(s * s for s in sizes)
    fwd = bs_ops = bs_bytes = 0
    layers = [g for g in gemms(cfg) if g[0] == "weight"]
    for i, (_, d_in, d_out) in enumerate(layers):
        w_ops = 2 * n * d_in * d_out
        a_ops = 2 * sq * d_out
        fwd += w_ops + a_ops + 2 * cross_edges * d_out
        n_w = 3 if i else 2          # forward, weight grad, input grad
        bs_ops += n_w * w_ops + 2 * a_ops
        bs_bytes += (n * d_in * xb // 8 + d_in * d_out * wb // 8
                     + n * d_out * 4)                        # forward
        bs_bytes += (n * d_in * xb // 8 + n * d_out * gb // 8
                     + d_in * d_out * 4)                     # weight grad
        if i:
            bs_bytes += (n * d_out * gb // 8 + d_in * d_out * wb // 8
                         + n * d_in * 4)                     # input grad
        for s in (xb, gb):                                   # aggregations
            bs_bytes += sq // 8 + n * d_out * s // 8 + n * d_out * 4
    return {"model_ops": 3 * fwd, "bitserial_ops": bs_ops,
            "bitserial_bytes": bs_bytes}
