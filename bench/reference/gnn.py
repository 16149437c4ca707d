"""Plain reference of the quantized GCN and GIN forward, in float64 numpy.

Written from the paper's equations (QGTC section 4 and Eq. 2), not from the
program: every tensor a GEMM reads is quantized per tensor to ``bits``
unsigned levels by min/max calibration, ``q = clip(floor((x - min) /
scale), 0, 2**bits - 1)`` with ``scale = (max - min) / 2**bits``, and the
GEMM is the float product of the dequantized values ``q * scale + min``.
Aggregation is ``(D + I)^-1 (A + I) h`` on the dense 0/1 adjacency.
Cluster-GCN updates then aggregates; GIN aggregates, adds ``eps * h`` and
runs a two-layer MLP. Hidden layers apply ReLU and are quantized again;
the last layer's output is the logits. Nothing here imports the program.
"""
from __future__ import annotations

import numpy as np

__all__ = ["fake_quant", "forward"]


def fake_quant(x: np.ndarray, bits: int) -> np.ndarray:
    """Quantize to ``bits`` levels over the whole tensor, then dequantize."""
    lo, hi = float(x.min()), float(x.max())
    scale = max((hi - lo) / float(1 << bits), 1e-8)
    q = np.clip(np.floor((x - lo) / scale), 0, (1 << bits) - 1)
    return q * scale + lo


def forward(params: dict, cfg: dict, adj: np.ndarray, x: np.ndarray,
            bits: int | None = None) -> np.ndarray:
    """Logits (n, classes) of one padded batch.

    ``params`` mirrors the model's float weights (``layer{l}`` -> ``w``/``b``
    for GCN, ``w1``/``b1``/``w2``/``b2``/``eps`` for GIN); ``adj`` is the
    batch's dense 0/1 adjacency without self loops and ``x`` its features,
    padding rows included. ``bits`` overrides the configuration's feature
    and weight bits alike (the lower-precision control uses it).
    """
    xb = int(bits or cfg["x_bits"])
    wb = int(bits or cfg["w_bits"])
    adj = adj.astype(np.float64)
    inv_deg = 1.0 / (adj.sum(axis=1, keepdims=True) + 1.0)

    def agg(v):
        return (adj @ v + v) * inv_deg

    def w(p, k):
        return fake_quant(np.asarray(p[k], np.float64), wb)

    h = fake_quant(np.asarray(x, np.float64), xb)
    n_layers = int(cfg["layers"])
    for layer in range(n_layers):
        p = params[f"layer{layer}"]
        if cfg["model"] == "gin":
            a = agg(h) + float(p["eps"]) * h
            a = fake_quant(a, xb)
            u = np.maximum(a @ w(p, "w1") + p["b1"], 0.0)
            u = fake_quant(u, xb)
            out = u @ w(p, "w2") + p["b2"]
        else:
            u = fake_quant(h @ w(p, "w") + p["b"], xb)
            out = agg(u)
        if layer == n_layers - 1:
            return out
        h = fake_quant(np.maximum(out, 0.0), xb)
    raise ValueError("a model has at least one layer")


# ------------------------------------------------------------------ training

def _quant(x: np.ndarray, bits: int):
    """(levels, min, scale) of per-tensor min/max quantization."""
    lo, hi = float(x.min()), float(x.max())
    scale = max((hi - lo) / float(1 << bits), 1e-8)
    return np.clip(np.floor((x - lo) / scale), 0, (1 << bits) - 1), lo, scale


def _deq(q) -> np.ndarray:
    lv, lo, scale = q
    return lv * scale + lo


def _in_range(x: np.ndarray, q, bits: int) -> np.ndarray:
    """Where quantization does not clip (the straight-through gate); the
    upper end is open."""
    _, lo, scale = q
    return (x >= lo) & (x < lo + scale * float(1 << bits))


def loss_and_grads(params: dict, cfg: dict, batch: dict, bits: dict):
    """Masked cross-entropy of one batch and its gradients (GCN).

    ``bits`` = {"x": activation bits, "w": weight bits, "g": gradient
    bits}. Forward as :func:`forward` (the input features quantized once
    over the batch). Backward by the straight-through estimator: each
    quantizer passes the gradient where it did not clip. With ``g`` > 0
    the cotangent entering each GEMM is itself quantized: the weight
    GEMM's incoming gradient, and the aggregation's ``g / (deg + 1)``,
    whose self term stays unquantized. The bias gradient is the column
    sum of the unquantized cotangent.
    """
    if cfg["model"] != "gcn":
        raise NotImplementedError("the training reference covers GCN")
    xb, wb, gb = bits["x"], bits["w"], bits["g"]
    adj = batch["adj"].astype(np.float64)
    inv = 1.0 / (adj.sum(axis=1, keepdims=True) + 1.0)
    n_layers = int(cfg["layers"])
    hq = _quant(np.asarray(batch["x"], np.float64), xb)
    h = None
    tape = []
    for layer in range(n_layers):
        p = params[f"layer{layer}"]
        if layer:
            hq = _quant(h, xb)
        w = np.asarray(p["w"], np.float64)
        wq = _quant(w, wb)
        u = _deq(hq) @ _deq(wq) + p["b"]
        uq = _quant(u, xb)
        ud = _deq(uq)
        out = (adj @ ud + ud) * inv
        tape.append({"h": h, "hq": hq, "w": w, "wq": wq, "u": u, "uq": uq,
                     "out": out})
        h = np.maximum(out, 0.0) if layer < n_layers - 1 else out
    logits = h
    y, mask = batch["y"], batch["mask"]
    valid = (y >= 0) & mask
    n = max(int(valid.sum()), 1)
    z = logits - logits.max(axis=1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
    yc = np.clip(y, 0, None)
    loss = -float(logp[np.arange(len(y)), yc][valid].sum()) / n
    g = np.exp(logp)
    g[np.arange(len(y)), yc] -= 1.0
    g *= valid[:, None] / n

    def gq(v):
        return _deq(_quant(v, gb)) if gb else v

    grads = {}
    for layer in reversed(range(n_layers)):
        t = tape[layer]
        if layer < n_layers - 1:
            g = g * (t["out"] > 0)
        gp = g * inv
        gu = adj.T @ gq(gp) + gp
        gu = np.where(_in_range(t["u"], t["uq"], xb), gu, 0.0)
        gd = gq(gu)
        gw = _deq(t["hq"]).T @ gd
        gw = np.where(_in_range(t["w"], t["wq"], wb), gw, 0.0)
        grads[f"layer{layer}"] = {"w": gw, "b": gu.sum(axis=0)}
        if layer:
            gh = gd @ _deq(t["wq"]).T
            g = np.where(_in_range(t["h"], t["hq"], xb), gh, 0.0)
    return loss, grads


def adamw_steps(params: dict, cfg: dict, batches: list, opt: dict,
                bits: dict) -> dict:
    """Run AdamW (decoupled weight decay, gradients clipped to a global
    norm) over ``batches``, one step each. Returns the losses, the first
    step's clipped gradients and the parameters after the last step."""
    p = {k: {n: np.asarray(v, np.float64) for n, v in g.items()}
         for k, g in params.items()}
    mu = {k: {n: np.zeros_like(v) for n, v in g.items()} for k, g in p.items()}
    nu = {k: {n: np.zeros_like(v) for n, v in g.items()} for k, g in p.items()}
    b1, b2, lr = opt["b1"], opt["b2"], opt["lr"]
    losses, first = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grads(p, cfg, batch, bits)
        losses.append(loss)
        norm = np.sqrt(sum(float(np.sum(v * v)) for g in grads.values()
                           for v in g.values()))
        clip = min(1.0, opt["grad_clip"] / (norm + 1e-9))
        grads = {k: {n: v * clip for n, v in g.items()}
                 for k, g in grads.items()}
        if first is None:
            first = grads
        for k, g in grads.items():
            for n, v in g.items():
                mu[k][n] = b1 * mu[k][n] + (1 - b1) * v
                nu[k][n] = b2 * nu[k][n] + (1 - b2) * v * v
                m_hat = mu[k][n] / (1 - b1 ** t)
                v_hat = nu[k][n] / (1 - b2 ** t)
                p[k][n] = p[k][n] - lr * (m_hat / (np.sqrt(v_hat) + opt["eps"])
                                          + opt["weight_decay"] * p[k][n])
    return {"losses": losses, "first_grads": first, "params": p}
