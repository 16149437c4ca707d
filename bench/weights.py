"""Model weights made from ``--seed``, on the device, in one jitted call.

The benchmark makes the weights and hands them to the program (which
quantizes them, as it would a trained checkpoint) and, as numpy copies, to
the plain reference. Glorot-normal matrices and zero biases, in the
parameter layout of ``models/gnn.py``.
"""
from __future__ import annotations

import numpy as np

__all__ = ["make_gnn_params", "to_numpy", "gnn_shapes"]


def gnn_shapes(cfg: dict) -> dict:
    """{layer: {name: shape}} of the float parameters."""
    dims = ([cfg["feature_dim"]] + [cfg["hidden"]] * (cfg["layers"] - 1)
            + [cfg["num_classes"]])
    out = {}
    for layer, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        if cfg["model"] == "gin":
            mid = max(d_out, cfg["hidden"])
            out[f"layer{layer}"] = {"w1": (d_in, mid), "b1": (mid,),
                                    "w2": (mid, d_out), "b2": (d_out,),
                                    "eps": ()}
        else:
            out[f"layer{layer}"] = {"w": (d_in, d_out), "b": (d_out,)}
    return out


def make_gnn_params(cfg: dict, seed: int) -> dict:
    import jax
    import jax.numpy as jnp

    shapes = gnn_shapes(cfg)
    seed = int(seed)

    def build(key):
        params = {}
        for layer, group in shapes.items():
            p = {}
            for name, shape in group.items():
                if name.startswith("w"):
                    key, sub = jax.random.split(key)
                    s = jnp.sqrt(2.0 / (shape[0] + shape[1]))
                    p[name] = jax.random.normal(sub, shape, jnp.float32) * s
                elif name == "eps":
                    p[name] = jnp.asarray(cfg["gin_eps"], jnp.float32)
                else:
                    p[name] = jnp.zeros(shape, jnp.float32)
            params[layer] = p
        return params

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return jax.jit(build)(key)


def to_numpy(params) -> dict:
    return {layer: {k: np.asarray(v, np.float64) for k, v in group.items()}
            for layer, group in params.items()}
