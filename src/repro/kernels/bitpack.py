"""Pallas TPU kernel: quantize (Eq. 2) + 3D-stacked bit compression (§4.2).

    x (M, K) f32  ->  packed (nbits, M, K/32) uint32

Packing is a shift-and-sum tree on the VPU: each 32-column word of K
has its bit lanes shifted into place, and the disjoint bits
are summed in int32 (a sum with no carries is their OR; Mosaic has no
unsigned reductions and no float32 -> uint32 cast, so the integer work
stays in int32 until the final same-width conversion). We considered
packing via an int matmul against a block-diagonal power-of-two matrix —
MXU-friendly — but fp32/int MXU accumulation cannot represent 2^31 sums
exactly, so the VPU tree is the correct TPU lowering.

A block spans whole rows of x: the packed word axis is the output's lane
axis, and only its full extent is a legal block there for any K.

The kernel fuses quantization so full-precision activations stream HBM->VMEM
once and only packed words stream back (the §4.5 fusion contract).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_M = 128


def _kernel(x_ref, scale_ref, zero_ref, o_ref, *, nbits, k_true):
    x = x_ref[...]  # (BM, K) f32
    bm, k = x.shape
    q = jnp.clip(jnp.floor((x - zero_ref[0, 0]) / scale_ref[0, 0]),
                 0.0, float((1 << nbits) - 1)).astype(jnp.int32)
    # Zero the K-padding region: padded input columns would otherwise
    # quantize to floor(-zero/scale) != 0 and corrupt the packed planes.
    col = jax.lax.broadcasted_iota(jnp.int32, (bm, k), 1)
    q = jnp.where(col < k_true, q, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (bm, 32), 1)
    for w in range(k // 32):  # static word slices: Mosaic cannot split lanes
        qw = q[:, 32 * w:32 * (w + 1)]
        for i in range(nbits):
            plane = ((qw >> i) & 1) << lane
            o_ref[i, :, w:w + 1] = jnp.sum(
                plane, axis=1, keepdims=True).astype(jnp.uint32)


def bitpack(
    x: jax.Array,
    scale: jax.Array,
    zero: jax.Array,
    nbits: int,
    k_true: int | None = None,
    *,
    block_m: int = DEFAULT_BLOCK_M,
    interpret: bool = False,
) -> jax.Array:
    """x must be pre-padded: M % block_m == 0, K % 32 == 0."""
    m, k = x.shape
    assert m % block_m == 0 and k % 32 == 0, (m, k)
    if k_true is None:
        k_true = k
    w = k // 32
    scale = jnp.asarray(scale, jnp.float32).reshape(1, 1)
    zero = jnp.asarray(zero, jnp.float32).reshape(1, 1)
    return pl.pallas_call(
        functools.partial(_kernel, nbits=nbits, k_true=k_true),
        grid=(m // block_m,),
        in_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((nbits, block_m, w), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((nbits, m, w), jnp.uint32),
        interpret=interpret,
    )(x, scale, zero)
