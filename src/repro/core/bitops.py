"""Pure-JAX bit decomposition / 3D-stacked bit compression / bit-serial matmul.

These are the XLA-path implementations of QGTC §3 (1-bit composition) and
§4.2 (3D-stacked bit compression). They are exact over the unsigned
quantized domain: for s-bit A (M,K) and t-bit B (K,N),

    bitserial_matmul(A, B, s, t)  ==  A @ B   (int32, exactly)

The packed layouts mirror the paper:
  A: (s, M, ceil(K/32))  uint32   -- "column-wise" compression: bits of the
                                     reduction dim K packed along words so a
                                     row of A reads contiguously (Fig. 4b)
  B: (t, ceil(K/32), N)  uint32   -- "row-wise" compression (Fig. 4c)
Little-endian within each 32-bit word (paper Fig. 4 note).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pad_to",
    "bit_decompose",
    "bit_compose",
    "pack_along_axis",
    "unpack_along_axis",
    "pack_a",
    "pack_b",
    "popcount_matmul_packed",
    "bitserial_matmul",
    "bitserial_matmul_planes",
    "bitserial_matmul_packed",
    "np_pack_planes",
]

WORD = 32


def pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad ``axis`` up to a multiple (paper's PAD8 / PAD128)."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def bit_decompose(q: jax.Array, nbits: int) -> jax.Array:
    """(..., ) int32 unsigned-range -> (nbits, ...) 0/1 int32 planes."""
    shifts = jnp.arange(nbits, dtype=jnp.int32).reshape((nbits,) + (1,) * q.ndim)
    return (q[None] >> shifts) & 1


def bit_compose(planes: jax.Array) -> jax.Array:
    """(nbits, ...) 0/1 -> int32 values. Inverse of bit_decompose."""
    nbits = planes.shape[0]
    shifts = jnp.arange(nbits, dtype=jnp.int32).reshape((nbits,) + (1,) * (planes.ndim - 1))
    return jnp.sum(planes.astype(jnp.int32) << shifts, axis=0)


def pack_along_axis(bits: jax.Array, axis: int = -1) -> jax.Array:
    """Pack 0/1 values into uint32 words along ``axis`` (little-endian).

    Shape (..., K, ...) -> (..., ceil(K/32), ...). K is zero-padded to a
    word boundary first.
    """
    axis = axis % bits.ndim
    bits = pad_to(bits, axis, WORD)
    k = bits.shape[axis]
    new_shape = bits.shape[:axis] + (k // WORD, WORD) + bits.shape[axis + 1 :]
    b = bits.reshape(new_shape).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(WORD, dtype=jnp.uint32)).reshape(
        (1,) * (axis + 1) + (WORD,) + (1,) * (bits.ndim - axis - 1)
    )
    return jnp.sum(b * weights, axis=axis + 1, dtype=jnp.uint32)


def unpack_along_axis(packed: jax.Array, axis: int = -1, size: int | None = None) -> jax.Array:
    """Inverse of pack_along_axis; optionally crop the axis back to ``size``."""
    axis = axis % packed.ndim
    shifts = jnp.arange(WORD, dtype=jnp.uint32).reshape(
        (1,) * (axis + 1) + (WORD,) + (1,) * (packed.ndim - axis - 1)
    )
    expanded = (jnp.expand_dims(packed, axis + 1) >> shifts.astype(jnp.uint32)) & jnp.uint32(1)
    # merge (axis: W, axis+1: 32) -> axis: W*32
    shp = list(expanded.shape)
    shp[axis : axis + 2] = [shp[axis] * WORD]
    out = expanded.reshape(shp).astype(jnp.int32)
    if size is not None:
        out = jax.lax.slice_in_dim(out, 0, size, axis=axis)
    return out


def pack_a(q: jax.Array, nbits: int) -> jax.Array:
    """A (M, K) s-bit int32 -> (s, M, ceil(K/32)) uint32 (column-wise, Fig 4b)."""
    planes = bit_decompose(q, nbits)  # (s, M, K)
    return pack_along_axis(planes, axis=-1)


def pack_b(q: jax.Array, nbits: int) -> jax.Array:
    """B (K, N) t-bit int32 -> (t, ceil(K/32), N) uint32 (row-wise, Fig 4c)."""
    planes = bit_decompose(q, nbits)  # (t, K, N)
    return pack_along_axis(planes, axis=-2)


def popcount_matmul_packed(a_packed: jax.Array, b_packed: jax.Array) -> jax.Array:
    """popcount(AND) GEMM over packed words: (M,W)x(W,N) -> int32 (M,N).

    This is the paper's Eq. 7 `popcnt(v_i & v_j)` extended to a matmul.
    Pure-jnp oracle; the Pallas kernel computes the same thing tiled.
    """
    anded = a_packed[:, :, None] & b_packed[None, :, :]
    return jnp.sum(jax.lax.population_count(anded).astype(jnp.int32), axis=1)


def bitserial_matmul_packed(a_packed: jax.Array, b_packed: jax.Array) -> jax.Array:
    """Packed (s,M,W) x (t,W,N) -> exact int32 (M,N) via Eq. 5/6 composition."""
    s, t = a_packed.shape[0], b_packed.shape[0]
    m, n = a_packed.shape[1], b_packed.shape[2]
    acc = jnp.zeros((m, n), jnp.int32)
    for i in range(s):
        for j in range(t):
            acc = acc + (popcount_matmul_packed(a_packed[i], b_packed[j]) << (i + j))
    return acc


def bitserial_matmul_planes(aq: jax.Array, bq: jax.Array, s: int, t: int) -> jax.Array:
    """Exact int32 matmul of unsigned s-bit x t-bit operands by per-plane dots.

    One int8 dot product per (i, j) bit-plane pair, shifted and summed
    (Eq. 5/6) — the XLA/MXU-friendly emulation of the TC bit-serial GEMM.
    """
    a_planes = bit_decompose(aq, s).astype(jnp.int8)  # (s, M, K)
    b_planes = bit_decompose(bq, t).astype(jnp.int8)  # (t, K, N)
    m, n = aq.shape[0], bq.shape[1]
    acc = jnp.zeros((m, n), jnp.int32)
    for i in range(s):
        for j in range(t):
            prod = jax.lax.dot_general(
                a_planes[i],
                b_planes[j],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            acc = acc + (prod << (i + j))
    return acc


def bitserial_matmul(
    aq: jax.Array,
    bq: jax.Array,
    s: int,
    t: int,
    impl: str = "dot",
) -> jax.Array:
    """Deprecated ``impl=`` shim; use ``repro.api.bitserial_mm`` instead.

    Translates the legacy impl strings onto the concrete implementations
    (``bitserial_matmul_planes`` / ``bitserial_matmul_packed``). Both return
    exactly aq @ bq (int32).
    """
    import warnings

    warnings.warn(
        "bitops.bitserial_matmul(impl=...) is deprecated; use "
        "repro.api.bitserial_mm (registry dispatch) or call "
        "bitserial_matmul_planes / bitserial_matmul_packed directly",
        DeprecationWarning,
        stacklevel=2,
    )
    if impl == "popcount":
        return bitserial_matmul_packed(pack_a(aq, s), pack_b(bq, t))
    if impl != "dot":
        raise ValueError(f"unknown impl {impl!r}")
    return bitserial_matmul_planes(aq, bq, s, t)


def packing_ratio(nbits: int, dtype_bits: int = 32) -> float:
    """Memory compression vs a full-precision tensor (for reporting)."""
    return dtype_bits / float(nbits)


def np_pack_words(bits: np.ndarray) -> np.ndarray:
    """Host-side (numpy) packing used by the subgraph packer; little-endian."""
    k = bits.shape[-1]
    pad = (-k) % WORD
    if pad:
        bits = np.pad(bits, [(0, 0)] * (bits.ndim - 1) + [(0, pad)])
    shaped = bits.reshape(bits.shape[:-1] + (-1, WORD)).astype(np.uint32)
    weights = (np.uint32(1) << np.arange(WORD, dtype=np.uint32))
    return (shaped * weights).sum(-1, dtype=np.uint32)


# Masks and shifts of an 8x8 bit-matrix transpose on a uint64 whose byte r is
# row r (Hacker's Delight, 7-3): bit c of byte r swaps with bit r of byte c.
_T8 = tuple((np.uint64(s), np.uint64(m)) for s, m in (
    (7, 0x00AA00AA00AA00AA), (14, 0x0000CCCC0000CCCC), (28, 0x00000000F0F0F0F0)))


def np_pack_planes(q: np.ndarray, nbits: int) -> np.ndarray:
    """Host-side bit-plane pack: (N, D) unsigned q -> (nbits, N, ceil(D/32)) uint32.

    Plane i holds bit i of every value, 32 features a word, little-endian
    (feature 32*w + j is bit j of word w): the words ``np_pack_words`` makes
    of ``(q >> i) & 1``, with D zero-padded to a word. Works a byte of q at
    a time (bits 8*b .. 8*b+7 come from byte b), at one byte per bit: each
    uint64 of 8 features' bytes is 8x8 bit-transposed into 8 plane bytes.
    """
    n, d = q.shape
    dp = -(-d // WORD) * WORD
    out = np.empty((nbits, n, dp // 8), np.uint8)
    for b in range(0, nbits, 8):
        qb = np.zeros((n, dp), np.uint8)
        qb[:, :d] = q >> b  # the low byte: bits b .. b+7
        x = qb.view("<u8")  # byte j: feature 8*c + j
        for s, m in _T8:
            t = (x ^ (x >> s)) & m
            x = x ^ t ^ (t << s)
        # now byte i of x[:, c] packs bit b+i of features 8*c .. 8*c+7
        planes = x.astype("<u8", copy=False).view(np.uint8).reshape(n, -1, 8)
        k = min(8, nbits - b)
        out[b:b + k] = planes.transpose(2, 0, 1)[:k]
    return out.view("<u4")
