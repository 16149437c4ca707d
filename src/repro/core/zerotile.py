"""Zero-tile occupancy maps + compaction (paper §4.3 zero-tile jumping).

On GPU the kernel discovers all-zero 8x128 adjacency tiles at runtime with
uint4 loads + warp ballots. TPUs have no warp primitives, so we precompute
the per-tile occupancy with an XLA reduce (cheap: one pass over the packed
1-bit matrix) and hand it to the Pallas kernel via scalar prefetch:

  mask mode    — occupancy (MT, KT) int32; kernel wraps compute in pl.when.
  compact mode — per m-tile row, the sorted indices of its non-zero k-tiles
                 padded to max_nnz; the BlockSpec index_map reads this to
                 skip the DMA of zero tiles entirely (true jumping).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["tile_occupancy", "tile_occupancy_planes", "compact_tiles",
           "compact_artifacts", "occupancy_stats", "tile_stats"]


def tile_occupancy(a_packed_plane: jax.Array, tile_m: int, tile_w: int) -> jax.Array:
    """(M, W) uint32 packed 1-bit matrix -> (M/tile_m, W/tile_w) int32 0/1.

    A tile is occupied iff any word in it is non-zero (paper's bitwise-OR
    reduction). M, W must be padded to tile multiples by the caller.
    """
    m, w = a_packed_plane.shape
    assert m % tile_m == 0 and w % tile_w == 0, (m, w, tile_m, tile_w)
    t = a_packed_plane.reshape(m // tile_m, tile_m, w // tile_w, tile_w)
    ored = jax.lax.reduce(
        t, jnp.uint32(0), jax.lax.bitwise_or, (1, 3)
    )
    return (ored != 0).astype(jnp.int32)


def tile_occupancy_planes(a_packed: jax.Array, tile_m: int, tile_w: int) -> jax.Array:
    """(s, M, W) packed bit-planes -> (M/tile_m, W/tile_w) int32 0/1.

    A tile is occupied iff any word of ANY plane is non-zero: a tile that is
    zero across all s planes contributes nothing to the bit-serial sum, so
    skipping it is exact for any bitwidth. For the GNN aggregation A is the
    1-bit adjacency (s == 1) and this reduces to ``tile_occupancy``.

    Callers holding a cached occupancy map should pass it down instead of
    re-reducing (kernels.ops enforces the tiles > occupancy > recompute
    precedence); the s == 1 case skips the cross-plane OR entirely.
    """
    plane = (a_packed[0] if a_packed.shape[0] == 1 else jax.lax.reduce(
        a_packed, jnp.uint32(0), jax.lax.bitwise_or, (0,)))
    return tile_occupancy(plane, tile_m, tile_w)


def compact_tiles(occ: jax.Array):
    """Occupancy (MT, KT) -> (indices (MT, max_nnz) int32, counts (MT,) int32).

    indices[i, :counts[i]] are the k-tile ids of row i's non-zero tiles in
    ascending order; the tail is padded with 0 (the kernel masks by count).
    ``max_nnz`` is the static KT bound — with jit we cannot shrink it
    data-dependently, but the kernel's grid can be sized to max(counts) when
    called eagerly (the serving path does exactly that).
    """
    mt, kt = occ.shape
    order = jnp.argsort(-occ, axis=1, stable=True)  # nonzeros first, stable=ascending ids
    counts = jnp.sum(occ, axis=1).astype(jnp.int32)
    idx = jnp.where(jnp.arange(kt)[None, :] < counts[:, None], order, 0)
    return idx.astype(jnp.int32), counts


def compact_artifacts(a_packed: jax.Array, tile_m: int, tile_w: int):
    """Eager one-step recipe for the kernels' ``tiles=`` contract.

    Pads a packed (M, W) plane or (s, M, W) plane stack to the tile grid,
    reduces occupancy, compacts, and syncs the max count to a HOST int —
    returns exactly the ``(idx, counts, s_max)`` triple
    ``kernels.ops.{bgemm,bitserial_gemm,bitserial_fused}(tiles=...)`` and
    the serve cache consume. Eager only: the host sync makes it unusable
    under jit (use ``jump="compact"`` there instead).
    """
    from repro.core.bitops import pad_to

    if a_packed.ndim == 2:
        a_packed = a_packed[None]
    ap = pad_to(pad_to(a_packed, 1, tile_m), 2, tile_w)
    occ = tile_occupancy_planes(ap, tile_m, tile_w)
    idx, counts = compact_tiles(occ)
    return idx, counts, int(jnp.max(counts))


def occupancy_stats(occ: jax.Array) -> dict:
    return tile_stats(int(occ.size), int(jnp.sum(occ)))


def tile_stats(total: int, nonzero: int) -> dict:
    """Zero-tile accounting of a map of ``total`` tiles, ``nonzero``
    occupied (host ints, no device sync)."""
    return {
        "tiles_total": total,
        "tiles_nonzero": nonzero,
        "tiles_zero": total - nonzero,
        "nonzero_ratio": nonzero / max(total, 1),
        "skip_ratio": 1.0 - nonzero / max(total, 1),
    }
