"""Cross-request non-zero tile reuse cache (paper §4.4, extended).

§4.4 reuses non-zero adjacency tiles across bit planes *within* one kernel
launch; a serving system sees the same subgraphs again and again (hot
partitions, repeat queries), so the same idea extends across requests: the
adjacency-derived artifacts — dense 0/1 form, packed bit-plane, per-tile
occupancy map, ``compact_tiles`` indices — depend only on the subgraph
structure, never on the features. Cache them by subgraph fingerprint and a
repeat request skips edge transfer, densify, bit-pack and occupancy
analysis entirely; only its (fresh) quantized features move (the
features-only §4.6 compound buffer, ``packing.transfer_packed_feats``).

Entries are PER SUBGRAPH, not per coalesced group: the micro-batcher
aligns each request's node offset to the kernel tile footprint
(``MicroBatcher(align=...)``), so :func:`compose_entries` assembles the
block-diagonal batch's artifacts from the members' cached entries by pure
offset shifting — dense blocks and packed bit-planes placed at
``(off, off)`` / ``(off, off // 32)``, occupancy placed at the tile-grid
offset, compact k-tile indices shifted by the member's column-tile offset,
and the sparse-graph-translation word-column remap (kernels/sgt.py) shifted
by the member's word offset ``off // 32``.
A repeat subgraph therefore hits the cache in ANY coalescing order; under
per-group keying a novel ordering was a guaranteed miss.

Composition dispatches 1 + members compiled programs: one init program
makes the batch's empty arrays on the members' device, then one placement
program per member writes that member in, its offset a traced int32. The
batch arrays are donated down that chain; the members' cached arrays never
are. The compiled set is bounded by buckets x aligned member sizes x SGT
presence, never by offsets or member order (:func:`compose_compiles`).

A miss costs one compiled program per missed member
(:func:`build_entries`): it slices the member out of the batch adjacency
already on the device, at a traced offset, and builds every artifact of its
entry there, whatever the jump policy, so an entry serves any policy that
later consumes it. The host ints an entry carries (non-zero tiles,
``s_max``, ``sgt_w``) come back for all the members of a step in one fetch,
after every program is dispatched. The compiled set is bounded by buckets x
aligned member sizes (:func:`build_compiles`).

TC-GNN (PAPERS.md) motivates the same tile-occupancy-centric view of
sparse adjacencies; here the occupancy map IS the cached object.
"""
from __future__ import annotations

import collections
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitops
from repro.core.zerotile import compact_tiles, tile_occupancy, tile_stats
from repro.kernels import sgt

__all__ = ["TileEntry", "TileCache", "build_entries", "build_compiles",
           "compose_entries", "compose_compiles"]


@dataclasses.dataclass
class TileEntry:
    """Device-resident adjacency artifacts for one subgraph (or batch)."""

    adj: jax.Array         # (n_pad, n_pad) 0/1 int32, dense
    inv_deg: jax.Array     # (n_pad, 1) f32, (deg+1)^-1
    a_packed: jax.Array    # (Mt, Wt) uint32 packed 1-bit plane, tile-padded
    occupancy: jax.Array   # (Mt/tm, Wt/tw) int32 0/1 tile-occupancy map
    compact_idx: jax.Array  # (Mt/tm, max_nnz) int32 non-zero k-tile ids
    compact_counts: jax.Array  # (Mt/tm,) int32
    occ_stats: dict        # tile_stats() snapshot (host ints)
    s_max: int = 0         # host int: max(compact_counts) — sizes the
    #                        compact kernel's K grid without a device sync
    # sparse-graph translation artifacts (kernels/sgt.py): the per-row-
    # window non-zero WORD-column remap. Depend only on block_m, so they
    # survive block_w retuning; None on entries built before SGT existed.
    sgt_idx: jax.Array | None = None     # (Mt/tm, Wt) int32 word ids
    sgt_counts: jax.Array | None = None  # (Mt/tm,) int32
    sgt_w: int = 0         # host int: max(sgt_counts) — sizes the SGT
    #                        kernel's K grid without a device sync

    def nbytes(self) -> int:
        n = 0
        for f in (self.adj, self.inv_deg, self.a_packed, self.occupancy,
                  self.compact_idx, self.compact_counts, self.sgt_idx,
                  self.sgt_counts):
            if f is not None:
                n += f.size * f.dtype.itemsize
        return n


def _member_arrays(adj, off, n_sub: int, tm: int, tw: int):
    """The entry arrays of the member at node offset ``off`` of the batch
    adjacency, and its host-bound int32 stats [tiles_nonzero, s_max,
    sgt_w]."""
    sub = jax.lax.dynamic_slice(adj, (off, off), (n_sub, n_sub))
    deg = jnp.sum(sub, axis=1, keepdims=True).astype(jnp.float32)
    inv_deg = 1.0 / (deg + 1.0)
    ap = bitops.pack_a(sub, 1)[0]
    ap = bitops.pad_to(bitops.pad_to(ap, 0, tm), 1, tw)
    occ = tile_occupancy(ap, tm, tw)
    idx, counts = compact_tiles(occ)
    # the SGT word-column remap rides along: same OR-reduction source,
    # word granularity (sgt.word_occupancy reuses the packed plane)
    s_idx, s_counts = compact_tiles(sgt.word_occupancy(ap, tm))
    stats = jnp.stack([jnp.sum(occ), jnp.max(counts), jnp.max(s_counts)])
    return (sub, inv_deg, ap, occ, idx, counts, s_idx, s_counts), stats


def _empty_batch(n_pad: int, tm: int, tw: int, adj_dtype, have_sgt: bool,
                 device):
    """The batch's arrays before any member is placed, made on ``device``:
    padding rows have degree 0 (inverse degree 1), every tile is empty."""
    mt, kt, wt = n_pad // tm, n_pad // (32 * tw), n_pad // 32
    sgt = ((jnp.zeros((mt, wt), jnp.int32), jnp.zeros((mt,), jnp.int32))
           if have_sgt else None)
    arrays = (jnp.zeros((n_pad, n_pad), adj_dtype),
              jnp.ones((n_pad, 1), jnp.float32),
              jnp.zeros((n_pad, wt), jnp.uint32),
              jnp.zeros((mt, kt), jnp.int32),
              jnp.zeros((mt, kt), jnp.int32),
              jnp.zeros((mt,), jnp.int32), sgt)
    return jax.device_put(arrays, jax.sharding.SingleDeviceSharding(device))


def _shift(idx, counts, base):
    """A member's tile-id remap moved to its batch position; slots past a
    row's count stay 0, as the whole-batch build leaves them."""
    mask = jnp.arange(idx.shape[1])[None, :] < counts[:, None]
    return jnp.where(mask, idx + base, 0).astype(jnp.int32)


def _place_member(batch, member, off, tm: int, tw: int):
    """Write one member's arrays into the batch's at node offset ``off``."""
    adj, inv_deg, a_packed, occ, idx, counts, sgt = batch
    m_adj, m_inv, m_packed, m_occ, m_idx, m_counts, m_sgt = member
    dus = jax.lax.dynamic_update_slice
    r0, k0 = off // tm, off // (32 * tw)
    adj = dus(adj, m_adj, (off, off))
    inv_deg = dus(inv_deg, m_inv, (off, 0))
    a_packed = dus(a_packed, m_packed, (off, off // 32))
    occ = dus(occ, m_occ, (r0, k0))
    idx = dus(idx, _shift(m_idx, m_counts, k0), (r0, 0))
    counts = dus(counts, m_counts, (r0,))
    if sgt is not None:
        # the SGT word-column remap shifts by the member's word offset
        (s_idx, s_counts), (m_sidx, m_scounts) = sgt, m_sgt
        sgt = (dus(s_idx, _shift(m_sidx, m_scounts, off // 32), (r0, 0)),
               dus(s_counts, m_scounts, (r0,)))
    return adj, inv_deg, a_packed, occ, idx, counts, sgt


# The build runs where the batch adjacency lives; the offset is traced.
_build = jax.jit(_member_arrays, static_argnums=(2, 3, 4))
# The init program has no array input, so its device is a static argument;
# placement runs where its inputs live. Only the batch arrays are donated.
_init = jax.jit(_empty_batch, static_argnums=(0, 1, 2, 3, 4, 5))
_place = jax.jit(_place_member, static_argnums=(3, 4), donate_argnums=(0,))


@functools.lru_cache(maxsize=1024)
def _offset_on(off: int, device) -> jax.Array:
    """A member offset as a device scalar, made once per (offset, device):
    offsets are few (multiples of the alignment below the top bucket), and
    a fresh host scalar per placement would cost a transfer each time."""
    return jax.device_put(np.int32(off), device)


def build_compiles() -> int:
    """Compiled build variants, shared by every caller in the process."""
    return _build._cache_size()


def build_entries(adj: jax.Array, members: list[tuple[int, int]],
                  block_m: int, block_w: int) -> list[TileEntry]:
    """Tile entries of the members ``(offset, n_sub)`` of the batch
    adjacency ``adj``, each the ``(n_sub, n_sub)`` block at
    ``(offset, offset)``.

    Dispatches one compiled program per member, then reads every member's
    host ints in one fetch (module docstring); the entries live on
    ``adj``'s device.
    """
    n = adj.shape[0]
    for off, n_sub in members:
        if off < 0 or off + n_sub > n:
            raise ValueError(f"member at offset {off} (size {n_sub}) does "
                             f"not fit the {n}-node batch adjacency")
    device, = adj.devices()
    built = [_build(adj, _offset_on(off, device), n_sub, block_m, block_w)
             for off, n_sub in members]
    stats = jax.device_get([st for _, st in built])
    entries = []
    for (arrays, _), (nonzero, s_max, sgt_w) in zip(built, stats):
        sub, inv_deg, ap, occ, idx, counts, s_idx, s_counts = arrays
        entries.append(TileEntry(
            adj=sub, inv_deg=inv_deg, a_packed=ap, occupancy=occ,
            compact_idx=idx, compact_counts=counts,
            occ_stats=tile_stats(occ.size, int(nonzero)), s_max=int(s_max),
            sgt_idx=s_idx, sgt_counts=s_counts, sgt_w=int(sgt_w)))
    return entries


def compose_compiles() -> int:
    """Compiled init and placement variants, shared by every caller in
    the process."""
    return _init._cache_size() + _place._cache_size()


def compose_entries(entries: list[TileEntry], offsets: list[int],
                    n_pad: int, block_m: int, block_w: int) -> TileEntry:
    """Assemble a block-diagonal batch entry from per-subgraph entries.

    ``entries[i]`` holds subgraph i's artifacts at its ALIGNED size
    ``entries[i].adj.shape[0]``; ``offsets[i]`` is its node offset in the
    batch (a multiple of lcm(block_m, 32 * block_w), so every placement
    lands on whole tile-grid coordinates). The result is bit-identical to
    building the artifacts from the full batch adjacency: off-diagonal
    tiles of a block-diagonal batch are zero, each diagonal block's
    occupancy/compact rows are exactly the member's own (k-tile ids
    shifted by the member's column-tile offset), and ``s_max`` is the max
    of the members' host-side counts — no device sync at coalesce time.

    Dispatches 1 + len(entries) compiled programs (module docstring); the
    result lives on the members' device.
    """
    if not entries:
        raise ValueError("compose_entries needs at least one entry")
    tm, tw = block_m, block_w
    step = 32 * tw  # node columns per k-tile
    if n_pad % tm or n_pad % step:
        raise ValueError(
            f"batch n_pad={n_pad} not a multiple of the tile grid "
            f"(block_m={tm}, {step} node columns per k-tile); pad the "
            f"bucket to lcm({tm}, {step})")
    for e, off in zip(entries, offsets):
        n_sub = e.adj.shape[0]
        if off % tm or off % step or off + n_sub > n_pad:
            raise ValueError(
                f"member offset {off} (size {n_sub}) not tile-aligned "
                f"inside n_pad={n_pad}; use MicroBatcher(align=...)")
    # SGT word-column remap composes only when every member carries it
    have_sgt = all(e.sgt_idx is not None for e in entries)
    device, = entries[0].adj.devices()
    batch = _init(n_pad, tm, tw, entries[0].adj.dtype, have_sgt, device)
    for e, off in zip(entries, offsets):
        sgt = (e.sgt_idx, e.sgt_counts) if have_sgt else None
        member = (e.adj, e.inv_deg, e.a_packed, e.occupancy, e.compact_idx,
                  e.compact_counts, sgt)
        batch = _place(batch, member, _offset_on(off, device), tm, tw)
    adj, inv_deg, a_packed, occ, idx, counts, sgt = batch
    sgt_idx, sgt_counts = sgt or (None, None)
    occ_stats = tile_stats(
        (n_pad // tm) * (n_pad // step),
        sum(e.occ_stats["tiles_nonzero"] for e in entries))
    return TileEntry(adj=adj, inv_deg=inv_deg, a_packed=a_packed,
                     occupancy=occ, compact_idx=idx, compact_counts=counts,
                     occ_stats=occ_stats,
                     s_max=max(e.s_max for e in entries),
                     sgt_idx=sgt_idx, sgt_counts=sgt_counts,
                     sgt_w=max(e.sgt_w for e in entries) if have_sgt else 0)


class TileCache:
    """LRU fingerprint -> :class:`TileEntry` map with hit/miss accounting.

    ``hits``/``misses``/``hit_rate`` count individual ``get`` lookups,
    i.e. per subgraph under composition keying. The per-batch outcome
    (full, partial or no hit) is the engine's: ``ServeStats.cache_hits``,
    ``cache_misses`` and ``cache_partial_hits``.

    Eviction is bounded two ways: ``capacity`` counts entries (the
    fallback bound), ``cache_bytes`` bounds RESIDENT BYTES — entries vary
    widely in size per fingerprint (a big subgraph's adjacency + SGT
    remap can outweigh dozens of small ones), so an entry count alone can
    blow the device-memory envelope. The bytes bound is strict: eviction
    pops LRU-first until resident bytes fit, and a single entry larger
    than the bound is itself evicted (the caller still holds the entry it
    just built; repeats rebuild rather than pinning an over-budget
    resident). ``resident_bytes`` is maintained incrementally and
    reported through ``ServeStats``.

    Replica awareness: the engine keys entries with the owning replica id
    as the LAST tuple element (``("sub", fp, replica)``), so the cache
    also maintains per-replica resident bytes (``bytes_by_replica`` — the
    cache-pressure signal for cold-fingerprint placement in
    serve/router.py) and can drop a failed replica's entries in one call
    (``drop_replica`` — the re-home accounting: those fingerprints
    re-warm on their new owner's first miss).
    """

    def __init__(self, capacity: int = 64, cache_bytes: int | None = None):
        if capacity <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        if cache_bytes is not None and cache_bytes <= 0:
            raise ValueError(f"cache_bytes must be positive, got {cache_bytes}")
        self.capacity = capacity
        self.cache_bytes = cache_bytes
        self.resident_bytes = 0
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._replica_bytes: collections.Counter = collections.Counter()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)

    def get(self, key) -> TileEntry | None:
        entry = self._entries.get(key)
        if entry is None:
            self.misses += 1
            return None
        self._entries.move_to_end(key)
        self.hits += 1
        return entry

    @staticmethod
    def _key_replica(key) -> int | None:
        """The owning replica id when the key carries one (last element)."""
        if (isinstance(key, tuple) and len(key) >= 2
                and isinstance(key[-1], int)):
            return key[-1]
        return None

    def _forget(self, key, entry: TileEntry) -> int:
        nb = entry.nbytes()
        self.resident_bytes -= nb
        rep = self._key_replica(key)
        if rep is not None:
            self._replica_bytes[rep] -= nb
            if self._replica_bytes[rep] <= 0:
                del self._replica_bytes[rep]
        return nb

    def put(self, key, entry: TileEntry) -> None:
        old = self._entries.pop(key, None)
        if old is not None:
            self._forget(key, old)
        self._entries[key] = entry
        nb = entry.nbytes()
        self.resident_bytes += nb
        rep = self._key_replica(key)
        if rep is not None:
            self._replica_bytes[rep] += nb
        while len(self._entries) > self.capacity or (
                self.cache_bytes is not None
                and self.resident_bytes > self.cache_bytes):
            k, evicted = self._entries.popitem(last=False)
            self._forget(k, evicted)
            self.evictions += 1

    def bytes_by_replica(self) -> dict:
        """replica id -> resident bytes (the cold-placement pressure)."""
        return dict(self._replica_bytes)

    def drop_replica(self, replica: int) -> tuple[int, int]:
        """Drop every entry owned by ``replica``; (entries, bytes) dropped.

        The failed replica's device-resident artifacts are unreachable;
        their fingerprints re-home (serve/router.py) and the new owner
        rebuilds on its first miss — the engine accounts the drop as
        ``cache_rehomed_entries``/``cache_rehomed_bytes``.
        """
        doomed = [k for k in self._entries
                  if self._key_replica(k) == replica]
        n_bytes = 0
        for k in doomed:
            n_bytes += self._forget(k, self._entries.pop(k))
        return len(doomed), n_bytes

    def clear(self) -> None:
        self._entries.clear()
        self._replica_bytes.clear()
        self.resident_bytes = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def nbytes(self) -> int:
        return sum(e.nbytes() for e in self._entries.values())
