"""Continuous-batching GNN serving: queue, buckets, tile cache, fast path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import QuantParams
from repro.graph import batching, datasets, packing, partition
from repro.models import gnn
from repro.serve import (GNNServer, MicroBatcher, SubgraphRequest,
                         make_buckets, requests_from_partitions)


@pytest.fixture(scope="module")
def setup():
    data = datasets.load("ogbn-arxiv", scale=0.008, seed=0)
    parts = partition.partition(data.csr, 8)
    cfg = gnn.GNNConfig.paper_gcn(data.features.shape[1], data.n_classes)
    params = gnn.init_params(jax.random.PRNGKey(0), cfg)
    qparams = gnn.quantize_params(params, cfg)
    return data, parts, cfg, qparams


def _fresh_requests(data, parts):
    return requests_from_partitions(data, parts)


# ------------------------------------------------------------ micro-batcher

def test_queue_coalesces_under_node_budget(setup):
    data, parts, _, _ = setup
    reqs = _fresh_requests(data, parts)
    budget_n = 2 * max(r.n_nodes for r in reqs)
    buckets = make_buckets(node_budget=budget_n,
                           edge_budget=4 * max(r.n_edges for r in reqs),
                           levels=2)
    mb = MicroBatcher(buckets)
    for r in reqs:
        mb.add(r)
    plans = []
    while (p := mb.next_plan()) is not None:
        plans.append(p)
    # every request served exactly once, FIFO order preserved
    served = [rid for p in plans for rid, _, _ in p.spans]
    assert served == [r.req_id for r in reqs]
    by_id = {r.req_id: r for r in reqs}
    for p in plans:
        b = p.batch
        # budget respected; padded to the chosen bucket's shape
        assert b.n_valid <= buckets[-1].n_pad
        assert b.n_nodes == p.bucket.n_pad
        assert b.edges.shape[1] == p.bucket.e_cap
        # block-diagonal: every edge stays inside its request's span
        spans = {rid: (off, off + n) for rid, off, n in p.spans}
        e = b.edges
        valid = e[0] >= 0
        assert int(valid.sum()) == b.n_edges
        for rid, (lo, hi) in spans.items():
            r = by_id[rid]
            in_span = valid & (e[0] >= lo) & (e[0] < hi)
            assert int(in_span.sum()) == r.n_edges
            assert ((e[1, in_span] >= lo) & (e[1, in_span] < hi)).all()
            # edges are the request's, shifted by the block offset
            np.testing.assert_array_equal(e[:, in_span], r.edges + lo)
            np.testing.assert_array_equal(
                b.features[lo:hi], r.features)


def test_oversized_request_rejected(setup):
    data, parts, _, _ = setup
    r = _fresh_requests(data, parts)[0]
    mb = MicroBatcher(make_buckets(node_budget=128, edge_budget=64))
    with pytest.raises(ValueError, match="exceeds the batch budget"):
        mb.add(r)


# ------------------------------------------------- bucketed jit compilation

def test_bucket_reuse_means_zero_recompiles(setup):
    from repro.serve.queue import buckets_for

    data, parts, cfg, qparams = setup
    reqs = _fresh_requests(data, parts)
    buckets = buckets_for(reqs, levels=3)
    server = GNNServer(qparams, cfg, buckets=buckets)
    for r in reqs:
        server.submit(r)
    out = server.drain()
    assert set(out) == {r.req_id for r in reqs}
    compiles_wave1 = server.n_compiles
    assert 0 < compiles_wave1 <= len(buckets)
    # second wave: same subgraph mix, fresh feature values -> the bucketed
    # shapes are already compiled, so the jit cache must not grow
    for r in reqs:
        server.submit(SubgraphRequest(edges=r.edges,
                                      features=r.features + 0.25,
                                      n_nodes=r.n_nodes))
    server.drain()
    assert server.n_compiles == compiles_wave1
    assert server.cache.hits > 0  # and the repeat hit the tile cache


# --------------------------------------------------------- tile cache parity

def test_tile_cache_hit_logits_bit_identical(setup):
    data, parts, cfg, qparams = setup
    b = batching.make_batches(data, parts, 2, shuffle=False)[0]
    server = GNNServer(qparams, cfg)
    preds1, lg1 = server.infer_batch(b, return_logits=True)  # cold: miss
    preds2, lg2 = server.infer_batch(b, return_logits=True)  # repeat: hit
    assert server.cache.misses == 1 and server.cache.hits == 1
    np.testing.assert_array_equal(lg1, lg2)  # bit-identical, not just close
    np.testing.assert_array_equal(preds1, preds2)
    # and identical to a cache-disabled server computing everything fresh
    fresh = GNNServer(qparams, cfg, cache_entries=0)
    _, lg3 = fresh.infer_batch(b, return_logits=True)
    assert fresh.cache is None
    np.testing.assert_array_equal(lg1, lg3)
    # hit shipped the smaller features-only compound buffer
    nb = packing.compound_nbytes(b, nbits=8)
    assert server.stats.transfer_bytes == nb["III_packed"] + nb["III_feats"]


def test_transfer_accounting_matches_compound_nbytes(setup):
    """Server metrics must match the Fig. 9b accounting incl. the header."""
    data, parts, cfg, qparams = setup
    bs = batching.make_batches(data, parts, 2, shuffle=False)[:2]
    server = GNNServer(qparams, cfg, cache_entries=0)
    for b in bs:
        server.infer_batch(b)
    want = sum(packing.compound_nbytes(b, nbits=8)["III_packed"] for b in bs)
    assert server.stats.transfer_bytes == want


# ------------------------------------------------------ quantized fast path

def test_prequantized_fast_path_matches_float_path(setup):
    data, parts, cfg, qparams = setup
    b = batching.make_batches(data, parts, 2, shuffle=False)[0]
    adj, packed, meta = packing.transfer_packed(b, nbits=cfg.x_bits)
    from repro.core import bitops
    xq = bitops.bit_compose(
        bitops.unpack_along_axis(packed, axis=2, size=meta["d"]))
    qpx = QuantParams(nbits=cfg.x_bits, scale=jnp.float32(meta["scale"]),
                      zero=jnp.float32(meta["zero"]))
    deg = jnp.sum(adj, axis=1, keepdims=True).astype(jnp.float32)
    inv_deg = 1.0 / (deg + 1.0)
    lg_fast = gnn.forward_qgtc(qparams, adj, (xq, qpx), inv_deg, cfg)
    # float path: dequantize then let forward_qgtc recalibrate + requantize
    x_float = xq.astype(jnp.float32) * meta["scale"] + meta["zero"]
    lg_float = gnn.forward_qgtc(qparams, adj, x_float, inv_deg, cfg)
    # same information, one extra quantization roundtrip -> within rounding
    # (compare valid nodes only: the zero-padded tail has near-tied logits)
    nv = b.n_valid
    fast, flt = np.asarray(lg_fast)[:nv], np.asarray(lg_float)[:nv]
    denom = np.maximum(np.abs(flt).max(), 1e-6)
    assert np.abs(fast - flt).max() / denom < 0.05
    # argmax agreement is secondary: untrained logits sit near-flat, so a
    # one-bin requantization shift can flip close calls
    agree = np.mean(np.argmax(fast, -1) == np.argmax(flt, -1))
    assert agree > 0.9


def test_as_quantized_rejects_malformed_pair():
    from repro.api import nn as qnn
    with pytest.raises(TypeError, match="QuantParams"):
        qnn.as_quantized((jnp.zeros((4, 4), jnp.int32), 0.5), 8)


def test_prequantized_bitwidth_mismatch_rescales(setup):
    """An 8-bit transfer feeding a 4-bit model must compute at 4 bits.

    as_quantized rescales a mismatched pair through float, so the result
    is EXACTLY the float path's — the fast path never silently changes
    the layer's configured precision.
    """
    import dataclasses

    data, parts, cfg, _ = setup
    cfg4 = dataclasses.replace(cfg, x_bits=4, w_bits=4)
    params = gnn.init_params(jax.random.PRNGKey(1), cfg4)
    qparams4 = gnn.quantize_params(params, cfg4)
    b = batching.make_batches(data, parts, 2, shuffle=False)[0]
    adj, packed, meta = packing.transfer_packed(b, nbits=8)
    from repro.core import bitops
    xq = bitops.bit_compose(
        bitops.unpack_along_axis(packed, axis=2, size=meta["d"]))
    qpx = QuantParams(nbits=8, scale=jnp.float32(meta["scale"]),
                      zero=jnp.float32(meta["zero"]))
    deg = jnp.sum(adj, axis=1, keepdims=True).astype(jnp.float32)
    inv_deg = 1.0 / (deg + 1.0)
    lg_pair = gnn.forward_qgtc(qparams4, adj, (xq, qpx), inv_deg, cfg4)
    x_float = xq.astype(jnp.float32) * meta["scale"] + meta["zero"]
    lg_float = gnn.forward_qgtc(qparams4, adj, x_float, inv_deg, cfg4)
    np.testing.assert_array_equal(np.asarray(lg_pair), np.asarray(lg_float))


# ------------------------------------------------- zero-tile jumping serving

def test_serve_compact_tiles_consumed_and_bit_identical(setup, monkeypatch):
    """With a compact-jump policy on a jump-capable backend, the jitted
    forward consumes the cached TileEntry.compact_idx/compact_counts: the
    logits are bit-identical to the dense forward on the same backend, and
    NO in-call occupancy analysis happens (the recompute helper is never
    traced) — repeat traffic gets the cached artifacts for free."""
    from repro import api
    from repro.core import zerotile

    data, parts, cfg, qparams = setup
    b = batching.make_batches(data, parts, 2, shuffle=False)[0]

    dense = GNNServer(qparams, cfg, backend="pallas")
    _, lg_dense = dense.infer_batch(b, return_logits=True)

    calls = {"n": 0}
    orig = zerotile.tile_occupancy_planes

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    monkeypatch.setattr(zerotile, "tile_occupancy_planes", counting)
    pol = api.ExecutionPolicy(jump="compact")
    srv = GNNServer(qparams, cfg, backend="pallas", policy=pol)
    _, lg1 = srv.infer_batch(b, return_logits=True)   # miss: builds entry
    _, lg2 = srv.infer_batch(b, return_logits=True)   # hit: cached tiles
    assert srv.cache.misses == 1 and srv.cache.hits == 1
    assert calls["n"] == 0  # tiles consumed, never recomputed in-call
    np.testing.assert_array_equal(lg1, lg2)
    np.testing.assert_array_equal(lg1, lg_dense)
    # the compact grid really was sized below the full tile-grid bound
    entry = next(iter(srv.cache._entries.values()))
    t_idx, t_cnt, s_max, t_kind = srv._jump_tiles(entry)
    assert t_idx is not None and 0 < s_max <= entry.compact_idx.shape[1]
    assert entry.s_max <= s_max and t_kind == "compact"
    # and a jump-incapable backend silently serves dense (no tiles)
    plain = GNNServer(qparams, cfg, policy=pol)  # default backend: xla_dot
    assert plain._jump_tiles(entry) == (None, None, 0, None)


def test_serve_sgt_tiles_consumed_and_bit_identical(setup, monkeypatch):
    """Under ``jump="sgt"`` the jitted forward consumes the cached
    word-column remap (TileEntry.sgt_idx/sgt_counts): logits bit-identical
    to dense, the translation built ONCE per subgraph (by the build program
    on the miss, not per call), and resident-bytes accounting flows into
    ServeStats."""
    from repro import api
    from repro.kernels import sgt
    from repro.serve import cache

    data, parts, cfg, qparams = setup
    b = batching.make_batches(data, parts, 2, shuffle=False)[0]

    dense = GNNServer(qparams, cfg, backend="pallas")
    _, lg_dense = dense.infer_batch(b, return_logits=True)

    # the build program is shared by the process (whether it traces here
    # depends on what ran before), so count its dispatches, and count the
    # translations traced anywhere else, i.e. inside the forward
    calls = {"builds": 0, "elsewhere": 0, "in_build": False}
    build, translate = cache._build, sgt.word_occupancy

    def counting_build(*a, **kw):
        calls["builds"] += 1
        calls["in_build"] = True
        try:
            return build(*a, **kw)
        finally:
            calls["in_build"] = False

    def counting_translate(*a, **kw):
        calls["elsewhere"] += not calls["in_build"]
        return translate(*a, **kw)

    monkeypatch.setattr(cache, "_build", counting_build)
    monkeypatch.setattr(sgt, "word_occupancy", counting_translate)
    pol = api.ExecutionPolicy(jump="sgt")
    srv = GNNServer(qparams, cfg, backend="pallas", policy=pol)
    _, lg1 = srv.infer_batch(b, return_logits=True)   # miss: builds entry
    assert calls["builds"] == 1
    _, lg2 = srv.infer_batch(b, return_logits=True)   # hit: cached remap
    assert srv.cache.misses == 1 and srv.cache.hits == 1
    # exactly one translation: the build program on the miss, none on the
    # hit; the jitted forward consumed the artifacts, never re-deriving
    # them in-call
    assert calls["builds"] == 1 and calls["elsewhere"] == 0
    np.testing.assert_array_equal(lg1, lg2)
    np.testing.assert_array_equal(lg1, lg_dense)
    entry = next(iter(srv.cache._entries.values()))
    t_idx, t_cnt, s_max, t_kind = srv._jump_tiles(entry)
    assert t_kind == "sgt" and t_idx is not None
    assert 0 < s_max <= entry.sgt_idx.shape[1]
    assert entry.sgt_w <= s_max  # pow2 rounding never shrinks the grid
    # the remap is block_m-keyed: a block_w-retuned policy still consumes
    # it, a block_m-changed one must not (wrong row windows)
    assert srv._jump_tiles(entry, api.ExecutionPolicy(
        jump="sgt", block_w=8))[3] == "sgt"
    assert srv._jump_tiles(entry, api.ExecutionPolicy(
        jump="sgt", block_m=16)) == (None, None, 0, None)
    # resident-bytes accounting reached the stats snapshot
    assert srv.stats.cache_resident_bytes == srv.cache.resident_bytes > 0
    # a jump-incapable backend silently serves dense (no sgt tiles)
    plain = GNNServer(qparams, cfg, policy=pol)  # default: xla_dot
    assert plain._jump_tiles(entry) == (None, None, 0, None)


@pytest.mark.parametrize("jump", ["none", "compact", "sgt"])
def test_ambient_policy_serves_like_an_explicit_one(setup, jump):
    """With no ``policy=`` the server takes the ambient ``repro.api``
    policy: built and served inside ``api.use`` it consumes that policy's
    cached tiles and gives the logits of a server handed the policy
    explicitly, compiling at most once per bucket."""
    from repro import api
    from repro.serve.queue import buckets_for

    data, parts, cfg, qparams = setup
    reqs = _fresh_requests(data, parts)[:4]
    buckets = buckets_for(reqs, levels=2)
    pol = api.ExecutionPolicy(jump=jump)

    def serve(srv):
        ids = [srv.submit(SubgraphRequest(edges=r.edges, features=r.features,
                                          n_nodes=r.n_nodes))
               for r in reqs]
        out = srv.drain(return_logits=True)
        return [out[i][1] for i in ids]

    explicit = GNNServer(qparams, cfg, backend="pallas", policy=pol,
                         buckets=buckets)
    want = serve(explicit)
    with api.use("pallas", policy=pol):
        ambient = GNNServer(qparams, cfg, buckets=buckets)
        got = serve(ambient)
        entry = next(iter(ambient.cache._entries.values()))
        kind = ambient._jump_tiles(entry)[3]
    assert ambient.policy is None
    assert kind == (None if jump == "none" else jump)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    for srv in (explicit, ambient):
        assert 0 < srv.n_compiles <= len(buckets)


def test_compose_entries_sgt_matches_scratch(setup):
    """A coalesced batch's SGT remap composed from per-subgraph cached
    entries (word-offset shifting) is bit-identical to building the
    translation from the full block-diagonal adjacency."""
    from repro.serve.cache import compose_entries

    data, parts, cfg, qparams = setup
    srv = GNNServer(qparams, cfg, backend="pallas")
    tm, tw = srv._tile_shape
    align = srv._align
    rng = np.random.default_rng(5)
    sizes = [align, 2 * align]
    adjs = [jnp.asarray((rng.random((s, s)) < 0.08).astype(np.int32))
            for s in sizes]
    entries = [srv._build_entry(a) for a in adjs]
    offsets = [0, align]
    n_pad = sum(sizes)
    composed = compose_entries(entries, offsets, n_pad, tm, tw)
    full = jnp.zeros((n_pad, n_pad), jnp.int32)
    for a, off in zip(adjs, offsets):
        full = full.at[off:off + a.shape[0], off:off + a.shape[0]].set(a)
    scratch = srv._build_entry(full)
    for f in ("sgt_idx", "sgt_counts", "compact_idx", "compact_counts",
              "a_packed", "occupancy"):
        np.testing.assert_array_equal(
            np.asarray(getattr(composed, f)),
            np.asarray(getattr(scratch, f)), err_msg=f)
    assert composed.sgt_w == scratch.sgt_w
    assert composed.s_max == scratch.s_max
    # entries built before SGT existed (sgt_idx=None) degrade the batch:
    # composition carries no remap rather than a wrong one
    import dataclasses
    legacy = dataclasses.replace(entries[0], sgt_idx=None, sgt_counts=None,
                                 sgt_w=0)
    degraded = compose_entries([legacy, entries[1]], offsets, n_pad, tm, tw)
    assert degraded.sgt_idx is None and degraded.sgt_counts is None


# ------------------------------------------------------- tile cache bounds

def test_tile_cache_bytes_lru_bound(setup):
    """``cache_bytes=`` is a strict resident-bytes LRU bound: eviction
    pops least-recently-used first until bytes fit, ``get`` refreshes
    recency, replacing a key deducts the old entry, and a single entry
    larger than the bound is itself evicted (the bound is never blown)."""
    from repro.serve.cache import TileCache

    data, parts, cfg, qparams = setup
    srv = GNNServer(qparams, cfg, backend="pallas")
    e_small = srv._build_entry(jnp.eye(128, dtype=jnp.int32))
    e_big = srv._build_entry(jnp.eye(256, dtype=jnp.int32))
    nb_s, nb_b = e_small.nbytes(), e_big.nbytes()
    assert 0 < nb_s < nb_b

    c = TileCache(capacity=16, cache_bytes=3 * nb_s)
    c.put("a", e_small)
    c.put("b", e_small)
    c.put("c", e_small)
    assert len(c) == 3 and c.resident_bytes == 3 * nb_s
    assert c.get("a") is e_small  # refresh "a": "b" is now LRU
    c.put("d", e_small)           # over budget -> evict "b"
    assert set(c._entries) == {"a", "c", "d"}
    assert c.resident_bytes == 3 * nb_s and c.evictions == 1
    c.put("a", e_small)           # same key: replace, no eviction
    assert c.resident_bytes == 3 * nb_s and c.evictions == 1
    assert nb_b > 3 * nb_s        # the 256-node adjacency alone > budget
    c.put("big", e_big)           # evicts LRU-first, then big itself
    assert len(c) == 0 and c.resident_bytes == 0
    c.put("a", e_small)
    assert c.resident_bytes == nb_s
    c.clear()
    assert c.resident_bytes == 0 and len(c) == 0

    # an entry alone above the bound never pins over-budget residency
    tiny = TileCache(capacity=16, cache_bytes=nb_s // 2)
    tiny.put("x", e_small)
    assert len(tiny) == 0 and tiny.resident_bytes == 0
    with pytest.raises(ValueError, match="cache_bytes"):
        TileCache(capacity=4, cache_bytes=0)


def test_server_cache_bytes_plumbs_through(setup):
    """GNNServer(cache_bytes=) bounds the live cache and the stats
    snapshot tracks residency under eviction pressure."""
    data, parts, cfg, qparams = setup
    probe = GNNServer(qparams, cfg, backend="pallas")
    batches = batching.make_batches(data, parts, 2, shuffle=False)[:2]
    e = probe._build_entry(
        jnp.zeros((batches[0].n_nodes, batches[0].n_nodes), jnp.int32))
    budget = int(e.nbytes() * 1.5)  # roughly one batch entry resident
    srv = GNNServer(qparams, cfg, backend="pallas", cache_bytes=budget)
    for b in batches:
        srv.infer_batch(b)
    assert srv.cache.cache_bytes == budget
    assert srv.cache.resident_bytes <= budget
    assert srv.stats.cache_resident_bytes == srv.cache.resident_bytes


# -------------------------------------------------------------- serve stats

def test_stats_latency_percentiles_and_throughput(setup):
    data, parts, cfg, qparams = setup
    server = GNNServer(qparams, cfg)
    for b in batching.make_batches(data, parts, 2, shuffle=False)[:2]:
        server.infer_batch(b)
    st = server.stats
    assert len(st.batch_latencies_s) == 2
    assert 0 < st.p50_s <= st.p95_s <= st.wall_s
    assert st.nodes_per_s > 0
    s = st.summary()
    assert s["batch_n"] == 2 and s["batch_p95_s"] >= s["batch_p50_s"] > 0


def test_percentile_nearest_rank():
    from repro.perf.report import latency_summary, percentile
    xs = [0.1, 0.2, 0.3, 0.4]
    assert percentile(xs, 50) == 0.2
    assert percentile(xs, 95) == 0.4
    assert percentile([], 50) == 0.0
    assert percentile([7.0], 95) == 7.0
    s = latency_summary(xs)
    assert s["n"] == 4 and s["p50_s"] == 0.2 and s["max_s"] == 0.4


def test_batch_iterator_per_epoch_permutation(setup):
    """The hoisted iterator yields each batch once per epoch, deterministically."""
    data, parts, _, _ = setup
    bs = batching.make_batches(data, parts, 2, shuffle=False)
    seq1 = [id(b) for _, b in batching.batch_iterator(bs, epochs=3, seed=5)]
    seq2 = [id(b) for _, b in batching.batch_iterator(bs, epochs=3, seed=5)]
    assert seq1 == seq2 and len(seq1) == 3 * len(bs)
    n = len(bs)
    for e in range(3):
        assert sorted(seq1[e * n:(e + 1) * n]) == sorted(id(b) for b in bs)
